"""One run of one cell: set up, measure a window, check, report.

The steps, in order, each in this one process (which holds the chips):

1. find the cell in ``BENCHMARK.json`` and the chips it asks for (no
   TPU, or too few chips: exit nonzero, print no result);
2. the driver sets up: builds inputs on the device from ``--seed``,
   compiles (JAX's persistent cache lives at a fixed path) and warms
   exactly the shapes the window uses.  ``setup_s`` runs from process
   start to here;
3. the window: ``--seconds`` of the cell's traffic, under the profiler
   when ``--trace 1`` (end-to-end numbers come from ``--trace 0`` runs;
   a traced run reports the per-layer metrics instead);
4. the device memory peak is read, the program's state freed, and what
   the window produced is compared with the plain reference;
5. the result line.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

from . import check as _check
from . import device as _device
from . import devtrace, layout


@dataclass
class Record:
    """What per-layer metric readers read: the driver's counters and
    samples from the window, and the reduced trace."""

    cell: layout.Cell
    counters: Dict[str, float] = field(default_factory=dict)
    samples: Dict[str, List[float]] = field(default_factory=dict)
    trace: Optional[devtrace.Trace] = None
    peaks: Dict[str, float] = field(default_factory=dict)


def span(name: str):
    """A benchmark host span in the profiler's trace (a no-op when no
    trace is being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextmanager
def _profiled(enabled: bool, log_dir: str):
    if not enabled:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # spans only: no per-call Python events
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def import_program() -> None:
    """The system under test: the ``repro`` package under ``src/`` of
    the checkout that holds this benchmark."""
    src = layout.PACKAGE_DIR.parent / "src"
    sys.path.insert(0, str(src))
    try:
        import repro  # noqa: F401
    except ImportError as exc:
        raise SystemExit(f"chipbench: the system under test (package "
                         f"'repro') is not under {src}: {exc}")


def use_cache() -> str:
    """JAX's persistent compilation cache at its fixed place (the
    program's own helper: ``$JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``).  Every executable is kept, small ones
    too, so that a cell's later runs load what its first compiled."""
    import jax
    from repro.launch.cache import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="chipbench/run.py",
        description="One run of one benchmark cell on this machine's TPU.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, *, root: Path, t_start: float,
         require_chips=_device.require_chips) -> int:
    args = parse_args(argv)
    cell = layout.load_cell(root, args.workload)
    try:
        devs = require_chips(cell.chips)
    except _device.NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    import_program()
    cache = use_cache()
    log = lambda msg: print(f"chipbench: {msg}", file=sys.stderr,
                            flush=True)
    log(f"cell {cell.name} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace} on {_device.describe(devs)}; compile cache {cache}")
    drv = layout.driver(root, cell.config["driver"]).Driver(
        cell, args.seed, devs, seconds=args.seconds, span=span, log=log)
    drv.setup()
    setup_s = time.monotonic() - t_start
    log(f"setup_s {setup_s!r}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        with _profiled(bool(args.trace), trace_dir):
            window = drv.window(args.seconds)
            closed = time.monotonic()
        mem_peak = _device.memory_peak_bytes(devs)
        trace = None
        if args.trace:
            written = time.monotonic()
            trace = devtrace.load(devtrace.find_xplane(trace_dir),
                                  drv.hlo_texts(), drv.timed_programs())
            log(f"trace written in {written - closed:.1f} s, read in "
                f"{time.monotonic() - written:.1f} s: {len(trace.ops)} "
                f"device ops, {len(trace.spans)} spans")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for line in window.notes:
        log(line)
    drv.release()
    checks = drv.check()

    result: Dict[str, Any] = {"correct": all(c.ok for c in checks),
                              "attempted": window.attempted,
                              "failed": window.failed}
    device: Dict[str, Any] = dict(_device.describe(devs),
                                  memory_peak_bytes=mem_peak)
    if args.trace:
        rec = Record(cell=cell, counters=window.counters,
                     samples=window.samples, trace=trace,
                     peaks=_device.peaks(devs[0].device_kind))
        result["metrics"] = {}
        for m in cell.per_layer:
            value = layout.metric_reader(root, m["name"]).read(rec)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        busy = trace.busy_s()
        device["busy_s"] = sum(busy.get(d.id, 0.0) for d in devs) / len(devs)
        device["window_s"] = trace.window_s()
        result["breakdown"] = {"device_ops": trace.top_ops(),
                               "idle_gaps": trace.top_gaps()}
        roof = trace.hbm_roofline(rec.peaks["hbm_bytes_per_s"])
        if roof and roof[1]:
            log(f"ops reading over 100% of the HBM roofline (miscounted "
                f"bytes): {roof[1][:10]}")
    else:
        values = dict(window.end_to_end, setup_s=setup_s)
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in values]
        if missing:
            raise RuntimeError(f"driver {cell.config['driver']!r} measures "
                               f"no {missing}")
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    result["checks"] = _check.report(checks)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


@dataclass
class Window:
    """What a driver's window returns."""

    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    counters: Dict[str, float] = field(default_factory=dict)
    samples: Dict[str, List[float]] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)


def clock() -> float:
    return time.perf_counter()

