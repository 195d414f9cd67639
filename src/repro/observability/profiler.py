"""SUNProfiler analog: nestable, device-sync-aware host region timers.

SUNDIALS' SUNProfiler brackets named regions (``SUNDIALS_MARK_BEGIN``/
``_END``) and renders a per-region summary; on GPU builds it syncs the
device before reading the clock so asynchronously-launched work is
charged to the region that launched it.  This is the same tool for the
JAX stack:

* ``with prof.region("integrate.execute"):`` — nestable context-manager
  regions; exit optionally blocks on an enqueued device token
  (``sync=True``) so dispatched-but-unfinished XLA work lands inside
  the region that dispatched it.  Every region also enters a
  ``jax.profiler.TraceAnnotation`` of its name, so it appears on the
  host plane of a JAX profiler trace, on the device ops' clock.
* ``prof.add_span(name, t0, t1)`` — raw span injection for events timed
  on a foreign clock (the serving queue's arrival/flush timestamps are
  mapped into the profiler timebase and recorded per bundle).
* ``prof.summary()`` / ``prof.render()`` — the per-region roll-up table
  (count, total, mean, max).
* ``prof.chrome_trace()`` / ``prof.export_chrome_trace(path)`` — the
  merged host-region + serving-queue timeline as Chrome-trace JSON
  (load in ``chrome://tracing`` or https://ui.perfetto.dev).

A disabled profiler records nothing; its regions are the bare
annotations, which record only while a JAX profiler trace is being
taken.
"""
from __future__ import annotations

import json
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation


@dataclass(frozen=True)
class Span:
    """One closed region instance on the profiler's timebase."""

    name: str
    t0: float
    t1: float
    tid: int = 0            # OS thread ident (pump thread vs caller)
    depth: int = 0          # nesting depth at entry (render indent)
    cat: str = "host"
    args: Optional[dict] = None

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


def _device_sync() -> None:
    """Block until previously-enqueued device work has retired, by
    enqueueing a trivial op and waiting on it (the portable analog of
    ``cudaDeviceSynchronize`` SUNProfiler uses on GPU builds)."""
    try:
        import jax
        import jax.numpy as jnp
        jax.block_until_ready(jnp.zeros(()) + 0.0)
    except Exception:       # profiling must never take the run down
        pass


class _Region:
    """An active region; created per ``with`` entry (regions nest)."""

    __slots__ = ("_prof", "name", "cat", "sync", "args", "_t0", "_depth",
                 "_tid", "_ann")

    def __init__(self, prof: "Profiler", name: str, cat: str, sync: bool,
                 args: Optional[dict]):
        self._prof = prof
        self.name = name
        self.cat = cat
        self.sync = sync
        self.args = args

    def __enter__(self):
        tl = self._prof._tls
        self._depth = getattr(tl, "depth", 0)
        tl.depth = self._depth + 1
        self._tid = threading.get_ident()
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = self._prof.clock()
        return self

    def __exit__(self, *exc):
        if self.sync:
            self._prof._sync_fn()
        t1 = self._prof.clock()
        self._ann.__exit__(*exc)
        self._prof._tls.depth = self._depth
        self._prof.add_span(self.name, self._t0, t1, cat=self.cat,
                            args=self.args, tid=self._tid,
                            depth=self._depth)
        return False


class Profiler:
    """Region timers + span store (thread-safe appends; the serving
    pump thread and the caller thread interleave freely)."""

    def __init__(self, enabled: bool = True, sync: bool = True,
                 clock: Callable[[], float] = time.perf_counter,
                 sync_fn: Callable[[], None] = _device_sync):
        self.enabled = bool(enabled)
        self.sync = bool(sync)
        self.clock = clock
        self._sync_fn = sync_fn
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.spans: List[Span] = []

    # -- recording ---------------------------------------------------------

    def now(self) -> float:
        """The profiler timebase (for mapping foreign clocks onto it)."""
        return self.clock()

    def region(self, name: str, cat: str = "host",
               sync: Optional[bool] = None, **args):
        """A nestable timed region; when disabled, only the annotation
        of the JAX profiler trace."""
        if not self.enabled:
            return TraceAnnotation(name)
        return _Region(self, name, cat,
                       self.sync if sync is None else bool(sync),
                       args or None)

    def add_span(self, name: str, t0: float, t1: float, *,
                 cat: str = "host", args: Optional[dict] = None,
                 tid: Optional[int] = None, depth: int = 0) -> None:
        """Record one closed span on the profiler timebase (used for
        events timed elsewhere, e.g. serving queue wait per bundle)."""
        if not self.enabled:
            return
        span = Span(name=name, t0=float(t0), t1=float(t1),
                    tid=tid if tid is not None else threading.get_ident(),
                    depth=depth, cat=cat, args=args)
        with self._lock:
            self.spans.append(span)

    def clear(self) -> None:
        with self._lock:
            self.spans = []

    # -- reporting ---------------------------------------------------------

    def summary(self) -> Dict[str, dict]:
        """Per-region roll-up: count / total_s / mean_s / max_s."""
        with self._lock:
            spans = list(self.spans)
        out: Dict[str, dict] = {}
        for s in spans:
            row = out.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                          "max_s": 0.0})
            row["count"] += 1
            row["total_s"] += s.dur
            row["max_s"] = max(row["max_s"], s.dur)
        for row in out.values():
            row["mean_s"] = row["total_s"] / row["count"]
        return out

    def render(self) -> str:
        """The SUNProfiler-style text table, sorted by total time."""
        rows = sorted(self.summary().items(),
                      key=lambda kv: -kv[1]["total_s"])
        width = max([len(name) for name, _ in rows] + [6])
        lines = [f"{'region':<{width}}  {'count':>7} {'total_s':>10} "
                 f"{'mean_s':>10} {'max_s':>10}"]
        for name, r in rows:
            lines.append(f"{name:<{width}}  {r['count']:>7d} "
                         f"{r['total_s']:>10.6f} {r['mean_s']:>10.6f} "
                         f"{r['max_s']:>10.6f}")
        return "\n".join(lines)

    def chrome_trace(self) -> dict:
        """Chrome-trace JSON (``traceEvents`` of complete ``"X"``
        events, microsecond timestamps relative to the first span) —
        loadable in chrome://tracing or Perfetto."""
        with self._lock:
            spans = list(self.spans)
        base = min((s.t0 for s in spans), default=0.0)
        tids = {}
        events = []
        for s in spans:
            tid = tids.setdefault(s.tid, len(tids) + 1)
            events.append({
                "name": s.name, "cat": s.cat, "ph": "X",
                "ts": (s.t0 - base) * 1e6, "dur": s.dur * 1e6,
                "pid": 1, "tid": tid, "args": dict(s.args or {})})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)
        return path
