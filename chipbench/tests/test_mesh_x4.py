"""The four-chip cell ``robertson_mesh_x4.bulk``: its layout, its
driver's lane sampler, its two readers, and one run of it on four CPU
devices."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from chipbench import devtrace, harness, layout
from chipbench.tests.support import ROOT

CELL = "robertson_mesh_x4.bulk"


def test_cell_loads_with_four_chips_and_its_metrics():
    cell = layout.load_cell(ROOT, CELL)
    assert cell.chips == 4
    assert cell.config["driver"] == "ensemble_mesh"
    assert cell.config["ngroups"] == 4 * 1048576
    assert cell.config["mesh"] == {"axis": "systems", "chips": 4}
    assert cell.traffic_name == "bulk"
    assert {m["name"] for m in cell.end_to_end} == {"cells_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "newton_iters_per_cell.ensemble", "lsetups_per_cell.ensemble",
        "kernel_hbm_roofline.ensemble", "device_idle_share.ensemble",
        "in_call_idle_share.ensemble", "shard_trip_spread.x4",
        "shard_busy_spread.x4"}
    for m in cell.per_layer:
        layout.metric_reader(ROOT, m["name"])


def _driver(seed, chips=4):
    cell = layout.load_cell(ROOT, CELL)
    mod = layout.driver(ROOT, cell.config["driver"])
    return mod.Driver(cell, seed, [object()] * chips, seconds=1.0,
                      span=None, log=print)


def test_lane_sampler_draws_from_every_shard():
    drv = _driver(2 ** 31 + 12345)
    shard = 1048576
    for i in range(3):
        lanes = drv.lanes(i)
        assert lanes.dtype == np.int32 and len(lanes) == 128
        assert len(np.unique(lanes)) == 128
        counts = np.bincount(lanes // shard, minlength=4)
        assert counts.tolist() == [32, 32, 32, 32]
        assert np.array_equal(lanes, drv.lanes(i))        # from (seed, i)
    assert not np.array_equal(drv.lanes(0), drv.lanes(1))
    assert not np.array_equal(drv.lanes(0), _driver(7).lanes(0))


def test_driver_refuses_an_uneven_split():
    with pytest.raises(ValueError, match="evenly"):
        _driver(1, chips=3)


def _record(counters=None, trace=None):
    return harness.Record(cell=layout.load_cell(ROOT, CELL),
                          counters=counters or {}, trace=trace)


def test_shard_trip_spread_reader():
    read = layout.metric_reader(ROOT, "shard_trip_spread.x4").read
    assert read(_record({"shard_trips_max": 1072.0,
                         "shard_trips_mean": 1072.0})) == 1.0
    assert read(_record({"shard_trips_max": 1100.0,
                         "shard_trips_mean": 1000.0})) == \
        pytest.approx(1.1)
    assert read(_record({"cells": 8.0, "nni": 3.0})) is None
    assert read(_record({"shard_trips_max": 3.0})) is None


def _trace(busy_by_device):
    ops = [devtrace.Op(dev, 1.0, 1.0 + busy, "%fusion.1 = f32[8] fusion()")
           for dev, busy in busy_by_device.items()]
    return devtrace.Trace(ops=ops, spans=[devtrace.Span(
        devtrace.WINDOW_SPAN, 0.0, 10.0)])


def test_shard_busy_spread_reader():
    read = layout.metric_reader(ROOT, "shard_busy_spread.x4").read
    assert read(_record(trace=_trace({0: 4.0, 1: 4.0, 2: 4.0,
                                      3: 4.0}))) == pytest.approx(1.0)
    assert read(_record(trace=_trace({0: 5.0, 1: 4.0, 2: 4.0,
                                      3: 3.0}))) == pytest.approx(1.25)
    assert read(_record()) is None
    assert read(_record(trace=devtrace.Trace(ops=[], spans=[]))) is None


_RUN = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path.insert(0, sys.argv[1])
    sys.path.insert(0, str(Path(sys.argv[1]) / "src"))
    from chipbench import device, harness
    from chipbench.tests.support import TINY, cpu_chips, make_tiny_root
    device.peaks = lambda kind: {"hbm_bytes_per_s": 819e9}   # no TPU here
    TINY.setdefault("robertson_mesh_x4",
                    {"ngroups": 1024, "lanes_per_call": 128})
    root = make_tiny_root(Path(sys.argv[2]))
    sys.exit(harness.main(["--workload", "robertson_mesh_x4.bulk",
                           "--seed", "4294967311", "--seconds", "0.5",
                           "--trace", sys.argv[3]], root=root,
                          t_start=0.0, require_chips=cpu_chips))
""")


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_four_cpu_devices(tmp_path, trace):
    """The cell end to end on four CPU devices at 1,024 systems: correct,
    and its counters read (the trace of a CPU run holds no TPU ops, so
    the trace's readers give nothing)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _RUN, str(ROOT), str(tmp_path), str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] % 1024 == 0
    assert res["device"]["count"] == 4
    if trace:
        assert res["metrics"]["shard_trip_spread.x4"]["value"] == 1.0
        assert "newton_iters_per_cell.ensemble" in res["metrics"]
    else:
        assert set(res["metrics"]) == {"cells_per_s", "setup_s"}
