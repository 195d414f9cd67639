"""A cell is found by its names: new files make a new cell."""
import json
import subprocess
import sys

from chipbench.tests.support import ROOT, run_cell


def test_cell_made_of_new_files_runs(tiny_root, capsys, monkeypatch):
    """A new deployment, traffic mix and per-layer metric, each a new
    file, plus a BENCHMARK.json entry: found and run with no existing
    file edited."""
    from chipbench import device

    cfg = json.loads((tiny_root / "chipbench/configs/"
                      "tiny_robertson_mesh.json").read_text())
    cfg["ngroups"] = 128
    cfg["rtol"] = 1e-3
    (tiny_root / "chipbench/configs/new_mesh.json").write_text(
        json.dumps(cfg))
    traffic = tiny_root / "chipbench" / "traffic"
    traffic.mkdir()
    (traffic / "narrow.json").write_text(json.dumps({
        "generator": "closed_loop",
        "params": {"k1": {"dist": "const", "value": 0.04},
                   "k2": {"dist": "const", "value": 1e4},
                   "k3": {"dist": "uniform", "low": 2e7, "high": 4e7}}}))
    metrics = tiny_root / "chipbench" / "metrics"
    metrics.mkdir()
    (metrics / "calls.new.py").write_text(
        "def read(rec):\n    return rec.counters.get('calls')\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "new_mesh", "source": "https://example.org/new",
        "file": "chipbench/configs/new_mesh.json", "reduced": [],
        "why": "a test deployment"})
    bench["workloads"].append({
        "name": "new_mesh.narrow", "config": "new_mesh",
        "traffic": "narrow", "chips": 1, "why": "a test cell"})
    bench["end_to_end"][0]["workloads"].append("new_mesh.narrow")
    bench["per_layer"].append({
        "name": "calls.new", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "test", "moves":
        "cells_per_s", "workloads": ["new_mesh.narrow"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    before = {p: p.read_bytes() for p in (ROOT / "chipbench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}

    res = run_cell(tiny_root, capsys, "new_mesh.narrow", seconds=0.5)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"cells_per_s", "setup_s"}
    assert res["attempted"] % 128 == 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"

    monkeypatch.setattr(device, "peaks", lambda kind: {
        "hbm_bytes_per_s": 819e9})
    res = run_cell(tiny_root, capsys, "new_mesh.narrow", seconds=0.5,
                   trace=1)
    assert res["correct"] is True
    assert res["metrics"]["calls.new"]["value"] >= 1
    assert set(res["metrics"]) == {"calls.new"}
    assert {"device_ops", "idle_gaps"} <= set(res["breakdown"])
    after = {p: p.read_bytes() for p in (ROOT / "chipbench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert before == after


def test_no_tpu_exits_nonzero_without_a_result():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "robertson_mesh.bulk", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""
