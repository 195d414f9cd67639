"""Helpers of the benchmark's tests: tiny deployments, a stand-in for
the chip check, one run of a cell."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {"robertson_mesh": {"ngroups": 256, "lanes_per_call": 32},
        "robertson_service": {}}


def cpu_chips(count):
    """Stands in for the chip check: the first ``count`` CPU devices."""
    import jax

    return jax.devices("cpu")[:count]


# the service deployment is not a cell yet (PERF.md, Open questions);
# its files are, and the tests drive them as one
SERVICE = {
    "config": {"name": "robertson_service", "source": "https://example.org",
               "file": "chipbench/configs/robertson_service.json",
               "reduced": [], "why": "the solve service"},
    "workload": {"name": "robertson_service.poisson",
                 "config": "robertson_service", "traffic": "poisson",
                 "chips": 1, "why": "the solve service"},
    "end_to_end": [{"name": f"serve_{q}_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["robertson_service.poisson"]}
                   for q in ("p50", "p95")],
    "per_layer": [{"name": name, "unit": unit, "better": better,
                   "source": source, "layer": "serving",
                   "moves": "serve_p95_ms",
                   "workloads": ["robertson_service.poisson"]}
                  for name, unit, better, source in (
                      ("queue_wait_p95_ms.serve", "ms", "lower",
                       "program_span"),
                      ("bundle_occupancy.serve", "%", "higher",
                       "program_counter"),
                      ("device_idle_share.serve", "%", "lower",
                       "device_trace"))],
}


def make_tiny_root(path: Path) -> Path:
    """A scratch checkout root whose ``BENCHMARK.json`` names the real
    cells and the service deployment, each cut to a size the CPU runs in
    seconds (the configuration files are new files under the scratch
    root)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if SERVICE["config"]["name"] not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append(dict(SERVICE["config"]))
        bench["workloads"].append(dict(SERVICE["workload"]))
        bench["end_to_end"].extend(SERVICE["end_to_end"])
        bench["per_layer"].extend(SERVICE["per_layer"])
    cfg_dir = path / "chipbench" / "configs"
    cfg_dir.mkdir(parents=True)
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cut = TINY[c["name"]]
        if "ngroups" in cut:
            cfg["ngroups"] = cut["ngroups"]
            cfg["check"]["lanes_per_call"] = cut["lanes_per_call"]
        c["file"] = f"chipbench/configs/tiny_{c['name']}.json"
        (path / c["file"]).write_text(json.dumps(cfg))
    (path / "BENCHMARK.json").write_text(json.dumps(bench))
    return path


def run_cell(root, capsys, workload, *, seed=5, seconds=1.0, trace=0):
    """One run of ``workload`` under ``root`` on the CPU; its result
    line as a dict."""
    from chipbench import harness

    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, t_start=0.0, require_chips=cpu_chips)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
