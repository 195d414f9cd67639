"""The control comes out not correct where the program comes out
correct: the reference put in the program's place, in bfloat16 (the
precision below the float32 the deployments state), on three seeds."""
import pytest

from chipbench import limits
from chipbench.tests.support import cpu_chips


@pytest.mark.parametrize("workload,seconds", [
    ("robertson_mesh.bulk", 0.0), ("robertson_service.poisson", 1.0)])
def test_control_fails_where_program_passes(tiny_root, workload, seconds):
    import json

    rows = limits.main(["--workload", workload, "--seeds", "11,12,13",
                        "--control-seeds", "11,12,13", "--seconds",
                        str(seconds)], root=tiny_root,
                       require_chips=cpu_chips)
    name = [c for c in json.loads((tiny_root / "BENCHMARK.json")
                                  .read_text())["workloads"]
            if c["name"] == workload][0]["config"]
    cfg = json.loads((tiny_root / f"chipbench/configs/tiny_{name}.json")
                     .read_text())
    limit = cfg["check"]["limits"]["worst_err"]
    assert len(rows) == 3
    for row in rows:
        assert row["program"]["worst_err"] <= limit
        assert row["control"]["worst_err"] > 3 * limit
