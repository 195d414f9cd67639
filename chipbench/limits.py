#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python chipbench/limits.py --workload robertson_mesh.bulk \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3 \
        --seconds 0

One process sets the cell up once; then, for each seed, it runs the
timed path (``--seconds`` of the cell's traffic; 0 means one call of a
closed loop) and prints the numbers ``correct`` compares.  For each
control seed it also puts the control in the program's place: the
plain reference, computed in the precision below the one the
deployment states (``--control-dtype``, bfloat16 for float32), at the
deployment's own tolerances, for the same systems the check compares;
a system it does not bring to ``tf`` counts as failed.  Rows are JSON,
one per seed.
"""
import json
import sys
import time
from pathlib import Path

import numpy as np

PROCESS_START = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]


def control_reading(drv, dtype) -> dict:
    from chipbench import check

    cfg = drv.cfg
    _, y0, params = drv.compared()
    y_ctl, reached = drv.problem.reference(
        y0, params, float(cfg["t0"]), float(cfg["tf"]), rtol=cfg["rtol"],
        atol=cfg["atol"], dtype=dtype)
    return {"worst_err": check.against_reference(drv.problem, cfg, y_ctl,
                                                 y0, params),
            "failed": int((~reached).sum()), "compared": int(len(y0))}


def main(argv=None, root=ROOT, require_chips=None):
    import argparse

    sys.path.insert(0, str(ROOT))
    import ml_dtypes
    from chipbench import device, harness, layout

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-dtype", default="bfloat16")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    ctl_dtype = np.dtype(getattr(ml_dtypes, args.control_dtype, None)
                         or args.control_dtype)

    cell = layout.load_cell(root, args.workload)
    devs = (require_chips or device.require_chips)(cell.chips)
    harness.import_program()
    harness.use_cache()
    log = lambda m: print(f"limits: {m}", file=sys.stderr, flush=True)
    drv = layout.driver(root, cell.config["driver"]).Driver(
        cell, seeds[0], devs, seconds=args.seconds, span=harness.span,
        log=log)
    drv.setup()
    log(f"set up in {time.monotonic() - PROCESS_START:.1f} s on "
        f"{device.describe(devs)}")
    rows = []
    for seed in sorted(set(seeds) | ctl_seeds):
        drv.reseed(seed, args.seconds)
        window = drv.window(args.seconds)
        row = {"seed": seed, "attempted": window.attempted,
               "program": {c.name: c.value for c in drv.check()}}
        if seed in ctl_seeds:
            row["control"] = control_reading(drv, ctl_dtype)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    main()
