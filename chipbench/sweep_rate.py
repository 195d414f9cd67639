#!/usr/bin/env python3
"""Find the highest request rate a service deployment sustains (its
knee) by a sweep of fixed open-loop rates on the chip.

    python chipbench/sweep_rate.py --workload robertson_service.poisson \
        --rates 10,20,30,40 --seconds 20 --seed 1

One process sets the cell's server up once and offers each rate for
``--seconds`` (a fresh, empty queue each time).  Per rate it prints one
JSON row: requests offered, the share answered by the window's close,
client-side latency percentiles (from the due time), the ratio of the
median latency of the window's last third to its first third (a
backlog that grows makes it climb), the most live requests in one
bundle, compiles, and how late the generator ran.  The knee is the
highest rate whose answers keep up (answered by the close near 1, the
latency ratio near 1); the cell's traffic file offers 0.8 of it.
"""
import json
import sys
import time
from pathlib import Path

PROCESS_START = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]


def sweep(argv=None, root=ROOT, require_chips=None):
    import argparse

    sys.path.insert(0, str(ROOT))
    from chipbench import device, gen, harness, layout
    from chipbench.drivers.service import percentile

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    cell = layout.load_cell(root, args.workload)
    devs = (require_chips or device.require_chips)(cell.chips)
    harness.import_program()
    harness.use_cache()
    log = lambda m: print(f"sweep: {m}", file=sys.stderr, flush=True)
    drv = layout.driver(root, cell.config["driver"]).Driver(
        cell, args.seed, devs, seconds=args.seconds, span=harness.span,
        log=log)
    drv.setup()
    log(f"set up in {time.monotonic() - PROCESS_START:.1f} s on "
        f"{device.describe(devs)}")
    rows = []
    for k, rate in enumerate(rates):
        due = gen.poisson_schedule(args.seed + k, rate, args.seconds)
        params = drv.request_params(len(due), args.seed + k)
        lat, late, futs, counters = drv.run_schedule(due, params,
                                                     args.seconds)
        answered = (due + lat) <= args.seconds
        third = len(due) // 3
        early, tail = lat[:third], lat[-third:]
        row = {
            "rate_per_s": rate, "offered": int(len(due)),
            "answered_by_close": float(answered.mean()),
            "p50_ms": 1e3 * percentile(lat, 0.5),
            "p95_ms": 1e3 * percentile(lat, 0.95),
            "p99_ms": 1e3 * percentile(lat, 0.99),
            "late_third_over_first_p50": percentile(tail, 0.5)
            / percentile(early, 0.5),
            "bundles": int(counters["bundles"]),
            "occupancy": counters["live_lanes"]
            / max(counters["padded_lanes"], 1.0),
            "max_live": int(counters["max_live"]),
            "compiles": int(counters["compiles"]),
            "generator_late_p99_ms": 1e3 * percentile(late, 0.99),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    sweep()
