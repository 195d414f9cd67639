"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (per the repo contract).
Modules may additionally stash a ``json_artifact = (path, payload)``
during ``run()``; the harness writes it out (e.g. ``ensemble_bench`` ->
``BENCH_ensemble.json``, the ensemble perf-trajectory artifact).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run vector_ops # one module
  PYTHONPATH=src python -m benchmarks.run --check    # CI perf gate
  PYTHONPATH=src python -m benchmarks.run --tune     # autotune cache

``--check`` re-times every configuration recorded in the committed
``BENCH_ensemble.json`` and exits 1 if any pallas-interpret config
falls below its regression floor — 80% of the committed pallas/jnp
speedup ratio, with the committed ratio capped at 1.25 first, so in
practice the gate asserts the kernels keep BEATING the jnp oracle
rather than reproducing a noisy high-water mark (timing gates the
>=4096-system configs; smaller ones are timer-noise-bound and
informational) — or if ANY config drifts past the 1e-14 accuracy
bound.  It then applies the same discipline to every entry in the
committed autotune cache (``.autotune/interpret.json``): the recorded
jnp-vs-pallas winner must still win on re-measure
(autotune_bench.check).  It then runs the serving front-end's
functional invariants (serving_bench.check: trace-cache behavior,
occupancy, warm-start win; latency informational).
This is the gate the CI smoke step runs (ensemble_bench.check
documents the cap rationale).

``--tune`` regenerates the autotune cache: every OP_TABLE op is timed
on both backends over a grid of shape signatures and the measured
winners/tiles are written to ``.autotune/interpret.json`` (committed,
like the BENCH files) — the measurement store that ``backend='auto'``
dispatch resolves from.
"""
from __future__ import annotations

import json
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)

MODULES = [
    "vector_ops",            # paper Fig. 3
    "meshvector_overhead",   # paper Fig. 4
    "brusselator_scaling",   # paper Figs. 7/8/9
    "linear_sum_bandwidth",  # paper Table 1
    "kernels_bench",         # kernel-path microbenchmarks
    "ensemble_bench",        # paper Fig. 5 submodel A/B -> BENCH_ensemble.json
    "sparse_bench",          # sparse-vs-dense Newton solve -> BENCH_sparse.json
    "roofline_table",        # EXPERIMENTS §Roofline (derived from dry-run)
    "serving_bench",         # dynamic-batching server -> BENCH_serving.json
]


def main() -> None:
    if "--tune" in sys.argv[1:]:
        from benchmarks import autotune_bench
        cache = autotune_bench.tune()
        print(f"tune,{len(cache.entries)},{cache.path}")
        sys.exit(0)
    if "--check" in sys.argv[1:]:
        from benchmarks import autotune_bench, ensemble_bench, serving_bench
        ok = ensemble_bench.check()
        print(f"perf_check,{'PASS' if ok else 'FAIL'},BENCH_ensemble.json")
        ok_tune = autotune_bench.check()
        print(f"autotune_check,{'PASS' if ok_tune else 'FAIL'},"
              f".autotune/interpret.json")
        ok_serve = serving_bench.check()
        print(f"serving_check,{'PASS' if ok_serve else 'FAIL'},"
              f"serving invariants (latency informational)")
        sys.exit(0 if (ok and ok_tune and ok_serve) else 1)
    picked = sys.argv[1:] or MODULES
    print("name,us_per_call,derived")
    for name in picked:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # keep the harness going
            print(f"{name}.ERROR,0,{type(e).__name__}:{e}")
            continue
        for r in rows:
            print(",".join(str(x) for x in r), flush=True)
        artifact = getattr(mod, "json_artifact", None)
        if artifact:
            path, payload = artifact
            with open(path, "w") as fh:
                json.dump(payload, fh, indent=2)
            print(f"{name}.json_artifact,0,{path}", flush=True)
        print(f"{name}.total_wall_s,{time.time()-t0:.1f},-", flush=True)


if __name__ == "__main__":
    main()
