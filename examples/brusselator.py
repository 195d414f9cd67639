"""The paper's §7 demonstration: 1D advection-reaction brusselator.

IMEX (ARK3(2)4L[2]SA) with the task-local Newton + batched 3x3 block
solver, vs the global Newton+GMRES configuration — the two solver
configurations of the paper's weak-scaling study.

Run:  PYTHONPATH=src python examples/brusselator.py [--nx 256] [--tf 1.0]
      [--solver task-local|global|both] [--pallas]
"""
import argparse
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from repro.apps import brusselator as br
from repro.apps.brusselator import BrusselatorConfig
from repro.core.policies import ExecPolicy, XLA_FUSED


def run(cfg, label, policy):
    t0 = time.time()
    y, st = br.integrate(cfg, policy=policy)
    wall = time.time() - t0
    print(f"  {label:11s}: steps={int(st.steps):5d} attempts={int(st.attempts):5d} "
          f"newton={int(st.nni):6d} err_fails={int(st.netf):3d} "
          f"conv_fails={int(st.ncfn):3d} success={bool(st.success)} "
          f"wall={wall:7.2f}s")
    return y, st, wall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nx", type=int, default=256)
    ap.add_argument("--tf", type=float, default=1.0)
    ap.add_argument("--solver", default="both",
                    choices=["task-local", "global", "both"])
    ap.add_argument("--pallas", action="store_true",
                    help="use the Pallas block-solve kernel (compiled on a "
                         "TPU, interpret mode elsewhere)")
    args = ap.parse_args()

    policy = (ExecPolicy(backend="pallas") if args.pallas
              else XLA_FUSED)
    print(f"brusselator1d: nx={args.nx} (={3*args.nx} ODEs), tf={args.tf}, "
          f"eps=5e-6 (stiff)")

    results = {}
    for solver in (["task-local", "global"] if args.solver == "both"
                   else [args.solver]):
        cfg = BrusselatorConfig(nx=args.nx, t_final=args.tf, solver=solver)
        results[solver] = run(cfg, solver, policy)

    if len(results) == 2:
        ytl = results["task-local"][0]
        ygl = results["global"][0]
        diff = float(jnp.max(jnp.abs(ytl - ygl)))
        speedup = results["global"][2] / results["task-local"][2]
        print(f"  solutions agree to {diff:.2e}; task-local is "
              f"{speedup:.2f}x faster (paper: task-local >> global)")
    y = next(iter(results.values()))[0]
    print(f"  final ranges: u [{float(y[:,0].min()):.4f}, "
          f"{float(y[:,0].max()):.4f}]  w [{float(y[:,2].min()):.4f}, "
          f"{float(y[:,2].max()):.4f}]")


if __name__ == "__main__":
    main()
