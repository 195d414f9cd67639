"""Submodel use case (paper §2, Fig. 5): many small independent stiff
kinetics systems integrated concurrently.

On GPUs the paper bundles cell groups into CVODE instances on CUDA
streams; the TPU-native expression is ONE vectorized adaptive integrator
(masked while_loop) whose Newton step solves the Fig.-1 block-diagonal
Jacobian with the batched Gauss-Jordan / Pallas kernel.

Each system is a Robertson-like problem with per-cell rate constants
(the "large variations in stiffness" the paper warns about): per-system
adaptive steps absorb it.

Everything goes through the unified front-end (``IVP`` + ``integrate``);
two method strings share the problem setup:

* default      — ``ensemble_dirk:sdirk2`` (adaptive SDIRK2 ensemble)
* ``--bdf``    — ``ensemble_bdf``, the CVODE-style batched BDF with
                 per-system order/step control and a *pluggable* linear
                 solver: ``--lin-solver setup|direct`` are the two
                 BlockDiagGJ block-kernel configurations, ``spgmr``
                 swaps in matrix-free Krylov without touching the
                 integrator (the paper's SUNLinearSolver point).

Run:  PYTHONPATH=src python examples/batched_kinetics.py [--cells 512]
      PYTHONPATH=src python examples/batched_kinetics.py --bdf --pallas
"""
import argparse
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from repro.core.context import Context
from repro.core.ivp import IVP, integrate
from repro.core.linsol import SPGMR, BlockDiagGJ
from repro.core.policies import ExecPolicy, XLA_FUSED
from repro.core.problems import batched_robertson, batched_robertson_soa


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", type=int, default=512)
    ap.add_argument("--tf", type=float, default=10.0)
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--bdf", action="store_true",
                    help="use the batched adaptive-order BDF ensemble")
    ap.add_argument("--order", type=int, default=5)
    ap.add_argument("--lin-solver", choices=("setup", "direct", "spgmr"),
                    default="setup",
                    help="ensemble-BDF linear solver: factor-once block "
                         "inverse, per-iteration block solve, or "
                         "matrix-free Krylov")
    ap.add_argument("--batch-tile", type=int, default=512,
                    help="systems per kernel program (bundle size)")
    args = ap.parse_args()

    n = args.cells
    f, jac, y0 = batched_robertson(n)
    policy = (ExecPolicy(backend="pallas", batch_tile=args.batch_tile)
              if args.pallas
              else XLA_FUSED)
    ctx = Context(policy=policy)
    opts = ctx.options(rtol=1e-5, atol=1e-10, max_steps=100_000)
    lin = {"setup": BlockDiagGJ(factor_once=True),
           "direct": BlockDiagGJ(factor_once=False),
           "spgmr": SPGMR(tol=1e-9, restart=30, max_restarts=4)}[
        args.lin_solver]
    # native SoA RHS/Jacobian forms (system axis last) make the ensemble
    # Newton hot loop fully conversion-free; same bits as the AoS forms
    f_soa, jac_soa = batched_robertson_soa(n)
    prob = IVP(f=f, jac=jac, y0=y0, f_soa=f_soa, jac_soa=jac_soa)
    kind = (f"BDF(1-{args.order}, {lin.name})" if args.bdf else "SDIRK2")
    print(f"integrating {n} independent stiff kinetics systems with {kind} "
          f"(block-diagonal Jacobian: {n} blocks of 3x3) to t={args.tf}")
    t0 = time.time()
    if args.bdf:
        sol = integrate(prob, 0.0, args.tf, method="ensemble_bdf",
                        ctx=ctx, opts=opts, order=args.order,
                        lin_solver=lin)
    else:
        sol = integrate(prob, 0.0, args.tf, method="ensemble_dirk:sdirk2",
                        ctx=ctx, opts=opts)
    wall = time.time() - t0
    y, st = sol.y, sol.stats
    steps = jax.device_get(st.steps)
    print(f"  all converged: {bool(sol.success)}   wall={wall:.2f}s")
    print(f"  per-system adaptive steps: min={steps.min()} "
          f"median={int(jnp.median(jnp.asarray(steps)))} max={steps.max()}"
          f"   (stiffer cells take more steps)")
    if args.bdf:
        nset = jax.device_get(st.nsetups)
        nni = jax.device_get(st.nni)
        print(f"  Newton iters (median): {int(jnp.median(jnp.asarray(nni)))}"
              f"   lsetups (median): {int(jnp.median(jnp.asarray(nset)))}"
              f"   (Jacobian reuse across steps)")
        if sol.nli is not None and int(sol.nli) > 0:
            print(f"  Krylov inner iterations: {int(sol.nli)}")
    print(f"  solver workspace: {sol.workspace_bytes / 1024:.1f} KiB "
          f"(history + Newton blocks)")
    mass = jnp.sum(y, axis=1)
    print(f"  mass conservation: max |1 - sum(y)| = "
          f"{float(jnp.max(jnp.abs(mass - 1.0))):.2e}")


if __name__ == "__main__":
    main()
