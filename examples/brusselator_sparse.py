"""Sparse ensemble Brusselator: dense vs sparse-direct vs preconditioned
Krylov on a banded-Jacobian ensemble (the ECP many-small-systems
workload, arXiv:2405.01713).

Each ensemble member is a 1-D Brusselator reaction-diffusion system
(n = 2*nx species-interleaved unknowns, banded Jacobian: 2x2 reaction
blocks + Laplacian neighbor coupling, fill ~ 4/nx).  Three pluggable
linear solvers integrate the SAME problem through the unified
front-end:

* ``BlockDiagGJ``        — dense batched Gauss-Jordan (O(n^2) storage)
* ``EnsembleSparseGJ``   — batched sparse LU on the shared pattern
                           (symbolic once, O(nnz) storage — the
                           SUNLINSOL_CUSOLVERSP_BATCHQR analog)
* ``SPGMR + BlockJacobi``— matrix-free GMRES, left block-Jacobi
                           preconditioning through PSetup/PSolve

Run:  PYTHONPATH=src python examples/brusselator_sparse.py
      [--nsys 64] [--nx 16] [--tf 2.0] [--pallas]
"""
import argparse
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core.context import Context
from repro.core.ivp import IVP, integrate
from repro.core.linsol import SPGMR, BlockDiagGJ, EnsembleSparseGJ
from repro.core.policies import ExecPolicy, XLA_FUSED
from repro.core.precond import BlockJacobiPrecond
from repro.core.problems import ensemble_brusselator


def run(label, prob, tf, ctx, opts, lin_solver):
    t0 = time.time()
    sol = integrate(prob, 0.0, tf, "ensemble_bdf", ctx=ctx, opts=opts,
                    lin_solver=lin_solver)
    jax.block_until_ready(sol.y)
    wall = time.time() - t0
    st = sol.stats
    nps = 0 if sol.npsolves is None else int(sol.npsolves)
    print(f"  {label:22s}: steps(med)={int(np.median(st.steps)):5d} "
          f"nni={int(sol.nni):7d} nli={int(sol.nli or 0):7d} "
          f"npsolves={nps:7d} nsetups={int(jnp.sum(st.nsetups)):6d} "
          f"ws={sol.workspace_bytes:9d}B "
          f"ok={bool(sol.success)!s:5s} wall={wall:6.2f}s")
    return sol, wall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nsys", type=int, default=64)
    ap.add_argument("--nx", type=int, default=16)
    ap.add_argument("--tf", type=float, default=2.0)
    ap.add_argument("--rtol", type=float, default=1e-6)
    ap.add_argument("--pallas", action="store_true",
                    help="dispatch the kernels as Pallas (interpret)")
    args = ap.parse_args()

    f, jac, pattern, y0 = ensemble_brusselator(args.nsys, args.nx)
    n = 2 * args.nx
    fill = pattern.sum() / (n * n)
    print(f"ensemble brusselator: nsys={args.nsys}, n={n} "
          f"(nnz={int(pattern.sum())}, fill={100 * fill:.1f}%), "
          f"tf={args.tf}")

    prob = IVP(f=f, jac=jac, jac_sparsity=pattern, y0=y0)
    policy = (ExecPolicy(backend="pallas") if args.pallas
              else XLA_FUSED)
    ctx = Context(policy=policy)
    opts = ctx.options(rtol=args.rtol, atol=1e-9, max_steps=400_000)

    sols = {}
    sols["dense"] = run("BlockDiagGJ (dense)", prob, args.tf, ctx, opts,
                        BlockDiagGJ())
    sols["sparse"] = run("EnsembleSparseGJ", prob, args.tf, ctx, opts,
                         EnsembleSparseGJ())
    sols["krylov"] = run("SPGMR+BlockJacobi", prob, args.tf, ctx, opts,
                         SPGMR(tol=1e-10, restart=10, max_restarts=6,
                               precond=BlockJacobiPrecond(block_size=2)))

    y_ref = sols["dense"][0].y
    for k in ("sparse", "krylov"):
        d = float(jnp.max(jnp.abs(sols[k][0].y - y_ref)))
        sp = sols["dense"][1] / max(sols[k][1], 1e-9)
        print(f"  {k:7s} vs dense: max|dy|={d:.2e}, "
              f"dense/{k} wall ratio={sp:.2f}x")
    ws_d = sols["dense"][0].workspace_bytes
    ws_s = sols["sparse"][0].workspace_bytes
    print(f"  newton storage: dense O(n^2)={ws_d}B, "
          f"sparse O(nnz)={ws_s}B ({ws_s / ws_d:.2f}x)")


if __name__ == "__main__":
    main()
