"""Kernel-path microbenchmarks: batched block solve & fused vecops.

Times the pure-jnp (XLA) implementations — the performance-relevant
backend on this host — and runs the Pallas kernels in interpret mode for
a correctness spot-check under benchmark shapes (their TPU performance
is modeled in EXPERIMENTS.md §Perf from BlockSpec arithmetic).

``--smoke`` runs the fast jnp-vs-pallas(interpret) A/B check over every
dispatched vector op (the CI gate): both backends are invoked through
the repro.core.dispatch table and must agree to tolerance, and every op
is additionally run under ``backend='auto'`` (the autotune-cache /
cost-model resolver) against the jnp oracle.  It also sweeps the
unified front-end: one ``repro.core.ivp.integrate`` call per canonical
method string under the jnp, pallas-interpret, AND auto policies,
asserting success (so a regression in any method family or in the
policy plumbing fails CI before the full suite runs).
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import direct, matrix
from repro.kernels import ops, ref


def _t(fn, *a, reps=20):
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    for nb, b in ((1024, 3), (8192, 3), (4096, 8)):
        A = jax.random.normal(key, (nb, b, b)) + (b + 2.0) * jnp.eye(b)
        r = jax.random.normal(jax.random.PRNGKey(1), (nb, b))
        gj = jax.jit(direct.gauss_jordan_batched)
        t_gj = _t(gj, A, r)
        lu = jax.jit(lambda A, r: direct.block_lu_solve(
            direct.block_lu_factor(matrix.BlockDiagMatrix(A)), r, b))
        t_lu = _t(lu, A, r)
        x = ops.block_solve(A, r, batch_tile=128)   # pallas interpret check
        err = float(jnp.max(jnp.abs(x - ref.block_solve_ref(A, r))))
        rows.append((f"block_solve.nb{nb}.b{b}.gj_xla", t_gj,
                     f"lu_us={t_lu:.1f},pallas_interp_err={err:.1e}"))
    for K, N in ((5, 2 ** 20),):
        c = jnp.arange(1.0, K + 1)
        X = jax.random.normal(key, (K, N))
        fused = jax.jit(lambda c, X: jnp.einsum("k,kn->n", c, X))
        pairwise = jax.jit(lambda c, X: sum(c[i] * X[i] for i in range(K)))
        rows.append((f"lincomb.K{K}.N{N}.fused", _t(fused, c, X),
                     f"pairwise_us={_t(pairwise, c, X):.1f}"))
    return rows


def smoke(n: int = 4096, tol: float = 1e-5):
    """Fast dispatch-layer A/B: every op, jnp vs pallas-interpret AND
    jnp vs backend='auto' (cache/cost-model-resolved per call site),
    with a per-op timing row.  Exits nonzero on any mismatch (CI
    gate)."""
    from repro.core import dispatch as dp
    from repro.core import vector as nv
    from repro.core.policies import AUTO, GRID_STRIDE, XLA_FUSED

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n,))
    y = jax.random.normal(jax.random.PRNGKey(1), (n,))
    z = jax.random.normal(jax.random.PRNGKey(2), (n,))
    w = jnp.abs(y) + 0.1
    m = (x > 0).astype(x.dtype)
    coeffs = [0.3, -1.2, 2.5]
    # ensemble block ops: a deliberately non-multiple-of-128 batch so the
    # gate also covers the bundle-tile padding path
    nb, bs = 516, 3
    Ab = jax.random.normal(jax.random.PRNGKey(3), (bs, bs, nb)) + \
        (bs + 2.0) * jnp.eye(bs)[:, :, None]
    rb = jax.random.normal(jax.random.PRNGKey(4), (bs, nb))
    # row-tiled GJ regime (b > 8) under the same ragged batch
    bt = 16
    At = jax.random.normal(jax.random.PRNGKey(9), (bt, bt, nb)) + \
        (bt + 2.0) * jnp.eye(bt)[:, :, None]
    rt = jax.random.normal(jax.random.PRNGKey(10), (bt, nb))
    # fused ensemble-Newton op operands (SoA (n, nsys), ragged batch)
    gmb = jnp.abs(jax.random.normal(jax.random.PRNGKey(11), (nb,)))
    wb = jnp.abs(jax.random.normal(jax.random.PRNGKey(12), (bs, nb))) + 0.1
    mb = jax.random.uniform(jax.random.PRNGKey(13), (nb,)) > 0.4
    q1 = 6
    eh = jnp.exp(jax.random.uniform(jax.random.PRNGKey(14), (nb,),
                                    minval=-0.7, maxval=0.7))
    qh = jax.random.randint(jax.random.PRNGKey(16), (nb,), 0, q1)
    Zh = jax.random.normal(jax.random.PRNGKey(15), (q1, bs, nb))
    # sparse ops: a banded CSR pattern (non-lane-multiple rows) and a
    # shared block pattern with a ragged system batch
    ncsr = 133
    pat_el = np.abs(np.arange(ncsr)[:, None] - np.arange(ncsr)) <= 2
    from repro.core.sunmatrix import SparseCSR
    csr = SparseCSR.from_dense(
        np.asarray(jax.random.normal(jax.random.PRNGKey(5),
                                     (ncsr, ncsr))) * pat_el)
    xs = jax.random.normal(jax.random.PRNGKey(6), (ncsr,))
    nblk, bb, nbs = 5, 3, 130
    brows, bcols = zip(*[(i, j) for i in range(nblk)
                         for j in range(nblk) if abs(i - j) <= 1])
    bpat = (tuple(brows), tuple(bcols), nblk)
    Vb = jax.random.normal(jax.random.PRNGKey(7),
                           (len(brows), bb, bb, nbs)) + \
        jnp.where((jnp.asarray(brows) == jnp.asarray(bcols))
                  [:, None, None, None],
                  (bb + 2.0) * jnp.eye(bb)[None, :, :, None], 0.0)
    xb = jax.random.normal(jax.random.PRNGKey(8), (nblk, bb, nbs))
    cases = {
        "linear_sum": lambda p: dp.linear_sum(2.0, x, -0.5, y, p),
        "linear_combination": lambda p: dp.linear_combination(
            coeffs, [x, y, z], p),
        "scale_add_multi": lambda p: jnp.stack(
            dp.scale_add_multi(coeffs, x, [x, y, z], p)),
        "axpy": lambda p: dp.axpy(1.7, x, y, p),
        "dot": lambda p: dp.dot(x, y, p),
        "wrms_norm": lambda p: dp.wrms_norm(x, w, p),
        "wrms_norm_mask": lambda p: dp.wrms_norm_mask(x, w, m, p),
        "dot_prod_multi": lambda p: dp.dot_prod_multi(x, [y, z, w], p),
        "block_solve_soa": lambda p: dp.block_solve_soa(Ab, rb, p),
        "block_inverse_soa": lambda p: dp.block_inverse_soa(Ab, p),
        "blockdiag_spmv_soa": lambda p: dp.blockdiag_spmv_soa(Ab, rb, p),
        "block_solve_soa.b16": lambda p: dp.block_solve_soa(At, rt, p),
        "block_inverse_soa.b16": lambda p: dp.block_inverse_soa(At, p),
        "newton_residual_soa": lambda p: dp.newton_residual_soa(
            rb, wb, rb, gmb, p, negate=True),
        "masked_update_wrms_soa": lambda p: jnp.concatenate(
            [x.ravel() for x in dp.masked_update_wrms_soa(rb, rb, wb,
                                                          mb, p)]),
        "lagrange_rescale_soa": lambda p: dp.lagrange_rescale_soa(
            eh, qh, Zh, mb, p),
        "wrms_soa": lambda p: dp.wrms_soa(rb, wb, p),
        "csr_spmv": lambda p: dp.csr_spmv(csr.data, xs, csr.pattern, p),
        "bsr_spmv_soa": lambda p: dp.bsr_spmv_soa(Vb, xb, bpat, p),
        "bsr_block_jacobi_inverse_soa":
            lambda p: dp.bsr_block_jacobi_inverse_soa(Vb, bpat, p),
    }
    rows, ok = [], True
    for name, fn in cases.items():
        a = np.asarray(fn(XLA_FUSED))
        t0 = time.perf_counter()
        b = np.asarray(fn(GRID_STRIDE))
        t_p = (time.perf_counter() - t0) * 1e6
        err = float(np.max(np.abs(a - b)))
        good = err <= tol
        ok &= good
        rows.append((f"smoke.{name}", "PASS" if good else "FAIL",
                     f"maxerr={err:.2e},pallas_us={t_p:.0f}"))
        # auto backend: whatever the cache/model resolves must agree too
        c = np.asarray(fn(AUTO))
        err_a = float(np.max(np.abs(a - c)))
        good_a = err_a <= tol
        ok &= good_a
        rows.append((f"smoke.auto.{name}", "PASS" if good_a else "FAIL",
                     f"maxerr={err_a:.2e}"))
    return rows, ok


def frontend_smoke():
    """One `integrate` call per canonical method string, under both the
    jnp and the pallas-interpret ExecPolicy.  Small problems, loose
    tolerances — this gates wiring, not accuracy."""
    import jax.numpy as jnp

    from repro.core.arkode import ODEOptions
    from repro.core.context import Context
    from repro.core.ivp import IVP, METHOD_STRINGS, integrate
    from repro.core.policies import AUTO, GRID_STRIDE, XLA_FUSED

    lam = 12.0
    f1 = lambda t, y: -lam * (y - jnp.cos(t))
    fe1 = lambda t, y: lam * jnp.cos(t) * jnp.ones_like(y)
    fi1 = lambda t, y: -lam * y
    nsys, n = 4, 3
    rates = jnp.linspace(2.0, lam, nsys)
    fb = lambda t, y: -rates[:, None] * (y - jnp.cos(t)[:, None])
    jb = lambda t, y: jnp.broadcast_to(
        -rates[:, None, None] * jnp.eye(n), (y.shape[0], n, n))

    scalar = IVP(f=f1, y0=jnp.zeros((2,)))
    imex = IVP(fe=fe1, fi=fi1, y0=jnp.zeros((2,)))
    ens = IVP(f=fb, jac=jb, y0=jnp.zeros((nsys, n)))

    rows, ok = [], True
    for pname, pol in (("jnp", XLA_FUSED), ("pallas", GRID_STRIDE),
                       ("auto", AUTO)):
        ctx = Context(policy=pol)
        opts = ctx.options(rtol=1e-4, atol=1e-7, max_steps=20_000)
        for m in METHOD_STRINGS:
            prob = imex if m.startswith("imex") else \
                ens if m.startswith("ensemble") else scalar
            t0 = time.perf_counter()
            sol = integrate(prob, 0.0, 1.0, m, ctx=ctx, opts=opts)
            us = (time.perf_counter() - t0) * 1e6
            good = bool(sol.success) and bool(
                jnp.all(jnp.isfinite(jnp.asarray(sol.y))))
            ok &= good
            rows.append((f"frontend.{pname}.{m}",
                         "PASS" if good else "FAIL",
                         f"nni={int(sol.nni)},ws={sol.workspace_bytes}B,"
                         f"us={us:.0f}"))
    return rows, ok


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        rows, ok = smoke()
        fr_rows, fr_ok = frontend_smoke()
        for r in rows + fr_rows:
            print(",".join(str(x) for x in r))
        sys.exit(0 if (ok and fr_ok) else 1)
    for r in run():
        print(",".join(str(x) for x in r))
