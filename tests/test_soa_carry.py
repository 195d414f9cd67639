"""SoA-carry acceptance gates (ISSUE 5 tentpole).

1. The refactored ensemble BDF (end-to-end SoA carry + fused Newton
   ops) reproduces the PRE-REFACTOR AoS-carry integrator under the jnp
   backend on batched_robertson (nsys in {130, 512}) to within a few
   units of rtol, with the same summed decisions to 2% — the reference
   below is a faithful condensation of the pre-SoA loop (einsum history
   rescale and predictor, per-iteration transposes), kept here as the
   oracle; the lane-dense predictor equals its einsums to 1e-13 on
   random float64 histories.  Native SoA RHS/Jacobian forms
   (``batched_robertson_soa``) must land on the same bits as the
   wrapped AoS forms.
2. jnp-vs-pallas(interpret) parity at 1e-10 for the fused Newton ops
   (+ the per-system ``wrms_soa`` and the Lagrange history rescale)
   with ragged batches; the rescale kernel's weights exact at eta = 1.
3. Layout gate: sunlint's ``hot-loop-layout`` jaxpr rule proves the
   traced Newton ``while_loop`` bodies (BDF and DIRK) contain no
   transposes or copying reshapes — replacing the old source grep,
   which a commented-out ``.T`` tripped and a helper-function
   transpose evaded.
4. MemoryHelper: back-to-back ensemble integrations on one Context do
   not double-buffer the history (donated carry; labels released per
   call, high-water flat across repeats).
"""
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, controller as ctrl, cvode as _cv
from repro.core import dispatch as dv
from repro.core.arkode import ODEOptions
from repro.core.linsol import BlockDiagGJ
from repro.core.policies import ExecPolicy, XLA_FUSED
from repro.core.problems import batched_robertson, batched_robertson_soa


# ---------------------------------------------------------------------------
# The pre-refactor AoS-carry ensemble BDF (condensed, default solver
# config): history (nsys, QMAX+1, n), Newton iterate (nsys, n), einsum
# history rescale, -g.T / dz.T transposes on every Newton iteration and
# jnp.transpose(J, (1,2,0)) at every lsetup — the oracle.
# ---------------------------------------------------------------------------


class _AosCarry(NamedTuple):
    t: jnp.ndarray
    h: jnp.ndarray
    q: jnp.ndarray
    Z: jnp.ndarray
    e1: jnp.ndarray
    e2: jnp.ndarray
    MJ: jnp.ndarray
    gam_saved: jnp.ndarray
    since_jac: jnp.ndarray
    ncf_prev: jnp.ndarray
    steps: jnp.ndarray
    att: jnp.ndarray
    netf: jnp.ndarray
    nni: jnp.ndarray
    nsetups: jnp.ndarray
    ncfn: jnp.ndarray
    stall: jnp.ndarray


def _aos_bdf_reference(f, jac, y0, t0, tf, *, order=5,
                       opts=ODEOptions(), msbp=20, dgmax=0.3):
    from jax import lax
    ls = BlockDiagGJ()
    policy = XLA_FUSED
    nsys, n = y0.shape
    dtype = y0.dtype
    QMAX = _cv.QMAX
    t0 = jnp.broadcast_to(jnp.asarray(t0, dtype), (nsys,))
    tf = jnp.broadcast_to(jnp.asarray(tf, dtype), (nsys,))
    h0 = jnp.where(opts.h0 > 0, jnp.full((nsys,), opts.h0, dtype),
                   jnp.maximum(1e-6 * (tf - t0), 1e-12))
    one = jnp.ones((), dtype)

    def wrms(v, w):
        return jnp.sqrt(jnp.mean((v * w) ** 2, axis=1))

    def cond(c):
        return jnp.any((c.t < tf * (1 - 1e-12)) & (~c.stall)) & \
            jnp.all(c.att < opts.max_steps)

    def body(c):
        active = (c.t < tf * (1 - 1e-12)) & (~c.stall)
        hs = jnp.where(active, jnp.minimum(c.h, tf - c.t), c.h)
        nvalid = jnp.minimum(c.steps, QMAX)
        eta_clip = jnp.where(active, hs / c.h, one)
        W = jax.vmap(_cv._lagrange_matrix)(eta_clip, nvalid)
        Z = jnp.einsum("sji,sik->sjk", W, c.Z)
        qi = c.q - 1
        alphas = jnp.asarray(_cv._ALPHA_T, dtype)[qi]
        beta = jnp.asarray(_cv._BETA_T, dtype)[qi]
        p_pred = jnp.minimum(nvalid, c.q)
        pred_c = jnp.asarray(_cv._PREDP_T, dtype)[p_pred]
        y_pred = jnp.einsum("sj,sjk->sk", pred_c, Z)
        psi = -jnp.einsum("sj,sjk->sk", alphas[:, 1:], Z[:, :-1])
        gamma = beta * hs
        t_new = c.t + hs
        w = 1.0 / (opts.rtol * jnp.abs(Z[:, 0]) + opts.atol)

        gamrat = gamma / jnp.where(c.gam_saved != 0, c.gam_saved, gamma)
        need = active & ((c.gam_saved == 0) | c.ncf_prev |
                         (c.since_jac >= msbp) |
                         (jnp.abs(gamrat - 1.0) > dgmax))

        def do_setup(_):
            J = jac(t_new, y_pred)
            return ls.soa_setup(jnp.transpose(J, (1, 2, 0)), gamma, policy)

        MJ_new = lax.cond(jnp.any(need), do_setup, lambda _: c.MJ,
                          operand=None)
        MJ = jax.tree_util.tree_map(
            lambda new, old: jnp.where(need, new, old), MJ_new, c.MJ)
        gam_saved = jnp.where(need, gamma, c.gam_saved)
        since_jac = jnp.where(need, 0, c.since_jac)
        gamrat = jnp.where(need, 1.0, gamrat)

        def nl_cond(s):
            z, it, dn_prev, crate, conv, div, nni_s = s
            return jnp.any(active & ~conv & ~div) & (it < opts.newton_max)

        def nl_body(s):
            z, it, dn_prev, crate, conv, div, nni_s = s
            iterate = active & ~conv & ~div
            g = z - gamma[:, None] * f(t_new, z) - psi
            dz_soa, _, _ = ls.soa_solve(MJ, gamma, gamrat, -g.T, policy)
            dz = dz_soa.T
            z_new = jnp.where(iterate[:, None], z + dz, z)
            dn = wrms(dz, w)
            crate_new = jnp.where(
                it > 0,
                jnp.maximum(0.3 * crate,
                            dn / jnp.maximum(dn_prev, 1e-30)), crate)
            conv_new = conv | (iterate &
                               (dn * jnp.minimum(one, crate_new) <
                                opts.newton_tol_fac))
            div_new = div | (iterate & (it > 0) & (dn > 2.0 * dn_prev))
            return (z_new, it + 1,
                    jnp.where(iterate, dn, dn_prev),
                    jnp.where(iterate, crate_new, crate),
                    conv_new, div_new, nni_s + iterate.astype(jnp.int32))

        s0 = (y_pred, jnp.zeros((), jnp.int32), jnp.zeros((nsys,), dtype),
              jnp.ones((nsys,), dtype), ~active, jnp.zeros((nsys,), bool),
              jnp.zeros((nsys,), jnp.int32))
        z, _, _, _, conv, _, nni_s = lax.while_loop(nl_cond, nl_body, s0)

        err = wrms(z - y_pred, w) / (c.q.astype(dtype) + 1.0)
        bad = ~jnp.isfinite(err) | ~conv
        err = jnp.where(bad, 2.0, err)
        accept = (err <= 1.0) & ~bad & active

        cst = ctrl.ControllerState(err_prev=c.e1, err_prev2=c.e2)
        eta, cst_new = ctrl.eta_from_error(opts.controller, cst, err,
                                           c.q + 1,
                                           after_failure=(~accept) & conv)
        eta = jnp.where(conv | ~active, eta, opts.eta_cf)
        eta = jnp.clip(eta, 0.1, 10.0)
        hs_safe = jnp.maximum(hs, jnp.finfo(dtype).tiny)
        eta = jnp.clip(eta, opts.hmin / hs_safe, opts.hmax / hs_safe)
        e1 = jnp.where(accept, cst_new.err_prev, c.e1)
        e2 = jnp.where(accept, cst_new.err_prev2, c.e2)

        Z_acc = jnp.roll(Z, 1, axis=1).at[:, 0].set(z)
        Z_next = jnp.where(accept[:, None, None], Z_acc, Z)
        q_next = jnp.where(accept, jnp.minimum(c.q + 1, order), c.q)
        nval_after = jnp.minimum(c.steps + accept.astype(jnp.int32), QMAX)
        W2 = jax.vmap(_cv._lagrange_matrix)(
            jnp.where(active, eta, one), nval_after)
        Z_next = jnp.einsum("sji,sik->sjk", W2, Z_next)

        t_next = jnp.where(accept, t_new, c.t)
        h_next = jnp.where(active, hs * eta, c.h)
        stall = c.stall | (active & (hs * eta < 1e-14))
        ncf = active & ~conv
        ai = active.astype(jnp.int32)
        return _AosCarry(
            t=t_next, h=h_next, q=q_next, Z=Z_next, e1=e1, e2=e2,
            MJ=MJ, gam_saved=gam_saved, since_jac=since_jac + ai,
            ncf_prev=ncf,
            steps=c.steps + accept.astype(jnp.int32),
            att=c.att + ai,
            netf=c.netf + ((~accept) & conv & active).astype(jnp.int32),
            nni=c.nni + nni_s,
            nsetups=c.nsetups + need.astype(jnp.int32),
            ncfn=c.ncfn + ncf.astype(jnp.int32), stall=stall)

    zero = jnp.zeros((nsys,), jnp.int32)
    Z0 = jnp.zeros((nsys, QMAX + 1, n), dtype).at[:, 0].set(y0)
    c = _AosCarry(
        t=t0, h=h0, q=jnp.ones((nsys,), jnp.int32), Z=Z0,
        e1=jnp.ones((nsys,), dtype), e2=jnp.ones((nsys,), dtype),
        MJ=ls.soa_carry_init(n, nsys, dtype),
        gam_saved=jnp.zeros((nsys,), dtype), since_jac=zero,
        ncf_prev=jnp.zeros((nsys,), bool), steps=zero, att=zero,
        netf=zero, nni=zero, nsetups=zero, ncfn=zero,
        stall=jnp.zeros((nsys,), bool))
    c = jax.lax.while_loop(cond, body, c)
    return c.Z[:, 0], c


# ---------------------------------------------------------------------------
# 1. trajectory parity, SoA carry vs pre-refactor AoS carry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nsys", [130, 512])
def test_soa_carry_bitwise_vs_pre_refactor_aos(nsys):
    """The SoA-carry jnp path against the pre-refactor AoS oracle.  The
    two accumulate the Lagrange rescale and the predictor in different
    orders (the oracle's einsums, the integrator's in-layout
    multiply-adds), so the trajectories are no longer the same bits:
    they agree at the solve's own accuracy, and make the same
    decisions to within a few in a thousand."""
    f, jac, y0 = batched_robertson(nsys)
    rtol, atol = 1e-5, 1e-10
    opts = ODEOptions(rtol=rtol, atol=atol, max_steps=100_000)
    y_ref, c_ref = _aos_bdf_reference(f, jac, y0, 0.0, 10.0, opts=opts)
    y_new, st = batched.ensemble_bdf_integrate(
        f, jac, y0, 0.0, 10.0, opts=opts, policy=XLA_FUSED)
    assert bool(jnp.all(st.success))
    y_ref, y_new = np.asarray(y_ref), np.asarray(y_new)
    # two solutions that each meet rtol differ by a few of its units
    units = np.abs(y_new - y_ref) / (rtol * np.abs(y_ref) + atol)
    assert units.max() <= 3.0, units.max()
    # the decision streams, summed over the lanes
    for k in ("steps", "nni", "nsetups"):
        ref = int(np.sum(np.asarray(getattr(c_ref, k))))
        new = int(np.sum(np.asarray(getattr(st, k))))
        assert abs(new - ref) <= 0.02 * ref, (k, ref, new)


@pytest.mark.parametrize("q", range(1, _cv.QMAX + 1))
def test_predict_soa_matches_aos_einsum(q):
    """The lane-dense predictor equals the per-lane coefficient gathers
    contracted as AoS einsums, at every valid depth, on a ragged
    float64 batch."""
    nsys, n = 130, 3
    Z = jax.random.normal(jax.random.PRNGKey(q), (_cv.QMAX + 1, n, nsys),
                          jnp.float64)
    Zaos = jnp.transpose(Z, (2, 0, 1))
    for nvalid in range(_cv.QMAX + 1):
        qv = jnp.full((nsys,), q, jnp.int32)
        nv = jnp.full((nsys,), nvalid, jnp.int32)
        y_pred, psi = batched._predict_soa(Z, qv, nv)
        pred_c = jnp.asarray(_cv._PREDP_T)[jnp.minimum(nv, qv)]
        alphas = jnp.asarray(_cv._ALPHA_T)[qv - 1]
        y_ref = jnp.einsum("sj,sjk->sk", pred_c, Zaos).T
        psi_ref = -jnp.einsum("sj,sjk->sk", alphas[:, 1:], Zaos[:, :-1]).T
        assert y_pred.dtype == psi.dtype == jnp.float64
        np.testing.assert_allclose(np.asarray(y_pred), np.asarray(y_ref),
                                   rtol=0, atol=1e-13)
        np.testing.assert_allclose(np.asarray(psi), np.asarray(psi_ref),
                                   rtol=0, atol=1e-13)


def test_native_soa_rhs_matches_wrapped_aos_bitwise():
    """batched_robertson_soa's native SoA f/jac land on the same bits
    as the transposing wrapper around the AoS forms."""
    nsys = 130
    f, jac, y0 = batched_robertson(nsys)
    f_soa, jac_soa = batched_robertson_soa(nsys)
    opts = ODEOptions(rtol=1e-5, atol=1e-10, max_steps=100_000)
    y_w, st_w = batched.ensemble_bdf_integrate(
        f, jac, y0, 0.0, 10.0, opts=opts)
    y_n, st_n = batched.ensemble_bdf_integrate(
        f, jac, y0, 0.0, 10.0, opts=opts, f_soa=f_soa, jac_soa=jac_soa)
    assert bool(jnp.all(st_n.success))
    assert np.array_equal(np.asarray(y_w), np.asarray(y_n))
    assert np.array_equal(np.asarray(st_w.steps), np.asarray(st_n.steps))


# ---------------------------------------------------------------------------
# 2. fused-op jnp vs pallas(interpret) parity, ragged batches
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb", [7, 130, 516])
@pytest.mark.parametrize("tile", [128, 512])
def test_fused_newton_ops_parity_ragged(nb, tile):
    n, q1 = 3, _cv.QMAX + 1
    pol = ExecPolicy(backend="pallas", interpret=True, batch_tile=tile)
    z = jax.random.normal(jax.random.PRNGKey(0), (n, nb))
    fv = jax.random.normal(jax.random.PRNGKey(1), (n, nb))
    psi = jax.random.normal(jax.random.PRNGKey(2), (n, nb))
    gam = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (nb,)))
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (n, nb))) + 0.1
    m = jax.random.uniform(jax.random.PRNGKey(5), (nb,)) > 0.4
    # step ratios over the controller's [0.1, 10], a fifth of the lanes
    # at exactly 1 (an unchanged step), every valid depth 0..QMAX
    eta = jnp.exp(jax.random.uniform(jax.random.PRNGKey(6), (nb,),
                                     minval=np.log(0.1),
                                     maxval=np.log(10.0)))
    eta = eta.at[::5].set(1.0)
    qv = jax.random.randint(jax.random.PRNGKey(8), (nb,), 0, q1)
    Z = jax.random.normal(jax.random.PRNGKey(7), (q1, n, nb))

    for negate in (False, True):
        a = dv.newton_residual_soa(z, fv, psi, gam, XLA_FUSED,
                                   negate=negate)
        b = dv.newton_residual_soa(z, fv, psi, gam, pol, negate=negate)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-10)
    za, dna = dv.masked_update_wrms_soa(z, fv, w, m, XLA_FUSED)
    zb, dnb = dv.masked_update_wrms_soa(z, fv, w, m, pol)
    np.testing.assert_allclose(np.asarray(za), np.asarray(zb),
                               rtol=0, atol=1e-10)
    np.testing.assert_allclose(np.asarray(dna), np.asarray(dnb),
                               rtol=0, atol=1e-10)
    ra = np.asarray(dv.lagrange_rescale_soa(eta, qv, Z, m, XLA_FUSED))
    rb = np.asarray(dv.lagrange_rescale_soa(eta, qv, Z, m, pol))
    # 1e-10 in units of the sum's own size: far from eta = 1 the weights
    # reach 1e7 and the terms cancel
    W = np.asarray(jax.vmap(_cv._lagrange_matrix)(eta, qv))
    size = 1.0 + np.einsum("sji,iks->jks", np.abs(W), np.abs(np.asarray(Z)))
    assert np.max(np.abs(ra - rb) / size) <= 1e-10
    # inactive systems pass through bit-exactly on both backends, and
    # so do active ones at eta = 1 on the kernel's
    off = ~np.asarray(m)
    same = off | (np.asarray(eta) == 1.0)
    assert np.array_equal(ra[:, :, off], np.asarray(Z)[:, :, off])
    assert np.array_equal(rb[:, :, same], np.asarray(Z)[:, :, same])
    r0 = dv.lagrange_rescale_soa(eta, qv, Z, jnp.zeros((nb,), bool), pol)
    assert np.array_equal(np.asarray(r0), np.asarray(Z))
    wa = dv.wrms_soa(z, w, XLA_FUSED)
    wb = dv.wrms_soa(z, w, pol)
    np.testing.assert_allclose(np.asarray(wa), np.asarray(wb),
                               rtol=0, atol=1e-10)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_lagrange_rescale_exact_at_unit_ratio(dtype):
    """The kernel's weights: each reciprocal denominator times its
    integer denominator rounds to exactly 1, so at eta = 1 the weights
    are the exact identity and row 0 is an exact delta at any eta; the
    jnp oracle's matrix agrees with them at every depth."""
    import math
    from repro.kernels import newton as knw
    q1 = _cv.QMAX + 1
    r = knw.reciprocal_denominators(q1, dtype)
    for q in range(q1):
        for i in range(q1):
            den = (-1) ** i * math.factorial(i) * math.factorial(q - i) \
                if i <= q else 0
            assert dtype(den) * dtype(r[q][i]) == (1 if i <= q else 0)
    pol = ExecPolicy(backend="pallas", interpret=True, batch_tile=128)
    nb = 3 * q1
    qv = jnp.tile(jnp.arange(q1), 3)
    eta = jnp.repeat(jnp.asarray([1.0, 0.5, 3.0], dtype), q1)
    # the identity as the history: the result is W itself, lane by lane
    Z = jnp.broadcast_to(jnp.eye(q1, dtype=dtype)[:, :, None], (q1, q1, nb))
    on = jnp.ones((nb,), bool)
    Wk = np.asarray(dv.lagrange_rescale_soa(eta, qv, Z, on, pol))
    Wr = np.asarray(jax.vmap(_cv._lagrange_matrix)(eta, qv))
    eye = np.eye(q1, dtype=dtype)
    for s in range(nb):
        if eta[s] == 1.0:
            assert np.array_equal(Wk[:, :, s], eye)
        assert np.array_equal(Wk[0, :, s], eye[0])
        np.testing.assert_allclose(
            Wk[:, :, s], Wr[s], rtol=0,
            atol=16 * np.finfo(dtype).eps * np.abs(Wr[s]).max())


# ---------------------------------------------------------------------------
# 3. layout gate: no layout conversions inside the Newton loop bodies
# (the sunlint jaxpr rule — the retired source grep passed on
# commented-out transposes and missed helper-function ones)
# ---------------------------------------------------------------------------


def test_newton_loop_body_has_no_transposes():
    from repro.analysis import lint
    ctx = lint.LintContext()
    violations = lint.run_rules(ctx, ["hot-loop-layout"])
    assert violations == [], "\n".join(
        f"{v.where}: {v.message}" for v in violations)


# ---------------------------------------------------------------------------
# 4. donated carry: back-to-back runs don't double-buffer the history
# ---------------------------------------------------------------------------


def test_history_not_double_buffered_across_runs():
    from repro.core.context import Context
    from repro.core.ivp import IVP, integrate

    nsys = 8
    f, jac, y0 = batched_robertson(nsys)
    prob = IVP(f=f, jac=jac, y0=y0)
    ctx = Context()
    opts = ctx.options(rtol=1e-5, atol=1e-10, max_steps=100_000)
    sol1 = integrate(prob, 0.0, 1.0, "ensemble_bdf", ctx=ctx, opts=opts)
    hw1 = ctx.memory.high_water_bytes
    live1 = ctx.memory.live_bytes
    sol2 = integrate(prob, 0.0, 1.0, "ensemble_bdf", ctx=ctx, opts=opts)
    assert bool(sol1.success) and bool(sol2.success)
    # labels were released between the calls, so the second history
    # registration reuses the same accounting slot: high-water is FLAT
    assert ctx.memory.high_water_bytes == hw1
    assert ctx.memory.live_bytes == live1
    # the donated-carry path really ran twice with identical results
    assert bool(jnp.all(sol1.y == sol2.y))
    # and the history workspace was actually accounted (nonzero)
    assert sol1.workspace_bytes >= \
        (_cv.QMAX + 1) * 3 * nsys * np.dtype(np.float64).itemsize


def test_donation_never_deletes_caller_arrays():
    """Donating the carry must not consume CALLER buffers: an (nsys,)
    t0 of the carry dtype short-circuits broadcast_to/asarray, so the
    carry takes an explicit copy (regression: the caller's t0 raised
    'Array has been deleted' after the integration)."""
    nsys = 6
    f, jac, y0 = batched_robertson(nsys)
    t0 = jnp.zeros((nsys,), jnp.float64)
    opts = ODEOptions(rtol=1e-5, atol=1e-10, max_steps=100_000)
    y, st = batched.ensemble_bdf_integrate(f, jac, y0, t0, 1.0, opts=opts)
    assert bool(jnp.all(st.success))
    # both caller arrays must still be alive and usable
    assert float(jnp.sum(t0)) == 0.0
    assert float(jnp.sum(y0)) == nsys
