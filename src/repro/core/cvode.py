"""CVODE analog: adaptive-step BDF (orders 1-5) for stiff ODEs.

Fixed-leading-coefficient BDF on a uniform history window:
* history Z holds y at t, t-h, ..., t-q*h (flattened);
* predictor = degree-q polynomial extrapolation;
* corrector solves  y - gamma f(t+h, y) = psi  by Newton (gamma = beta_q h);
* on step-size change the history is rebuilt by evaluating the degree-q
  interpolant on the new uniform grid (this is how VODE/CVODE's
  fixed-leading-coefficient strategy handles variable h);
* order ramps 1 -> q_target during startup (one order per accepted step).

Simplifications vs CVODE proper (documented in DESIGN.md): order is
ramped up but not adaptively lowered, and the LTE constant is the
uniform-grid value.  Functional (Adams/fixed-point) mode is provided for
nonstiff problems via :func:`adams_integrate`.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree

import warnings

from . import controller as ctrl
from . import dispatch as dv
from . import status
from .nonlinsol import FixedPointSolver, NewtonSolver
from .policies import ExecPolicy
from .arkode import ODEOptions, IntegratorStats, _bind_lin_solver

QMAX = 5

# Uniform-grid BDF coefficients, normalized alpha_0 = 1:
#   sum_j alpha_j y_{n+1-j} = h * beta * f_{n+1}
_BDF_ALPHA = [
    [1.0, -1.0, 0.0, 0.0, 0.0, 0.0],
    [1.0, -4 / 3, 1 / 3, 0.0, 0.0, 0.0],
    [1.0, -18 / 11, 9 / 11, -2 / 11, 0.0, 0.0],
    [1.0, -48 / 25, 36 / 25, -16 / 25, 3 / 25, 0.0],
    [1.0, -300 / 137, 300 / 137, -200 / 137, 75 / 137, -12 / 137],
]
_BDF_BETA = [1.0, 2 / 3, 6 / 11, 12 / 25, 60 / 137]

# Extrapolation predictor coefficients on a uniform grid, by polynomial
# DEGREE p (row p uses Z[0..p]):  y_pred = sum_j (-1)^j C(p+1, j+1) y_{n-j}.
# Degree 0 = constant predictor (used on the very first step, when only
# y0 is in the history — CVODE instead seeds the Nordsieck array with
# h*f0; our constant guess only weakens the first-step error estimate).
_PREDP = [[1.0] + [0.0] * QMAX]
for p in range(1, QMAX + 1):
    row = [((-1.0) ** j) * math.comb(p + 1, j + 1) for j in range(p + 1)]
    _PREDP.append(row + [0.0] * (QMAX + 1 - len(row)))

# host float64 tables: exact whether or not x64 is on when this module
# is imported, and importing it touches no device.  Index them through
# jnp.asarray(table, dtype)[q] at the working precision.
_ALPHA_T = np.array(_BDF_ALPHA)
_BETA_T = np.array(_BDF_BETA)
_PREDP_T = np.array(_PREDP)


def _lagrange_matrix(eta, q_cur):
    """(QMAX+1, QMAX+1) matrix W with  Z_new[j] = sum_i W[j,i] Z_old[i].

    Old nodes sit at x_i = -i (units of h_old); new nodes at -j*eta.
    Rows/cols beyond q_cur are masked to identity so stale history slots
    stay untouched (they are ignored by the masked coefficient tables).
    """
    idx = jnp.arange(QMAX + 1, dtype=eta.dtype)
    pts = -idx * eta                                    # new node positions
    # Lagrange basis L_i(p) = prod_{k != i} (p + k) / (k - i)
    p = pts[:, None, None]                              # (j, 1, 1)
    k = idx[None, None, :]                              # (1, 1, k)
    i = idx[None, :, None]                              # (1, i, 1)
    num = jnp.where(k == i, 1.0, p + k)
    den = jnp.where(k == i, 1.0, k - i)
    # only product over k <= q_cur
    mask_k = (idx[None, None, :] <= q_cur)
    ratio = jnp.where(mask_k, num / den, 1.0)
    W = jnp.prod(ratio, axis=2)                         # (j, i)
    valid_i = (idx[None, :] <= q_cur)
    W = jnp.where(valid_i, W, 0.0)
    valid_j = (idx[:, None] <= q_cur)
    eye = jnp.eye(QMAX + 1, dtype=eta.dtype)
    return jnp.where(valid_j, W, eye)


def bdf_integrate(f: Callable, y0, t0, tf, *, order: int = 5,
                  opts: ODEOptions = ODEOptions(),
                  lin_solver: Optional[Callable] = None,
                  dense_jac: bool = False,
                  nonlin_solver: Optional[NewtonSolver] = None,
                  mem=None, telemetry: Optional[int] = None):
    """Integrate stiff y' = f(t, y) with BDF up to ``order``.

    ``lin_solver`` is a :class:`repro.core.linsol.LinearSolver` object
    or a legacy callable ``(t, z, gamma, rhs) -> dz`` solving
    (I - gamma J) dz = rhs; defaults to matrix-free SPGMR, or
    :class:`~repro.core.linsol.DenseGJ` if ``dense_jac=True``.
    ``nonlin_solver`` defaults to the ODEOptions Newton tolerances;
    ``mem`` registers the BDF history workspace when given.
    ``telemetry=K`` threads a K-slot step-telemetry ring through the
    loop carry (one scalar record per step attempt, every value an
    already-computed intermediate) and appends it to the return tuple;
    the default ``None`` leaves the traced loop byte-identical to a
    build without the feature (sunlint ``telemetry-purity``).
    """
    assert 1 <= order <= QMAX
    if lin_solver is None and dense_jac:
        from .linsol import DenseGJ
        lin_solver = DenseGJ()
    lin_solve = _bind_lin_solver(lin_solver, f, opts, mem)
    nls = nonlin_solver or NewtonSolver.from_options(opts)
    y0_flat, unravel = ravel_pytree(y0)
    n = y0_flat.shape[0]
    if mem is not None:
        mem.register("bdf.history", (QMAX + 1, n), y0_flat.dtype)
    t0 = jnp.asarray(t0, dtype=y0_flat.dtype)
    tf = jnp.asarray(tf, dtype=t0.dtype)

    def f_flat(t, yf):
        return ravel_pytree(f(t, unravel(yf)))[0]

    def lin_solve_flat(t, zf, gamma, rhsf):
        dz = lin_solve(t, unravel(zf), gamma, unravel(rhsf))
        return ravel_pytree(dz)[0]

    from .arkode import _initial_h
    h0 = jnp.where(opts.h0 > 0, opts.h0,
                   _initial_h(lambda t, y: unravel(f_flat(t, ravel_pytree(y)[0])),
                              t0, y0, tf, opts.rtol, opts.atol,
                              opts.policy))

    class Carry(NamedTuple):
        t: jnp.ndarray
        h: jnp.ndarray
        q: jnp.ndarray               # current order
        Z: jnp.ndarray               # (QMAX+1, n) history, Z[0] = y(t)
        cst: ctrl.ControllerState
        stats: IntegratorStats
        retcode: jnp.ndarray         # scalar int32 CV_*-style status
        ncf_cur: jnp.ndarray         # consecutive Newton conv failures
        nef_cur: jnp.ndarray         # consecutive error-test failures

    def cond(c):
        return ((c.t < tf * (1 - 1e-12) - 1e-300) &
                (c.stats.attempts < opts.max_steps) & (c.retcode == 0))

    def step(c):
        h = jnp.minimum(c.h, tf - c.t)
        # number of valid history entries is steps+1 -> max usable degree
        nvalid_m1 = jnp.minimum(c.stats.steps, QMAX)
        # if we clipped h to hit tf, rescale history accordingly
        eta_clip = h / c.h
        Z = jnp.einsum("ji,ik->jk", _lagrange_matrix(eta_clip, nvalid_m1),
                       c.Z)
        qi = c.q - 1
        alphas = jnp.asarray(_ALPHA_T)[qi]          # (QMAX+1,)
        beta = jnp.asarray(_BETA_T)[qi]
        p_pred = jnp.minimum(nvalid_m1, c.q)        # predictor degree
        pred_c = jnp.asarray(_PREDP_T)[p_pred]
        y_pred = pred_c @ Z                          # (n,)
        psi = -(alphas[1:] @ Z[:-1])                 # uses y_n .. y_{n-q+1}
        # NOTE: alphas[j] multiplies y_{n+1-j}; history Z[i] = y_{n-i}
        # so sum_{j>=1} alpha_j y_{n+1-j} = sum_{i>=0} alpha_{i+1} Z[i].
        gamma = beta * h
        t_new = c.t + h
        w_flat = 1.0 / (opts.rtol * jnp.abs(Z[0]) + opts.atol)

        def wnorm(v):
            return dv.wrms_norm(v, w_flat, opts.policy)

        def gfun(z):
            return z - gamma * f_flat(t_new, z) - psi

        def nsolve(z, rhs):
            return lin_solve_flat(t_new, z, gamma, rhs)

        z, nst = nls.solve(gfun, y_pred, nsolve, wnorm=wnorm,
                           policy=opts.policy)
        nl_ok = nst.converged
        # LTE estimate ~ C_q (y - y_pred); C_q = 1/(q+1) (uniform grid)
        err_raw = wnorm(z - y_pred) / (c.q.astype(h.dtype) + 1.0)
        bad = ~jnp.isfinite(err_raw) | ~nl_ok
        err = jnp.where(bad, 2.0, err_raw)
        accept = (err <= 1.0) & ~bad
        eta, cst = ctrl.eta_from_error(
            opts.controller, c.cst, err, c.q + 1, after_failure=(~accept) & nl_ok)
        eta = jnp.where(nl_ok, eta, opts.eta_cf)
        cst = jax.tree_util.tree_map(
            lambda a, b: jnp.where(accept, a, b), cst, c.cst)
        # accepted: shift history and insert z at slot 0
        Z_acc = jnp.roll(Z, 1, axis=0).at[0].set(z)
        Z_next = jnp.where(accept, Z_acc, Z)
        q_next = jnp.where(accept, jnp.minimum(c.q + 1, order), c.q)
        # rescale history onto the new uniform grid (only over the rows
        # that hold computed values: steps+accept of them + the new one)
        eta = jnp.clip(eta, 0.1, 10.0)
        nval_after = jnp.minimum(c.stats.steps + accept.astype(jnp.int32),
                                 QMAX)
        Z_next = jnp.einsum("ji,ik->jk",
                            _lagrange_matrix(eta, nval_after), Z_next)
        t_n = jnp.where(accept, t_new, c.t)
        h_n = jnp.clip(h * eta, opts.hmin, opts.hmax)
        # CV_*-style escalation, scalar form of the ensemble contract:
        # consecutive-failure ceilings, h underflow, non-finite iterate
        ncf_cur = jnp.where(accept, 0,
                            c.ncf_cur + (~nl_ok).astype(jnp.int32))
        nef_cur = jnp.where(
            accept, 0,
            c.nef_cur + ((~accept) & nl_ok &
                         jnp.isfinite(err_raw)).astype(jnp.int32))
        # relative underflow (t + h == t): stiff problems legitimately
        # visit tiny absolute h near transients and recover
        hfail = c.t + h * eta == c.t
        rc = c.retcode
        rc = jnp.where((nef_cur >= status.MXNEF) | (hfail & nl_ok),
                       status.ERR_FAILURE, rc)
        rc = jnp.where((ncf_cur >= status.MXNCF) | (hfail & ~nl_ok),
                       status.CONV_FAILURE, rc)
        rc = jnp.where(nl_ok & ~jnp.isfinite(err_raw),
                       status.RHSFUNC_FAIL, rc)
        st = c.stats
        st = st._replace(
            steps=st.steps + accept.astype(jnp.int32),
            attempts=st.attempts + 1,
            nfi=st.nfi + 1 + nst.iters, nni=st.nni + nst.iters,
            netf=st.netf + ((~accept) & nl_ok).astype(jnp.int32),
            ncfn=st.ncfn + (~nl_ok).astype(jnp.int32),
            last_h=h, t=t_n)
        carry = Carry(t_n, h_n, q_next, Z_next, cst, st, rc, ncf_cur,
                      nef_cur)
        # telemetry record: already-computed intermediates only
        rec = (t_new, h, c.q, nst.iters, err, nl_ok, accept)
        return carry, rec

    def body(c):
        return step(c)[0]

    Z0 = jnp.zeros((QMAX + 1, n), dtype=y0_flat.dtype).at[0].set(y0_flat)
    zero = jnp.zeros((), jnp.int32)
    stats0 = IntegratorStats(zero, zero, zero, zero, zero, zero, zero,
                             h0, t0, jnp.zeros((), bool))
    c = Carry(t0, h0, jnp.ones((), jnp.int32), Z0,
              ctrl.init_state(t0.dtype), stats0, zero, zero, zero)
    ring = None
    if telemetry is None:
        c = lax.while_loop(cond, body, c)
    else:
        from ..observability.telemetry import ring_init, ring_record

        def tel_body(cr):
            new_c, (t_new, h, q, iters, err, nl_ok, accept) = step(cr[0])
            # scalar integrator: there is no lsetup trigger (matrix-free
            # or per-iteration solve) and no masked-lane concept — the
            # constants are built here, outside the disabled trace
            rec = (t_new, h, q, iters, err, jnp.zeros((), bool), nl_ok,
                   accept, jnp.ones((), bool))
            return new_c, ring_record(cr[1], rec)

        c, ring = lax.while_loop(
            lambda cr: cond(cr[0]), tel_body,
            (c, ring_init(telemetry, (), y0_flat.dtype)))
    success = c.t >= tf * (1 - 1e-10)
    # loop exit with a healthy retcode but tf unreached == the attempts
    # ceiling fired: reconcile to TOO_MUCH_WORK (CV_TOO_MUCH_WORK)
    retcode = jnp.where((c.retcode == 0) & ~success,
                        status.TOO_MUCH_WORK, c.retcode)
    stats = c.stats._replace(success=success, retcode=retcode)
    if ring is not None:
        return unravel(c.Z[0]), stats, ring
    return unravel(c.Z[0]), stats


def bdf_fixed(f: Callable, y0, t0, tf, n_steps: int, *, order: int = 2,
              lin_solver: Optional[Callable] = None, dense_jac: bool = True,
              newton_iters: Optional[int] = None,
              policy: Optional[ExecPolicy] = None,
              opts: Optional[ODEOptions] = None):
    """Fixed-step BDF(order) with exact startup via high-order ERK.

    For convergence-order tests: global error should scale as h^order.
    Newton depth and the vector-op policy route through ``opts``
    (``newton_max``, floored at 8 — fixed-step Newton has no retry
    path — and ``policy``); the bare ``newton_iters`` / ``policy``
    kwargs are deprecated compat shims.
    """
    from .arkode import erk_fixed
    from .butcher import DORMAND_PRINCE

    if opts is None:
        opts = ODEOptions()
    # Fixed-step Newton has no failure/retry path, so its depth is
    # floored at 8 regardless of the adaptive default (newton_max=4):
    # a generic opts=ctx.options() must not silently halve the legacy
    # depth and let nonlinear error pollute the measured orders.  Raise
    # it with opts=ODEOptions(newton_max=12).
    newton_depth = max(opts.newton_max, 8)
    if newton_iters is not None:
        warnings.warn("repro-compat: bdf_fixed(newton_iters=...) is "
                      "deprecated; pass opts=ODEOptions(newton_max=...)",
                      DeprecationWarning, stacklevel=2)
        newton_depth = newton_iters    # exact, for backward compat
    if policy is not None:
        warnings.warn("repro-compat: bdf_fixed(policy=...) is deprecated; "
                      "pass opts=ODEOptions(policy=...)",
                      DeprecationWarning, stacklevel=2)
        opts = opts._replace(policy=policy)
    if lin_solver is None and dense_jac:
        from .linsol import DenseGJ
        lin_solver = DenseGJ()
    lin_solve = _bind_lin_solver(lin_solver, f, opts)
    y0_flat, unravel = ravel_pytree(y0)
    n = y0_flat.shape[0]
    h = (tf - t0) / n_steps
    qi = order - 1
    alphas = jnp.asarray(_ALPHA_T)[qi]
    beta = jnp.asarray(_BETA_T)[qi]

    def f_flat(t, yf):
        return ravel_pytree(f(t, unravel(yf)))[0]

    def lin_solve_flat(t, zf, gamma, rhsf):
        return ravel_pytree(lin_solve(t, unravel(zf), gamma,
                                      unravel(rhsf)))[0]

    # startup: seed history with DP5 fixed steps (accurate enough)
    hist = [y0_flat]
    y_cur = y0
    for k in range(order - 1):
        y_cur = erk_fixed(f, y_cur, t0 + k * h, t0 + (k + 1) * h, 4,
                          DORMAND_PRINCE)
        hist.insert(0, ravel_pytree(y_cur)[0])
    Z = jnp.stack(hist + [jnp.zeros_like(y0_flat)] *
                  (QMAX + 1 - len(hist)))   # Z[0] most recent

    def step(carry, k):
        Z, = carry
        t_new = t0 + (k + order) * h     # t of the new point
        psi = -(alphas[1:] @ Z[:-1])
        gamma = beta * h

        def wnorm(v):
            return jnp.sqrt(dv.dot(v, v, opts.policy) / n)

        def gfun(z):
            return z - gamma * f_flat(t_new, z) - psi

        def nsolve(z, rhs):
            return lin_solve_flat(t_new, z, gamma, rhs)

        # fixed tol=1e-10: the nonlinear error must stay far below the
        # discretization error being measured by the order tests
        nls = NewtonSolver(tol=1e-10, max_iters=newton_depth)
        z, _ = nls.solve(gfun, Z[0], nsolve, wnorm=wnorm,
                         policy=opts.policy)
        Z = jnp.roll(Z, 1, axis=0).at[0].set(z)
        return (Z,), None

    (Z,), _ = lax.scan(step, (Z,), jnp.arange(n_steps - (order - 1)))
    return unravel(Z[0])


def adams_integrate(f: Callable, y0, t0, tf,
                    opts: ODEOptions = ODEOptions(), m_aa: int = 2,
                    nonlin_solver: Optional[FixedPointSolver] = None,
                    mem=None):
    """CVODE functional-iteration mode for nonstiff problems:
    Adams-Moulton(2) (trapezoid) corrector solved by Anderson-accelerated
    fixed-point, AB2 predictor, adaptive h via predictor-corrector diff.
    ``nonlin_solver`` (:class:`~repro.core.nonlinsol.FixedPointSolver`)
    defaults to the ODEOptions-derived tolerance."""
    fps = nonlin_solver or FixedPointSolver.from_options(opts, m=m_aa)
    y0_flat, unravel = ravel_pytree(y0)
    n = y0_flat.shape[0]
    if mem is not None:
        mem.register("adams.anderson", (2 * fps.m, n), y0_flat.dtype)
    t0 = jnp.asarray(t0, dtype=y0_flat.dtype)
    tf = jnp.asarray(tf, dtype=t0.dtype)

    def f_flat(t, yf):
        return ravel_pytree(f(t, unravel(yf)))[0]

    from .arkode import _initial_h
    h0 = jnp.where(opts.h0 > 0, opts.h0,
                   _initial_h(lambda t, y: unravel(f_flat(t, ravel_pytree(y)[0])),
                              t0, y0, tf, opts.rtol, opts.atol,
                              opts.policy))

    class Carry(NamedTuple):
        t: jnp.ndarray
        y: jnp.ndarray
        fprev: jnp.ndarray
        h: jnp.ndarray
        cst: ctrl.ControllerState
        stats: IntegratorStats
        give_up: jnp.ndarray

    def cond(c):
        return ((c.t < tf * (1 - 1e-12) - 1e-300) &
                (c.stats.attempts < opts.max_steps) & (~c.give_up))

    def body(c):
        h = jnp.minimum(c.h, tf - c.t)
        fn = f_flat(c.t, c.y)
        # AB2 predictor (falls back to Euler when fprev invalid = first step)
        first = c.stats.steps == 0
        y_pred = jnp.where(first, c.y + h * fn,
                           c.y + h * (1.5 * fn - 0.5 * c.fprev))
        t_new = c.t + h

        def gfun(z):
            return c.y + 0.5 * h * (fn + f_flat(t_new, z))

        z, fst = fps.solve(gfun, y_pred)
        w = 1.0 / (opts.rtol * jnp.abs(c.y) + opts.atol)
        err = dv.wrms_norm(z - y_pred, w, opts.policy) / 6.0
        bad = ~jnp.isfinite(err) | ~fst.converged
        err = jnp.where(bad, 2.0, err)
        accept = (err <= 1.0) & ~bad
        eta, cst = ctrl.eta_from_error(opts.controller, c.cst, err, 3,
                                       after_failure=~accept)
        eta = jnp.where(fst.converged, eta, opts.eta_cf)
        cst = jax.tree_util.tree_map(
            lambda a, b: jnp.where(accept, a, b), cst, c.cst)
        t_n = jnp.where(accept, t_new, c.t)
        y_n = jnp.where(accept, z, c.y)
        f_n = jnp.where(accept, fn, c.fprev)
        h_n = jnp.clip(h * eta, opts.hmin, opts.hmax)
        st = c.stats
        st = st._replace(steps=st.steps + accept.astype(jnp.int32),
                         attempts=st.attempts + 1,
                         nfe=st.nfe + 2 + fst.iters,
                         netf=st.netf + (~accept).astype(jnp.int32),
                         last_h=h, t=t_n)
        return Carry(t_n, y_n, f_n, h_n, cst, st, h * eta < 1e-14)

    zero = jnp.zeros((), jnp.int32)
    stats0 = IntegratorStats(zero, zero, zero, zero, zero, zero, zero,
                             h0, t0, jnp.zeros((), bool))
    c = Carry(t0, y0_flat, jnp.zeros_like(y0_flat), h0,
              ctrl.init_state(t0.dtype), stats0, jnp.zeros((), bool))
    c = lax.while_loop(cond, body, c)
    stats = c.stats._replace(success=c.t >= tf * (1 - 1e-10))
    return unravel(c.y), stats
