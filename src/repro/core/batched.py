"""Ensemble (submodel) integration — the paper's Fig. 5 use case, TPU-native.

SUNDIALS' submodel pattern: many small independent ODE systems (one per
grid cell) are grouped into bundles and integrated concurrently by
distinct CVODE instances on different CUDA streams.  On TPU, concurrency
comes from *batching*: one vectorized integrator advances every system
simultaneously, each with its own adaptive step size; systems that have
reached ``tf`` are masked no-ops inside the shared ``while_loop``.
This removes the stream/thread machinery entirely while preserving the
semantics (independent adaptive integrations) — see DESIGN.md §2.

The block-diagonal Jacobian of Fig. 1 appears here as the vmapped dense
(b×b) stage Jacobian; the batched Newton solve uses the batched
Gauss-Jordan / Pallas block-solve kernel.

Three integrators share the masked-while_loop pattern:

* :func:`ensemble_erk_integrate`  — adaptive explicit RK (nonstiff);
* :func:`ensemble_dirk_integrate` — adaptive DIRK, fixed-count Newton
  ``while_loop`` per stage;
* :func:`ensemble_bdf_integrate`  — the CVODE-style subsystem: adaptive
  order (BDF 1-5) + step per system, convergence-tested modified Newton
  with Jacobian reuse and gamma-refresh (lsetup/lsolve split), linear
  algebra routed through the SoA block kernels via ExecPolicy dispatch,
  and a :func:`ensemble_bdf_integrate_sharded` shard_map path that
  scales the system axis across devices.

**Hot-loop layout (SoA everywhere, nsys LAST).**  The BDF and DIRK
Newton paths carry every iteration-sized array — BDF history ``Z``
(QMAX+1, n, nsys), Newton iterate ``z`` (n, nsys), weights, residuals —
in the structure-of-arrays layout the kernels and the LinearSolver SoA
surface speak natively, so the loop body performs ZERO layout
conversions per Newton iteration (the old AoS carry transposed the
residual in and the correction out on every iteration, and the Jacobian
at every lsetup).  User RHS/Jacobian callables stay in the documented
AoS batch convention (``(t:(nsys,), y:(nsys,n))``); pass native SoA
forms (``f_soa(t, y:(n,nsys))``, ``jac_soa -> (n,n,nsys)``) to make the
boundary conversion-free as well — otherwise a thin wrapper transposes
at the call site only (same cost as the old layout, paid once per RHS
evaluation instead of spread over every op).

The per-iteration work runs through three fused dispatch ops
(``newton_residual_soa``, ``masked_update_wrms_soa``,
``lagrange_rescale_soa``; see :mod:`repro.kernels.newton`), and the BDF
step loop is executed with its carry **donated** so XLA updates the
history window in place instead of double-buffering it.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import controller as ctrl
from . import cvode as _cv
from . import dispatch as dv
from . import status
from .arkode import ODEOptions
from .butcher import ButcherTable
from .policies import ExecPolicy, XLA_FUSED


def _donated_loop(cond, body, carry):
    """Run the masked step loop with the carry buffers donated.

    Only safe when every carry leaf is a distinct buffer freshly
    allocated inside the integrator — true for the BDF carry (``y0``
    is copied into the history window and ``t`` is an explicit copy,
    since broadcast_to can alias a caller-shaped ``t0``), NOT for the
    ERK/DIRK carries, which hold ``y0`` itself and must leave the
    caller's buffer alive.
    At top level XLA may then reuse the carry in place — back-to-back
    integrations never hold two live copies of the (QMAX+1, n, nsys)
    history.  Under an outer trace (an enclosing jit or shard_map) the
    inner jit inlines and donation is a no-op, which is exactly the
    while_loop carry aliasing XLA applies there anyway.
    """
    return jax.jit(lambda c: lax.while_loop(cond, body, c),
                   donate_argnums=0)(carry)


def _wrap_soa(f, jac, f_soa, jac_soa):
    """Default SoA RHS/Jacobian forms: thin transposing wrappers around
    the AoS batch callables when no native SoA form is supplied (the
    only remaining layout conversion, at the user-function boundary)."""
    if f_soa is None:
        f_soa = lambda t, z: f(t, z.T).T
    if jac_soa is None:
        jac_soa = lambda t, z: jnp.transpose(jac(t, z.T), (1, 2, 0))
    return f_soa, jac_soa


class EnsembleStats(NamedTuple):
    steps: jnp.ndarray       # (nsys,) accepted steps per system
    attempts: jnp.ndarray
    netf: jnp.ndarray
    nni: jnp.ndarray
    success: jnp.ndarray     # (nsys,) bool
    nsetups: Optional[jnp.ndarray] = None   # (nsys,) lsetup count (BDF)
    ncfn: Optional[jnp.ndarray] = None      # (nsys,) Newton conv failures
    nli: Optional[jnp.ndarray] = None       # (nsys,) linear (Krylov) iters,
    # a solver-level count broadcast per system (direct solvers report 0)
    npsolves: Optional[jnp.ndarray] = None  # (nsys,) preconditioner solves,
    # broadcast like nli (0 without a Preconditioner object)
    retcodes: Optional[jnp.ndarray] = None  # (nsys,) int32 CV_*-style flag
    # per system (repro.core.status; 0 == SUCCESS, negative == quarantined)
    ok: Optional[jnp.ndarray] = None        # (nsys,) bool, retcodes == 0
    # loop trips of the whole batch (ensemble BDF), broadcast like nli:
    # step-loop iterations, Newton-loop iterations over all steps, and
    # steps whose lsetup branch ran.  The kernels work over every lane
    # on each trip, so sum(attempts) / (trips * nsys) is the step loop's
    # lane occupancy and sum(nni) / (newton_trips * nsys) the Newton's.
    # On the sharded path each entry holds its own shard's trips (each
    # device runs its own loops): occupancy there sums over the shard's
    # lanes and divides by the shard's lane count
    trips: Optional[jnp.ndarray] = None
    newton_trips: Optional[jnp.ndarray] = None
    setup_trips: Optional[jnp.ndarray] = None

    def masked(self, live) -> "EnsembleStats":
        """Stats restricted to the ``live`` lanes of a padded bundle.

        A serving bundle padded to a bucket size carries dead lanes
        (``tf == t0`` no-op systems) whose mere presence must not leak
        into aggregates: a dead lane did no work, so its per-lane
        counters are zeroed and it reports success (sums and means over
        the batch then describe live systems only).  The solver-level
        broadcast counters (``nli``, ``npsolves``) are GLOBAL totals of
        the batched inner solves, and the loop trips (``trips``,
        ``newton_trips``, ``setup_trips``) count the batch's loops —
        none is per-lane attributable, and they pass through
        unchanged.
        """
        live = jnp.asarray(live, bool)

        def z(x):
            return None if x is None else jnp.where(live, x, 0)

        return self._replace(
            steps=z(self.steps), attempts=z(self.attempts),
            netf=z(self.netf), nni=z(self.nni),
            success=self.success | ~live,
            nsetups=z(self.nsetups), ncfn=z(self.ncfn),
            retcodes=z(self.retcodes),      # dead lane -> SUCCESS (0)
            ok=None if self.ok is None else self.ok | ~live)


class SolverSession(NamedTuple):
    """Opaque warm-start continuation state for ``ensemble_bdf``.

    The final SoA step-loop carry of one integration, exported with
    ``return_session=True`` and accepted back via ``session=`` so a
    repeat/streaming client re-enters the BDF loop at its terminal
    order and step size instead of paying the cold order-1 restart.
    Every leaf keeps the system axis LAST (the hot-loop layout), so
    per-lane slicing (``lanes``) and bundle assembly (``concat``) are
    uniform ``[..., idx]`` / concatenate-on-last-axis operations — the
    serving layer composes mixed warm/cold bundles this way.

    ``h <= 0`` is the cold-lane sentinel: re-entry substitutes the
    default ``h0`` there, which is how :meth:`cold` sessions reproduce
    the plain ``y0`` start exactly (one trace serves any warm/cold lane
    mix).  The exported leaves are fresh loop outputs and NEVER alias
    the donated step-loop carry; on re-entry the session is copied into
    fresh buffers before donation so the caller's handle stays valid
    (audited by sunlint's donation-aliasing rule).
    """

    t: jnp.ndarray        # (nsys,) time reached
    h: jnp.ndarray        # (nsys,) step size; <= 0 marks a cold lane
    q: jnp.ndarray        # (nsys,) int32 current BDF order
    Z: jnp.ndarray        # (QMAX+1, n, nsys) uniform-grid history, SoA
    e1: jnp.ndarray       # (nsys,) controller err_prev
    e2: jnp.ndarray       # (nsys,) controller err_prev2
    steps: jnp.ndarray    # (nsys,) int32 cumulative accepted steps
    #                       (bounds how much of Z is valid history)

    @property
    def nsys(self) -> int:
        return self.Z.shape[-1]

    @property
    def n(self) -> int:
        return self.Z.shape[-2]

    @classmethod
    def cold(cls, y0: jnp.ndarray, t0) -> "SolverSession":
        """A cold-start session for ``y0`` (nsys, n) at ``t0`` — the
        value-exact equivalent of passing ``y0`` without a session."""
        nsys, n = y0.shape
        dtype = y0.dtype
        return cls(
            t=jnp.broadcast_to(jnp.asarray(t0, dtype), (nsys,)),
            h=jnp.zeros((nsys,), dtype),                   # cold sentinel
            q=jnp.ones((nsys,), jnp.int32),
            Z=jnp.zeros((_cv.QMAX + 1, n, nsys), dtype).at[0].set(y0.T),
            e1=jnp.ones((nsys,), dtype), e2=jnp.ones((nsys,), dtype),
            steps=jnp.zeros((nsys,), jnp.int32))

    def lanes(self, idx) -> "SolverSession":
        """The session restricted to lane(s) ``idx`` (kept as an nsys
        axis: pass a slice/array so the result can be re-concatenated)."""
        return jax.tree_util.tree_map(lambda x: x[..., idx], self)

    @staticmethod
    def concat(sessions) -> "SolverSession":
        """Stack per-lane sessions into one bundle along the system
        axis (the serving layer's mixed warm/cold bundle assembly)."""
        sessions = list(sessions)
        return jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=-1), *sessions)


def ensemble_erk_integrate(f: Callable, y0: jnp.ndarray, t0, tf,
                           table: ButcherTable,
                           opts: ODEOptions = ODEOptions()):
    """Adaptive ERK over a batch of independent systems.

    f  : (t:(nsys,), y:(nsys, n)) -> (nsys, n)   vectorized RHS
    y0 : (nsys, n);  t0, tf broadcastable to (nsys,)
    Each system carries its own (t, h); the loop runs until all done.

    Tables without an embedding (``table.b_emb is None``) provide no
    error estimate, so adaptivity is impossible: the integrator falls
    back to fixed-step semantics (every step accepted, h never grown)
    instead of silently disabling error control and letting h run away
    at ``eta_max``.
    """
    nsys, n = y0.shape
    has_emb = table.b_emb is not None
    dtype = y0.dtype
    t0 = jnp.broadcast_to(jnp.asarray(t0, dtype), (nsys,))
    tf = jnp.broadcast_to(jnp.asarray(tf, dtype), (nsys,))
    # opts.h0 seeds the step; without an embedding it IS the fixed step.
    h = jnp.where(opts.h0 > 0, jnp.full((nsys,), opts.h0, dtype),
                  jnp.maximum(1e-6 * (tf - t0), 1e-12))
    p = max(table.emb_order + 1, 2)

    def cond(c):
        t, y, h, e1, steps, att, netf, stall = c
        return jnp.any((t < tf * (1 - 1e-12)) & (~stall)) & \
            jnp.all(att < opts.max_steps)

    def body(c):
        t, y, h, e1, steps, att, netf, stall = c
        active = (t < tf * (1 - 1e-12)) & (~stall)
        hs = jnp.minimum(h, tf - t)                      # (nsys,)
        ks = []
        for i in range(table.stages):
            yi = y
            for j in range(i):
                if table.A[i][j] != 0.0:
                    yi = yi + (hs * table.A[i][j])[:, None] * ks[j]
            ks.append(f(t + table.c[i] * hs, yi))
        y_new = y
        for bi, k in zip(table.b, ks):
            if bi != 0.0:
                y_new = y_new + (hs * bi)[:, None] * k
        y_err = jnp.zeros_like(y)
        if has_emb:
            for bi, bh, k in zip(table.b, table.b_emb, ks):
                if (bi - bh) != 0.0:
                    y_err = y_err + (hs * (bi - bh))[:, None] * k
        w = 1.0 / (opts.rtol * jnp.abs(y) + opts.atol)
        # per-system WRMS through the dispatched op (ExecPolicy-routed;
        # the .T views are exact layout changes XLA folds away on the
        # jnp backend — the ERK carry itself stays AoS, it has no
        # Newton hot loop to justify an SoA flip)
        err = dv.wrms_soa(y_err.T, w.T, opts.policy)        # (nsys,)
        bad = ~jnp.isfinite(err) | ~jnp.all(jnp.isfinite(y_new), axis=1)
        err = jnp.where(bad, 2.0, err)
        accept = (err <= 1.0) & ~bad & active
        if has_emb:
            # per-system PI controller
            e = jnp.maximum(err, 1e-10)
            eprev = jnp.maximum(e1, 1e-10)
            eta = opts.controller.safety * e ** (-opts.controller.k1 / p) * \
                eprev ** (opts.controller.k2 / p)
            eta = jnp.clip(eta, opts.controller.eta_min,
                           opts.controller.eta_max)
            eta = jnp.where(accept | ~active, eta, jnp.minimum(eta, 0.3))
        else:
            # no embedding -> no error signal: keep h fixed (shrink only
            # on a non-finite step so the loop can still bail out)
            e = jnp.maximum(err, 1e-10)
            eta = jnp.where(bad & active, 0.5, 1.0)
        t = jnp.where(accept, t + hs, t)
        y = jnp.where(accept[:, None], y_new, y)
        h_next = jnp.where(active, jnp.clip(hs * eta, 1e-14, None), h)
        stall = stall | (active & (h_next < 1e-13))
        e1 = jnp.where(accept, e, e1)
        return (t, y, h_next, e1,
                steps + accept.astype(jnp.int32),
                att + active.astype(jnp.int32),
                netf + (active & ~accept).astype(jnp.int32), stall)

    zero = jnp.zeros((nsys,), jnp.int32)
    c = (t0, y0, h, jnp.ones((nsys,), dtype), zero, zero, zero,
         jnp.zeros((nsys,), bool))
    t, y, h, e1, steps, att, netf, stall = lax.while_loop(cond, body, c)
    return y, EnsembleStats(steps=steps, attempts=att, netf=netf,
                            nni=zero, success=t >= tf * (1 - 1e-10))


def ensemble_dirk_integrate(fi: Callable, jac: Callable, y0: jnp.ndarray,
                            t0, tf, table: ButcherTable,
                            opts: ODEOptions = ODEOptions(),
                            policy: ExecPolicy = XLA_FUSED,
                            newton_iters: int = 4,
                            f_soa: Optional[Callable] = None,
                            jac_soa: Optional[Callable] = None,
                            telemetry: Optional[int] = None):
    """Adaptive DIRK over a batch of independent *stiff* systems with the
    batched block-diagonal Newton solve (the paper's submodel solver).

    fi  : (t:(nsys,), y:(nsys,n)) -> (nsys,n)
    jac : (t:(nsys,), y:(nsys,n)) -> (nsys,n,n)   per-system Jacobian
    Newton matrix M_j = I - h a_ii J_j is solved for ALL systems in one
    batched Gauss-Jordan (kernels/block_solve on TPU).

    The stage Newton iterations run in the SoA hot-loop layout shared
    with :func:`ensemble_bdf_integrate` (iterate/residual ``(n, nsys)``,
    fused ``newton_residual_soa`` + dispatched ``block_solve_soa``);
    the layout flips once per *stage*, not once per iteration.  Native
    SoA forms ``f_soa(t, y:(n,nsys)) -> (n,nsys)`` /
    ``jac_soa -> (n,n,nsys)`` remove even the per-RHS-call transposes.
    """
    from .linsol import newton_blocks_soa

    nsys, n = y0.shape
    dtype = y0.dtype
    f_s, jac_s = _wrap_soa(fi, jac, f_soa, jac_soa)
    t0 = jnp.broadcast_to(jnp.asarray(t0, dtype), (nsys,))
    tf = jnp.broadcast_to(jnp.asarray(tf, dtype), (nsys,))
    # opts.h0 seeds the step, same contract as ensemble_erk_integrate
    h = jnp.where(opts.h0 > 0, jnp.full((nsys,), opts.h0, dtype),
                  jnp.maximum(1e-6 * (tf - t0), 1e-12))
    p = max(table.emb_order + 1, 2)
    unit_w = jnp.ones((n, nsys), dtype)      # unweighted per-system RMS

    def cond(c):
        t, y, h, e1, steps, att, netf, nni, rc, ncf_cur, nef_cur = c
        # integer att ceiling kept in the cond (sunlint bounded-loops);
        # it never binds — lanes quarantine with TOO_MUCH_WORK first
        return jnp.any((t < tf * (1 - 1e-12)) & (rc == 0)) & \
            jnp.all(att <= opts.max_steps)

    def step(c):
        t, y, h, e1, steps, att, netf, nni, rc, ncf_cur, nef_cur = c
        active = (t < tf * (1 - 1e-12)) & (rc == 0)
        hs = jnp.minimum(h, tf - t)
        ks = []
        nl_ok = jnp.ones((nsys,), bool)
        nni_step = jnp.zeros((nsys,), jnp.int32)
        for i in range(table.stages):
            r = y
            for j in range(i):
                if table.A[i][j] != 0.0:
                    r = r + (hs * table.A[i][j])[:, None] * ks[j]
            aii = table.A[i][i]
            ti = t + table.c[i] * hs
            if aii == 0.0:
                ks.append(fi(ti, r))
            else:
                # ---- SoA stage Newton (shared hot-loop layout) ----
                gam = hs * aii                            # (nsys,)
                rs = r.T                                  # (n, nsys), once
                # a real while_loop (not a Python unroll) so the body is
                # a single jaxpr sunlint's hot-loop-layout rule audits,
                # exactly like the BDF Newton loop
                def nl_cond(nc, _k=newton_iters):
                    _z, it, _nni = nc
                    return it < _k

                def nl_body(nc, ti=ti, rs=rs, gam=gam):
                    z_s, it, nni_s = nc
                    rhs = dv.newton_residual_soa(z_s, f_s(ti, z_s), rs,
                                                 gam, policy, negate=True)
                    M = newton_blocks_soa(jac_s(ti, z_s), gam)
                    z_s = z_s + dv.block_solve_soa(M, rhs, policy)
                    # nni counts per ACTIVE system: finished systems are
                    # masked no-ops and must not accrue iterations
                    return (z_s, it + 1,
                            nni_s + active.astype(jnp.int32))

                z_s, _, nni_step = lax.while_loop(
                    nl_cond, nl_body, (rs, jnp.int32(0), nni_step))
                fz = f_s(ti, z_s)          # final RHS: residual AND stage
                g = dv.newton_residual_soa(z_s, fz, rs, gam, policy)
                res = dv.wrms_soa(g, unit_w, policy)
                tol_nl = opts.newton_tol_fac * (
                    opts.rtol * dv.wrms_soa(z_s, unit_w, policy)
                    + opts.atol)
                nl_ok = nl_ok & ((res <= jnp.maximum(tol_nl, 1e-12)) |
                                 ~active)
                # the stage derivative is the SAME evaluation the
                # residual used (a native f_soa has no AoS twin XLA
                # could CSE against) — back to AoS once per stage
                ks.append(fz.T)
        y_new = y
        for bi, k in zip(table.b, ks):
            if bi != 0.0:
                y_new = y_new + (hs * bi)[:, None] * k
        y_err = jnp.zeros_like(y)
        if table.b_emb is not None:
            for bi, bh, k in zip(table.b, table.b_emb, ks):
                if (bi - bh) != 0.0:
                    y_err = y_err + (hs * (bi - bh))[:, None] * k
        w = 1.0 / (opts.rtol * jnp.abs(y) + opts.atol)
        # dispatched per-system WRMS (.T views fuse on the jnp backend)
        err_raw = dv.wrms_soa(y_err.T, w.T, policy)
        bad = ~jnp.isfinite(err_raw) | ~nl_ok
        err = jnp.where(bad, 2.0, err_raw)
        accept = (err <= 1.0) & ~bad & active
        e = jnp.maximum(err, 1e-10)
        eprev = jnp.maximum(e1, 1e-10)
        eta = opts.controller.safety * e ** (-opts.controller.k1 / p) * \
            eprev ** (opts.controller.k2 / p)
        eta = jnp.clip(eta, opts.controller.eta_min, opts.controller.eta_max)
        eta = jnp.where(accept | ~active, eta, jnp.minimum(eta, 0.3))
        eta = jnp.where(nl_ok | ~active, eta, opts.eta_cf)
        t_new = t + hs
        t = jnp.where(accept, t_new, t)
        y = jnp.where(accept[:, None], y_new, y)
        h_next = jnp.where(active, jnp.clip(hs * eta, 1e-14, None), h)
        e1 = jnp.where(accept, e, e1)
        # per-lane retcode escalation, same contract as the BDF loop:
        # decided only for active lanes, sticky once nonzero
        ncf = active & ~nl_ok
        etf = active & nl_ok & ~accept & jnp.isfinite(err_raw)
        ncf_cur = jnp.where(accept, 0, ncf_cur + ncf.astype(jnp.int32))
        nef_cur = jnp.where(accept, 0, nef_cur + etf.astype(jnp.int32))
        # relative underflow check (t + h == t), as in the BDF loop
        hfail = active & (t + h_next == t)
        nanstep = active & nl_ok & ~jnp.isfinite(err_raw)
        att_next = att + active.astype(jnp.int32)
        unfinished = t < tf * (1 - 1e-12)
        rc = jnp.where(active & unfinished & (att_next >= opts.max_steps),
                       status.TOO_MUCH_WORK, rc)
        rc = jnp.where(active & ((nef_cur >= status.MXNEF) |
                                 (hfail & nl_ok)),
                       status.ERR_FAILURE, rc)
        rc = jnp.where(active & ((ncf_cur >= status.MXNCF) |
                                 (hfail & ~nl_ok)),
                       status.CONV_FAILURE, rc)
        rc = jnp.where(nanstep, status.RHSFUNC_FAIL, rc)
        carry = (t, y, h_next, e1,
                 steps + accept.astype(jnp.int32),
                 att_next,
                 netf + (active & ~accept).astype(jnp.int32),
                 nni + nni_step, rc, ncf_cur, nef_cur)
        # telemetry record: existing intermediates only (DIRK has no
        # order ramp and no lsetup trigger — those fields are constants
        # filled in by the telemetry-enabled wrapper below, so the
        # disabled trace gains no equations)
        rec = (t_new, hs, nni_step, err, nl_ok, accept, active)
        return carry, rec

    def body(c):
        return step(c)[0]

    zero = jnp.zeros((nsys,), jnp.int32)
    c = (t0, y0, h, jnp.ones((nsys,), dtype), zero, zero, zero,
         zero, zero, zero, zero)
    ring = None
    if telemetry is None:
        c = lax.while_loop(cond, body, c)
    else:
        from ..observability.telemetry import ring_init, ring_record

        def tel_body(cr):
            new_c, (t_new, hs, nni_step, err, nl_ok, accept,
                    active) = step(cr[0])
            rec = (t_new, hs, jnp.full((nsys,), p, jnp.int32), nni_step,
                   err, jnp.zeros((nsys,), bool), nl_ok, accept, active)
            return new_c, ring_record(cr[1], rec)

        c, ring = lax.while_loop(
            lambda cr: cond(cr[0]), tel_body,
            (c, ring_init(telemetry, (nsys,), dtype)))
    t, y, h, e1, steps, att, netf, nni, rc, _, _ = c
    retcodes = jnp.where((rc == 0) & (t < tf * (1 - 1e-10)),
                         status.TOO_MUCH_WORK, rc)
    st = EnsembleStats(steps=steps, attempts=att, netf=netf, nni=nni,
                       success=t >= tf * (1 - 1e-10),
                       retcodes=retcodes, ok=retcodes == 0)
    if ring is not None:
        return y, st, ring
    return y, st


# ---------------------------------------------------------------------------
# Batched adaptive BDF (the CVODE-style ensemble integrator)
# ---------------------------------------------------------------------------


class _BdfCarry(NamedTuple):
    t: jnp.ndarray            # (nsys,)
    h: jnp.ndarray            # (nsys,)
    q: jnp.ndarray            # (nsys,) current BDF order
    Z: jnp.ndarray            # (QMAX+1, n, nsys) uniform-grid history, SoA
    e1: jnp.ndarray           # (nsys,) controller err_prev
    e2: jnp.ndarray           # (nsys,) controller err_prev2
    MJ: Any                   # saved linear object (solver-defined pytree;
    #                           every leaf keeps the nsys axis LAST)
    gam_saved: jnp.ndarray    # (nsys,) gamma at last lsetup
    since_jac: jnp.ndarray    # (nsys,) attempts since last Jacobian refresh
    ncf_prev: jnp.ndarray     # (nsys,) Newton failed last attempt -> refresh
    steps: jnp.ndarray
    att: jnp.ndarray
    netf: jnp.ndarray
    nni: jnp.ndarray
    nsetups: jnp.ndarray
    ncfn: jnp.ndarray
    nli: jnp.ndarray          # scalar: inner linear iterations (Krylov)
    nps: jnp.ndarray          # scalar: preconditioner applications
    retcode: jnp.ndarray      # (nsys,) int32 CV_*-style status lane;
    #                           nonzero == quarantined (repro.core.status)
    ncf_cur: jnp.ndarray      # (nsys,) consecutive Newton conv failures
    #                           on the CURRENT step (reset on accept)
    nef_cur: jnp.ndarray      # (nsys,) consecutive error-test failures
    #                           on the CURRENT step (reset on accept)
    trips: jnp.ndarray        # scalar: step-loop iterations
    newton_trips: jnp.ndarray  # scalar: Newton-loop iterations, all steps
    setup_trips: jnp.ndarray  # scalar: steps whose lsetup branch ran


def _lane_rows(table, index, dtype):
    """``table[index]`` (host table, per-lane ``index``) as one row per
    column, each shaped like ``index``.  A select among the table's
    constants: an (nsys, k) gather would put k on a TPU's lane axis,
    padded to 128, and need a re-layout before every contraction."""
    return [lax.select_n(index, *[jnp.full(index.shape, v, dtype)
                                  for v in col]) for col in table.T]


def _predict_soa(Z, q, nvalid):
    """The BDF predictor and the corrector's history term of the
    (QMAX+1, n, nsys) history ``Z``, per lane at order ``q`` with
    ``nvalid`` valid entries:

        y_pred = sum_j PREDP[min(nvalid, q), j] Z[j]
        psi    = -sum_{j < QMAX} ALPHA[q - 1, j + 1] Z[j]

    as unrolled elementwise multiply-adds in Z's own layout: float32
    VPU arithmetic at the working precision, no transpose and no
    matmul (which a TPU would round to bfloat16 by default).  Each sum
    starts from Z[0] itself (its coefficient less one), so every add
    takes exactly one product: a backend that fuses a multiply into
    the add (XLA's CPU code) then fuses the same one in every lane,
    and a lane's bits do not depend on the batch width."""
    dtype = Z.dtype
    head = np.eye(1, _cv.QMAX + 1)
    pc = _lane_rows(_cv._PREDP_T - head, jnp.minimum(nvalid, q), dtype)
    ps = _lane_rows(-_cv._ALPHA_T[:, 1:] - head[:, :-1], q - 1, dtype)
    y_pred, psi = Z[0], Z[0]
    for j in range(_cv.QMAX + 1):
        y_pred = y_pred + pc[j] * Z[j]
    for j in range(_cv.QMAX):
        psi = psi + ps[j] * Z[j]
    return y_pred, psi


def ensemble_bdf_integrate(f: Callable, jac: Callable, y0: jnp.ndarray,
                           t0, tf, *, order: int = 5,
                           opts: ODEOptions = ODEOptions(),
                           policy: ExecPolicy = XLA_FUSED,
                           linear_solver=None,
                           lin_mode: Optional[str] = None,
                           jac_sparsity=None,
                           msbp: int = 20, dgmax: float = 0.3,
                           mem=None,
                           f_soa: Optional[Callable] = None,
                           jac_soa: Optional[Callable] = None,
                           session: Optional[SolverSession] = None,
                           return_session: bool = False,
                           telemetry: Optional[int] = None):
    """Adaptive batched BDF (orders 1-``order``) over ``nsys`` independent
    stiff systems — the CVODE submodel pipeline, TPU-native.

    f   : (t:(nsys,), y:(nsys,n)) -> (nsys,n)   vectorized RHS
    jac : (t:(nsys,), y:(nsys,n)) -> (nsys,n,n) per-system dense Jacobian
    y0  : (nsys, n);  t0, tf broadcastable to (nsys,)

    **SoA hot loop.**  The entire step-loop carry is structure-of-arrays
    with the system axis LAST: history ``Z`` is (QMAX+1, n, nsys), the
    Newton iterate/residual/weights are (n, nsys) — the layout the
    LinearSolver SoA surface and the fused kernels consume natively, so
    the Newton body performs no transposes at all.  Each iteration is
    exactly: one fused residual (``newton_residual_soa``, emitting the
    rhs ``-g`` in a single HBM pass), one lsolve, and one fused masked
    update + correction norm (``masked_update_wrms_soa``).  The
    twice-per-step Lagrange history rebuild runs through
    ``lagrange_rescale_soa``, which takes each system's step ratio and
    valid depth (the pallas kernel makes the weights itself) and
    short-circuits bundles with no active system instead of sweeping
    the full (QMAX+1, n, nsys) window.
    ``f_soa`` / ``jac_soa`` (signatures ``(t:(nsys,), y:(n,nsys)) ->
    (n,nsys)`` and ``-> (n,n,nsys)``) supply native SoA RHS/Jacobian
    forms; without them the AoS callables are wrapped with a transpose
    at the call boundary only.  The step loop runs with its carry
    donated (:func:`_donated_loop`), so repeated integrations reuse the
    history buffers in place.

    Each system carries its own (t, h, order, history, controller state):
    step size and order ramp are controlled per system, and systems that
    reach ``tf`` become masked no-ops inside the shared ``while_loop``.

    The nonlinear corrector is a convergence-tested **modified Newton**
    (CVODE semantics, not a fixed unroll): the Newton matrix
    ``M_j = I - gamma_j J_j`` is built from a *saved* Jacobian and only
    refreshed when it is stale — on the first step, after a Newton
    convergence failure, every ``msbp`` attempts, or when gamma has
    drifted by more than ``dgmax`` since the last lsetup (CVODE's
    ``CVLsetup`` triggers).

    **lsetup cost note:** the refresh is a single ``lax.cond`` over the
    whole batch, so whenever ANY system trips a trigger, ``jac`` (and
    the solver's setup) is evaluated over ALL ``nsys`` systems and the
    fresh results are merged into the carry only where ``need`` holds.
    This is the right trade for a vectorized ensemble (per-system
    branching would serialize the batch), but it means lsetup cost
    scales with nsys, not with the number of stale systems.  The merge
    select itself is skipped when every system needs the refresh (the
    cold-start and post-failure common case) — the fresh object is
    taken wholesale instead of paying an MJ-sized ``where`` per leaf.

    Linear algebra is a **pluggable object**: ``linear_solver`` is any
    :class:`repro.core.linsol.LinearSolver` with an SoA batch path
    (``soa_setup`` / ``soa_solve``), dispatched through ``policy``:

    * :class:`~repro.core.linsol.BlockDiagGJ` ``(factor_once=True)`` —
      the default: lsetup inverts every block once
      (:func:`repro.core.dispatch.block_inverse_soa`, the batched
      factor-once analog of the paper's cuSolver batchQR setup) and each
      Newton iteration is a single block-diagonal SpMV
      (:func:`repro.core.dispatch.blockdiag_spmv_soa`); gamma drift
      between lsetups is absorbed by CVODE's ``2/(1+gamrat)`` step
      scaling.
    * :class:`~repro.core.linsol.BlockDiagGJ` ``(factor_once=False)`` —
      the saved Jacobian is kept instead, M is rebuilt with the current
      gamma and every Newton iteration solves it with
      :func:`repro.core.dispatch.block_solve_soa`; the refresh logic
      then gates only Jacobian evaluations.
    * any Krylov solver (:class:`~repro.core.linsol.SPGMR`, ...) — the
      saved Jacobian backs a matrix-free solve of the flattened
      block-diagonal system (one batched SpMV per inner iteration);
      inner iterations are reported in ``stats.nli``, and a
      :class:`~repro.core.precond.Preconditioner` passed as the
      solver's ``precond=`` has its psetup run at the lsetup triggers
      and its psolve applications counted in ``stats.npsolves``.
    * :class:`~repro.core.linsol.EnsembleSparseGJ` — the batched sparse
      direct solver: symbolic analysis once per run, numeric refactor
      at the lsetup triggers, O(nnz) saved storage.

    ``jac_sparsity`` (an (n, n) boolean pattern, or the problem's
    ``IVP.jac_sparsity`` via the unified front-end) is bound to any
    solver with a sparse path (``with_sparsity``): the persistent
    Newton carry then holds only the pattern's values — dense ``jac``
    output is compressed at each lsetup and never stored.

    ``lin_mode='setup' | 'direct'`` is the deprecated string form of the
    two ``BlockDiagGJ`` configurations (kept as a compat shim).

    **Warm-start continuation.**  ``session=`` re-enters the step loop
    from a :class:`SolverSession` exported by a previous call with
    ``return_session=True`` (the return value becomes ``(y, stats,
    session)``): history window, per-system order, step size, and
    controller memory all resume, so a streaming client skips the cold
    BDF order-1 ramp entirely.  With a session, ``y0``/``t0`` may be
    ``None`` (shapes and start times come from the session; a non-None
    ``y0`` is shape-checked against it).  ``h <= 0`` lanes are cold
    (default ``h0`` is substituted), so :meth:`SolverSession.cold`
    lanes and warm lanes mix freely in one bundle under ONE trace.  The
    saved linear object (``MJ``) is deliberately NOT part of the
    session — the first warm step trips the ``gam_saved == 0`` lsetup
    trigger and refreshes the Jacobian at the re-entry point.  Session
    leaves are copied into fresh buffers before the carry is donated
    (the caller's session handle must survive the call), and the
    exported session is built from the loop *outputs* — it never
    aliases a donated buffer.  ``stats.steps`` counts THIS call's
    accepted steps; the exported ``session.steps`` stays cumulative
    (it bounds the valid history depth).

    The block kernels pad the system batch to the policy's
    ``batch_tile`` internally, so ``nsys`` need not be a multiple of
    128.  ``mem`` (a :class:`~repro.core.memory.MemoryHelper`) registers
    the history window and saved Newton blocks for workspace accounting.

    Simplifications vs CVODE proper match :func:`repro.core.cvode.
    bdf_integrate`: order ramps 1 -> ``order`` but is not adaptively
    lowered, and every lsetup re-evaluates the Jacobian (no ``jok``
    fast path — the batched analytic ``jac`` is one fused elementwise
    pass, cheaper than the bookkeeping).

    **Step telemetry.**  ``telemetry=K`` threads a K-slot
    :class:`~repro.observability.telemetry.TelemetryRing` through the
    step-loop carry, recording one ``(t, h, q, newton_iters, err_ratio,
    lsetup_fired, converged, accepted, active)`` record per step attempt
    per system; the ring is appended LAST to the return tuple.  Every
    recorded value is an intermediate the step already computes, so with
    ``telemetry=None`` (the default) the loop trace is *identical* to a
    build without this feature (sunlint ``telemetry-purity``).

    **Phases and loop trips.**  Each phase of the step runs under a
    ``jax.named_scope`` (``ensemble_bdf.rescale``, ``.predict``,
    ``.lsetup``, ``.newton``, ``.error_test``, ``.update``) that names
    its compiled ops in a device trace, and ``stats.trips``,
    ``stats.newton_trips`` and ``stats.setup_trips`` count the batch's
    step-loop, Newton-loop and lsetup-branch iterations.
    """
    from .linsol import BlockDiagGJ

    assert 1 <= order <= _cv.QMAX
    if lin_mode is not None:
        warnings.warn(
            "repro-compat: ensemble_bdf_integrate(lin_mode=...) is "
            "deprecated; pass linear_solver=BlockDiagGJ(factor_once="
            f"{lin_mode == 'setup'}) (or any LinearSolver with an SoA "
            "batch path)", DeprecationWarning, stacklevel=2)
        assert lin_mode in ("setup", "direct")
        if linear_solver is None:
            linear_solver = BlockDiagGJ(factor_once=(lin_mode == "setup"))
    ls = linear_solver if linear_solver is not None else BlockDiagGJ()
    if jac_sparsity is not None:
        from .linsol import encode_sparsity
        ls = ls.with_sparsity(encode_sparsity(jac_sparsity))
    if session is not None:
        n, nsys = session.n, session.nsys
        dtype = session.Z.dtype
        if y0 is not None and tuple(y0.shape) != (nsys, n):
            raise ValueError(
                f"y0 shape {tuple(y0.shape)} disagrees with the session "
                f"({(nsys, n)}); pass y0=None to resume from the session")
    else:
        if y0 is None:
            raise ValueError("ensemble_bdf_integrate needs y0 (or a "
                             "session= to resume from)")
        nsys, n = y0.shape
        dtype = y0.dtype
    QMAX = _cv.QMAX
    f_s, jac_s = _wrap_soa(f, jac, f_soa, jac_soa)
    if mem is not None:
        mem.register("ensemble_bdf.history", (QMAX + 1, n, nsys), dtype)
        # the persistent saved linear object is solver-defined: dense
        # Newton blocks, sparse values, preconditioner data, ...
        for suffix, shape in ls.soa_workspace_shapes(n, nsys):
            mem.register(f"ensemble_bdf.{suffix}", shape, dtype)
    if session is not None:
        t0 = session.t          # per-lane resume times
    t0 = jnp.broadcast_to(jnp.asarray(t0, dtype), (nsys,))
    tf = jnp.broadcast_to(jnp.asarray(tf, dtype), (nsys,))
    h0 = jnp.where(opts.h0 > 0, jnp.full((nsys,), opts.h0, dtype),
                   jnp.maximum(1e-6 * (tf - t0), 1e-12))
    one = jnp.ones((), dtype)

    def cond(c):
        # the integer att backstop can never bind — a lane reaching
        # max_steps attempts quarantines itself with TOO_MUCH_WORK and
        # drops out of the retcode mask — but it keeps an explicit
        # iteration ceiling in the cond (sunlint bounded-loops)
        with jax.named_scope("ensemble_bdf.update"):
            return jnp.any((c.t < tf * (1 - 1e-12)) & (c.retcode == 0)) & \
                jnp.all(c.att <= opts.max_steps)

    def step(c):
        # each phase of the step runs under a named scope: the compiled
        # ops carry it in their op_name metadata (device-trace names
        # only; the arithmetic is unchanged)
        with jax.named_scope("ensemble_bdf.predict"):
            active = (c.t < tf * (1 - 1e-12)) & (c.retcode == 0)
            hs = jnp.where(active, jnp.minimum(c.h, tf - c.t), c.h)
            nvalid = jnp.minimum(c.steps, QMAX)
        with jax.named_scope("ensemble_bdf.rescale"):
            # if h was clipped to hit tf, rescale the history accordingly
            # (fused masked rebuild).  Unclipped systems have eta_clip ==
            # 1.0 exactly (hs == c.h -> hs/c.h == 1.0) and the Lagrange
            # matrix at eta=1 is the exact identity, so masking them out
            # is a value-level no-op that lets the kernel short-circuit
            # whole bundles in the common no-clip case instead of
            # sweeping the full (QMAX+1, n, nsys) window every step
            eta_clip = jnp.where(active, hs / c.h, one)
            Z = dv.lagrange_rescale_soa(eta_clip, nvalid, c.Z,
                                        active & (eta_clip != one), policy)
        with jax.named_scope("ensemble_bdf.predict"):
            beta = jnp.asarray(_cv._BETA_T, dtype)[c.q - 1]  # (nsys,)
            # O(Q*n*nsys) once per step — NOT per Newton iteration
            y_pred, psi = _predict_soa(Z, c.q, nvalid)
            gamma = beta * hs                        # (nsys,)
            t_new = c.t + hs
            w = 1.0 / (opts.rtol * jnp.abs(Z[0]) + opts.atol)  # (n, nsys)

        # ---- lsetup: refresh J (and in 'setup' mode the block inverse)
        # only where stale; skipped entirely when no system needs it.
        # NOTE the batch-granular cost: one system tripping a trigger
        # evaluates jac over ALL nsys systems (docstring lsetup note) --
        with jax.named_scope("ensemble_bdf.lsetup"):
            gamrat = gamma / jnp.where(c.gam_saved != 0, c.gam_saved, gamma)
            need = active & ((c.gam_saved == 0) | c.ncf_prev |
                             (c.since_jac >= msbp) |
                             (jnp.abs(gamrat - 1.0) > dgmax))
            any_need = jnp.any(need)

            def do_setup(_):
                return ls.soa_setup(jac_s(t_new, y_pred), gamma, policy)

            MJ_new = lax.cond(any_need, do_setup, lambda _: c.MJ,
                              operand=None)
            # solver-defined pytree; every leaf keeps nsys LAST, so the
            # per-system mask broadcasts against the trailing axis.  When
            # EVERY system needs the refresh (cold start, the common
            # case) the fresh object is taken wholesale — no MJ-sized
            # select.
            MJ = lax.cond(
                jnp.all(need),
                lambda: MJ_new,
                lambda: jax.tree_util.tree_map(
                    lambda new, old: jnp.where(need, new, old), MJ_new,
                    c.MJ))
            gam_saved = jnp.where(need, gamma, c.gam_saved)
            since_jac = jnp.where(need, 0, c.since_jac)
            gamrat = jnp.where(need, 1.0, gamrat)

        # ---- convergence-tested modified Newton, all-SoA: residual,
        # lsolve, masked update and correction norm each one fused op
        # on (n, nsys) arrays — no layout conversion per iteration ----
        def lsolve(rhs):
            return ls.soa_solve(MJ, gamma, gamrat, rhs, policy, mem=mem)

        def nl_cond(s):
            z, it, dn_prev, crate, conv, div, nni_s, nli_s, nps_s = s
            return jnp.any(active & ~conv & ~div) & (it < opts.newton_max)

        def nl_body(s):
            z, it, dn_prev, crate, conv, div, nni_s, nli_s, nps_s = s
            iterate = active & ~conv & ~div
            rhs = dv.newton_residual_soa(z, f_s(t_new, z), psi, gamma,
                                         policy, negate=True)
            dz, nli_inc, nps_inc = lsolve(rhs)
            z_new, dn = dv.masked_update_wrms_soa(z, dz, w, iterate,
                                                  policy)
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
                    conv_new, div_new, nni_s + iterate.astype(jnp.int32),
                    nli_s + nli_inc, nps_s + nps_inc)

        with jax.named_scope("ensemble_bdf.newton"):
            s0 = (y_pred, jnp.zeros((), jnp.int32),
                  jnp.zeros((nsys,), dtype), jnp.ones((nsys,), dtype),
                  ~active, jnp.zeros((nsys,), bool),
                  jnp.zeros((nsys,), jnp.int32), jnp.zeros((), jnp.int32),
                  jnp.zeros((), jnp.int32))
            # the final iteration count is the loop's trip count: the
            # loop runs while any lane iterates
            z, newton_trips, _, _, conv, _, nni_s, nli_s, nps_s = \
                lax.while_loop(nl_cond, nl_body, s0)

        # ---- local error test (LTE ~ (z - pred)/(q+1), uniform grid) ----
        with jax.named_scope("ensemble_bdf.error_test"):
            err_raw = dv.wrms_soa(z - y_pred, w, policy) / \
                (c.q.astype(dtype) + 1.0)
            bad = ~jnp.isfinite(err_raw) | ~conv
            err = jnp.where(bad, 2.0, err_raw)
            accept = (err <= 1.0) & ~bad & active

            cst = ctrl.ControllerState(err_prev=c.e1, err_prev2=c.e2)
            eta, cst_new = ctrl.eta_from_error(opts.controller, cst, err,
                                               c.q + 1,
                                               after_failure=(~accept) & conv)
            eta = jnp.where(conv | ~active, eta, opts.eta_cf)
            eta = jnp.clip(eta, 0.1, 10.0)
            # fold the [hmin, hmax] step bounds into eta itself: the
            # history below is rescaled onto the hs*eta grid, so clamping
            # h after the fact would leave the stored grid and the
            # carried h disagreeing whenever the bound engages
            hs_safe = jnp.maximum(hs, jnp.finfo(dtype).tiny)
            eta = jnp.clip(eta, opts.hmin / hs_safe, opts.hmax / hs_safe)
            e1 = jnp.where(accept, cst_new.err_prev, c.e1)
            e2 = jnp.where(accept, cst_new.err_prev2, c.e2)

        # accepted systems: shift history, insert z, ramp order
        with jax.named_scope("ensemble_bdf.update"):
            Z_acc = jnp.roll(Z, 1, axis=0).at[0].set(z)
            Z_next = jnp.where(accept[None, None, :], Z_acc, Z)
            q_next = jnp.where(accept, jnp.minimum(c.q + 1, order), c.q)
        # rescale each system's history onto its new uniform grid
        with jax.named_scope("ensemble_bdf.rescale"):
            nval_after = jnp.minimum(c.steps + accept.astype(jnp.int32),
                                     QMAX)
            Z_next = dv.lagrange_rescale_soa(jnp.where(active, eta, one),
                                             nval_after, Z_next, active,
                                             policy)

        with jax.named_scope("ensemble_bdf.update"):
            t_next = jnp.where(accept, t_new, c.t)
            h_next = jnp.where(active, hs * eta, c.h)
            ncf = active & ~conv
            etf = (~accept) & conv & active
            ai = active.astype(jnp.int32)
            att_next = c.att + ai

            # ---- per-lane retcode escalation (CVODE CVHandleFailure
            # semantics, carried in data).  Failure is only ever DECIDED
            # for currently-active lanes, so a quarantined lane's retcode
            # is sticky and healthy lanes see pure where() no-ops — the
            # no-fault trace stays value-identical.  Priority (last write
            # wins): TOO_MUCH_WORK < ERR_FAILURE < CONV_FAILURE <
            # RHSFUNC_FAIL, mirroring CVODE's specific-beats-generic
            # flags.
            ncf_cur = jnp.where(accept, 0,
                                c.ncf_cur + ncf.astype(jnp.int32))
            nef_cur = jnp.where(accept, 0,
                                c.nef_cur + etf.astype(jnp.int32))
            # step-size underflow is RELATIVE (t + h == t, the classic
            # "h below the ULP of t" check): stiff lanes legitimately
            # visit tiny absolute h near transients and recover, so an
            # absolute floor would quarantine healthy integrations
            hfail = active & (c.t + hs * eta == c.t)
            nanstep = active & conv & ~jnp.isfinite(err_raw)
            unfinished = t_next < tf * (1 - 1e-12)
            rc = c.retcode
            rc = jnp.where(active & unfinished &
                           (att_next >= opts.max_steps),
                           status.TOO_MUCH_WORK, rc)
            rc = jnp.where(active & ((nef_cur >= status.MXNEF) |
                                     (hfail & conv)),
                           status.ERR_FAILURE, rc)
            rc = jnp.where(active & ((ncf_cur >= status.MXNCF) |
                                     (hfail & ~conv)),
                           status.CONV_FAILURE, rc)
            rc = jnp.where(nanstep, status.RHSFUNC_FAIL, rc)

            carry = _BdfCarry(
                t=t_next, h=h_next, q=q_next, Z=Z_next, e1=e1, e2=e2,
                MJ=MJ, gam_saved=gam_saved, since_jac=since_jac + ai,
                ncf_prev=ncf,
                steps=c.steps + accept.astype(jnp.int32),
                att=att_next,
                netf=c.netf + etf.astype(jnp.int32),
                nni=c.nni + nni_s,
                nsetups=c.nsetups + need.astype(jnp.int32),
                ncfn=c.ncfn + ncf.astype(jnp.int32),
                nli=c.nli + nli_s, nps=c.nps + nps_s,
                retcode=rc, ncf_cur=ncf_cur, nef_cur=nef_cur,
                trips=c.trips + 1,
                newton_trips=c.newton_trips + newton_trips,
                setup_trips=c.setup_trips + any_need.astype(jnp.int32))
        # telemetry record: every element is an intermediate the step
        # computed anyway — with telemetry off the tuple is discarded
        # and the traced loop is identical to a build without it
        rec = (t_new, hs, c.q, nni_s, err, need, conv, accept, active)
        return carry, rec

    def body(c):
        return step(c)[0]

    # donation requires every carry leaf to be a DISTINCT, internally
    # owned buffer: each counter gets its own zeros, and t is an
    # explicit copy — broadcast_to/asarray short-circuit when the
    # caller already passes an (nsys,) array of the right dtype, and
    # donating that alias would delete the CALLER's t0.  The session
    # re-entry leaves (t, Z, e1, e2, steps) are copied for the same
    # reason: donating them would invalidate the caller's session
    # handle (h and q pass through `where`/`clip`, which already
    # produce fresh buffers).
    zero = lambda: jnp.zeros((nsys,), jnp.int32)
    if session is None:
        steps0 = jnp.zeros((nsys,), jnp.int32)
        Z0 = jnp.zeros((QMAX + 1, n, nsys), dtype).at[0].set(y0.T)
        h_init = h0
        q_init = jnp.ones((nsys,), jnp.int32)
        e1_init = jnp.ones((nsys,), dtype)
        e2_init = jnp.ones((nsys,), dtype)
        steps_init = zero()
    else:
        steps0 = jnp.asarray(session.steps, jnp.int32)
        Z0 = jnp.array(session.Z, copy=True)
        # h <= 0 marks a cold lane: substitute the default h0 there so
        # cold sessions reproduce the plain-y0 start exactly
        h_init = jnp.where(session.h > 0, session.h, h0)
        q_init = jnp.clip(jnp.asarray(session.q, jnp.int32), 1, order)
        e1_init = jnp.array(session.e1, copy=True)
        e2_init = jnp.array(session.e2, copy=True)
        steps_init = jnp.array(steps0, copy=True)
    c = _BdfCarry(
        t=jnp.array(t0, copy=True), h=h_init,
        q=q_init, Z=Z0,
        e1=e1_init, e2=e2_init,
        MJ=ls.soa_carry_init(n, nsys, dtype),
        gam_saved=jnp.zeros((nsys,), dtype), since_jac=zero(),
        ncf_prev=jnp.zeros((nsys,), bool), steps=steps_init, att=zero(),
        netf=zero(), nni=zero(), nsetups=zero(), ncfn=zero(),
        nli=jnp.zeros((), jnp.int32), nps=jnp.zeros((), jnp.int32),
        retcode=zero(), ncf_cur=zero(), nef_cur=zero(),
        trips=jnp.zeros((), jnp.int32),
        newton_trips=jnp.zeros((), jnp.int32),
        setup_trips=jnp.zeros((), jnp.int32))
    # every carry leaf is freshly allocated above -> donate, so the
    # history window is updated in place across the step loop
    ring = None
    if telemetry is None:
        c = _donated_loop(cond, body, c)
    else:
        from ..observability.telemetry import ring_init, ring_record

        def tel_body(cr):
            new_c, rec = step(cr[0])
            return new_c, ring_record(cr[1], rec)

        c, ring = _donated_loop(
            lambda cr: cond(cr[0]), tel_body,
            (c, ring_init(telemetry, (nsys,), dtype)))
    # cond's integer backstop can in principle exit the loop with lanes
    # still marked healthy but unfinished; reconcile them to
    # TOO_MUCH_WORK so retcodes == 0 <=> the lane actually reached tf
    retcodes = jnp.where(
        (c.retcode == 0) & (c.t < tf * (1 - 1e-10)),
        status.TOO_MUCH_WORK, c.retcode)
    st = EnsembleStats(
        steps=c.steps - steps0, attempts=c.att, netf=c.netf, nni=c.nni,
        success=c.t >= tf * (1 - 1e-10), nsetups=c.nsetups, ncfn=c.ncfn,
        nli=jnp.broadcast_to(c.nli, (nsys,)),
        npsolves=jnp.broadcast_to(c.nps, (nsys,)),
        retcodes=retcodes, ok=retcodes == 0,
        trips=jnp.broadcast_to(c.trips, (nsys,)),
        newton_trips=jnp.broadcast_to(c.newton_trips, (nsys,)),
        setup_trips=jnp.broadcast_to(c.setup_trips, (nsys,)))
    out = [c.Z[0].T, st]
    if return_session:
        # built from the loop OUTPUTS — fresh buffers, never the
        # donated inputs (sunlint donation-aliasing audits this path).
        # Quarantine hygiene: a failed lane must NOT resume from its
        # poisoned step size / order / history depth — it is exported
        # as a cold lane (h <= 0 sentinel, order 1, zero valid history
        # depth) anchored at its last accepted state Z[0] (failed step
        # attempts never update Z[0], so it is the last good y).
        lane_ok = retcodes == 0
        out.append(SolverSession(
            t=c.t,
            h=jnp.where(lane_ok, c.h, jnp.zeros((), dtype)),
            q=jnp.where(lane_ok, c.q, 1),
            Z=c.Z, e1=jnp.where(lane_ok, c.e1, one),
            e2=jnp.where(lane_ok, c.e2, one),
            steps=jnp.where(lane_ok, c.steps, 0)))
    if ring is not None:
        out.append(ring)
    return tuple(out)


def make_ensemble_mesh(n_devices: int = 0):
    """1-D ('systems',) mesh for the ensemble subsystem: the batch of
    independent ODE systems is sharded across all (or the first
    ``n_devices``) local devices; each device advances its shard with no
    collectives (the paper's one-integrator-per-stream bundles)."""
    from jax.sharding import Mesh
    devs = jax.devices()
    if n_devices:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), ("systems",))


def ensemble_bdf_integrate_sharded(f: Callable, jac: Callable,
                                   y0: jnp.ndarray, t0, tf, *,
                                   params=None, mesh=None,
                                   axis: str = "systems",
                                   f_soa: Optional[Callable] = None,
                                   jac_soa: Optional[Callable] = None,
                                   **kw):
    """Shard :func:`ensemble_bdf_integrate` over the system axis.

    One call advances ``device_count x`` more systems: the batch is split
    across ``mesh`` with ``shard_map`` and every device runs the masked
    adaptive loop on its shard *independently* — there are no collectives,
    and per-device ``while_loop`` trip counts diverge freely (a device
    whose systems finish early simply stops stepping).  This is the TPU
    expression of the paper's one-CVODE-instance-per-stream bundles, with
    the bundle size per device further tiled by ``ExecPolicy.batch_tile``.
    :func:`repro.core.ivp.integrate` reaches it with ``mesh=``.

    params : optional pytree of per-system arrays (leading axis nsys),
             sharded alongside ``y0``; ``f``/``jac`` and ``f_soa``/
             ``jac_soa`` are then called as ``f(t, y, params_shard)``.
             Closed-over global arrays sized (nsys, ...) would NOT be
             sharded — route them through ``params`` instead.
    f_soa, jac_soa : optional native SoA forms (system axis last), in
             the same form as ``f``/``jac``: each device evaluates them
             on its ``(n, nsys / ndev)`` slice with no transpose.
             Without them the AoS forms are wrapped with a transpose
             inside each device's loop.
    mesh   : a 1-D ('systems',) mesh by default
             (:func:`make_ensemble_mesh`).
    If nsys is not a multiple of the device count the batch is padded
    with finished dummy systems (tf = t0: masked no-ops from step one).
    The whole call runs under ``jax.named_scope("ensemble_bdf.shards")``:
    the padding, the un-padding, the cross-shard ``nli``/``npsolves``
    sums and each shard's work outside its step loop carry that scope
    in a device trace; the step's own phases stay innermost.
    ``session=``/``return_session=`` and ``telemetry=`` are refused.
    """
    from jax.sharding import PartitionSpec as P

    if kw.pop("session", None) is not None or kw.pop("return_session",
                                                    False):
        raise ValueError(
            "ensemble_bdf_integrate_sharded takes no session=/"
            "return_session=: a SolverSession's (.., nsys) leaves would "
            "close over the shard_map body unsharded; warm-start "
            "continuation is a serving-layer (single-mesh-shard) "
            "feature for now")
    if kw.pop("telemetry", None) is not None:
        raise ValueError(
            "ensemble_bdf_integrate_sharded takes no telemetry=: the "
            "step-telemetry ring is not sharded")
    if mesh is None:
        mesh = make_ensemble_mesh()
    ndev = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    with jax.named_scope("ensemble_bdf.shards"):
        nsys, n = y0.shape
        dtype = y0.dtype
        t0a = jnp.broadcast_to(jnp.asarray(t0, dtype), (nsys,))
        tfa = jnp.broadcast_to(jnp.asarray(tf, dtype), (nsys,))
        pad = (-nsys) % ndev
        if pad:
            y0 = jnp.concatenate([y0, jnp.broadcast_to(y0[-1:], (pad, n))])
            t0a = jnp.concatenate([t0a, jnp.full((pad,), t0a[-1], dtype)])
            # tf = t0 -> padded systems are inactive from the first cond
            tfa = jnp.concatenate([tfa, jnp.full((pad,), t0a[-1], dtype)])
            if params is not None:
                params = jax.tree_util.tree_map(
                    lambda p: jnp.concatenate(
                        [p, jnp.broadcast_to(p[-1:], (pad,) + p.shape[1:])]),
                    params)

        spec = P(axis)

        def body(y0_l, t0_l, tf_l, params_l):
            def local(fn):
                if fn is None or params is None:
                    return fn
                return lambda t, y: fn(t, y, params_l)

            return ensemble_bdf_integrate(
                local(f), local(jac), y0_l, t0_l, tf_l,
                f_soa=local(f_soa), jac_soa=local(jac_soa), **kw)

        stats_spec = EnsembleStats(*([spec] * len(EnsembleStats._fields)))
        params_spec = jax.tree_util.tree_map(lambda _: spec, params)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(spec, spec, spec, params_spec),
                           out_specs=(spec, stats_spec), check_vma=False)
        y, st = fn(y0, t0a, tfa, params)
        shard = y0.shape[0] // ndev
        # each shard broadcast its own local Krylov and psolve totals over
        # its slice; restore the documented invariant (every entry == the
        # GLOBAL total) by summing one representative entry per shard.
        # trips, newton_trips and setup_trips stay per shard: each device
        # runs its own loops
        if st.nli is not None:
            st = st._replace(nli=jnp.broadcast_to(jnp.sum(st.nli[::shard]),
                                                  st.nli.shape))
        if st.npsolves is not None:
            st = st._replace(npsolves=jnp.broadcast_to(
                jnp.sum(st.npsolves[::shard]), st.npsolves.shape))
        if pad:
            y = y[:nsys]
            st = jax.tree_util.tree_map(lambda s: s[:nsys], st)
    return y, st
