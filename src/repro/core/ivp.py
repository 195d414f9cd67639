"""Unified IVP front-end: one problem object, one ``integrate`` call.

Before this layer the integrator surface was divergent free functions
(``arkode.erk_integrate``/``imex_integrate`` taking ``ODEOptions``,
``cvode.bdf_integrate`` with its own kwargs, ``batched.ensemble_*``
selecting linear algebra by string).  This module is the SUNDIALS-style
composition point:

* :class:`IVP` — the problem: ``f`` (or ``fe`` + ``fi`` for additive
  IMEX splittings), optional analytic ``jac``, and ``y0``.  For
  ``ensemble_*`` methods ``f``/``jac`` are the vectorized batch forms
  (``(t:(nsys,), y:(nsys,n))``).
* :func:`integrate` — ``(problem, t0, tf, method, *, ctx, opts, ...)``
  returning one :class:`Solution` regardless of method.  The method is
  a string ``family[:variant]``:

  ===========================  =========================================
  ``"erk[:dopri5]"``           adaptive explicit RK (any ERK table)
  ``"dirk[:sdirk2|sdirk33]"``  adaptive DIRK + Newton
  ``"imex[:ark324]"``          adaptive additive IMEX-ARK
  ``"bdf"``                    adaptive BDF 1-5 (CVODE; ``order=`` kwarg)
  ``"adams"``                  functional-iteration Adams (nonstiff)
  ``"ensemble_erk[:table]"``   batched adaptive ERK
  ``"ensemble_dirk[:table]"``  batched adaptive DIRK, block-diag Newton
  ``"ensemble_bdf"``           batched adaptive-order BDF (SoA kernels)
  ===========================  =========================================

* pluggable solvers: ``lin_solver`` takes any
  :class:`repro.core.linsol.LinearSolver` (SPGMR/SPFGMR/SPBCGS/SPTFQMR/
  PCG/DenseGJ for scalar methods, BlockDiagGJ or a Krylov solver for
  ``ensemble_bdf``); ``nonlin_solver`` takes a
  :class:`repro.core.nonlinsol.NewtonSolver` /
  :class:`~repro.core.nonlinsol.FixedPointSolver`.
* the :class:`repro.core.context.Context` carries the ExecPolicy, the
  MemoryHelper (so :class:`Solution` reports a real workspace
  high-water mark), and run-wide counters.

Every method string routes to the corresponding legacy entry point with
identical numerics — the parity suite in ``tests/test_unified_api.py``
pins trajectory equality to 1e-12.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import arkode, batched, butcher, cvode
from .arkode import ODEOptions
from .context import Context

Pytree = Any

# canonical method strings (one per family:variant the parity suite and
# the CI front-end smoke iterate over)
METHOD_STRINGS = (
    "erk:dopri5",
    "erk:bogacki_shampine",
    "dirk:sdirk2",
    "dirk:sdirk33",
    "imex:ark324",
    "bdf",
    "adams",
    "ensemble_erk:bogacki_shampine",
    "ensemble_dirk:sdirk2",
    "ensemble_bdf",
)

_ERK_ALIASES = {"dopri5": "dormand_prince", "bs32": "bogacki_shampine",
                "heun": "heun_euler"}
_DIRK_ALIASES = {"esdirk3": "ark324_esdirk"}


@dataclass(frozen=True)
class IVP:
    """An initial-value problem: RHS (full or additive split), optional
    analytic Jacobian, and the initial state.

    f   : full RHS ``f(t, y)`` — exclusive with ``fe``+``fi``
    fe  : explicit (nonstiff) part for IMEX methods
    fi  : implicit (stiff) part for IMEX methods
    jac : analytic Jacobian — required by the ``ensemble_dirk`` /
          ``ensemble_bdf`` methods (batched ``(t, y) -> (nsys, n, n)``)
    f_soa, jac_soa : optional native SoA forms of ``f``/``jac`` for the
          ensemble hot loop (system axis LAST: ``f_soa(t, y:(n,nsys))
          -> (n,nsys)``, ``jac_soa -> (n,n,nsys)``).  When supplied,
          ``ensemble_bdf``/``ensemble_dirk`` evaluate the RHS/Jacobian
          with ZERO layout conversions; otherwise the AoS forms are
          wrapped with a transpose at the call boundary.
    jac_sparsity : static per-system Jacobian sparsity, an (n, n)
          boolean/0-1 pattern shared by every ensemble member.  When
          set, ``ensemble_bdf`` binds it to any ``lin_solver`` with a
          sparse path (``EnsembleSparseGJ``, sparse Krylov): the
          persistent Newton storage drops from O(n^2) to O(nnz) per
          system and sparse kernels replace the dense sweeps.
    y0  : initial state pytree (``(nsys, n)`` for ensemble methods)
    params : optional pytree of per-system data (arrays with leading axis
          nsys for ensemble methods).  When set, ``f``, ``fe``, ``fi``,
          ``jac``, ``f_soa`` and ``jac_soa`` are called as ``(t, y,
          params)``; ``integrate(..., mesh=)`` hands each device its
          slice, the one route for per-system data onto a mesh.
    """

    f: Optional[Callable] = None
    fe: Optional[Callable] = None
    fi: Optional[Callable] = None
    jac: Optional[Callable] = None
    f_soa: Optional[Callable] = None
    jac_soa: Optional[Callable] = None
    jac_sparsity: Optional[Any] = None
    y0: Pytree = None
    params: Pytree = None

    def __post_init__(self):
        if (self.f is None) == (self.fe is None and self.fi is None):
            raise ValueError("IVP wants either f=... or fe=... and fi=...")
        if (self.fe is None) != (self.fi is None):
            raise ValueError("IMEX splittings need BOTH fe and fi")
        if self.y0 is None:
            raise ValueError("IVP needs y0")

    @property
    def full_rhs(self) -> Callable:
        """The complete RHS: ``f``, or ``fe + fi`` for split problems —
        what the non-IMEX method families integrate, so an IMEX-split
        problem run through e.g. ``bdf`` treats the WHOLE system
        implicitly instead of silently dropping ``fe``."""
        if self.f is not None:
            return self.f
        fe, fi = self.fe, self.fi
        return lambda t, y, *params: jax.tree_util.tree_map(
            jnp.add, fe(t, y, *params), fi(t, y, *params))


class Solution(NamedTuple):
    """One result type for every method (the CVodeGetXxx roll-up)."""

    y: Pytree                      # state at tf
    t: jnp.ndarray                 # time reached (scalar methods); the
    #                                target tf for ensemble methods, whose
    #                                per-system progress lives in stats
    success: jnp.ndarray           # bool (scalar, or all-systems for ensemble)
    stats: Any                     # the raw IntegratorStats / EnsembleStats
    method: str
    lin_solver: str                # linear-solver name ("spgmr", ...)
    nonlin_solver: str             # "newton" | "fixed_point" | "none"
    nni: jnp.ndarray               # nonlinear iterations (summed over systems)
    nli: Optional[jnp.ndarray]     # inner linear iterations (None if untracked)
    nsetups: Optional[jnp.ndarray]  # lsetup count (ensemble_bdf only)
    workspace_bytes: int           # this call's registered workspace
    high_water_bytes: int          # run-wide memory high-water (ctx.memory)
    npsolves: Optional[jnp.ndarray] = None   # preconditioner applications
    npsetups: Optional[jnp.ndarray] = None   # preconditioner setups (ride
    #                                          the lsetup triggers)
    session: Optional[Any] = None  # ensemble_bdf warm-start continuation
    #                                state (return_session=True); see
    #                                repro.core.batched.SolverSession
    timings: Optional[dict] = None  # wall-clock split: {"queue_wait",
    #                                 "compile", "execute"} via the serving
    #                                 front-end, or {"lower", "compile",
    #                                 "execute"} from a timed direct
    #                                 integrate() call (None otherwise)
    telemetry: Optional[Any] = None  # StepTelemetry (step-level ring
    #                                  records) when the context enables
    #                                  observability telemetry or the call
    #                                  passes telemetry=K; None otherwise
    retcodes: Optional[jnp.ndarray] = None  # CV_*-style status
    #   (repro.core.status): (nsys,) int32 for ensemble methods, scalar
    #   for threaded scalar methods (bdf); None where not yet threaded
    ok: Optional[jnp.ndarray] = None  # retcodes == 0 (same shape); a
    #   quarantined lane's y is its last ACCEPTED state, not garbage
    degraded: bool = False         # True when the serving tier re-ran
    #   this bundle under the jnp oracle policy after a pallas-side
    #   failure (one-shot backend fallback)


def _split(method: str):
    fam, _, var = method.partition(":")
    return fam, (var or None)


def _erk_table(var):
    name = _ERK_ALIASES.get(var or "dopri5", var or "dopri5")
    return butcher.ERK_TABLES[name]


def _dirk_table(var):
    name = _DIRK_ALIASES.get(var or "sdirk2", var or "sdirk2")
    return butcher.DIRK_TABLES[name]


def _need(problem: IVP, attr: str, method: str):
    if attr == "f":          # every non-IMEX family integrates fe+fi whole
        return problem.full_rhs
    v = getattr(problem, attr)
    if v is None:
        raise ValueError(f"method {method!r} needs IVP.{attr}")
    return v


def _bind_params(problem: IVP) -> IVP:
    """``problem`` with its ``params`` bound into every callable (the
    one-device path; on a mesh each device binds its own slice)."""
    p = problem.params

    def bind(fn):
        return None if fn is None else (lambda t, y: fn(t, y, p))

    return dataclasses.replace(
        problem, params=None,
        **{k: bind(getattr(problem, k))
           for k in ("f", "fe", "fi", "jac", "f_soa", "jac_soa")})


#: families that accept the step-telemetry ring (the implicit adaptive
#: loops whose per-step behavior the SUNLogger analog records)
_TELEMETRY_FAMILIES = ("bdf", "ensemble_dirk", "ensemble_bdf")

_KNOWN_FAMILIES = ("erk", "dirk", "imex", "bdf", "adams",
                   "ensemble_erk", "ensemble_dirk", "ensemble_bdf")


def integrate(problem: IVP, t0, tf, method: str = "bdf", *,
              ctx: Optional[Context] = None,
              opts: Optional[ODEOptions] = None,
              lin_solver=None, nonlin_solver=None,
              order: int = 5, live=None,
              timed: Optional[bool] = None, mesh=None,
              **method_kw) -> Solution:
    """Integrate ``problem`` from t0 to tf with ``method``.

    ctx           : :class:`~repro.core.context.Context`; a private one
                    is created (and its counters discarded) if omitted.
    opts          : ODEOptions; defaults to ``ctx.options()`` so the
                    context's ExecPolicy is applied.  An explicit opts
                    wins entirely (its policy included).
    lin_solver    : LinearSolver object (or legacy callable) for the
                    pluggable-linear-solver families (dirk, imex, bdf,
                    ensemble_bdf); a ValueError elsewhere.
    nonlin_solver : NewtonSolver / FixedPointSolver config object
                    (dirk, imex, bdf, adams); a ValueError elsewhere.
    order         : max BDF order for the ``bdf`` / ``ensemble_bdf``
                    families.
    live          : optional (nsys,) bool mask for ensemble methods on
                    bundles padded with dead lanes (a serving bundle
                    padded to its bucket size): the Solution's stats and
                    aggregates (nni, nsetups, success, ...) then count
                    LIVE lanes only (:meth:`~repro.core.batched.
                    EnsembleStats.masked`); a ValueError for scalar
                    methods.
    timed         : True runs the dispatch through the AOT pipeline
                    (``jit(...).lower().compile()``) and reports the
                    ``{lower, compile, execute}`` wall-time split in
                    ``Solution.timings`` — the same keys the serving
                    path populates, so profiler regions and timings
                    agree.  Defaults to ``ctx.observability.profile``;
                    falls back to the untimed path under an outer trace.
    mesh          : a :class:`jax.sharding.Mesh` with a ``'systems'``
                    axis (``ensemble_bdf`` only): the systems, and
                    ``IVP.params``, are split over its devices and each
                    device integrates its own with no collectives
                    (:func:`~repro.core.batched.
                    ensemble_bdf_integrate_sharded`).  The Solution is
                    per lane over all systems, as on one device;
                    ``workspace_bytes`` is one device's.  ``telemetry``
                    and ``session=``/``return_session=`` are refused.
    method_kw     : passed through to the underlying integrator
                    (``dense_jac``, ``msbp``, ``m_aa``, ...;
                    ``ensemble_bdf`` additionally takes ``session=`` /
                    ``return_session=`` for warm-start continuation —
                    the exported session lands in ``Solution.session``;
                    ``telemetry=K`` on the ``bdf``/``ensemble_dirk``/
                    ``ensemble_bdf`` families threads a K-slot step-
                    telemetry ring through the loop, surfaced as
                    ``Solution.telemetry`` — also switched on for all
                    three via ``ctx.observability.telemetry``).
    """
    ctx = ctx if ctx is not None else Context()
    opts = opts if opts is not None else ctx.options()
    mem = ctx.memory
    live0 = mem.live_bytes
    labels0 = set(mem.workspaces)
    fam, var = _split(method)
    if fam not in _KNOWN_FAMILIES:
        raise ValueError(
            f"unknown method {method!r}; families: {', '.join(_KNOWN_FAMILIES)} "
            f"(canonical strings: {', '.join(METHOD_STRINGS)})")
    nli = None
    nsetups = None
    npsolves = None
    npsetups = None
    obs = ctx.observability
    # -- step telemetry: explicit telemetry=K wins; the context config
    # switches it on for every telemetry-capable family
    tel_cap = method_kw.pop("telemetry", None)
    if tel_cap is not None and fam not in _TELEMETRY_FAMILIES:
        raise ValueError(
            f"method {method!r} takes no telemetry= (step telemetry "
            f"covers the implicit adaptive families: "
            f"{', '.join(_TELEMETRY_FAMILIES)})")
    if tel_cap is None and obs.telemetry and fam in _TELEMETRY_FAMILIES:
        tel_cap = obs.telemetry_capacity
    if mesh is not None and fam != "ensemble_bdf":
        raise ValueError(f"method {method!r} takes no mesh= (the sharded "
                         "system axis is an ensemble_bdf feature)")
    if mesh is not None and tel_cap is not None:
        raise ValueError("integrate(..., mesh=) takes no step telemetry "
                         "(telemetry= or the context's observability "
                         "telemetry): the ring is not sharded")
    if problem.params is not None and mesh is None:
        problem = _bind_params(problem)
    if live is not None and not fam.startswith("ensemble"):
        raise ValueError(f"method {method!r} takes no live= mask (dead-"
                         "lane masking applies to ensemble bundles only)")
    # a solver object passed to a family that cannot consume it is an
    # error, not a silent no-op (Solution must never report a swap that
    # did not happen)
    if lin_solver is not None and fam not in ("dirk", "imex", "bdf",
                                              "ensemble_bdf"):
        raise ValueError(f"method {method!r} takes no lin_solver (the "
                         "pluggable families are dirk, imex, bdf, "
                         "ensemble_bdf)")
    if nonlin_solver is not None and fam not in ("dirk", "imex", "bdf",
                                                 "adams"):
        raise ValueError(f"method {method!r} takes no nonlin_solver (the "
                         "pluggable families are dirk, imex, bdf, adams)")
    lname = getattr(lin_solver, "name",
                    "custom" if lin_solver is not None else None)
    nlname = "newton" if fam in ("dirk", "imex", "bdf", "ensemble_dirk",
                                 "ensemble_bdf") else \
             "fixed_point" if fam == "adams" else "none"

    return_session = bool(method_kw.pop("return_session", False)) \
        if fam == "ensemble_bdf" else False
    if lname is None:
        if fam in ("dirk", "imex"):
            lname = "spgmr"
        elif fam == "bdf":
            lname = "dense_gj" if method_kw.get("dense_jac") else "spgmr"
        elif fam in ("ensemble_dirk", "ensemble_bdf"):
            lname = "blockdiag_gj"
        else:
            lname = "none"

    def _dispatch():
        """The family dispatch as a nullary closure, so the timed path
        can push the WHOLE call through jit().lower().compile() and
        report the AOT stage split.  Returns ``(y, st, session, ring)``
        (session/ring None when not requested)."""
        session = None
        ring = None
        if fam == "erk":
            f = _need(problem, "f", method)
            y, st = arkode.erk_integrate(f, problem.y0, t0, tf,
                                         _erk_table(var), opts, mem=mem)
        elif fam == "dirk":
            fi = _need(problem, "f", method)  # full RHS, treated implicitly
            y, st = arkode.dirk_integrate(fi, problem.y0, t0, tf,
                                          _dirk_table(var), opts,
                                          lin_solver=lin_solver,
                                          nonlin_solver=nonlin_solver,
                                          mem=mem)
        elif fam == "imex":
            fe = _need(problem, "fe", method)
            fi = _need(problem, "fi", method)
            tab = butcher.IMEX_TABLES[var or "ark324"]
            y, st = arkode.imex_integrate(fe, fi, problem.y0, t0, tf, tab,
                                          opts, lin_solver=lin_solver,
                                          nonlin_solver=nonlin_solver,
                                          mem=mem)
        elif fam == "bdf":
            f = _need(problem, "f", method)  # full RHS, treated implicitly
            out = cvode.bdf_integrate(f, problem.y0, t0, tf, order=order,
                                      opts=opts, lin_solver=lin_solver,
                                      nonlin_solver=nonlin_solver, mem=mem,
                                      telemetry=tel_cap, **method_kw)
            if tel_cap is not None:
                y, st, ring = out
            else:
                y, st = out
        elif fam == "adams":
            f = _need(problem, "f", method)
            y, st = cvode.adams_integrate(f, problem.y0, t0, tf, opts,
                                          nonlin_solver=nonlin_solver,
                                          mem=mem, **method_kw)
        elif fam == "ensemble_erk":
            f = _need(problem, "f", method)
            y, st = batched.ensemble_erk_integrate(f, problem.y0, t0, tf,
                                                   _erk_table(var), opts)
        elif fam == "ensemble_dirk":
            f = _need(problem, "f", method)
            jac = _need(problem, "jac", method)
            out = batched.ensemble_dirk_integrate(
                f, jac, problem.y0, t0, tf, _dirk_table(var), opts,
                policy=opts.policy, f_soa=problem.f_soa,
                jac_soa=problem.jac_soa, telemetry=tel_cap, **method_kw)
            if tel_cap is not None:
                y, st, ring = out
            else:
                y, st = out
        else:  # ensemble_bdf (families validated above)
            f = _need(problem, "f", method)
            jac = _need(problem, "jac", method)
            kw = dict(order=order, opts=opts, policy=opts.policy,
                      linear_solver=lin_solver,
                      jac_sparsity=problem.jac_sparsity, mem=mem,
                      f_soa=problem.f_soa, jac_soa=problem.jac_soa,
                      return_session=return_session, telemetry=tel_cap,
                      **method_kw)
            if mesh is None:
                out = list(batched.ensemble_bdf_integrate(
                    f, jac, problem.y0, t0, tf, **kw))
            else:
                out = list(batched.ensemble_bdf_integrate_sharded(
                    f, jac, problem.y0, t0, tf, params=problem.params,
                    mesh=mesh, **kw))
            if tel_cap is not None:
                ring = out.pop()
            if return_session:
                session = out.pop()
            y, st = out
        return y, st, session, ring

    # -- timed (AOT) vs plain dispatch.  The timed path reports the
    # {lower, compile, execute} split (same keys the serving path uses)
    # and brackets each stage in a profiler region; it cannot run under
    # an outer trace (block_until_ready on tracers), so it degrades to
    # the plain path there.
    profile_on = obs.profile if timed is None else bool(timed)
    if profile_on and any(
            isinstance(leaf, jax.core.Tracer) for leaf in
            jax.tree_util.tree_leaves((problem.y0, problem.params, t0, tf,
                                       method_kw))):
        profile_on = False
    timings = None
    if profile_on:
        import time as _time
        prof = ctx.profiler
        t_a = _time.perf_counter()
        with prof.region("integrate.lower", method=method):
            lowered = jax.jit(_dispatch).lower()
        t_b = _time.perf_counter()
        with prof.region("integrate.compile", method=method):
            compiled = lowered.compile()
        t_c = _time.perf_counter()
        with prof.region("integrate.execute", method=method):
            out = jax.block_until_ready(compiled())
        t_d = _time.perf_counter()
        timings = {"lower": t_b - t_a, "compile": t_c - t_b,
                   "execute": t_d - t_c}
    else:
        out = _dispatch()
    y, st, session, ring = out

    if fam == "ensemble_bdf":
        nli = st.nli[0] if st.nli is not None else None
        nsetups = st.nsetups
        npsolves = st.npsolves[0] if st.npsolves is not None else None
        # SUNDIALS accounting: psetup rides the lsetup triggers, so the
        # setup count is the lsetup total whenever a psetup/psolve
        # preconditioner is configured on the solver (same duck test
        # the solver layer applies)
        from .linsol import _is_precond_obj
        if _is_precond_obj(getattr(lin_solver, "precond", None)):
            npsetups = jnp.sum(st.nsetups)

    is_ensemble = fam.startswith("ensemble")
    if live is not None:
        # padded-bundle hygiene: dead lanes are zeroed out of the stats
        # BEFORE any aggregate below (success, nni sum, nsetups), so a
        # bundle padded to its bucket size reports live-lane work only
        st = st.masked(jnp.asarray(live, bool))
        if nsetups is not None:
            nsetups = st.nsetups
    success = jnp.all(st.success) if is_ensemble else st.success
    t_reached = getattr(st, "t", None)
    if t_reached is None:
        # EnsembleStats carries no per-system t; this is the TARGET time
        # (check `success` / stats.success for systems that stalled)
        t_reached = jnp.asarray(tf)
    nni = jnp.sum(st.nni) if is_ensemble else st.nni
    workspace = mem.live_bytes - live0
    # workspaces are per-call: release only the labels THIS call added
    # (foreign registrations on a shared ctx.memory stay live); the
    # high-water mark persists either way
    for label in set(mem.workspaces) - labels0:
        mem.release(label)
    ctx.record(st, nli)
    tel_obj = None
    if ring is not None:
        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(ring)):
            # under an outer trace the host-side wrapper cannot be
            # built; hand the raw (traced) ring through and let the
            # caller wrap it once values are concrete
            tel_obj = ring
        else:
            from ..observability.telemetry import StepTelemetry
            tel_obj = StepTelemetry(
                ring, live=None if live is None else live)
    # -- CV_*-style status surface: ensemble stats carry a per-lane
    # retcodes array, threaded scalar methods a scalar retcode; both
    # land on the same Solution fields (after dead-lane masking above,
    # so padded bundle lanes always read SUCCESS)
    retcodes = getattr(st, "retcodes", None)
    if retcodes is None:
        retcodes = getattr(st, "retcode", None)
    ok = getattr(st, "ok", None)
    if ok is None and retcodes is not None:
        ok = retcodes == 0
    if ctx.logger.enabled_for("WARNING") and retcodes is not None \
            and not isinstance(retcodes, jax.core.Tracer):
        import numpy as _np

        arr = _np.atleast_1d(_np.asarray(retcodes))
        failed = _np.nonzero(arr != 0)[0]
        if failed.size:
            from . import status as _status
            by_code = {
                _status.retcode_name(int(code)):
                    int((arr == code).sum())
                for code in _np.unique(arr[failed])}
            ctx.logger.warning(
                "integrate.lane_failed", method=method,
                failed=int(failed.size), nsys=int(arr.size),
                retcodes=by_code,
                lanes=[int(i) for i in failed[:16]])
    if ctx.logger.enabled_for("INFO"):
        ctx.logger.info(
            "integrate.done", method=method, lin_solver=lname or "none",
            steps=Context._concrete(getattr(st, "steps", None)),
            nni=Context._concrete(nni),
            success=Context._concrete(success))
    return Solution(y=y, t=t_reached, success=success, stats=st,
                    method=method, lin_solver=lname or "none",
                    nonlin_solver=nlname, nni=nni, nli=nli,
                    nsetups=nsetups, workspace_bytes=workspace,
                    high_water_bytes=mem.high_water_bytes,
                    npsolves=npsolves, npsetups=npsetups,
                    session=session, timings=timings, telemetry=tel_obj,
                    retcodes=retcodes, ok=ok)
