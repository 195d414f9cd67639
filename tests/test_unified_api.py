"""Unified solver-stack API tests (the PR-3 acceptance gates).

* method-matrix parity: ``integrate(..., method=M)`` is
  trajectory-identical (1e-12) to each legacy entry point for every
  canonical method string;
* every method string also runs under the Pallas and ``auto`` policies
  with the Newton iteration count of the jnp run;
* pluggability: swapping SPGMR <-> BlockDiagGJ on the ensemble-BDF path
  changes no trajectory beyond 1e-8 while Solution reports distinct
  solver stats and a nonzero memory high-water mark;
* compat shims (lin_mode=..., bdf_fixed bare kwargs) still work but
  DeprecationWarn — and the pyproject filterwarnings gate turns any
  unguarded use in the suite into an error;
* normalized SolveStats across all five Krylov solvers;
* NewtonSolver tolerances sourced from ODEOptions;
* Context counters and MemoryHelper workspace accounting.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arkode, batched, butcher, cvode, krylov
from repro.core.arkode import ODEOptions
from repro.core.context import Context
from repro.core.ivp import IVP, METHOD_STRINGS, Solution, integrate
from repro.core.linsol import SPGMR, BlockDiagGJ, DenseGJ
from repro.core.memory import MemoryHelper
from repro.core.nonlinsol import FixedPointSolver, NewtonSolver
from repro.core.policies import AUTO, XLA_FUSED, ExecPolicy

LAM = 30.0


def _f1(t, y):
    return -LAM * (y - jnp.cos(t))


def _fe1(t, y):
    return LAM * jnp.cos(t) * jnp.ones_like(y)


def _fi1(t, y):
    return -LAM * y


def _batched_decay(nsys=5, n=3):
    rates = jnp.linspace(5.0, 40.0, nsys)

    def f(t, y):
        return -rates[:, None] * (y - jnp.cos(t)[:, None])

    def jac(t, y):
        return jnp.broadcast_to(-rates[:, None, None] * jnp.eye(n),
                                (y.shape[0], n, n))

    return f, jac, jnp.zeros((nsys, n))


_FB, _JB, _YB = _batched_decay()
_OPTS = ODEOptions(rtol=1e-6, atol=1e-9)


def _problem(method):
    if method.startswith("imex"):
        return IVP(fe=_fe1, fi=_fi1, y0=jnp.zeros((2,)))
    if method.startswith("ensemble"):
        return IVP(f=_FB, jac=_JB, y0=_YB)
    return IVP(f=_f1, y0=jnp.zeros((2,)))


def _legacy(method, prob, t0, tf, opts):
    """The pre-unification entry point for each canonical string."""
    fam, _, var = method.partition(":")
    if fam == "erk":
        return arkode.erk_integrate(prob.f, prob.y0, t0, tf,
                                    butcher.ERK_TABLES[
                                        "dormand_prince" if var == "dopri5"
                                        else var], opts)
    if fam == "dirk":
        return arkode.dirk_integrate(prob.f, prob.y0, t0, tf,
                                     butcher.DIRK_TABLES[var], opts)
    if fam == "imex":
        return arkode.imex_integrate(prob.fe, prob.fi, prob.y0, t0, tf,
                                     butcher.IMEX_TABLES[var], opts)
    if fam == "bdf":
        return cvode.bdf_integrate(prob.f, prob.y0, t0, tf, order=5,
                                   opts=opts)
    if fam == "adams":
        return cvode.adams_integrate(prob.f, prob.y0, t0, tf, opts)
    if fam == "ensemble_erk":
        return batched.ensemble_erk_integrate(
            prob.f, prob.y0, t0, tf, butcher.ERK_TABLES[var], opts)
    if fam == "ensemble_dirk":
        return batched.ensemble_dirk_integrate(
            prob.f, prob.jac, prob.y0, t0, tf, butcher.DIRK_TABLES[var],
            opts)
    if fam == "ensemble_bdf":
        return batched.ensemble_bdf_integrate(
            prob.f, prob.jac, prob.y0, t0, tf, order=5, opts=opts)
    raise AssertionError(method)


@pytest.mark.parametrize("method", METHOD_STRINGS)
def test_method_matrix_parity(method):
    """integrate(method=M) == legacy entry point, to 1e-12."""
    prob = _problem(method)
    sol = integrate(prob, 0.0, 1.0, method, opts=_OPTS)
    y_ref, st_ref = _legacy(method, prob, 0.0, 1.0, _OPTS)
    assert isinstance(sol, Solution)
    assert bool(sol.success)
    np.testing.assert_allclose(np.asarray(sol.y), np.asarray(y_ref),
                               rtol=0, atol=1e-12)
    # unified stats carry the same accepted-step count
    assert int(jnp.sum(sol.stats.steps)) == int(jnp.sum(st_ref.steps))


# small problems at loose tolerances: these cases check wiring, not accuracy
_SMOKE_LAM = 12.0
_SMOKE_RATES = jnp.linspace(2.0, _SMOKE_LAM, 4)


def _smoke_problem(method):
    lam, rates, n = _SMOKE_LAM, _SMOKE_RATES, 3
    if method.startswith("imex"):
        return IVP(fe=lambda t, y: lam * jnp.cos(t) * jnp.ones_like(y),
                   fi=lambda t, y: -lam * y, y0=jnp.zeros((2,)))
    if method.startswith("ensemble"):
        return IVP(f=lambda t, y: -rates[:, None] * (y - jnp.cos(t)[:, None]),
                   jac=lambda t, y: jnp.broadcast_to(
                       -rates[:, None, None] * jnp.eye(n),
                       (y.shape[0], n, n)),
                   y0=jnp.zeros((rates.shape[0], n)))
    return IVP(f=lambda t, y: -lam * (y - jnp.cos(t)), y0=jnp.zeros((2,)))


def _smoke_solve(method, policy):
    ctx = Context(policy=policy)
    opts = ctx.options(rtol=1e-4, atol=1e-7, max_steps=20_000)
    return integrate(_smoke_problem(method), 0.0, 1.0, method, ctx=ctx,
                     opts=opts)


@functools.lru_cache(maxsize=None)
def _smoke_jnp_nni(method):
    return int(_smoke_solve(method, XLA_FUSED).nni)


@pytest.mark.parametrize("policy", [ExecPolicy(backend="pallas"), AUTO],
                         ids=["pallas", "auto"])
@pytest.mark.parametrize("method", METHOD_STRINGS)
def test_method_matrix_under_kernel_policies(method, policy):
    """integrate(method=M) under the Pallas policy (interpret mode off a
    TPU) and under ``auto`` succeeds with a finite y and takes the same
    Newton iterations as the jnp run of M."""
    sol = _smoke_solve(method, policy)
    assert bool(sol.success)
    assert bool(jnp.all(jnp.isfinite(jnp.asarray(sol.y))))
    assert int(sol.nni) == _smoke_jnp_nni(method)


def test_sdirk33_is_third_order():
    """The new dirk:sdirk33 table (Alexander SDIRK-3-3) really is
    order 3 (fixed-step convergence on the stiff decay problem)."""
    import math
    ls = arkode.dense_lin_solver(_f1)
    a = LAM * LAM / (1 + LAM * LAM)
    b = LAM / (1 + LAM * LAM)
    exact = a * np.cos(1.0) + b * np.sin(1.0) - a * np.exp(-LAM)
    errs = []
    for n in (40, 80, 160):
        y = arkode.dirk_fixed(_f1, jnp.zeros((1,)), 0.0, 1.0, n,
                              butcher.SDIRK33, lin_solver=ls)
        errs.append(abs(float(y[0]) - exact))
    order = math.log2(errs[-2] / errs[-1])
    assert order > 2.5, (order, errs)


# ---------------------------------------------------------------------------
# pluggability: the PR acceptance criterion
# ---------------------------------------------------------------------------


def test_ensemble_bdf_solver_swap_krylov_vs_blockdiag():
    """SPGMR <-> BlockDiagGJ on ensemble_bdf: trajectories within 1e-8,
    distinct solver stats, nonzero memory high-water mark."""
    prob = IVP(f=_FB, jac=_JB, y0=_YB)
    opts = ODEOptions(rtol=1e-6, atol=1e-10)
    ctx = Context()
    # full-subspace GMRES (restart >= nsys*n = 15) -> near-exact solves
    sol_k = integrate(prob, 0.0, 2.0, "ensemble_bdf", ctx=ctx, opts=opts,
                      lin_solver=SPGMR(tol=1e-12, restart=30,
                                       max_restarts=6))
    sol_d = integrate(prob, 0.0, 2.0, "ensemble_bdf", ctx=ctx, opts=opts,
                      lin_solver=BlockDiagGJ(factor_once=False))
    assert bool(sol_k.success) and bool(sol_d.success)
    np.testing.assert_allclose(np.asarray(sol_k.y), np.asarray(sol_d.y),
                               rtol=0, atol=1e-8)
    # distinct solver stats: the Krylov path reports inner iterations,
    # the direct path reports none; names differ
    assert sol_k.lin_solver == "spgmr" and sol_d.lin_solver == "blockdiag_gj"
    assert int(sol_k.nli) > 0
    assert int(sol_d.nli) == 0
    assert int(jnp.sum(sol_k.nsetups)) > 0
    # real workspace accounting: history + Newton blocks registered
    assert sol_k.workspace_bytes > 0
    assert ctx.memory.high_water_bytes > 0
    assert sol_k.high_water_bytes >= sol_k.workspace_bytes


def test_ensemble_bdf_default_is_factor_once_blockdiag():
    """No lin_solver -> BlockDiagGJ(factor_once=True), bitwise equal to
    passing it explicitly."""
    prob = IVP(f=_FB, jac=_JB, y0=_YB)
    opts = ODEOptions(rtol=1e-6, atol=1e-10)
    sol_def = integrate(prob, 0.0, 1.0, "ensemble_bdf", opts=opts)
    sol_exp = integrate(prob, 0.0, 1.0, "ensemble_bdf", opts=opts,
                        lin_solver=BlockDiagGJ(factor_once=True))
    assert bool(jnp.all(sol_def.y == sol_exp.y))
    assert sol_def.lin_solver == "blockdiag_gj"


def test_scalar_bdf_lin_solver_objects():
    """DenseGJ and SPGMR objects plug into the scalar BDF and agree with
    the legacy dense_jac / default paths bitwise."""
    opts = ODEOptions(rtol=1e-7, atol=1e-10)
    prob = IVP(f=_f1, y0=jnp.zeros((2,)))
    sol_dense = integrate(prob, 0.0, 1.5, "bdf", opts=opts,
                          lin_solver=DenseGJ())
    y_ref, _ = cvode.bdf_integrate(_f1, jnp.zeros((2,)), 0.0, 1.5,
                                   opts=opts, dense_jac=True)
    assert bool(jnp.all(sol_dense.y == y_ref))
    assert sol_dense.lin_solver == "dense_gj"
    sol_gm = integrate(prob, 0.0, 1.5, "bdf", opts=opts,
                       lin_solver=SPGMR())
    y_ref2, _ = cvode.bdf_integrate(_f1, jnp.zeros((2,)), 0.0, 1.5,
                                    opts=opts)
    assert bool(jnp.all(sol_gm.y == y_ref2))


# ---------------------------------------------------------------------------
# backward-compat shims: still working, but deprecation-gated
# ---------------------------------------------------------------------------


def test_lin_mode_shim_warns_and_matches_object_api():
    prob_f, prob_jac, y0 = _FB, _JB, _YB
    opts = ODEOptions(rtol=1e-6, atol=1e-10)
    with pytest.warns(DeprecationWarning, match="repro-compat"):
        y_shim, _ = batched.ensemble_bdf_integrate(
            prob_f, prob_jac, y0, 0.0, 1.0, opts=opts, lin_mode="direct")
    y_obj, _ = batched.ensemble_bdf_integrate(
        prob_f, prob_jac, y0, 0.0, 1.0, opts=opts,
        linear_solver=BlockDiagGJ(factor_once=False))
    assert bool(jnp.all(y_shim == y_obj))


def test_bdf_fixed_bare_kwargs_shim():
    with pytest.warns(DeprecationWarning, match="repro-compat"):
        y_shim = cvode.bdf_fixed(_f1, jnp.zeros((1,)), 0.0, 1.0, 40,
                                 order=2, newton_iters=8)
    y_opts = cvode.bdf_fixed(_f1, jnp.zeros((1,)), 0.0, 1.0, 40, order=2,
                             opts=ODEOptions(newton_max=8))
    assert bool(jnp.all(y_shim == y_opts))


# ---------------------------------------------------------------------------
# normalized SolveStats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,solver", [
    ("gmres", krylov.gmres), ("fgmres", krylov.fgmres),
    ("pcg", krylov.pcg), ("bicgstab", krylov.bicgstab),
    ("tfqmr", krylov.tfqmr)])
def test_solvestats_true_residual_convention(name, solver):
    """res_norm is the TRUE ||b - A x|| at exit for every solver, and
    converged is res_norm <= max(tol*||b||, atol) — identical semantics
    across the family (callers need no per-solver special cases)."""
    n = 20
    key = jax.random.PRNGKey(0)
    Q = jax.random.normal(key, (n, n)) * 0.1
    A = Q @ Q.T + 5.0 * jnp.eye(n)          # SPD: every solver applies
    b = jax.random.normal(jax.random.PRNGKey(1), (n,))
    tol = 1e-10
    x, st = solver(lambda v: A @ v, b, tol=tol, **(
        {} if name in ("gmres", "fgmres") else {"maxiter": 400}))
    true_res = float(jnp.linalg.norm(b - A @ x))
    np.testing.assert_allclose(float(st.res_norm), true_res,
                               rtol=1e-6, atol=1e-13)
    target = tol * float(jnp.linalg.norm(b))
    assert bool(st.converged) == (float(st.res_norm) <= target)
    assert bool(st.converged)
    assert int(st.iters) > 0


def test_krylov_mem_registration():
    n = 64
    A = 3.0 * jnp.eye(n)
    b = jnp.ones((n,))
    mem = MemoryHelper()
    krylov.gmres(lambda v: A @ v, b, tol=1e-10, restart=10, mem=mem)
    assert "spgmr.basis" in mem.workspaces
    assert mem.high_water_bytes >= 11 * n * 8
    # idempotent per label: a second identical call must not double-count
    hw = mem.high_water_bytes
    krylov.gmres(lambda v: A @ v, b, tol=1e-10, restart=10, mem=mem)
    assert mem.high_water_bytes == hw


# ---------------------------------------------------------------------------
# nonlinear-solver objects and context plumbing
# ---------------------------------------------------------------------------


def test_newton_solver_takes_tolerances_from_options():
    opts = ODEOptions(newton_tol_fac=0.03, newton_max=7)
    nls = NewtonSolver.from_options(opts)
    assert nls.tol == 0.03 and nls.max_iters == 7
    fps = FixedPointSolver.from_options(ODEOptions(atol=1e-6,
                                                   newton_tol_fac=0.1), m=4)
    assert fps.m == 4 and fps.tol == pytest.approx(0.1 * 1e-6 + 1e-12)
    # a custom Newton config actually changes integrator behavior
    opts_run = ODEOptions(rtol=1e-6, atol=1e-9)
    prob = IVP(f=_f1, y0=jnp.zeros((2,)))
    sol_tight = integrate(prob, 0.0, 1.0, "dirk:sdirk2", opts=opts_run,
                          nonlin_solver=NewtonSolver(tol=1e-10,
                                                     max_iters=12))
    sol_def = integrate(prob, 0.0, 1.0, "dirk:sdirk2", opts=opts_run)
    assert int(sol_tight.nni) > int(sol_def.nni)


def test_context_counters_and_options():
    ctx = Context()
    opts = ctx.options(rtol=1e-5, atol=1e-8)
    assert opts.policy is ctx.policy
    prob = IVP(f=_f1, y0=jnp.zeros((2,)))
    integrate(prob, 0.0, 0.5, "erk:dopri5", ctx=ctx, opts=opts)
    integrate(prob, 0.0, 0.5, "bdf", ctx=ctx, opts=opts)
    assert ctx.counters["integrations"] == 2
    assert ctx.counters["steps"] > 0
    assert ctx.counters["newton_iters"] > 0


def test_memory_helper_register_release():
    mem = MemoryHelper()
    nb = mem.register("a", (10, 10), jnp.float64)
    assert nb == 800 and mem.live_bytes == 800
    mem.register("b", (5,), jnp.float32)
    assert mem.live_bytes == 820 and mem.high_water_bytes == 820
    mem.release("a")
    assert mem.live_bytes == 20
    assert mem.high_water_bytes == 820      # the mark persists
    mem.release()
    assert mem.live_bytes == 0


def test_solution_reports_workspace_for_scalar_bdf():
    """Krylov basis + BDF history register with the context memory
    helper (they were dead code before this layer)."""
    ctx = Context()
    prob = IVP(f=_f1, y0=jnp.zeros((4,)))
    sol = integrate(prob, 0.0, 1.0, "bdf", ctx=ctx,
                    opts=ODEOptions(rtol=1e-6, atol=1e-9))
    # bdf history (QMAX+1=6 rows) + spgmr basis/hessenberg
    assert sol.workspace_bytes >= 6 * 4 * 8
    assert "bdf.history" not in ctx.memory.workspaces  # released per-call
    assert ctx.memory.high_water_bytes == sol.high_water_bytes


def test_split_problem_through_non_imex_methods_uses_full_rhs():
    """An IMEX-split IVP run through bdf/dirk/erk must integrate fe+fi
    (the full RHS), not silently drop the explicit part."""
    prob = IVP(fe=_fe1, fi=_fi1, y0=jnp.zeros((2,)))
    opts = ODEOptions(rtol=1e-7, atol=1e-10)
    full = lambda t, y: _fe1(t, y) + _fi1(t, y)      # == _f1
    for method in ("bdf", "dirk:sdirk2", "erk:dopri5"):
        sol = integrate(prob, 0.0, 1.0, method, opts=opts)
        y_ref, _ = _legacy(method, IVP(f=full, y0=jnp.zeros((2,))),
                           0.0, 1.0, opts)
        np.testing.assert_allclose(np.asarray(sol.y), np.asarray(y_ref),
                                   rtol=0, atol=1e-12, err_msg=method)


def test_integrate_releases_only_its_own_workspaces():
    ctx = Context()
    ctx.memory.register("user.buffer", (100,), jnp.float64)
    integrate(IVP(f=_f1, y0=jnp.zeros((2,))), 0.0, 0.5, "bdf", ctx=ctx,
              opts=ODEOptions(rtol=1e-5, atol=1e-8))
    # the user's registration survives; integrate's own labels are gone
    assert ctx.memory.workspaces == {"user.buffer": 800}
    assert ctx.memory.live_bytes == 800


def test_ivp_validation():
    with pytest.raises(ValueError):
        IVP(y0=jnp.zeros((2,)))                      # no RHS
    with pytest.raises(ValueError):
        IVP(f=_f1, fe=_fe1, fi=_fi1, y0=jnp.zeros((2,)))  # both forms
    with pytest.raises(ValueError):
        IVP(f=_f1, y0=None)                          # no y0
    with pytest.raises(ValueError):
        integrate(IVP(f=_f1, y0=jnp.zeros((2,))), 0.0, 1.0, "rk4")
    with pytest.raises(ValueError):
        # ensemble_bdf needs an analytic jac
        integrate(IVP(f=_FB, y0=_YB), 0.0, 1.0, "ensemble_bdf")
    with pytest.raises(ValueError):
        # a solver the family cannot consume is an error, not a silent
        # no-op with a lying Solution.lin_solver
        integrate(IVP(f=_FB, jac=_JB, y0=_YB), 0.0, 1.0,
                  "ensemble_dirk:sdirk2", lin_solver=SPGMR())
    with pytest.raises(ValueError):
        integrate(IVP(f=_f1, y0=jnp.zeros((2,))), 0.0, 1.0, "erk:dopri5",
                  nonlin_solver=NewtonSolver())
