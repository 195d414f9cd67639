"""Demonstration-problem tests (paper §7): correctness of both solver
configurations + the properties the paper claims."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import brusselator as br
from repro.apps.brusselator import BrusselatorConfig
from repro.core.policies import ExecPolicy

TF = 0.2


def test_task_local_matches_global():
    cfg_tl = BrusselatorConfig(nx=96, solver="task-local")
    cfg_gl = BrusselatorConfig(nx=96, solver="global")
    y_tl, st_tl = br.integrate(cfg_tl, t_final=TF)
    y_gl, st_gl = br.integrate(cfg_gl, t_final=TF)
    assert bool(st_tl.success) and bool(st_gl.success)
    np.testing.assert_allclose(np.asarray(y_tl), np.asarray(y_gl),
                               rtol=1e-7, atol=1e-9)


def test_against_explicit_reference():
    cfg = BrusselatorConfig(nx=64)
    y, st = br.integrate(cfg, t_final=TF)
    ref = br.reference_solution(cfg, TF, n_steps=20000)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-4,
                               atol=1e-6)
    # IMEX must need FAR fewer steps than the explicit stability limit
    # h_stab ~ eps = 5e-6  ->  explicit needs ~ tf/eps = 4e4 steps
    assert int(st.steps) < 500


def test_pallas_block_solver_path():
    cfg = BrusselatorConfig(nx=64, solver="task-local")
    pol = ExecPolicy(backend="pallas", interpret=True, batch_tile=128)
    y_pal, st = br.integrate(cfg, t_final=0.05, policy=pol)
    y_jnp, _ = br.integrate(cfg, t_final=0.05)
    assert bool(st.success)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_jnp),
                               rtol=1e-8, atol=1e-10)


def test_reaction_jacobian_is_exact():
    cfg = BrusselatorConfig(nx=8)
    fi = br.reaction_rhs(cfg)
    jac = br.reaction_jacobian(cfg)
    y = br.initial_state(cfg) + 0.05
    J_ad = jax.jacfwd(lambda yy: fi(0.0, yy))(y)   # (nx,3,nx,3)
    J_an = jac(0.0, y)
    for i in range(cfg.nx):
        np.testing.assert_allclose(np.asarray(J_ad[i, :, i, :]),
                                   np.asarray(J_an[i]), rtol=1e-10)
        # off-diagonal blocks are exactly zero (point-local reactions)
        if i:
            assert float(jnp.abs(J_ad[i, :, 0, :]).max()) == 0.0


def test_advection_is_conservative_and_periodic():
    cfg = BrusselatorConfig(nx=32)
    fe = br.advection_rhs(cfg)
    y = br.initial_state(cfg)
    dy = fe(0.0, y)
    # upwind advection conserves the total of each species (periodic BC)
    np.testing.assert_allclose(np.asarray(jnp.sum(dy, axis=0)),
                               np.zeros(3), atol=1e-10)


def test_mass_behavior_under_integration():
    """u+v evolves only through the A source and u-term (sanity physics)."""
    cfg = BrusselatorConfig(nx=48)
    y, st = br.integrate(cfg, t_final=0.1)
    assert bool(st.success)
    assert bool(jnp.all(y[:, 0] > 0)) and bool(jnp.all(y[:, 1] > 0))
    # w is pinned near B by the stiff relaxation
    np.testing.assert_allclose(np.asarray(y[:, 2]), cfg.B, rtol=0.2)
