"""Batched-BDF ensemble subsystem tests: per-system adaptivity, the
jnp-oracle vs Pallas(interpret) block-kernel parity (incl. a batch that
is not a multiple of 128), Jacobian-reuse (lsetup) accounting, and the
shard_map system-axis path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import batched, dispatch as dv
from repro.core.arkode import ODEOptions
from repro.core.linsol import BlockDiagGJ
from repro.core.policies import ExecPolicy, XLA_FUSED
from repro.kernels import ops, ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# the batched-kinetics example problem (Robertson with per-cell rates)
# is shared with the example and the benchmark
from repro.core.problems import batched_robertson as _kinetics


def _decay(nsys, n):
    rates = jnp.linspace(10.0, 80.0, nsys)

    def f(t, y):
        return -rates[:, None] * (y - jnp.cos(t)[:, None])

    def jac(t, y):
        return jnp.broadcast_to(-rates[:, None, None] * jnp.eye(n),
                                (y.shape[0], n, n))

    lam = np.asarray(rates)[:, None]

    def exact(t):
        return (lam * (lam * np.cos(t) + np.sin(t)) -
                lam ** 2 * np.exp(-lam * t)) / (lam ** 2 + 1)

    return f, jac, exact


@pytest.mark.parametrize("factor_once", [True, False],
                         ids=["setup", "direct"])
def test_bdf_accuracy_and_per_system_control(factor_once):
    nsys, n = 6, 3
    f, jac, exact = _decay(nsys, n)
    y0 = jnp.zeros((nsys, n))
    y, st = batched.ensemble_bdf_integrate(
        f, jac, y0, 0.0, 2.0, opts=ODEOptions(rtol=1e-6, atol=1e-10),
        linear_solver=BlockDiagGJ(factor_once=factor_once))
    assert bool(jnp.all(st.success))
    np.testing.assert_allclose(np.asarray(y),
                               np.broadcast_to(exact(2.0), (nsys, n)),
                               rtol=1e-4, atol=1e-6)
    # per-system step control: step counts differ across stiffness
    steps = np.asarray(st.steps)
    assert steps.min() != steps.max()
    # modified Newton reuses the Jacobian: lsetups well below steps
    assert np.all(np.asarray(st.nsetups) < 0.7 * steps)
    # nni is counted per system
    assert np.asarray(st.nni).min() > 0


def test_bdf_high_order_beats_low_order():
    """Order ramp must pay off: BDF5 needs far fewer steps than BDF2.
    (order=1 is not compared: the scalar seed bdf_integrate stalls there
    on this problem too — shared fixed-leading-coefficient limitation.)"""
    nsys, n = 4, 3
    f, jac, _ = _decay(nsys, n)
    y0 = jnp.zeros((nsys, n))
    opts = ODEOptions(rtol=1e-7, atol=1e-10)
    _, st5 = batched.ensemble_bdf_integrate(f, jac, y0, 0.0, 2.0,
                                            order=5, opts=opts)
    _, st2 = batched.ensemble_bdf_integrate(f, jac, y0, 0.0, 2.0,
                                            order=2, opts=opts)
    assert bool(jnp.all(st5.success)) and bool(jnp.all(st2.success))
    assert np.median(np.asarray(st5.steps)) < \
        0.7 * np.median(np.asarray(st2.steps))


@pytest.mark.parametrize("factor_once", [True, False],
                         ids=["setup", "direct"])
def test_bdf_kinetics_jnp_vs_pallas_parity(factor_once):
    """Acceptance gate: trajectories agree between the jnp oracle and the
    Pallas(interpret) fused-kernel path at controller-tolerance scale on
    the batched-kinetics example, with nsys NOT a multiple of 128.

    The bound is the controller's, not machine eps: the fused
    Newton/history kernels round independently of XLA's fusion of the
    inline oracles (e.g. z + corr*spmv FMA-contracts inline but not
    across a kernel boundary), so per-system accept/order decisions can
    flip and the two *valid* adaptive trajectories separate by the
    local error the controller permits — which the WRMS control bounds
    PER COMPONENT as C*(rtol*|y_i| + atol), so the comparison uses the
    same mixed form (C=100) and the ~1e-5-magnitude intermediate
    species stays genuinely exercised.  Op-level parity is gated
    separately at 1e-10 (test_soa_carry.py, kernels_bench --smoke); the
    jnp path itself is pinned bitwise to the pre-SoA integrator in
    test_soa_carry.py."""
    nsys = 130
    ls = BlockDiagGJ(factor_once=factor_once)
    f, jac, y0 = _kinetics(nsys)
    opts = ODEOptions(rtol=1e-5, atol=1e-10, max_steps=100_000)
    y_j, st_j = batched.ensemble_bdf_integrate(
        f, jac, y0, 0.0, 10.0, opts=opts, policy=XLA_FUSED,
        linear_solver=ls)
    pol = ExecPolicy(backend="pallas", interpret=True, batch_tile=256)
    y_p, st_p = batched.ensemble_bdf_integrate(
        f, jac, y0, 0.0, 10.0, opts=opts, policy=pol, linear_solver=ls)
    assert bool(jnp.all(st_j.success)) and bool(jnp.all(st_p.success))
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(y_p),
                               rtol=100 * opts.rtol, atol=100 * opts.atol)
    # physically sensible on BOTH backends: mass conserved to tol scale
    assert float(jnp.max(jnp.abs(jnp.sum(y_j, 1) - 1.0))) < 1e-4
    assert float(jnp.max(jnp.abs(jnp.sum(y_p, 1) - 1.0))) < 1e-4


def test_bdf_per_lane_tf_clip_rescale_parity():
    """Lanes with their own tf: each lane's last step is clipped to its
    tf on its own trip, so the clip-site history rescale runs for some
    lanes of a bundle tile and passes the others through.  jnp oracle
    vs the Pallas(interpret) kernels, in the tolerance form of
    :func:`test_bdf_kinetics_jnp_vs_pallas_parity` with C = 10: the two
    backends agree to within 1.2 of the control's units here, while a
    kernel path that skipped the clip rescale lands 30-40 units off."""
    nsys = 130
    f, jac, y0 = _kinetics(nsys)
    tf = jnp.linspace(2.0, 10.0, nsys)
    opts = ODEOptions(rtol=1e-5, atol=1e-10, max_steps=100_000)
    y_j, st_j = batched.ensemble_bdf_integrate(
        f, jac, y0, 0.0, tf, opts=opts, policy=XLA_FUSED)
    pol = ExecPolicy(backend="pallas", interpret=True, batch_tile=128)
    y_p, st_p = batched.ensemble_bdf_integrate(
        f, jac, y0, 0.0, tf, opts=opts, policy=pol)
    assert bool(jnp.all(st_j.success)) and bool(jnp.all(st_p.success))
    # the lanes finish on different trips: the clip engages lane by lane
    assert len(np.unique(np.asarray(st_p.steps))) > 1
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(y_p),
                               rtol=10 * opts.rtol, atol=10 * opts.atol)


def test_bdf_matches_scalar_cvode_reference():
    """One system of the ensemble path vs the scalar CVODE analog."""
    from repro.core import cvode
    n = 3
    f1 = lambda t, y: -40.0 * (y - jnp.cos(t))
    fb = lambda t, y: -40.0 * (y - jnp.cos(t)[:, None])
    jacb = lambda t, y: jnp.broadcast_to(-40.0 * jnp.eye(n),
                                         (y.shape[0], n, n))
    y0 = jnp.zeros((n,))
    opts = ODEOptions(rtol=1e-7, atol=1e-12)
    y_ref, st_ref = cvode.bdf_integrate(f1, y0, 0.0, 1.5, opts=opts,
                                        dense_jac=True)
    y_ens, st_ens = batched.ensemble_bdf_integrate(
        fb, jacb, y0[None, :], 0.0, 1.5, opts=opts)
    assert bool(st_ref.success) and bool(jnp.all(st_ens.success))
    # both must hit the analytic solution at their shared tolerance
    lam = 40.0
    exact = (lam * (lam * np.cos(1.5) + np.sin(1.5)) -
             lam ** 2 * np.exp(-lam * 1.5)) / (lam ** 2 + 1)
    np.testing.assert_allclose(np.asarray(y_ens)[0], exact, rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(y_ref), exact, rtol=1e-5,
                               atol=1e-7)


# ---------------------------------------------------------------------------
# dispatched SoA block ops: jnp oracle vs pallas-interpret
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb", [7, 130, 516])
@pytest.mark.parametrize("b", [3, 8, 16, 24])
def test_block_ops_dispatch_parity_ragged_batches(nb, b):
    """b <= 8 exercises the fully-unrolled GJ kernels, b >= 16 the
    row-tiled elimination that replaced them at large block sizes."""
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (b, b, nb)) + \
        (b + 2.0) * jnp.eye(b)[:, :, None]
    r = jax.random.normal(jax.random.PRNGKey(1), (b, nb))
    for tile in (128, 512):
        pol = ExecPolicy(backend="pallas", interpret=True, batch_tile=tile)
        np.testing.assert_allclose(
            np.asarray(dv.block_solve_soa(A, r, pol)),
            np.asarray(dv.block_solve_soa(A, r, XLA_FUSED)), atol=1e-10)
        np.testing.assert_allclose(
            np.asarray(dv.block_inverse_soa(A, pol)),
            np.asarray(dv.block_inverse_soa(A, XLA_FUSED)), atol=1e-10)
        np.testing.assert_allclose(
            np.asarray(dv.blockdiag_spmv_soa(A, r, pol)),
            np.asarray(dv.blockdiag_spmv_soa(A, r, XLA_FUSED)), atol=1e-12)


def test_block_inverse_kernel_vs_ref():
    key = jax.random.PRNGKey(2)
    A = jax.random.normal(key, (4, 4, 200)) + 6.0 * jnp.eye(4)[:, :, None]
    inv = ops.block_inverse_soa(A, batch_tile=128)
    np.testing.assert_allclose(np.asarray(inv),
                               np.asarray(ref.block_inverse_soa_ref(A)),
                               atol=1e-10)
    # identity check through the spmv kernel (lsetup @ lsolve round trip)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 200))
    y = ops.blockdiag_spmv_soa(inv, ops.blockdiag_spmv_soa(A, x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-9)


def test_batch_tile_knob_is_honored():
    """Tiles above one lane must reach the kernel grid (regression: the
    old wrappers clamped every tile to 128), and the tile must divide
    the lane-padded batch so padding stays below one lane (regression:
    a rounded-up tile could pad nb=516 out to 1024, ~2x the work)."""
    from repro.kernels.ops import _batch_tile
    assert _batch_tile(4096, 512) == 512
    assert _batch_tile(4096, 300) == 256     # largest divisor <= knob
    assert _batch_tile(200, 512) == 256      # clamped to padded batch
    assert _batch_tile(7, 128) == 128
    assert _batch_tile(516, 512) == 128      # 640 % 512 != 0 -> one lane
    assert _batch_tile(516, 128 * 5) == 640  # exact bundle still taken


def test_gj_vmem_tile_cap_shrinks_with_b_squared():
    """Compiled-mode GJ tiles are clamped so the (b, width, tile) f64
    accumulator stays under GJ_VMEM_BYTES — the cap shrinks ~1/b^2.
    Interpret mode (CPU emulation, no VMEM) is uncapped.  This branch
    only executes on real TPU, so it is pinned here as pure arithmetic."""
    from repro.kernels.ops import _gj_batch_tile
    kw = dict(itemsize=8, interpret=False)
    # no cap under interpret emulation
    assert _gj_batch_tile(4096, 4096, b=16, width=17,
                          itemsize=8, interpret=True) == 4096
    # b=16 solve: 2MiB/(8*16*17)=963 -> 896 lanes-floor -> divisor 512
    assert _gj_batch_tile(4096, 4096, b=16, width=17, **kw) == 512
    # b=24 solve: 2MiB/(8*24*25)=436 -> 384 -> divisor 256
    assert _gj_batch_tile(4096, 4096, b=24, width=25, **kw) == 256
    # small blocks: cap (21k+) never binds on a practical tile
    assert _gj_batch_tile(4096, 512, b=3, width=4, **kw) == 512
    # floor at one lane even when the budget math rounds to zero
    assert _gj_batch_tile(4096, 4096, b=64, width=65, **kw) == 128


# ---------------------------------------------------------------------------
# sharded system axis (subprocess with its own fake-device XLA flags)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bdf_sharded_matches_single_device():
    code = textwrap.dedent("""
        import jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp, numpy as np
        from repro.core import batched
        from repro.core.arkode import ODEOptions
        nsys, n = 10, 3   # not divisible by 4 -> exercises padding
        rates = jnp.linspace(10.0, 80.0, nsys)
        def f(t, y, prm):
            return -prm[:, None] * (y - jnp.cos(t)[:, None])
        def jac(t, y, prm):
            return jnp.broadcast_to(-prm[:, None, None] * jnp.eye(n),
                                    (y.shape[0], n, n))
        y0 = jnp.zeros((nsys, n))
        opts = ODEOptions(rtol=1e-6, atol=1e-10)
        y_sh, st = batched.ensemble_bdf_integrate_sharded(
            f, jac, y0, 0.0, 2.0, params=rates, opts=opts)
        y_1, _ = batched.ensemble_bdf_integrate(
            lambda t, y: f(t, y, rates), lambda t, y: jac(t, y, rates),
            y0, 0.0, 2.0, opts=opts)
        assert y_sh.shape == (nsys, n)
        assert bool(jnp.all(st.success))
        assert st.steps.shape == (nsys,)
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_1),
                                   rtol=0, atol=1e-12)
        # pluggable Krylov under shard_map: nli must keep its invariant
        # (every entry == the GLOBAL inner-iteration total, not a
        # per-shard broadcast)
        from repro.core.linsol import SPGMR
        y_k, st_k = batched.ensemble_bdf_integrate_sharded(
            f, jac, y0, 0.0, 2.0, params=rates, opts=opts,
            linear_solver=SPGMR(tol=1e-12, restart=30, max_restarts=6))
        assert int(np.asarray(st_k.nli)[0]) > 0
        assert len(np.unique(np.asarray(st_k.nli))) == 1
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_1),
                                   rtol=0, atol=1e-6)
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"      # children never touch the chip
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK" in out.stdout
