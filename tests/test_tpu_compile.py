"""Compile the main-path Pallas kernels for a TPU v5e without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described rather than attached (``v5e:2x2``, one chip of it).  Nothing
runs: these tests catch what interpret mode cannot — block shapes and
layouts Mosaic refuses, primitives it does not lower, x64-only index
types — at real widths (nsys = 32768, float32), with ``jax_enable_x64``
both off and on, since the float64 reference shares the process with
the chip path.

Kernels are compiled with ``interpret=False`` and the roofline device
named explicitly: code that asks ``jax.default_backend()`` still sees
the CPU here.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

NSYS = 32768
X64 = pytest.mark.parametrize("x64", [False, True], ids=["x64off", "x64on"])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, x64, fn, *shapes):
    """Lower and compile ``fn`` for one described v5e chip over float32
    operands of ``shapes``; returns the compiled program's HLO text."""
    with jax.enable_x64(x64):
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


NEWTON = {
    "newton_residual_soa": (
        lambda z, f, p, g: ops.newton_residual_soa(
            z, f, p, g, interpret=False, negate=True),
        [(3, NSYS)] * 3 + [(NSYS,)]),
    "masked_update_wrms_soa": (
        lambda z, d, w, m: ops.masked_update_wrms_soa(
            z, d, w, m, interpret=False),
        [(3, NSYS)] * 3 + [(NSYS,)]),
    "history_rescale_soa": (
        lambda W, Z, a: ops.history_rescale_soa(W, Z, a, interpret=False),
        [(6, 6, NSYS), (6, 3, NSYS), (NSYS,)]),
    "wrms_soa": (
        lambda v, w: ops.wrms_soa(v, w, interpret=False),
        [(3, NSYS)] * 2),
}


@X64
@pytest.mark.parametrize("op", sorted(NEWTON))
def test_newton_kernel_compiles(one_chip, op, x64):
    fn, shapes = NEWTON[op]
    _assert_kernel(_compile(one_chip, x64, fn, *shapes))


@X64
@pytest.mark.parametrize("b", [3, 8])
def test_unrolled_block_kernels_compile(one_chip, b, x64):
    _assert_kernel(_compile(
        one_chip, x64,
        lambda A: ops.block_inverse_soa(A, interpret=False),
        (b, b, NSYS)))
    _assert_kernel(_compile(
        one_chip, x64,
        lambda A, x: ops.blockdiag_spmv_soa(A, x, interpret=False),
        (b, b, NSYS), (b, NSYS)))


@X64
@pytest.mark.parametrize("b", [16, 24])
def test_row_tiled_block_kernels_compile(one_chip, b, x64):
    """b > 8 takes the row-tiled Gauss-Jordan kernels, which must not
    use scatter (Pallas TPU has no lowering for it)."""
    _assert_kernel(_compile(
        one_chip, x64,
        lambda A, r: ops.block_solve_soa(A, r, interpret=False),
        (b, b, NSYS), (b, NSYS)))
    _assert_kernel(_compile(
        one_chip, x64,
        lambda A: ops.block_inverse_soa(A, interpret=False),
        (b, b, NSYS)))


VECTOR = {
    "wrms_norm": (lambda x, w: ops.wrms_norm(x, w, interpret=False),
                  [(NSYS,)] * 2),
    "dot_prod_multi": (
        lambda x, Y: ops.dot_prod_multi(x, Y, interpret=False),
        [(NSYS,), (3, NSYS)]),
    # a policy tile below one (8, 128) vreg tile is rounded up to it
    "linear_combination": (
        lambda c, X: ops.linear_combination(c, X, block_elems=128,
                                            interpret=False),
        [(3,), (3, NSYS)]),
}


@X64
@pytest.mark.parametrize("op", sorted(VECTOR))
def test_vector_kernel_compiles(one_chip, op, x64):
    fn, shapes = VECTOR[op]
    _assert_kernel(_compile(one_chip, x64, fn, *shapes))


def test_bsr_kernels_compile(one_chip):
    pattern = dict(brows=(0, 0, 1, 1), bcols=(0, 1, 0, 1), nblk=2)
    _assert_kernel(_compile(
        one_chip, True,
        lambda v, x: ops.bsr_spmv_soa(v, x, interpret=False, **pattern),
        (4, 4, 4, NSYS), (2, 4, NSYS)))
    _assert_kernel(_compile(
        one_chip, True,
        lambda v: ops.bsr_diag_inverse_soa(v, interpret=False, **pattern),
        (4, 4, 4, NSYS)))


def test_csr_kernel_refuses_to_compile():
    """The CSR lane gather has no Mosaic lowering: selecting it compiled
    fails loudly instead of running something else in its place."""
    indptr = (0, 1, 2)
    with pytest.raises(NotImplementedError, match="csr_spmv"):
        ops.csr_spmv(jnp.ones(2), jnp.ones(2), indptr=indptr,
                     indices=(0, 1), interpret=False)


def _ensemble_bdf_text(one_chip, x64, backend):
    """Compiled chip program of the float32 Robertson ensemble-BDF run
    under ``backend``."""
    from repro.core import problems
    from repro.core.arkode import ODEOptions
    from repro.core.batched import ensemble_bdf_integrate
    from repro.core.linsol import BlockDiagGJ
    from repro.core.policies import ExecPolicy

    policy = ExecPolicy(backend=backend, interpret=False, device="tpu_v5e")
    with jax.enable_x64(False):         # float32 rate constants
        f, jac, _ = problems.batched_robertson(NSYS)
        f_soa, jac_soa = problems.batched_robertson_soa(NSYS)

    def run(y0):
        y, st = ensemble_bdf_integrate(
            f, jac, y0, 0.0, 40.0, policy=policy,
            opts=ODEOptions(rtol=1e-4, atol=1e-8, policy=policy),
            linear_solver=BlockDiagGJ(), f_soa=f_soa, jac_soa=jac_soa)
        return y, st.retcodes

    return _compile(one_chip, x64, run, (NSYS, 3))


@X64
def test_ensemble_bdf_compiles_with_kernels(one_chip, x64):
    """The whole ensemble-BDF integration under the compiled Pallas
    policy: every hot-loop op is a Mosaic kernel in the chip program."""
    text = _ensemble_bdf_text(one_chip, x64, "pallas")
    _assert_kernel(text)
    # the Newton kernels run inside the step loop, not beside it
    assert text.count("tpu_custom_call") >= 5


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_ensemble_bdf_keeps_float32_on_chip(one_chip, backend):
    """No contraction of the float32 program is rounded to bfloat16, the
    TPU's default matmul precision: with it, every lane of the jnp
    ensemble failed its Newton iteration on a v5e."""
    assert "bf16" not in _ensemble_bdf_text(one_chip, False, backend)


def test_device_kind_table():
    from repro.analysis import roofline
    assert roofline.device_for_kind("TPU v5 lite") == "tpu_v5e"
    with pytest.raises(ValueError, match="device_kind"):
        roofline.device_for_kind("TPU v99")


def test_policy_interpret_follows_backend():
    from repro.core.policies import ExecPolicy
    pol = ExecPolicy(backend="pallas")
    assert pol.interpret is None
    assert pol.interpreted() == (jax.default_backend() != "tpu")
    assert ExecPolicy(backend="pallas", interpret=False).interpreted() \
        is False
    x = np.arange(5.0)
    # derived mode on this backend matches the oracle
    np.testing.assert_allclose(
        ops.wrms_norm(jnp.asarray(x), jnp.ones(5)),
        np.sqrt(np.mean(x * x)))
