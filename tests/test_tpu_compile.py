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
import re

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
    "lagrange_rescale_soa": (
        lambda e, q, Z, a: ops.lagrange_rescale_soa(e, q, Z, a,
                                                    interpret=False),
        [(NSYS,), (NSYS,), (6, 3, NSYS), (NSYS,)]),
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


def _ensemble_bdf_text(one_chip, x64, backend, nsys=NSYS):
    """Compiled chip program of the float32 Robertson ensemble-BDF run
    under ``backend``."""
    from repro.core import problems
    from repro.core.arkode import ODEOptions
    from repro.core.batched import ensemble_bdf_integrate
    from repro.core.linsol import BlockDiagGJ
    from repro.core.policies import ExecPolicy

    policy = ExecPolicy(backend=backend, interpret=False, device="tpu_v5e")
    with jax.enable_x64(False):         # float32 rate constants
        f, jac, _ = problems.batched_robertson(nsys)
        f_soa, jac_soa = problems.batched_robertson_soa(nsys)

    def run(y0):
        y, st = ensemble_bdf_integrate(
            f, jac, y0, 0.0, 40.0, policy=policy,
            opts=ODEOptions(rtol=1e-4, atol=1e-8, policy=policy),
            linear_solver=BlockDiagGJ(), f_soa=f_soa, jac_soa=jac_soa)
        return y, st.retcodes

    return _compile(one_chip, x64, run, (nsys, 3))


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


PHASES = {f"ensemble_bdf.{p}" for p in ("rescale", "predict", "lsetup",
                                         "newton", "error_test", "update")}
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_NOT_RUN = ("parameter", "get-tuple-element", "tuple", "bitcast",
            "constant")
# instructions XLA adds on its own, which carry no scope of the program
_XLA_OWN = ("copy", "copy-start", "copy-done", "broadcast")


def _computations(text):
    """``{computation: [instruction lines]}`` of an HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        s = line.strip()
        if s.endswith("{") and " -> " in s:
            cur = s.split()[1 if s.startswith("ENTRY") else 0].lstrip("%")
            comps[cur] = []
        elif s == "}":
            cur = None
        elif cur is not None and " = " in s:
            comps[cur].append(s)
    return comps


def _reachable(comps, root):
    """``root`` and every computation it calls, transitively."""
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo.extend(n for s in comps[c]
                        for n in re.findall(r"%([\w.\-]+)", s)
                        if n in comps)
    return seen


def _phase(line):
    """The innermost ``ensemble_bdf.*`` scope of an instruction."""
    m = _OP_NAME.search(line)
    found = [c for c in (m.group(1) if m else "").split("/")
             if c.startswith("ensemble_bdf.")]
    return found[-1] if found else None


def _opcode(line):
    rhs = line.split(" = ", 1)[1]
    m = re.match(r"(?:\(.*?\)|\S+)\s+([\w\-]+)\(", rhs)
    return m.group(1) if m else ""


def _loop_body(comps, scope):
    """The body computation of the one ``while`` whose op_name ends in
    ``scope + "while"``."""
    bodies = [re.search(r"body=%([\w.\-]+)", s).group(1)
              for lines in comps.values() for s in lines
              if _opcode(s) == "while" and _OP_NAME.search(s)
              and _OP_NAME.search(s).group(1).endswith(scope + "while")]
    assert len(bodies) == 1, (scope, bodies)
    return bodies[0]


def test_ensemble_bdf_ops_carry_their_phase(one_chip):
    """Every instruction the step loop runs names its phase of the step
    in its op_name metadata, apart from the copies, prefetches and
    broadcasts XLA adds on its own; every fusion of the Newton loop
    names the Newton phase.  The device trace's ops are attributed to a
    phase by that name."""
    _assert_phases(_ensemble_bdf_text(one_chip, False, "pallas", nsys=1024))


def _assert_phases(text):
    """The checks of :func:`test_ensemble_bdf_ops_carry_their_phase` on
    one compiled program's text."""
    comps = _computations(text)
    step = _reachable(comps, _loop_body(comps, "jit(<lambda>)/"))
    newton = _reachable(comps, _loop_body(comps, "ensemble_bdf.newton/"))
    kernels = [s for c in step for s in comps[c]
               if "tpu_custom_call" in s and _opcode(s) == "custom-call"]
    assert len(kernels) >= 5
    for s in kernels:
        assert _phase(s) in PHASES, s[:120]
    fusions = [s for c in newton for s in comps[c] if _opcode(s) == "fusion"]
    assert fusions
    for s in fusions:
        assert _phase(s) == "ensemble_bdf.newton", s[:120]
    # reducers (``to_apply=``) and fused bodies are not run on their own
    inner = {n for lines in comps.values() for s in lines
             for n in re.findall(r"to_apply=%([\w.\-]+)", s)}
    run = [(c, s) for c in comps
           if "fused_computation" not in c and c not in inner
           for s in comps[c] if _opcode(s) not in _NOT_RUN]
    in_step = [s for c, s in run if c in step]
    unscoped = [s for s in in_step if _phase(s) is None]
    for s in in_step:
        assert _phase(s) in PHASES or _opcode(s) in _XLA_OWN, s[:120]
    print(f"{len(unscoped)} of {len(in_step)} step-loop instructions "
          f"unscoped (XLA's own copies, prefetches and broadcasts); "
          f"{sum(_phase(s) is None for _, s in run)} of {len(run)} in all")


def _ensemble_mesh_text(topo, x64, nsys):
    """Compiled program of the float32 Robertson ensemble through
    ``integrate(..., mesh=)`` over the described v5e 2x2 (one shard of
    ``nsys / 4`` systems a chip), with the native SoA family and the
    rate constants in ``IVP.params``."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import problems
    from repro.core.arkode import ODEOptions
    from repro.core.ivp import IVP, integrate
    from repro.core.linsol import BlockDiagGJ
    from repro.core.policies import ExecPolicy

    mesh = Mesh(np.asarray(topo.devices), ("systems",))
    policy = ExecPolicy(backend="pallas", interpret=False, device="tpu_v5e")
    f, jac, f_soa, jac_soa = problems.robertson_family()

    def run(y0, params):
        sol = integrate(
            IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa, y0=y0,
                params=params), 0.0, 40.0, "ensemble_bdf",
            opts=ODEOptions(rtol=1e-4, atol=1e-8, policy=policy),
            lin_solver=BlockDiagGJ(), mesh=mesh)
        return sol.y, sol.retcodes

    shard = NamedSharding(mesh, P("systems"))
    with jax.enable_x64(x64):
        y0 = jax.ShapeDtypeStruct((nsys, 3), jnp.float32, sharding=shard)
        params = {k: jax.ShapeDtypeStruct((nsys,), jnp.float32,
                                          sharding=shard)
                  for k in ("k1", "k2", "k3")}
        return jax.jit(run).lower(y0, params).compile().as_text()


@X64
def test_ensemble_mesh_compiles_with_kernels(topo, x64):
    """The four-chip deployment (4,194,304 systems, 1,048,576 a chip)
    through ``integrate(..., mesh=)``: the Pallas kernels run in each
    chip's step loop, and every instruction outside the step loop, but
    XLA's own copies, prefetches and broadcasts, carries the sharded
    wrapper's scope ``ensemble_bdf.shards``."""
    text = _ensemble_mesh_text(topo, x64, 4 * 1048576)
    _assert_kernel(text)
    assert text.count("tpu_custom_call") >= 5
    comps = _computations(text)
    body = _loop_body(comps, "jit(<lambda>)/")
    step = _reachable(comps, body)
    step |= {c for lines in comps.values() for s in lines
             if _opcode(s) == "while"
             and re.search(rf"body=%{re.escape(body)}\b", s)
             for c in _reachable(
                 comps, re.search(r"condition=%([\w.\-]+)", s).group(1))}
    inner = {n for lines in comps.values() for s in lines
             for n in re.findall(r"to_apply=%([\w.\-]+)", s)}
    outside = [s for c in comps
               if "fused_computation" not in c and c not in inner
               and c not in step
               for s in comps[c] if _opcode(s) not in _NOT_RUN]
    assert outside
    for s in outside:
        m = _OP_NAME.search(s)
        assert (m and "ensemble_bdf.shards" in m.group(1).split("/")) \
            or _opcode(s) in _XLA_OWN, s[:120]


def test_ensemble_mesh_ops_carry_their_phase(topo):
    """The checks of :func:`test_ensemble_bdf_ops_carry_their_phase` on
    the sharded program: inside each chip's step loop the phases are
    the one-chip program's."""
    _assert_phases(_ensemble_mesh_text(topo, False, 4 * 1024))


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
