"""Policy-driven N_Vector op dispatch — the ExecPolicy wiring (paper §4.1).

SUNDIALS lets applications swap kernel-launch policies per vector
without touching integrator source.  This module is the analog: a
single **op table** maps each hot vector operation to its two
implementations —

* ``'jnp'``    — the pure-jnp oracles in :mod:`repro.core.vector`
                 (XLA fuses; the default, and the only backend XLA:CPU
                 can lower without ``interpret``), and
* ``'pallas'`` — the fused Pallas kernels in :mod:`repro.kernels`
                 (one HBM pass per fused op; tile sizes come from the
                 :class:`~repro.core.policies.ExecPolicy`).

Integrators call the module-level wrappers (``linear_combination``,
``wrms_norm``, ...) with an optional ``policy``; ``None`` or a
``backend='jnp'`` policy falls through to :mod:`repro.core.vector`
unchanged, so existing callers keep bit-identical behavior.

The pallas boundary handles, per pytree leaf:

* **flattening** — each leaf is raveled to 1-D; fused multi-operand ops
  stack corresponding leaves into a ``(K, n)`` operand;
* **lane padding** — tiles are lane-aligned (multiples of 128) and
  clamped to the leaf size so a 6-element vector pads to 128, not to the
  policy's full streaming tile; the kernels' wrappers zero-pad ragged
  tails (zero weights/coeffs contribute nothing to reductions);
* **dtype preservation** — outputs keep ``jnp.result_type`` of the data
  operands (SUNDIALS realtype semantics: a float64 step-size coefficient
  must not upcast a float32 state), matching ``vector._keep_dtype``.

Reductions return the *node-local* value; :class:`MeshVector` finishes
them with its single collective exactly as before.
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import tree_util

from . import vector as nv
from .policies import ExecPolicy, XLA_FUSED

Pytree = Any

LANE = 128


# ---------------------------------------------------------------------------
# Boundary helpers (pytree <-> flat lane-padded kernel operands)
# ---------------------------------------------------------------------------


def _ceil_lane(n: int) -> int:
    return max(LANE, -(-n // LANE) * LANE)


def _stream_tile(n: int, policy: ExecPolicy) -> int:
    """Streaming tile: the policy's block, clamped to the (lane-padded)
    leaf so small vectors don't pad to a full GridStride tile."""
    return max(LANE, min(policy.block_elems, _ceil_lane(n)))


def _reduce_tile(n: int, policy: ExecPolicy) -> int:
    return max(LANE, min(policy.reduce_tile, _ceil_lane(n)))


def _leaves(tree: Pytree):
    return tree_util.tree_leaves(tree)


def _rebuild(tree: Pytree, flat_leaves):
    treedef = tree_util.tree_structure(tree)
    shapes = [l.shape for l in _leaves(tree)]
    return tree_util.tree_unflatten(
        treedef, [f.reshape(s) for f, s in zip(flat_leaves, shapes)])


def _coeff_array(coeffs: Sequence, dtype) -> jnp.ndarray:
    return jnp.stack([jnp.asarray(c) for c in coeffs]).astype(dtype)


# ---------------------------------------------------------------------------
# Pallas-backed implementations (leaf-wise over pytrees)
# ---------------------------------------------------------------------------


def _pl_linear_combination(coeffs, vecs, *, policy: ExecPolicy) -> Pytree:
    from repro.kernels import ops as kops
    assert len(coeffs) == len(vecs) and len(vecs) >= 1
    leaf_rows = [_leaves(v) for v in vecs]          # [K][L] leaves
    out = []
    for leaves in zip(*leaf_rows):                  # iterate leaf positions
        want = jnp.result_type(*leaves)
        X = jnp.stack([l.ravel().astype(want) for l in leaves])
        n = X.shape[1]
        z = kops.linear_combination(
            _coeff_array(coeffs, want), X,
            block_elems=_stream_tile(n, policy), interpret=policy.interpreted())
        out.append(z)
    return _rebuild(vecs[0], out)


def _pl_linear_sum(a, x, b, y, *, policy: ExecPolicy) -> Pytree:
    return _pl_linear_combination([a, b], [x, y], policy=policy)


def _pl_axpy(a, x, y, *, policy: ExecPolicy) -> Pytree:
    return _pl_linear_combination([a, 1.0], [x, y], policy=policy)


def _pl_scale_add_multi(coeffs, x, ys, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    K = len(coeffs)
    assert len(ys) == K
    x_leaves = _leaves(x)
    y_rows = [_leaves(y) for y in ys]
    per_leaf = []                                   # [L] arrays of (K, n)
    for pos, xl in enumerate(x_leaves):
        want = jnp.result_type(xl, *(row[pos] for row in y_rows))
        Y = jnp.stack([row[pos].ravel().astype(want) for row in y_rows])
        n = Y.shape[1]
        Z = kops.scale_add_multi(
            _coeff_array(coeffs, want), xl.ravel().astype(want), Y,
            block_elems=_stream_tile(n, policy), interpret=policy.interpreted())
        per_leaf.append(Z)
    return [_rebuild(x, [Z[k] for Z in per_leaf]) for k in range(K)]


def _pl_dot(x, y, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    lx, ly = _leaves(x), _leaves(y)
    acc_t = jnp.result_type(*(l.dtype for l in lx + ly))
    acc = jnp.zeros((), dtype=acc_t)
    for xl, yl in zip(lx, ly):
        n = xl.size
        acc = acc + kops.dot(
            xl.ravel().astype(acc_t), yl.ravel().astype(acc_t),
            reduce_tile=_reduce_tile(n, policy), interpret=policy.interpreted())
    return acc


def _pl_wrms_norm(x, w, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    n_total = nv.tree_size(x)
    lx, lw = _leaves(x), _leaves(w)
    acc_t = jnp.result_type(*(l.dtype for l in lx + lw))
    ss = jnp.zeros((), dtype=acc_t)
    for xl, wl in zip(lx, lw):
        ss = ss + kops.wrms_ss(
            xl.ravel().astype(acc_t), wl.ravel().astype(acc_t),
            reduce_tile=_reduce_tile(xl.size, policy),
            interpret=policy.interpreted())
    return jnp.sqrt(ss / n_total)


def _pl_wrms_norm_mask(x, w, mask, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    n_total = nv.tree_size(x)
    lx, lw, lm = _leaves(x), _leaves(w), _leaves(mask)
    acc_t = jnp.result_type(*(l.dtype for l in lx + lw + lm))
    ss = jnp.zeros((), dtype=acc_t)
    for xl, wl, ml in zip(lx, lw, lm):
        ss = ss + kops.wrms_mask_ss(
            xl.ravel().astype(acc_t), wl.ravel().astype(acc_t),
            ml.ravel().astype(acc_t),
            reduce_tile=_reduce_tile(xl.size, policy),
            interpret=policy.interpreted())
    return jnp.sqrt(ss / n_total)


def _pl_dot_prod_multi(x, ys, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    K = len(ys)
    x_leaves = _leaves(x)
    y_rows = [_leaves(y) for y in ys]
    acc_t = jnp.result_type(*(l.dtype for l in x_leaves),
                            *(l.dtype for row in y_rows for l in row))
    acc = jnp.zeros((K,), dtype=acc_t)
    for pos, xl in enumerate(x_leaves):
        Y = jnp.stack([row[pos].ravel().astype(acc_t) for row in y_rows])
        acc = acc + kops.dot_prod_multi(
            xl.ravel().astype(acc_t), Y,
            reduce_tile=_reduce_tile(xl.size, policy),
            interpret=policy.interpreted())
    return acc


def _pl_wrms_ss(x, w, *, policy: ExecPolicy):
    """Node-local raw sum((x*w)^2) — MeshVector's partial before psum."""
    from repro.kernels import ops as kops
    lx, lw = _leaves(x), _leaves(w)
    acc_t = jnp.result_type(*(l.dtype for l in lx + lw))
    ss = jnp.zeros((), dtype=acc_t)
    for xl, wl in zip(lx, lw):
        ss = ss + kops.wrms_ss(
            xl.ravel().astype(acc_t), wl.ravel().astype(acc_t),
            reduce_tile=_reduce_tile(xl.size, policy),
            interpret=policy.interpreted())
    return ss


def _jnp_wrms_ss(x, w, *, policy=None):
    xw = nv.prod(x, w)
    return nv.dot(xw, xw)


# ---------------------------------------------------------------------------
# Batched block-diagonal linear algebra (the ensemble subsystem's SoA ops:
# A is (b, b, NB) with the system batch on the lane axis).  The jnp
# oracles are the semantic ground truth the Pallas kernels are parity-
# tested against; the pallas implementations pad NB to the policy's
# batch_tile (the bundle-size knob) inside repro.kernels.ops.
# ---------------------------------------------------------------------------


def _gj_vmem(policy: ExecPolicy):
    """VMEM budget for the row-tiled GJ accumulator, from the policy's
    roofline device entry (None -> the kernels' GJ_VMEM_BYTES default;
    only consulted in compiled mode)."""
    from repro.analysis.roofline import get_device
    return get_device(policy.device_name()).vmem_bytes


def _jnp_block_solve_soa(A, r, *, policy=None):
    from .direct import gauss_jordan_batched
    x = gauss_jordan_batched(jnp.transpose(A, (2, 0, 1)),
                             jnp.transpose(r, (1, 0)))
    return jnp.transpose(x, (1, 0))


def _pl_block_solve_soa(A, r, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    return kops.block_solve_soa(A, r, batch_tile=policy.batch_tile,
                                interpret=policy.interpreted(),
                                vmem_bytes=_gj_vmem(policy))


def _jnp_block_inverse_soa(A, *, policy=None):
    from repro.kernels import ref as kref
    return kref.block_inverse_soa_ref(A)


def _pl_block_inverse_soa(A, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    return kops.block_inverse_soa(A, batch_tile=policy.batch_tile,
                                  interpret=policy.interpreted(),
                                  vmem_bytes=_gj_vmem(policy))


def _jnp_blockdiag_spmv_soa(A, x, *, policy=None):
    from repro.kernels import ref as kref
    return kref.blockdiag_spmv_soa_ref(A, x)


def _pl_blockdiag_spmv_soa(A, x, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    return kops.blockdiag_spmv_soa(A, x, batch_tile=policy.batch_tile,
                                   interpret=policy.interpreted())


# ---------------------------------------------------------------------------
# Fused ensemble-Newton ops (SoA (n, nsys) layout, nsys on the lanes).
# The jnp oracles are the bitwise ground truth of the pre-SoA integrator
# (the history-rescale oracle deliberately evaluates the AoS einsum on
# transposed views so the jnp backend keeps its accumulation order; see
# kernels/ref.py); the pallas kernels are the one-HBM-pass fusions.
# ---------------------------------------------------------------------------


def _jnp_newton_residual_soa(z, fval, psi, gamma, negate, *, policy=None):
    from repro.kernels import ref as kref
    return kref.newton_residual_soa_ref(z, fval, psi, gamma, negate)


def _pl_newton_residual_soa(z, fval, psi, gamma, negate, *,
                            policy: ExecPolicy):
    from repro.kernels import ops as kops
    return kops.newton_residual_soa(z, fval, psi, gamma,
                                    batch_tile=policy.batch_tile,
                                    interpret=policy.interpreted(),
                                    negate=negate)


def _jnp_masked_update_wrms_soa(z, dz, w, mask, *, policy=None):
    from repro.kernels import ref as kref
    return kref.masked_update_wrms_soa_ref(z, dz, w, mask)


def _pl_masked_update_wrms_soa(z, dz, w, mask, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    return kops.masked_update_wrms_soa(z, dz, w, mask,
                                       batch_tile=policy.batch_tile,
                                       interpret=policy.interpreted())


def _jnp_lagrange_rescale_soa(eta, q, Z, active, *, policy=None):
    # the per-lane Lagrange matrices, then the masked AoS einsum: the
    # integrator's pre-kernel expression, kept bitwise
    from repro.core import cvode as _cv
    from repro.kernels import ref as kref
    W = jax.vmap(_cv._lagrange_matrix)(eta, q)
    return kref.history_rescale_soa_ref(jnp.transpose(W, (1, 2, 0)), Z,
                                        active)


def _pl_lagrange_rescale_soa(eta, q, Z, active, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    return kops.lagrange_rescale_soa(eta, q, Z, active,
                                     batch_tile=policy.batch_tile,
                                     interpret=policy.interpreted())


def _jnp_wrms_soa(v, w, *, policy=None):
    from repro.kernels import ref as kref
    return kref.wrms_soa_ref(v, w)


def _pl_wrms_soa(v, w, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    return kops.wrms_soa(v, w, batch_tile=policy.batch_tile,
                         interpret=policy.interpreted())


# ---------------------------------------------------------------------------
# Sparse ops (static shared patterns).  Patterns ride along as hashable
# tuples — ``csr_spmv`` takes ``(indptr, indices)``, the BSR ops take
# ``(brows, bcols, nblk)`` — so they key the kernel jit caches and the
# structure is compiled into the program (SUNMATRIX_CUSPARSE's
# store-the-pattern-once, with zero index arrays in device memory).
# ---------------------------------------------------------------------------


def _jnp_csr_spmv(data, x, pattern, *, policy=None):
    from repro.kernels import ref as kref
    indptr, indices = pattern
    return kref.csr_spmv_ref(data, x, indptr, indices)


def _pl_csr_spmv(data, x, pattern, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    indptr, indices = pattern
    return kops.csr_spmv(data, x, indptr=tuple(indptr),
                         indices=tuple(indices),
                         block_elems=policy.block_elems,
                         interpret=policy.interpreted())


def _jnp_bsr_spmv_soa(values, x, pattern, *, policy=None):
    from repro.kernels import ref as kref
    brows, bcols, nblk = pattern
    return kref.bsr_spmv_soa_ref(values, x, brows, bcols, nblk)


def _pl_bsr_spmv_soa(values, x, pattern, *, policy: ExecPolicy):
    from repro.kernels import ops as kops
    brows, bcols, nblk = pattern
    return kops.bsr_spmv_soa(values, x, brows=tuple(brows),
                             bcols=tuple(bcols), nblk=nblk,
                             batch_tile=policy.batch_tile,
                             interpret=policy.interpreted())


def _jnp_bsr_block_jacobi_inverse_soa(values, pattern, *, policy=None):
    from repro.kernels import ref as kref
    brows, bcols, nblk = pattern
    return kref.bsr_diag_inverse_soa_ref(values, brows, bcols, nblk)


def _pl_bsr_block_jacobi_inverse_soa(values, pattern, *,
                                     policy: ExecPolicy):
    from repro.kernels import ops as kops
    brows, bcols, nblk = pattern
    return kops.bsr_diag_inverse_soa(values, brows=tuple(brows),
                                     bcols=tuple(bcols), nblk=nblk,
                                     batch_tile=policy.batch_tile,
                                     interpret=policy.interpreted())


def _ignore_policy(fn):
    @functools.wraps(fn)
    def wrapped(*args, policy=None):
        return fn(*args)
    return wrapped


# ---------------------------------------------------------------------------
# The op table.  Every entry has a 'jnp' and (for the hot ops) a 'pallas'
# implementation with identical signatures plus a keyword-only `policy`.
# ---------------------------------------------------------------------------

OP_TABLE = {
    # streaming
    "linear_sum": {"jnp": _ignore_policy(nv.linear_sum),
                   "pallas": _pl_linear_sum},
    "linear_combination": {"jnp": _ignore_policy(nv.linear_combination),
                           "pallas": _pl_linear_combination},
    "scale_add_multi": {"jnp": _ignore_policy(nv.scale_add_multi),
                        "pallas": _pl_scale_add_multi},
    "axpy": {"jnp": _ignore_policy(nv.axpy), "pallas": _pl_axpy},
    # reductions
    "dot": {"jnp": _ignore_policy(nv.dot), "pallas": _pl_dot},
    "wrms_norm": {"jnp": _ignore_policy(nv.wrms_norm),
                  "pallas": _pl_wrms_norm},
    "wrms_norm_mask": {"jnp": _ignore_policy(nv.wrms_norm_mask),
                       "pallas": _pl_wrms_norm_mask},
    "dot_prod_multi": {"jnp": _ignore_policy(nv.dot_prod_multi),
                       "pallas": _pl_dot_prod_multi},
    "wrms_ss": {"jnp": _jnp_wrms_ss, "pallas": _pl_wrms_ss},
    # batched block-diagonal (ensemble) linear algebra, SoA layout
    "block_solve_soa": {"jnp": _jnp_block_solve_soa,
                        "pallas": _pl_block_solve_soa},
    "block_inverse_soa": {"jnp": _jnp_block_inverse_soa,
                          "pallas": _pl_block_inverse_soa},
    "blockdiag_spmv_soa": {"jnp": _jnp_blockdiag_spmv_soa,
                           "pallas": _pl_blockdiag_spmv_soa},
    # fused ensemble-Newton hot-loop ops (SoA, nsys last)
    "newton_residual_soa": {"jnp": _jnp_newton_residual_soa,
                            "pallas": _pl_newton_residual_soa},
    "masked_update_wrms_soa": {"jnp": _jnp_masked_update_wrms_soa,
                               "pallas": _pl_masked_update_wrms_soa},
    "lagrange_rescale_soa": {"jnp": _jnp_lagrange_rescale_soa,
                             "pallas": _pl_lagrange_rescale_soa},
    "wrms_soa": {"jnp": _jnp_wrms_soa, "pallas": _pl_wrms_soa},
    # sparse matrices (static shared patterns)
    "csr_spmv": {"jnp": _jnp_csr_spmv, "pallas": _pl_csr_spmv},
    "bsr_spmv_soa": {"jnp": _jnp_bsr_spmv_soa,
                     "pallas": _pl_bsr_spmv_soa},
    "bsr_block_jacobi_inverse_soa": {
        "jnp": _jnp_bsr_block_jacobi_inverse_soa,
        "pallas": _pl_bsr_block_jacobi_inverse_soa},
}


def op_names() -> frozenset:
    """The canonical dispatch op set — the single source of truth that
    :class:`~repro.core.policies.ExecPolicy` override validation and
    sunlint's table-coherence rule check against."""
    return frozenset(OP_TABLE)


def _positional_arity(fn):
    """Number of positional parameters, following ``functools.wraps``
    chains (so ``_ignore_policy(nv.axpy)`` reports nv.axpy's arity).
    ``None`` for variadic implementations."""
    sig = inspect.signature(fn)
    n = 0
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            n += 1
        elif p.kind is p.VAR_POSITIONAL:
            return None
    return n


def _accepts_policy(fn) -> bool:
    # the dispatch contract is on the callable actually invoked, so do
    # NOT follow __wrapped__ here: _ignore_policy's wrapper adds the
    # policy kwarg that its wrapped oracle lacks.
    sig = inspect.signature(fn, follow_wrapped=False)
    return ("policy" in sig.parameters
            or any(p.kind is p.VAR_KEYWORD
                   for p in sig.parameters.values()))


def validate_op_table(table=None):
    """Fail fast on a half-registered op.

    Checks every entry of ``table`` (default :data:`OP_TABLE`) for: a
    callable ``'jnp'`` oracle AND a callable ``'pallas'`` kernel, no
    stray backend keys, matching positional arities between the two
    implementations, and the keyword-only ``policy`` argument the
    dispatcher passes.  All offenders are collected and reported in ONE
    aggregated ``ValueError`` — previously a half-registered op
    surfaced as a late ``AttributeError`` at first dispatch.
    """
    table = OP_TABLE if table is None else table
    problems = []
    for op in sorted(table):
        impls = table[op]
        if not isinstance(impls, dict):
            problems.append(f"{op}: entry is {type(impls).__name__}, "
                            f"expected a {{'jnp', 'pallas'}} dict")
            continue
        stray = sorted(set(impls) - {"jnp", "pallas"})
        if stray:
            problems.append(f"{op}: unknown backend keys {stray}")
        for backend in ("jnp", "pallas"):
            fn = impls.get(backend)
            if fn is None:
                problems.append(f"{op}: missing {backend!r} "
                                f"implementation")
            elif not callable(fn):
                problems.append(f"{op}: {backend!r} implementation is "
                                f"not callable")
            elif not _accepts_policy(fn):
                problems.append(f"{op}: {backend!r} implementation does "
                                f"not accept the keyword-only `policy` "
                                f"argument")
        jnp_fn, pl_fn = impls.get("jnp"), impls.get("pallas")
        if callable(jnp_fn) and callable(pl_fn):
            a_j, a_p = _positional_arity(jnp_fn), _positional_arity(pl_fn)
            if a_j is not None and a_p is not None and a_j != a_p:
                problems.append(f"{op}: arity mismatch — jnp oracle "
                                f"takes {a_j} positional args, pallas "
                                f"kernel takes {a_p}")
    if problems:
        raise ValueError(
            "OP_TABLE validation failed (%d problem%s):\n  - %s"
            % (len(problems), "" if len(problems) == 1 else "s",
               "\n  - ".join(problems)))


validate_op_table()


def dispatch(op: str, policy: Optional[ExecPolicy] = None):
    """Resolve `op` to the implementation selected by `policy`.

    ``None`` means :data:`~repro.core.policies.XLA_FUSED`.  Unknown ops
    and backends raise ``ValueError``; ops without a pallas
    implementation fall back to jnp (there are none today, but the
    table is the extension point).

    ``backend='auto'`` defers the choice to the call site: the returned
    callable extracts the argument shape signature at trace time and
    lets :mod:`repro.core.autotune` pick the backend and tile from the
    measured cache (falling back to the analytical model in
    :mod:`repro.analysis.opcost`).  Per-op ``policy.op_overrides`` pin
    individual ops first.
    """
    policy = XLA_FUSED if policy is None else policy
    impls = OP_TABLE.get(op)
    if impls is None:
        raise ValueError(f"unknown dispatch op {op!r}; valid OP_TABLE "
                         f"ops: {', '.join(sorted(OP_TABLE))}")
    backend = policy.backend_for(op) if hasattr(policy, "backend_for") \
        else policy.backend
    if backend == "auto":
        from . import autotune
        return functools.partial(autotune.resolve, op, policy)
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown ExecPolicy backend: {backend!r}")
    fn = impls.get(backend, impls["jnp"])
    return functools.partial(fn, policy=policy)


# ---------------------------------------------------------------------------
# Documentation rendering: the op-table matrices in the policies module
# docstring and the README are generated FROM this table (one row per
# OP_TABLE key), so new ops cannot drift out of the docs — a test
# asserts the rendered text is embedded verbatim.
# ---------------------------------------------------------------------------

# short impl descriptions per backend; the renderer iterates OP_TABLE
# keys, so an op missing here still gets a row (with generic text).
OP_NOTES = {
    "linear_sum": ("vector.linear_sum", "vecops lincomb (K=2)"),
    "linear_combination": ("vector.linear_combination",
                           "vecops lincomb kernel"),
    "scale_add_multi": ("vector.scale_add_multi", "vecops scale_add_multi"),
    "axpy": ("vector.axpy", "vecops lincomb (K=2)"),
    "dot": ("vector.dot", "vecops dot_partial"),
    "wrms_norm": ("vector.wrms_norm", "vecops wrms_partial"),
    "wrms_norm_mask": ("vector.wrms_norm_mask", "vecops wrms_mask_partial"),
    "dot_prod_multi": ("vector.dot_prod_multi", "vecops multi_dot_partial"),
    "wrms_ss": ("vector prod+dot", "vecops wrms_partial (raw ss)"),
    "block_solve_soa": ("direct.gauss_jordan_batched",
                        "GJ kernel (b>8: row-tiled)"),
    "block_inverse_soa": ("ref.block_inverse_soa_ref",
                          "GJ inverse (b>8: row-tiled)"),
    "blockdiag_spmv_soa": ("jnp.einsum", "blockdiag_spmv kernel"),
    "newton_residual_soa": ("ref (z - gamma*f - psi)",
                            "newton fused residual"),
    "masked_update_wrms_soa": ("ref (where + wrms)",
                               "newton fused update+WRMS"),
    "lagrange_rescale_soa": ("Lagrange W + AoS einsum",
                             "newton in-kernel weights"),
    "wrms_soa": ("ref (per-system WRMS)", "newton wrms_soa kernel"),
    "csr_spmv": ("segment_sum", "sparse ELL gather kernel"),
    "bsr_spmv_soa": ("einsum+segment_sum", "sparse unrolled-pattern"),
    "bsr_block_jacobi_inverse_soa": ("jnp.linalg.inv",
                                     "diag gather + GJ inverse"),
}


def op_table_rows():
    """(op, jnp description, pallas description) per OP_TABLE entry."""
    return [(op,) + OP_NOTES.get(op, ("jnp oracle", "pallas kernel"))
            for op in OP_TABLE]


def render_op_table(fmt: str = "rst") -> str:
    """Render the backend matrix from :data:`OP_TABLE` ('rst' for the
    policies-module docstring, 'md' for the README)."""
    rows = op_table_rows()
    heads = ("op", "'jnp' backend", "'pallas' backend")
    widths = [max(len(r[i]) for r in rows + [heads]) for i in range(3)]
    if fmt == "md":
        lines = ["| " + " | ".join(h.ljust(w)
                                   for h, w in zip(heads, widths)) + " |",
                 "|" + "|".join("-" * (w + 2) for w in widths) + "|"]
        lines += ["| " + " | ".join(c.ljust(w)
                                    for c, w in zip(r, widths)) + " |"
                  for r in rows]
        return "\n".join(lines)
    rule = "  ".join("=" * w for w in widths)
    lines = [rule, "  ".join(h.ljust(w)
                             for h, w in zip(heads, widths)).rstrip(), rule]
    lines += ["  ".join(c.ljust(w)
                        for c, w in zip(r, widths)).rstrip() for r in rows]
    lines.append(rule)
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Public wrappers — what the integrators call.
# ---------------------------------------------------------------------------


def linear_sum(a, x: Pytree, b, y: Pytree,
               policy: Optional[ExecPolicy] = None) -> Pytree:
    return dispatch("linear_sum", policy)(a, x, b, y)


def linear_combination(coeffs: Sequence, vecs: Sequence[Pytree],
                       policy: Optional[ExecPolicy] = None) -> Pytree:
    return dispatch("linear_combination", policy)(coeffs, vecs)


def scale_add_multi(coeffs: Sequence, x: Pytree, ys: Sequence[Pytree],
                    policy: Optional[ExecPolicy] = None):
    return dispatch("scale_add_multi", policy)(coeffs, x, ys)


def axpy(a, x: Pytree, y: Pytree,
         policy: Optional[ExecPolicy] = None) -> Pytree:
    return dispatch("axpy", policy)(a, x, y)


def dot(x: Pytree, y: Pytree, policy: Optional[ExecPolicy] = None):
    return dispatch("dot", policy)(x, y)


def wrms_norm(x: Pytree, w: Pytree, policy: Optional[ExecPolicy] = None):
    return dispatch("wrms_norm", policy)(x, w)


def wrms_norm_mask(x: Pytree, w: Pytree, mask: Pytree,
                   policy: Optional[ExecPolicy] = None):
    return dispatch("wrms_norm_mask", policy)(x, w, mask)


def dot_prod_multi(x: Pytree, ys: Sequence[Pytree],
                   policy: Optional[ExecPolicy] = None):
    return dispatch("dot_prod_multi", policy)(x, ys)


def wrms_ss(x: Pytree, w: Pytree, policy: Optional[ExecPolicy] = None):
    """Node-local sum((x*w)^2) (no sqrt, no /N) — the partial MeshVector
    feeds to its collective."""
    return dispatch("wrms_ss", policy)(x, w)


def block_solve_soa(A: jnp.ndarray, r: jnp.ndarray,
                    policy: Optional[ExecPolicy] = None) -> jnp.ndarray:
    """Solve every block system: A:(b,b,NB), r:(b,NB) -> x:(b,NB)."""
    return dispatch("block_solve_soa", policy)(A, r)


def block_inverse_soa(A: jnp.ndarray,
                      policy: Optional[ExecPolicy] = None) -> jnp.ndarray:
    """Invert every block: A:(b,b,NB) -> A^{-1}:(b,b,NB) (lsetup)."""
    return dispatch("block_inverse_soa", policy)(A)


def blockdiag_spmv_soa(A: jnp.ndarray, x: jnp.ndarray,
                       policy: Optional[ExecPolicy] = None) -> jnp.ndarray:
    """y = blockdiag(A) @ x: A:(b,b,NB), x:(b,NB) -> (b,NB) (lsolve)."""
    return dispatch("blockdiag_spmv_soa", policy)(A, x)


def newton_residual_soa(z: jnp.ndarray, fval: jnp.ndarray,
                        psi: jnp.ndarray, gamma: jnp.ndarray,
                        policy: Optional[ExecPolicy] = None, *,
                        negate: bool = False) -> jnp.ndarray:
    """Fused Newton residual g = z - gamma*f - psi; z/f/psi (n, nsys),
    gamma (nsys,).  ``negate=True`` emits -g (the Newton rhs) in the
    same pass; the sign is applied to the computed g so both variants
    round identically."""
    return dispatch("newton_residual_soa", policy)(z, fval, psi, gamma,
                                                   negate)


def masked_update_wrms_soa(z: jnp.ndarray, dz: jnp.ndarray,
                           w: jnp.ndarray, mask: jnp.ndarray,
                           policy: Optional[ExecPolicy] = None):
    """Fused masked iterate update + per-system WRMS of the correction:
    -> (where(mask, z+dz, z), wrms-per-system of dz)."""
    return dispatch("masked_update_wrms_soa", policy)(z, dz, w, mask)


def lagrange_rescale_soa(eta: jnp.ndarray, q: jnp.ndarray,
                         Z: jnp.ndarray, active: jnp.ndarray,
                         policy: Optional[ExecPolicy] = None
                         ) -> jnp.ndarray:
    """Masked per-system Lagrange history rebuild onto a step ``eta``
    times the old one, over the ``q``-deep valid history: eta, q, active
    (nsys,), Z (q1,n,nsys) -> where(active, sum_i W[j,i]*Z[i], Z[j])
    with W = ``cvode._lagrange_matrix(eta, q)`` per system.  The pallas
    backend makes the weights in the kernel and short-circuits bundles
    with no active lane off eta == 1."""
    return dispatch("lagrange_rescale_soa", policy)(eta, q, Z, active)


def wrms_soa(v: jnp.ndarray, w: jnp.ndarray,
             policy: Optional[ExecPolicy] = None) -> jnp.ndarray:
    """Per-system WRMS over the state axis: v/w (n, nsys) -> (nsys,) —
    the batched row of the wrms_norm family (ensemble error tests)."""
    return dispatch("wrms_soa", policy)(v, w)


def csr_spmv(data: jnp.ndarray, x: jnp.ndarray, pattern,
             policy: Optional[ExecPolicy] = None) -> jnp.ndarray:
    """y = A @ x for a static-pattern CSR matrix: data:(nnz,), x:(m,),
    pattern = (indptr, indices) hashable tuples."""
    return dispatch("csr_spmv", policy)(data, x, pattern)


def bsr_spmv_soa(values: jnp.ndarray, x: jnp.ndarray, pattern,
                 policy: Optional[ExecPolicy] = None) -> jnp.ndarray:
    """Ensemble shared-pattern BSR SpMV: values:(nnzb,b,b,NB),
    x:(nblk,b,NB), pattern = (brows, bcols, nblk) -> y:(nblk,b,NB)."""
    return dispatch("bsr_spmv_soa", policy)(values, x, pattern)


def bsr_block_jacobi_inverse_soa(values: jnp.ndarray, pattern,
                                 policy: Optional[ExecPolicy] = None
                                 ) -> jnp.ndarray:
    """Invert every diagonal block of the shared pattern (block-Jacobi
    psetup): values:(nnzb,b,b,NB) -> (b,b,nblk*NB), block-major."""
    return dispatch("bsr_block_jacobi_inverse_soa", policy)(values,
                                                            pattern)


if __name__ == "__main__":      # regenerate the docs' op-table matrices
    print(render_op_table("rst"))
    print()
    print(render_op_table("md"))
