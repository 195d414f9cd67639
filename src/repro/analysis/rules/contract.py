"""kernel-contract: the jnp oracle and the Pallas kernel are
interchangeable.

For every op in the table and every :class:`OpSig` in the context's
contract grid, both backends are abstractly evaluated
(``jax.eval_shape`` — no kernel runs) and must agree on the full
output shape/dtype tree.  Tiling is checked against the roofline
device table: :func:`repro.analysis.opcost.tile_for` must pick a
lane-multiple tile whose working set (``vmem_rows * tile * itemsize``)
fits every device row's VMEM budget, and the kernels' batch-tile
helper must return a lane-multiple divisor of the lane-padded batch.
"""
import jax
import jax.numpy as jnp

from repro.analysis import lint


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _csr_pattern(n):
    """Tridiagonal CSR (indptr, indices) hashable tuples; nnz=3n-2."""
    indptr, indices = [0], []
    for i in range(n):
        cols = [j for j in (i - 1, i, i + 1) if 0 <= j < n]
        indices.extend(cols)
        indptr.append(len(indices))
    return tuple(indptr), tuple(indices)


def _bsr_pattern(nblk):
    """Block-tridiagonal (brows, bcols, nblk); nnzb=3*nblk-2."""
    brows, bcols = [], []
    for i in range(nblk):
        for j in (i - 1, i, i + 1):
            if 0 <= j < nblk:
                brows.append(i)
                bcols.append(j)
    return tuple(brows), tuple(bcols), nblk


# Each factory: sig -> (abstract array args, call(impl, args, policy)).
# Static operands (coefficient tuples, sparsity patterns, the negate
# flag) are closed over; only arrays are traced.


def _f_linear_sum(sig):
    x = _sds((sig.n,), sig.dtype)
    return (x, x), lambda fn, a, pol: fn(2.0, a[0], -0.5, a[1],
                                         policy=pol)


def _f_axpy(sig):
    x = _sds((sig.n,), sig.dtype)
    return (x, x), lambda fn, a, pol: fn(1.5, a[0], a[1], policy=pol)


def _f_linear_combination(sig):
    x = _sds((sig.n,), sig.dtype)
    coeffs = tuple(float(i + 1) for i in range(sig.k))
    return ((x,) * sig.k,
            lambda fn, a, pol: fn(coeffs, list(a), policy=pol))


def _f_scale_add_multi(sig):
    x = _sds((sig.n,), sig.dtype)
    coeffs = tuple(float(i + 1) for i in range(sig.k))
    return ((x,) * (sig.k + 1),
            lambda fn, a, pol: fn(coeffs, a[0], list(a[1:]),
                                  policy=pol))


def _f_reduction(sig):
    x = _sds((sig.n,), sig.dtype)
    return (x, x), lambda fn, a, pol: fn(a[0], a[1], policy=pol)


def _f_reduction_mask(sig):
    x = _sds((sig.n,), sig.dtype)
    return ((x, x, x),
            lambda fn, a, pol: fn(a[0], a[1], a[2], policy=pol))


def _f_dot_prod_multi(sig):
    x = _sds((sig.n,), sig.dtype)
    return ((x,) * (sig.k + 1),
            lambda fn, a, pol: fn(a[0], list(a[1:]), policy=pol))


def _f_block_solve(sig):
    A = _sds((sig.b, sig.b, sig.nsys), sig.dtype)
    r = _sds((sig.b, sig.nsys), sig.dtype)
    return (A, r), lambda fn, a, pol: fn(a[0], a[1], policy=pol)


def _f_block_inverse(sig):
    A = _sds((sig.b, sig.b, sig.nsys), sig.dtype)
    return (A,), lambda fn, a, pol: fn(a[0], policy=pol)


def _f_newton_residual(sig):
    z = _sds((sig.n, sig.nsys), sig.dtype)
    g = _sds((sig.nsys,), sig.dtype)
    return ((z, z, z, g),
            lambda fn, a, pol: fn(a[0], a[1], a[2], a[3], False,
                                  policy=pol))


def _f_masked_update(sig):
    z = _sds((sig.n, sig.nsys), sig.dtype)
    m = _sds((sig.nsys,), jnp.bool_)
    return ((z, z, z, m),
            lambda fn, a, pol: fn(a[0], a[1], a[2], a[3], policy=pol))


def _f_lagrange_rescale(sig):
    eta = _sds((sig.nsys,), sig.dtype)
    q = _sds((sig.nsys,), jnp.int32)
    Z = _sds((sig.k, sig.n, sig.nsys), sig.dtype)
    act = _sds((sig.nsys,), jnp.bool_)
    return ((eta, q, Z, act),
            lambda fn, a, pol: fn(a[0], a[1], a[2], a[3], policy=pol))


def _f_wrms_soa(sig):
    v = _sds((sig.n, sig.nsys), sig.dtype)
    return (v, v), lambda fn, a, pol: fn(a[0], a[1], policy=pol)


def _f_csr_spmv(sig):
    pattern = _csr_pattern(sig.n)
    data = _sds((sig.nnz,), sig.dtype)
    x = _sds((sig.n,), sig.dtype)
    return ((data, x),
            lambda fn, a, pol: fn(a[0], a[1], pattern, policy=pol))


def _f_bsr_spmv(sig):
    nblk = sig.n // sig.b
    pattern = _bsr_pattern(nblk)
    values = _sds((sig.nnz, sig.b, sig.b, sig.nsys), sig.dtype)
    x = _sds((nblk, sig.b, sig.nsys), sig.dtype)
    return ((values, x),
            lambda fn, a, pol: fn(a[0], a[1], pattern, policy=pol))


def _f_bsr_diag_inverse(sig):
    nblk = sig.n // sig.b
    pattern = _bsr_pattern(nblk)
    values = _sds((sig.nnz, sig.b, sig.b, sig.nsys), sig.dtype)
    return ((values,),
            lambda fn, a, pol: fn(a[0], pattern, policy=pol))


ARG_FACTORIES = {
    "linear_sum": _f_linear_sum,
    "axpy": _f_axpy,
    "linear_combination": _f_linear_combination,
    "scale_add_multi": _f_scale_add_multi,
    "dot": _f_reduction,
    "wrms_norm": _f_reduction,
    "wrms_ss": _f_reduction,
    "wrms_norm_mask": _f_reduction_mask,
    "dot_prod_multi": _f_dot_prod_multi,
    "block_solve_soa": _f_block_solve,
    "block_inverse_soa": _f_block_inverse,
    "blockdiag_spmv_soa": _f_block_solve,
    "newton_residual_soa": _f_newton_residual,
    "masked_update_wrms_soa": _f_masked_update,
    "lagrange_rescale_soa": _f_lagrange_rescale,
    "wrms_soa": _f_wrms_soa,
    "csr_spmv": _f_csr_spmv,
    "bsr_spmv_soa": _f_bsr_spmv,
    "bsr_block_jacobi_inverse_soa": _f_bsr_diag_inverse,
}


def _tree_spec(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return [(tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves]


@lint.register(
    "kernel-contract",
    "oracle/kernel shape+dtype agreement; lane-multiple VMEM-feasible "
    "tiles on every roofline device")
def check(ctx):
    from repro.analysis.opcost import LANE, _lane_ceil, op_cost, tile_for
    from repro.analysis.roofline import DEVICES
    from repro.core.policies import ExecPolicy
    from repro.kernels.ops import _batch_tile

    pol = ExecPolicy(backend="pallas", interpret=True)
    out = []
    for op in sorted(ctx.op_table):
        sigs = ctx.contract_sigs.get(op)
        if not sigs:
            out.append(lint.Violation(
                "kernel-contract", op,
                "op has no contract OpSig grid (add it to "
                "default_contract_sigs / the context)"))
            continue
        factory = ARG_FACTORIES.get(op)
        if factory is None:
            out.append(lint.Violation(
                "kernel-contract", op,
                "op has no argument factory (add it to "
                "rules/contract.py ARG_FACTORIES)"))
            continue
        impls = ctx.op_table[op]
        for sig in sigs:
            where = sig.key()       # "op|dtype|n=..,nsys=..,..."
            arrays, call = factory(sig)
            try:
                shp_jnp = jax.eval_shape(
                    lambda *a: call(impls["jnp"], a, pol), *arrays)
                shp_pl = jax.eval_shape(
                    lambda *a: call(impls["pallas"], a, pol), *arrays)
            except Exception as e:  # a backend that cannot even trace
                out.append(lint.Violation(
                    "kernel-contract", where,
                    f"abstract evaluation failed: "
                    f"{type(e).__name__}: {str(e).splitlines()[0]}"))
                continue
            if _tree_spec(shp_jnp) != _tree_spec(shp_pl):
                out.append(lint.Violation(
                    "kernel-contract", where,
                    f"backend output mismatch: jnp={_tree_spec(shp_jnp)}"
                    f" pallas={_tree_spec(shp_pl)}"))
            # tile feasibility on every roofline device row
            for dev_name, dev in DEVICES.items():
                tile = tile_for(sig, dev)
                if tile % LANE:
                    out.append(lint.Violation(
                        "kernel-contract", where,
                        f"tile_for({dev_name}) chose {tile}, not a "
                        f"lane multiple of {LANE}"))
                if dev.vmem_bytes is not None:
                    rows = max(1, op_cost(sig).vmem_rows)
                    need = rows * tile * sig.itemsize
                    if need > dev.vmem_bytes:
                        out.append(lint.Violation(
                            "kernel-contract", where,
                            f"tile_for({dev_name}) working set "
                            f"{need}B (rows={rows}, tile={tile}) "
                            f"exceeds VMEM budget "
                            f"{dev.vmem_bytes}B"))
            # kernels' batch-tile: lane-multiple divisor of the
            # lane-padded batch, for every batched sig
            if sig.nsys:
                bt = _batch_tile(sig.nsys, pol.batch_tile)
                padded = _lane_ceil(sig.nsys)
                if bt % LANE or padded % bt:
                    out.append(lint.Violation(
                        "kernel-contract", where,
                        f"_batch_tile({sig.nsys}, "
                        f"{pol.batch_tile}) = {bt} is not a "
                        f"lane-multiple divisor of the lane-padded "
                        f"batch {padded}"))
    return out
