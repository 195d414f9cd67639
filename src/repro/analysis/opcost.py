"""Analytical per-op dispatch cost model (the roofline, per OP_TABLE op).

Every op in :data:`repro.core.dispatch.OP_TABLE` gets an analytical
flops/bytes model parameterized on the call-site quantities that decide
the jnp-vs-pallas winner — (shapes, dtype, b, nsys, K, nnz) — evaluated
against the :data:`repro.analysis.roofline.DEVICES` table.  The model
feeds ``backend='auto'`` dispatch (:mod:`repro.core.autotune`): it
predicts the winning backend and a VMEM-feasible tile when no measured
autotune-cache entry covers the call site, and its predictions are
audited against every measured entry in ``ctx.dispatch_report()``.

Modeling structure (why two byte counts per backend):

* ``hbm_bytes``    — the fused kernel's minimal single-pass traffic:
  what a *compiled* Pallas kernel streams from HBM (accumulator passes
  stay in VMEM and are free at this granularity).
* ``jnp_bytes``    — the jnp oracle's *algorithmic* traffic.  Sequential
  oracles materialize intermediates: the b-pivot Gauss-Jordan scan
  rewrites the whole augmented system per pivot (read + write), so its
  traffic is ~2b x the fused single pass — the term that makes the
  batched direct solves memory-bound wins for the fused kernels.
* ``pallas_bytes`` — the Pallas kernel's traffic when "VMEM" is host
  RAM, i.e. under the interpreter: accumulator passes are real traffic
  there (one read-modify-write sweep per pivot), but without the
  oracle's double materialization.

Time model per backend (``predict``):

  jnp     : kernels * jnp_launch + max(flops/peak, jnp_bytes/bw)
  pallas  : pallas_call + steps * pallas_step
            + max(flops/peak, hbm_bytes/bw)              [compiled]
  pallas  : pallas_call + steps * pallas_step
            + body_steps * body_ops * interp_op
            + pallas_bytes/bw                            [interpret]

``jnp_kernels`` counts the oracle's *dispatches*: one fused XLA kernel
for the flat streaming ops, but per-primitive eager dispatches for the
SoA/sparse oracles (strided layouts and gathers don't fuse on the CPU
path, so the oracle pays the launch constant once per primitive — and
the b-pivot Gauss-Jordan scan pays it per pivot pass).  That fixed
overhead, not bandwidth, is what makes the fused interpret kernels win
every batched op on the pseudo-device.

``body_ops`` approximates the number of primitive array operations one
kernel-body execution issues — under the interpreter each costs a
numpy-dispatch overhead per body execution.  ``body_steps`` is the
number of body executions: the SoA kernels process a whole
(rows x tile) block per grid step (body_steps = grid steps), while the
flat streaming kernels loop over LANE-sized sub-blocks inside each
tile (body_steps = axis/LANE) — which is why the streaming jnp oracle
(one fused kernel) beats interpret mode on flat vectors while losing
every SoA op.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

from repro.analysis.roofline import Device, get_device

LANE = 128

#: ops whose tiled axis is the SoA system batch (tile knob: batch_tile);
#: everything else streams over flat elements (tile knob: block_elems /
#: reduce_tile).
BATCHED_OPS = frozenset({
    "block_solve_soa", "block_inverse_soa", "blockdiag_spmv_soa",
    "newton_residual_soa", "masked_update_wrms_soa", "lagrange_rescale_soa",
    "wrms_soa", "bsr_spmv_soa", "bsr_block_jacobi_inverse_soa",
})

REDUCTION_OPS = frozenset({
    "dot", "wrms_norm", "wrms_norm_mask", "dot_prod_multi", "wrms_ss",
})


def _lane_ceil(n: int) -> int:
    return max(LANE, -(-int(n) // LANE) * LANE)


@dataclasses.dataclass(frozen=True)
class OpSig:
    """Shape signature of one dispatch call site — the autotune-cache
    key fields.  Unused fields stay 0 (e.g. ``b`` for streaming ops)."""

    op: str
    dtype: str          # canonical jnp dtype name ('float64', ...)
    n: int = 0          # flat elements (streaming) / state length (SoA)
    nsys: int = 0       # SoA lane-axis system batch (0 = not batched)
    b: int = 0          # block size
    k: int = 0          # operand count K / history depth q1
    nnz: int = 0        # sparse nonzeros (CSR) or pattern blocks (BSR)

    @property
    def itemsize(self) -> int:
        return {"float64": 8, "float32": 4, "float16": 2,
                "bfloat16": 2}.get(self.dtype, 8)

    @property
    def axis_len(self) -> int:
        """Length of the tiled axis (batch for SoA ops, elements else)."""
        return self.nsys if self.op in BATCHED_OPS else self.n

    def key(self) -> str:
        """Stable cache-key string for this signature."""
        return (f"{self.op}|{self.dtype}|n={self.n},nsys={self.nsys},"
                f"b={self.b},k={self.k},nnz={self.nnz}")


def _tree_size(x: Any) -> int:
    from jax import tree_util
    return sum(int(l.size) for l in tree_util.tree_leaves(x))


def _dtype_name(x: Any) -> str:
    from jax import numpy as jnp, tree_util
    leaves = tree_util.tree_leaves(x)
    return str(jnp.result_type(*[l.dtype for l in leaves]))


def _sig_pairwise(op: str, args: Tuple) -> OpSig:
    x = args[1]
    return OpSig(op, _dtype_name(x), n=_tree_size(x), k=2)


def _sig_linear_combination(op: str, args: Tuple) -> OpSig:
    coeffs, vecs = args
    return OpSig(op, _dtype_name(vecs[0]), n=_tree_size(vecs[0]),
                 k=len(coeffs))


def _sig_scale_add_multi(op: str, args: Tuple) -> OpSig:
    coeffs, x, _ys = args
    return OpSig(op, _dtype_name(x), n=_tree_size(x), k=len(coeffs))


def _sig_reduction(op: str, args: Tuple) -> OpSig:
    return OpSig(op, _dtype_name(args[0]), n=_tree_size(args[0]), k=1)


def _sig_dot_prod_multi(op: str, args: Tuple) -> OpSig:
    x, ys = args
    return OpSig(op, _dtype_name(x), n=_tree_size(x), k=len(ys))


def _sig_block(op: str, args: Tuple) -> OpSig:
    A = args[0]
    b, _, nsys = A.shape
    return OpSig(op, str(A.dtype), n=b, nsys=nsys, b=b)


def _sig_soa_elementwise(op: str, args: Tuple) -> OpSig:
    z = args[0]
    n, nsys = z.shape
    return OpSig(op, str(z.dtype), n=n, nsys=nsys)


def _sig_lagrange_rescale(op: str, args: Tuple) -> OpSig:
    _eta, _q, Z, _active = args
    q1, n, nsys = Z.shape
    return OpSig(op, str(Z.dtype), n=n, nsys=nsys, k=q1)


def _sig_csr(op: str, args: Tuple) -> OpSig:
    data, x, _pattern = args
    return OpSig(op, str(data.dtype), n=int(x.size), nnz=int(data.size))


def _sig_bsr_spmv(op: str, args: Tuple) -> OpSig:
    values, _x, pattern = args
    nnzb, b, _, nsys = values.shape
    return OpSig(op, str(values.dtype), n=int(pattern[2]) * b,
                 nsys=nsys, b=b, nnz=nnzb)


def _sig_bsr_diag_inverse(op: str, args: Tuple) -> OpSig:
    values, pattern = args
    nnzb, b, _, nsys = values.shape
    return OpSig(op, str(values.dtype), n=int(pattern[2]) * b,
                 nsys=nsys, b=b, nnz=nnzb)


#: per-op signature extractors — keys name EXACTLY the modeled op set
#: (sunlint's table-coherence rule checks them against OP_TABLE).
SIG_EXTRACTORS = {
    "linear_sum": _sig_pairwise,
    "axpy": _sig_pairwise,
    "linear_combination": _sig_linear_combination,
    "scale_add_multi": _sig_scale_add_multi,
    "dot": _sig_reduction,
    "wrms_norm": _sig_reduction,
    "wrms_ss": _sig_reduction,
    "wrms_norm_mask": _sig_reduction,
    "dot_prod_multi": _sig_dot_prod_multi,
    "block_solve_soa": _sig_block,
    "block_inverse_soa": _sig_block,
    "blockdiag_spmv_soa": _sig_block,
    "newton_residual_soa": _sig_soa_elementwise,
    "masked_update_wrms_soa": _sig_soa_elementwise,
    "wrms_soa": _sig_soa_elementwise,
    "lagrange_rescale_soa": _sig_lagrange_rescale,
    "csr_spmv": _sig_csr,
    "bsr_spmv_soa": _sig_bsr_spmv,
    "bsr_block_jacobi_inverse_soa": _sig_bsr_diag_inverse,
}


def signature(op: str, args: Tuple) -> OpSig:
    """Extract the :class:`OpSig` for one dispatch call.  ``args`` are
    the positional arguments of the public wrapper (sans policy); under
    jit they are tracers with concrete shapes/dtypes, so this works at
    trace time — which is exactly when ``auto`` dispatch resolves."""
    fn = SIG_EXTRACTORS.get(op)
    if fn is None:
        raise ValueError(f"no signature extractor for dispatch op {op!r}")
    return fn(op, args)


@dataclasses.dataclass(frozen=True)
class OpCost:
    """Analytical work/traffic of one op at one signature."""

    flops: float
    hbm_bytes: float       # fused single-pass traffic (compiled pallas)
    jnp_bytes: float       # jnp-oracle algorithmic traffic
    pallas_bytes: float    # pallas traffic with VMEM = RAM (interpret)
    jnp_kernels: int       # oracle dispatches: fused kernels (streaming)
    #                        or eager primitive launches (SoA/sparse)
    body_ops: int          # primitive array ops per kernel-body exec
    vmem_rows: int         # accumulator rows per batched system (tile
    #                        working set = vmem_rows * tile * itemsize)


def _cost_lincomb(sig: OpSig) -> OpCost:
    s, n, k = sig.itemsize, sig.n, sig.k
    io = (k + 1) * n * s
    return OpCost((2 * k - 1) * n, io, io, io, 1, k + 1, k + 1)


def _cost_scale_add_multi(sig: OpSig) -> OpCost:
    s, n, k = sig.itemsize, sig.n, sig.k
    io = (2 * k + 1) * n * s
    return OpCost(2 * k * n, io, io, io, 1, 2 * k, 2 * k + 1)


def _cost_reduction(sig: OpSig) -> OpCost:
    s, n = sig.itemsize, sig.n
    io = 2 * n * s
    return OpCost(3 * n, io, io, io, 1, 3, 2)


def _cost_reduction_mask(sig: OpSig) -> OpCost:
    s, n = sig.itemsize, sig.n
    io = 3 * n * s
    return OpCost(4 * n, io, io, io, 1, 4, 3)


def _cost_dot_prod_multi(sig: OpSig) -> OpCost:
    s, n, k = sig.itemsize, sig.n, sig.k
    io = (k + 1) * n * s
    return OpCost(2 * k * n, io, io, io, 1, 2 * k, k + 1)


def _cost_block_solve(sig: OpSig) -> OpCost:
    s, nsys, b = sig.itemsize, sig.nsys, sig.b
    width = b + 1
    io = (b * width + b) * nsys * s        # read A,r; write x
    sweep = b * (b * width) * nsys * s     # b pivot passes
    body = 2 * b * b if b <= 8 else 5 * b
    # the oracle's GJ scan dispatches its body eagerly per pivot
    return OpCost(2 * b * b * width * nsys, io, 2 * sweep, sweep,
                  b * body, body, b * width)


def _cost_block_inverse(sig: OpSig) -> OpCost:
    s, nsys, b = sig.itemsize, sig.nsys, sig.b
    io = 2 * b * b * nsys * s
    sweep = b * (2 * b * b) * nsys * s
    body = 2 * b * b if b <= 8 else 5 * b
    return OpCost(4 * b ** 3 * nsys, io, 2 * sweep, sweep,
                  b * body, body, b * b)


def _cost_blockdiag_spmv(sig: OpSig) -> OpCost:
    s, nsys, b = sig.itemsize, sig.nsys, sig.b
    io = (b * b + 2 * b) * nsys * s
    return OpCost(2 * b * b * nsys, io, io, io, 2 * b, 2 * b,
                  b * b + 2 * b)


def _cost_newton_residual(sig: OpSig) -> OpCost:
    s, n, nsys = sig.itemsize, sig.n, sig.nsys
    io = 4 * n * nsys * s
    return OpCost(3 * n * nsys, io, io, io, 4, 4, 4 * n)


def _cost_masked_update_wrms(sig: OpSig) -> OpCost:
    s, n, nsys = sig.itemsize, sig.n, sig.nsys
    io = (5 * n + 1) * nsys * s
    return OpCost(6 * n * nsys, io, io, io, 6, 6, 5 * n)


def _cost_lagrange_rescale(sig: OpSig) -> OpCost:
    s, n, nsys, k = sig.itemsize, sig.n, sig.nsys, sig.k
    # Z in and out plus the eta, q and active rows; the kernel makes the
    # k x k weights of each lane in VMEM (about k^3 operations), while
    # the oracle writes and reads them
    io = (2 * k * n + 3) * nsys * s
    wts = 2 * k * k * nsys * s
    return OpCost((2 * k * k * n + k ** 3) * nsys, io, io + wts, io + wts,
                  2 * k, 2 * k, 2 * k * n + k * k + 2)


def _cost_wrms_soa(sig: OpSig) -> OpCost:
    s, n, nsys = sig.itemsize, sig.n, sig.nsys
    io = (2 * n + 1) * nsys * s
    return OpCost(3 * n * nsys, io, io, io, 3, 3, 2 * n)


def _cost_csr_spmv(sig: OpSig) -> OpCost:
    s, n, nnz = sig.itemsize, sig.n, sig.nnz
    io = (2 * nnz + 2 * n) * s
    # the oracle's gather + segment-sum lowers to ~a dozen eager
    # primitives (gathers don't fuse on the CPU path)
    return OpCost(2 * nnz, io, io, io, 16,
                  2 * max(1, nnz // max(n, 1)), 4)


def _cost_bsr_spmv(sig: OpSig) -> OpCost:
    s, n, nsys, b, nnz = (sig.itemsize, sig.n, sig.nsys, sig.b, sig.nnz)
    nblk = max(1, n // max(b, 1))
    io = (nnz * b * b + 2 * nblk * b) * nsys * s
    return OpCost(2 * nnz * b * b * nsys, io, io, io, 2 * nnz, 2 * nnz,
                  nnz * b * b + 2 * nblk * b)


def _cost_bsr_diag_inverse(sig: OpSig) -> OpCost:
    s, n, nsys, b, nnz = (sig.itemsize, sig.n, sig.nsys, sig.b, sig.nnz)
    nblk = max(1, n // max(b, 1))
    io = (nnz + nblk) * b * b * nsys * s
    sweep = nblk * b * (2 * b * b) * nsys * s
    body = nblk * (2 * b * b if b <= 8 else 5 * b)
    return OpCost(4 * b ** 3 * nblk * nsys, io, 2 * sweep, sweep,
                  b * body, body, 2 * b * b)


#: per-op cost models — keys name EXACTLY the modeled op set (sunlint's
#: table-coherence rule checks them against OP_TABLE and the README).
COST_MODELS = {
    "linear_sum": _cost_lincomb,
    "axpy": _cost_lincomb,
    "linear_combination": _cost_lincomb,
    "scale_add_multi": _cost_scale_add_multi,
    "dot": _cost_reduction,
    "wrms_norm": _cost_reduction,
    "wrms_ss": _cost_reduction,
    "wrms_norm_mask": _cost_reduction_mask,
    "dot_prod_multi": _cost_dot_prod_multi,
    "block_solve_soa": _cost_block_solve,
    "block_inverse_soa": _cost_block_inverse,
    "blockdiag_spmv_soa": _cost_blockdiag_spmv,
    "newton_residual_soa": _cost_newton_residual,
    "masked_update_wrms_soa": _cost_masked_update_wrms,
    "lagrange_rescale_soa": _cost_lagrange_rescale,
    "wrms_soa": _cost_wrms_soa,
    "csr_spmv": _cost_csr_spmv,
    "bsr_spmv_soa": _cost_bsr_spmv,
    "bsr_block_jacobi_inverse_soa": _cost_bsr_diag_inverse,
}


def op_cost(sig: OpSig) -> OpCost:
    """The per-op analytical model — flops and the three byte counts
    (see module docstring), parameterized on the signature."""
    fn = COST_MODELS.get(sig.op)
    if fn is None:
        raise ValueError(f"no cost model for dispatch op {sig.op!r}")
    return fn(sig)


# ---------------------------------------------------------------------------
# Tile selection — the policy-visible successor of ops.GJ_VMEM_BYTES /
# _gj_batch_tile: pick the tile from the device's VMEM budget (compiled)
# or maximize the tile to amortize per-step overhead (interpret).
# ---------------------------------------------------------------------------


def tile_for(sig: OpSig, device: Device,
             requested: Optional[int] = None) -> int:
    """Lane-aligned tile along the op's tiled axis.

    Interpret pseudo-device: per-grid-step interpreter overhead
    dominates, so the whole (lane-padded) axis is one step — capped at
    2^16 lanes-elements per operand row to bound working memory.
    Compiled devices: the largest lane multiple whose working set
    ``vmem_rows * tile * itemsize`` fits the device VMEM budget,
    clamped to the caller's requested tile.
    """
    axis = max(1, sig.axis_len)
    if device.vmem_bytes is None:
        tile = min(_lane_ceil(axis), 1 << 16)
    else:
        rows = max(1, op_cost(sig).vmem_rows)
        cap = device.vmem_bytes // (rows * sig.itemsize)
        tile = max(LANE, cap // LANE * LANE)
    if requested:
        tile = min(tile, max(LANE, requested // LANE * LANE))
    return min(tile, _lane_ceil(axis))


@dataclasses.dataclass(frozen=True)
class Prediction:
    """Model output for one (op signature, device)."""

    sig: OpSig
    device: str
    t_jnp: float
    t_pallas: float
    tile: int

    @property
    def winner(self) -> str:
        return "jnp" if self.t_jnp <= self.t_pallas else "pallas"

    @property
    def ratio(self) -> float:
        """Predicted jnp/pallas time ratio (>1 -> pallas wins)."""
        return self.t_jnp / max(self.t_pallas, 1e-12)


def predict(sig: OpSig, device: str | Device,
            requested_tile: Optional[int] = None) -> Prediction:
    """Roofline-evaluate both backends for ``sig`` on ``device``."""
    dev = device if isinstance(device, Device) else get_device(device)
    cost = op_cost(sig)
    tile = tile_for(sig, dev, requested_tile)
    steps = max(1, math.ceil(_lane_ceil(max(1, sig.axis_len)) / tile))
    t_jnp = (cost.jnp_kernels * dev.jnp_launch +
             max(cost.flops / dev.peak_flops, cost.jnp_bytes / dev.bw("jnp")))
    if dev.interpret:
        # SoA kernels touch a whole (rows x tile) block per grid step;
        # the flat streaming kernels sub-loop over LANE-wide blocks
        # inside each tile, so they re-dispatch the body per lane block.
        body_steps = steps if sig.op in BATCHED_OPS else \
            max(1, _lane_ceil(max(1, sig.axis_len)) // LANE)
        t_pallas = (dev.pallas_call + steps * dev.pallas_step +
                    body_steps * cost.body_ops * dev.interp_op +
                    cost.pallas_bytes / dev.bw("pallas"))
    else:
        t_pallas = (dev.pallas_call + steps * dev.pallas_step +
                    max(cost.flops / dev.peak_flops,
                        cost.hbm_bytes / dev.bw("pallas")))
    return Prediction(sig=sig, device=dev.name, t_jnp=t_jnp,
                      t_pallas=t_pallas, tile=tile)
