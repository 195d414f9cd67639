"""jit'd public wrappers for the Pallas kernels (padding, layout, dispatch).

Callers use these; the raw kernels live in their own modules and the
pure-jnp oracles in ref.py.  ``interpret=None`` (the default) derives
the mode from the backend: the kernels compile to Mosaic on a TPU and
run interpreted anywhere else (the CPU test suite validates them that
way); ``True``/``False`` force either mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import LANE, resolve_interpret
from . import block_solve as _bs
from . import blockdiag_spmv as _sp
from . import newton as _nw
from . import sparse as _sx
from . import vecops as _vo

#: f32 rows per (8, 128) vreg tile
SUBLANE = 8

# VMEM budget for the row-tiled Gauss-Jordan accumulator (compiled
# mode): the (b, width, tile) working set is kept under this many
# bytes, so the bundle tile shrinks ~1/b^2 as blocks grow (b=16 f64
# caps near 7 lanes, b=24 near 3) instead of spilling.  Interpret mode
# (CPU emulation) has no VMEM and pays per-grid-step interpreter
# overhead instead, so the cap only applies when compiling.
GJ_VMEM_BYTES = 2 * 1024 * 1024


def _lane_ceil(n: int) -> int:
    """Smallest lane-aligned size >= n (tile clamp for short vectors)."""
    return max(LANE, -(-n // LANE) * LANE)


def _vec_tile(n: int, tile: int) -> int:
    """Tile for a flat vector of ``n`` elements: the whole lane-padded
    vector when it fits in ``tile``, else ``tile`` rounded up to whole
    (SUBLANE, LANE) vreg tiles — a block that does not span the whole
    axis must tile its second-minor dimension by SUBLANE rows."""
    n = _lane_ceil(n)
    if n <= tile:
        return n
    step = SUBLANE * LANE
    return -(-tile // step) * step


def _pad_to(x: jnp.ndarray, mult: int, axis: int, fill=0.0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill), n


def _batch_tile(nb: int, batch_tile: int) -> int:
    """Bundle tile for the batched block kernels: the largest
    lane-multiple divisor of the lane-padded batch that does not exceed
    the policy's ``batch_tile`` (systems per grid program — the TPU
    analog of the paper's CUDA-stream bundle size).  Requiring the tile
    to divide the padded batch bounds the padding below one lane of
    identity blocks; a tile that merely rounds ``batch_tile`` up could
    force the batch itself to pad up to a tile multiple (e.g. nb=516
    with a 512 tile would eliminate 1024 blocks, ~2x the work)."""
    lanes = _lane_ceil(nb) // LANE
    dmax = max(1, min(batch_tile // LANE, lanes))
    d = max(dd for dd in range(1, dmax + 1) if lanes % dd == 0)
    return d * LANE


def _gj_batch_tile(nb: int, batch_tile: int, *, b: int, width: int,
                   itemsize: int, interpret,
                   vmem_bytes=None) -> int:
    """Bundle tile for the Gauss-Jordan kernels: :func:`_batch_tile`
    with, in compiled mode, the requested tile first clamped so the
    row-tiled accumulator ``(b, width, tile)`` fits the VMEM budget
    — i.e. the tile shrinks with b^2.  Small blocks (the unrolled
    kernels) are unaffected: their cap exceeds any practical tile.

    ``vmem_bytes`` overrides the default :data:`GJ_VMEM_BYTES` budget —
    the cost-model dispatch layer passes the roofline device table's
    budget here so the clamp is a policy-visible decision rather than a
    module constant."""
    if not resolve_interpret(interpret):
        budget = GJ_VMEM_BYTES if vmem_bytes is None else vmem_bytes
        cap = budget // (itemsize * b * width)
        batch_tile = min(batch_tile, max(LANE, cap // LANE * LANE))
    return _batch_tile(nb, batch_tile)


def _pad_blocks_identity(Ap: jnp.ndarray, nb: int) -> jnp.ndarray:
    """Make padding blocks (SoA batch axis 2 beyond ``nb``) identity so
    the no-pivot elimination stays well-defined on them."""
    if Ap.shape[2] == nb:
        return Ap
    b = Ap.shape[0]
    eye = jnp.eye(b, dtype=Ap.dtype)[:, :, None]
    padmask = (jnp.arange(Ap.shape[2]) >= nb)[None, None, :]
    return jnp.where(padmask, eye, Ap)


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret",
                                             "scale_rows", "vmem_bytes"))
def block_solve(A: jnp.ndarray, r: jnp.ndarray, *, batch_tile: int = 4 * LANE,
                interpret=None, scale_rows: bool = True,
                vmem_bytes=None):
    """Batched block solve, AoS API: A:(nb,b,b), r:(nb,b) -> x:(nb,b).

    Transposes to the SoA lane-major layout, pads the batch to the tile
    (padding blocks are identity so the no-pivot elimination is safe),
    runs the kernel, and transposes back.  TPU callers holding SoA data
    should call :func:`block_solve_soa` directly and skip the transposes.
    """
    nb, b, _ = A.shape
    tile = _gj_batch_tile(nb, batch_tile, b=b, width=b + 1,
                          itemsize=A.dtype.itemsize, interpret=interpret,
                          vmem_bytes=vmem_bytes)
    Asoa = jnp.transpose(A, (1, 2, 0))          # (b, b, nb)
    rsoa = jnp.transpose(r, (1, 0))             # (b, nb)
    Ap, _ = _pad_to(Asoa, tile, axis=2)
    # make padded blocks identity to keep the elimination well-defined
    Ap = _pad_blocks_identity(Ap, nb)
    rp, _ = _pad_to(rsoa, tile, axis=1)
    x = _bs.block_solve_soa(Ap, rp, batch_tile=tile, interpret=interpret,
                            scale_rows=scale_rows)
    return jnp.transpose(x[:, :nb], (1, 0))


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret",
                                             "scale_rows", "vmem_bytes"))
def block_solve_soa(A: jnp.ndarray, r: jnp.ndarray, *,
                    batch_tile: int = 4 * LANE, interpret=None,
                    scale_rows: bool = True, vmem_bytes=None):
    """SoA API (lane-major batch): A:(b,b,NB), r:(b,NB) -> x:(b,NB)."""
    b, _, nb = A.shape
    tile = _gj_batch_tile(nb, batch_tile, b=b, width=b + 1,
                          itemsize=A.dtype.itemsize, interpret=interpret,
                          vmem_bytes=vmem_bytes)
    Ap, _ = _pad_to(A, tile, axis=2)
    Ap = _pad_blocks_identity(Ap, nb)
    rp, _ = _pad_to(r, tile, axis=1)
    x = _bs.block_solve_soa(Ap, rp, batch_tile=tile, interpret=interpret,
                            scale_rows=scale_rows)
    return x[:, :nb]


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret",
                                             "scale_rows", "vmem_bytes"))
def block_inverse_soa(A: jnp.ndarray, *, batch_tile: int = 4 * LANE,
                      interpret=None, scale_rows: bool = True,
                      vmem_bytes=None):
    """Per-block inverse, SoA layout: A:(b,b,NB) -> A^{-1}:(b,b,NB).

    The lsetup half of the ensemble Newton pipeline: invert every Newton
    block once, then each Newton iteration applies it with one
    :func:`blockdiag_spmv_soa` pass (lsolve)."""
    b, _, nb = A.shape
    tile = _gj_batch_tile(nb, batch_tile, b=b, width=b,
                          itemsize=A.dtype.itemsize, interpret=interpret,
                          vmem_bytes=vmem_bytes)
    Ap, _ = _pad_to(A, tile, axis=2)
    Ap = _pad_blocks_identity(Ap, nb)
    x = _bs.block_inverse_soa(Ap, batch_tile=tile, interpret=interpret,
                              scale_rows=scale_rows)
    return x[:, :, :nb]


@functools.partial(jax.jit, static_argnames=("block_elems", "interpret"))
def linear_combination(coeffs: jnp.ndarray, X: jnp.ndarray, *,
                       block_elems: int = 8 * LANE, interpret=None):
    """Fused Z = sum_k coeffs[k] X[k];  X:(K, N) any N (padded inside)."""
    K, N = X.shape
    tile = _vec_tile(N, block_elems)
    Xp, _ = _pad_to(X, tile, axis=1)
    z = _vo.linear_combination(coeffs, Xp, block_elems=tile,
                               interpret=interpret)
    return z[:N]


@functools.partial(jax.jit, static_argnames=("block_elems", "interpret"))
def scale_add_multi(coeffs: jnp.ndarray, x: jnp.ndarray, Y: jnp.ndarray, *,
                    block_elems: int = 8 * LANE, interpret=None):
    """Fused Z[k] = coeffs[k]*x + Y[k];  x:(N,), Y:(K,N) any N."""
    K, N = Y.shape
    tile = _vec_tile(N, block_elems)
    xp, _ = _pad_to(x, tile, axis=0)
    Yp, _ = _pad_to(Y, tile, axis=1)
    z = _vo.scale_add_multi(coeffs, xp, Yp, block_elems=tile,
                            interpret=interpret)
    return z[:, :N]


@functools.partial(jax.jit, static_argnames=("reduce_tile", "interpret"))
def wrms_norm(x: jnp.ndarray, w: jnp.ndarray, *, reduce_tile: int = 64 * LANE,
              interpret=None):
    """Fused WRMS norm of 1-D x with weights w (BlockReduce policy)."""
    (N,) = x.shape
    tile = _vec_tile(N, reduce_tile)
    xp, _ = _pad_to(x, tile, axis=0)
    wp, _ = _pad_to(w, tile, axis=0)   # pad weights with 0 -> no contribution
    parts = _vo.wrms_partial(xp, wp, reduce_tile=tile, interpret=interpret)
    return jnp.sqrt(jnp.sum(parts) / N)


@functools.partial(jax.jit, static_argnames=("reduce_tile", "interpret"))
def dot(x: jnp.ndarray, y: jnp.ndarray, *, reduce_tile: int = 64 * LANE,
        interpret=None):
    (N,) = x.shape
    tile = _vec_tile(N, reduce_tile)
    xp, _ = _pad_to(x, tile, axis=0)
    yp, _ = _pad_to(y, tile, axis=0)
    parts = _vo.dot_partial(xp, yp, reduce_tile=tile, interpret=interpret)
    return jnp.sum(parts)


@functools.partial(jax.jit, static_argnames=("reduce_tile", "interpret"))
def wrms_ss(x: jnp.ndarray, w: jnp.ndarray, *, reduce_tile: int = 64 * LANE,
            interpret=None):
    """Raw sum((x*w)^2) of 1-D x — the per-leaf partial the dispatch
    layer accumulates across pytree leaves before the final sqrt(/N)."""
    (N,) = x.shape
    tile = _vec_tile(N, reduce_tile)
    xp, _ = _pad_to(x, tile, axis=0)
    wp, _ = _pad_to(w, tile, axis=0)
    parts = _vo.wrms_partial(xp, wp, reduce_tile=tile, interpret=interpret)
    return jnp.sum(parts)


@functools.partial(jax.jit, static_argnames=("reduce_tile", "interpret"))
def wrms_mask_ss(x: jnp.ndarray, w: jnp.ndarray, m: jnp.ndarray, *,
                 reduce_tile: int = 64 * LANE, interpret=None):
    """Raw sum((x*w*m)^2) of 1-D x (masked WRMS partial)."""
    (N,) = x.shape
    tile = _vec_tile(N, reduce_tile)
    xp, _ = _pad_to(x, tile, axis=0)
    wp, _ = _pad_to(w, tile, axis=0)
    mp, _ = _pad_to(m, tile, axis=0)
    parts = _vo.wrms_mask_partial(xp, wp, mp, reduce_tile=tile,
                                  interpret=interpret)
    return jnp.sum(parts)


@functools.partial(jax.jit, static_argnames=("reduce_tile", "interpret"))
def wrms_norm_mask(x: jnp.ndarray, w: jnp.ndarray, m: jnp.ndarray, *,
                   reduce_tile: int = 64 * LANE, interpret=None):
    """Masked WRMS norm of 1-D x: sqrt(sum((x*w*m)^2)/N)."""
    (N,) = x.shape
    tile = _vec_tile(N, reduce_tile)
    xp, _ = _pad_to(x, tile, axis=0)
    wp, _ = _pad_to(w, tile, axis=0)   # zero weights -> no contribution
    mp, _ = _pad_to(m, tile, axis=0)
    parts = _vo.wrms_mask_partial(xp, wp, mp, reduce_tile=tile,
                                  interpret=interpret)
    return jnp.sqrt(jnp.sum(parts) / N)


@functools.partial(jax.jit, static_argnames=("reduce_tile", "interpret"))
def dot_prod_multi(x: jnp.ndarray, Y: jnp.ndarray, *,
                   reduce_tile: int = 64 * LANE, interpret=None):
    """d_k = <x, Y[k]>;  x:(N,), Y:(K,N) -> (K,), single fused pass."""
    (N,) = x.shape
    tile = _vec_tile(N, reduce_tile)
    xp, _ = _pad_to(x, tile, axis=0)
    Yp, _ = _pad_to(Y, tile, axis=1)
    parts = _vo.multi_dot_partial(xp, Yp, reduce_tile=tile,
                                  interpret=interpret)
    return jnp.sum(parts, axis=(0, 2))


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def blockdiag_spmv(A: jnp.ndarray, x: jnp.ndarray, *,
                   batch_tile: int = 4 * LANE, interpret=None):
    """AoS API: A:(nb,b,b), x:(nb,b) -> y:(nb,b)."""
    nb, b, _ = A.shape
    tile = _batch_tile(nb, batch_tile)
    Asoa = jnp.transpose(A, (1, 2, 0))
    xsoa = jnp.transpose(x, (1, 0))
    Ap, _ = _pad_to(Asoa, tile, axis=2)
    xp, _ = _pad_to(xsoa, tile, axis=1)
    y = _sp.blockdiag_spmv_soa(Ap, xp, batch_tile=tile, interpret=interpret)
    return jnp.transpose(y[:, :nb], (1, 0))


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def blockdiag_spmv_soa(A: jnp.ndarray, x: jnp.ndarray, *,
                       batch_tile: int = 4 * LANE, interpret=None):
    """SoA API: A:(b,b,NB), x:(b,NB) -> y:(b,NB); pads NB to the bundle
    tile (zero-padded systems produce zeros, sliced off)."""
    b, _, nb = A.shape
    tile = _batch_tile(nb, batch_tile)
    Ap, _ = _pad_to(A, tile, axis=2)
    xp, _ = _pad_to(x, tile, axis=1)
    y = _sp.blockdiag_spmv_soa(Ap, xp, batch_tile=tile, interpret=interpret)
    return y[:, :nb]


# ---------------------------------------------------------------------------
# Fused ensemble-Newton ops (SoA layout, batch on the lane axis)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret",
                                             "negate"))
def newton_residual_soa(z: jnp.ndarray, fval: jnp.ndarray,
                        psi: jnp.ndarray, gamma: jnp.ndarray, *,
                        batch_tile: int = 4 * LANE, interpret=None,
                        negate: bool = False):
    """Fused g = z - gamma*f - psi (``negate=True`` -> -g, the Newton
    rhs); z/f/psi (n, NB), gamma (NB,), any NB (padded inside)."""
    n, nb = z.shape
    tile = _batch_tile(nb, batch_tile)
    zp, _ = _pad_to(z, tile, axis=1)
    fp, _ = _pad_to(fval, tile, axis=1)
    pp, _ = _pad_to(psi, tile, axis=1)
    gp, _ = _pad_to(gamma, tile, axis=0)
    g = _nw.newton_residual(zp, fp, pp, gp, batch_tile=tile,
                            interpret=interpret, negate=negate)
    return g[:, :nb]


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def masked_update_wrms_soa(z: jnp.ndarray, dz: jnp.ndarray, w: jnp.ndarray,
                           mask: jnp.ndarray, *,
                           batch_tile: int = 4 * LANE,
                           interpret=None):
    """Fused masked z += dz and per-system WRMS of dz: z/dz/w (n, NB),
    mask (NB,) -> (z_new, dn); padded systems report dn = 0."""
    n, nb = z.shape
    tile = _batch_tile(nb, batch_tile)
    zp, _ = _pad_to(z, tile, axis=1)
    dp, _ = _pad_to(dz, tile, axis=1)
    wp, _ = _pad_to(w, tile, axis=1)
    mp, _ = _pad_to(mask.astype(z.dtype), tile, axis=0)
    z_new, dn = _nw.masked_update_wrms(zp, dp, wp, mp, batch_tile=tile,
                                       interpret=interpret)
    return z_new[:, :nb], dn[:nb]


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def lagrange_rescale_soa(eta: jnp.ndarray, q: jnp.ndarray, Z: jnp.ndarray,
                         active: jnp.ndarray, *,
                         batch_tile: int = 4 * LANE,
                         interpret=None):
    """Masked Lagrange history rebuild from per-lane step ratio ``eta``
    and valid depth ``q`` (any real or integer dtype), Z (q1,n,NB),
    active (NB,) -> Z_new; inactive and padded systems go to the kernel
    at eta = 1, which copies their Z through."""
    q1, _, nb = Z.shape
    tile = _batch_tile(nb, batch_tile)
    e = jnp.where(active != 0, eta, 1).astype(Z.dtype)
    ep, _ = _pad_to(e, tile, axis=0, fill=1.0)
    qp, _ = _pad_to(q.astype(Z.dtype), tile, axis=0)
    Zp, _ = _pad_to(Z, tile, axis=2)
    Zn = _nw.lagrange_rescale(ep, qp, Zp, batch_tile=tile,
                              interpret=interpret)
    return Zn[:, :, :nb]


@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def wrms_soa(v: jnp.ndarray, w: jnp.ndarray, *,
             batch_tile: int = 4 * LANE, interpret=None):
    """Per-system WRMS over the state axis: v/w (n, NB) -> (NB,)."""
    n, nb = v.shape
    tile = _batch_tile(nb, batch_tile)
    vp, _ = _pad_to(v, tile, axis=1)
    wp, _ = _pad_to(w, tile, axis=1)
    return _nw.wrms_soa(vp, wp, batch_tile=tile,
                        interpret=interpret)[:nb]


# ---------------------------------------------------------------------------
# Sparse ops (static shared patterns, passed as hashable tuples)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("indptr", "indices",
                                             "block_elems", "interpret"))
def csr_spmv(data: jnp.ndarray, x: jnp.ndarray, *, indptr: tuple,
             indices: tuple, block_elems: int = 8 * LANE,
             interpret=None):
    """y = A @ x for CSR A with a STATIC pattern: data:(nnz,), x:(ncol,).

    The pattern is ELL-ized at trace time (host numpy on the static
    tuples): kmax = max row length, padded slots get zero data and
    column 0, rows ride the lane axis.  ``indptr``/``indices`` must be
    hashable tuples — they key the jit cache, one compile per pattern,
    exactly the SUNMATRIX_CUSPARSE store-the-pattern-once economics.
    """
    import numpy as np
    ip = np.asarray(indptr)
    ci = np.asarray(indices, np.int32)
    n_rows = len(ip) - 1
    row_len = np.diff(ip)
    kmax = max(1, int(row_len.max()) if n_rows else 1)
    src = np.zeros((n_rows, kmax), np.int32)
    valid = np.zeros((n_rows, kmax), bool)
    for i in range(n_rows):
        s, e = int(ip[i]), int(ip[i + 1])
        src[i, : e - s] = np.arange(s, e)
        valid[i, : e - s] = True
    cols = np.where(valid, ci[src] if len(ci) else 0, 0).astype(np.int32)
    data_ell = jnp.where(jnp.asarray(valid), data[jnp.asarray(src)], 0.0)
    tile = min(block_elems, _lane_ceil(n_rows))
    d_t, _ = _pad_to(data_ell.T, tile, axis=1)       # (kmax, NR)
    c_t, _ = _pad_to(jnp.asarray(cols.T), tile, axis=1)
    xp, _ = _pad_to(x, LANE, axis=0)
    y = _sx.csr_spmv_ell(d_t, c_t, xp, row_tile=tile, interpret=interpret)
    return y[:n_rows]


@functools.partial(jax.jit, static_argnames=("brows", "bcols", "nblk",
                                             "batch_tile", "interpret"))
def bsr_spmv_soa(values: jnp.ndarray, x: jnp.ndarray, *, brows: tuple,
                 bcols: tuple, nblk: int, batch_tile: int = 4 * LANE,
                 interpret=None):
    """Ensemble shared-pattern BSR SpMV: values (nnzb, b, b, NB),
    x (nblk, b, NB) -> y (nblk, b, NB); pads the system batch NB to the
    bundle tile (zero-padded systems produce zeros, sliced off)."""
    nnzb, b, _, nb = values.shape
    tile = _batch_tile(nb, batch_tile)
    Vp, _ = _pad_to(values, tile, axis=3)
    xp, _ = _pad_to(x, tile, axis=2)
    y = _sx.bsr_spmv_soa(Vp, xp, brows=tuple(brows), bcols=tuple(bcols),
                         nblk=nblk, batch_tile=tile, interpret=interpret)
    return y[:, :, :nb]


@functools.partial(jax.jit, static_argnames=("brows", "bcols", "nblk",
                                             "batch_tile", "interpret"))
def bsr_diag_inverse_soa(values: jnp.ndarray, *, brows: tuple,
                         bcols: tuple, nblk: int,
                         batch_tile: int = 4 * LANE,
                         interpret=None):
    """Invert every diagonal block of the shared pattern — the
    block-Jacobi psetup: values (nnzb, b, b, NB) -> (b, b, nblk*NB),
    flattened batch block-major (block I of system s at I*NB + s).

    No new kernel: the diagonal-block positions are static, so this is
    a trace-time gather plus the existing Gauss-Jordan inverse kernel
    over the flattened nblk*NB batch.
    """
    nnzb, b, _, NB = values.shape
    diag_idx = []
    for I in range(nblk):
        hits = [e for e, (i, j) in enumerate(zip(brows, bcols))
                if i == I and j == I]
        if not hits:
            raise ValueError(f"pattern lacks diagonal block ({I},{I})")
        diag_idx.append(hits[0])
    D = values[jnp.asarray(diag_idx)]                # (nblk, b, b, NB)
    Dsoa = jnp.transpose(D, (1, 2, 0, 3)).reshape(b, b, nblk * NB)
    return block_inverse_soa(Dsoa, batch_tile=batch_tile,
                             interpret=interpret)
