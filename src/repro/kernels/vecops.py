"""Pallas TPU kernels for the hot N_Vector operations.

The paper's Fig. 9/Table 1 show that for time integration the dominant
cost is the *vector* operations (``N_VLinearSum`` above all), which are
memory-bandwidth-bound.  Two kernels:

* :func:`linear_combination` — Z = sum_k c_k X_k in ONE pass over the
  operands.  ARKODE evaluates y_new = y + h*sum b_i k_i (s+1 operands);
  done with pairwise N_VLinearSum this reads/writes 3 vectors per pair
  (2(s+1) vector reads + s+1 writes); fused it is s+1 reads + 1 write —
  the SUNDIALS "fused vector operation" realized as a single VMEM-tiled
  kernel.  Streaming op -> ThreadDirect/GridStride policy sets the tile.

* :func:`scale_add_multi` — Z_k = c_k * x + Y_k for all k in one pass:
  x is read ONCE from HBM instead of once per destination
  (N_VScaleAddMulti, the fused op ARKODE uses to form stage RHS data).

* :func:`wrms_partial` / :func:`dot_partial` — BlockReduce-policy
  reductions: each grid program reduces its tile to one partial in a
  (grid,) output; the final (tiny) sum happens in XLA.  One pass, no
  intermediate (x*w)^2 vector materialized in HBM.

* :func:`wrms_mask_partial` — masked WRMS partials (N_VWrmsNormMask):
  the mask multiply happens in-register, never in HBM.

* :func:`multi_dot_partial` — d_k = <x, Y_k> partials for all k with x
  read once (N_VDotProdMulti, the fused Gram-Schmidt reduction).

Vectors are lane-padded by ops.py and viewed as ``(rows, LANE)`` here
(the same bytes as XLA's tiled 1-D layout), so every block is a whole
number of (8, 128) vreg tiles or spans the whole array — the only
shapes Mosaic lowers.  A reduction writes one ``(1, LANE)`` row of
lane partials per grid program; the caller finishes the sum in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import LANE, as_rows, grid_block, resolve_interpret, whole_block


def _lincomb_kernel(c_ref, x_ref, z_ref, *, K: int):
    """z tile = sum_k c[k] * x[k] tile.  x_ref: (K, R, LANE)."""
    acc = c_ref[0] * x_ref[0]
    for k in range(1, K):
        acc = acc + c_ref[k] * x_ref[k]
    z_ref[...] = acc


def linear_combination(coeffs: jnp.ndarray, X: jnp.ndarray, *,
                       block_elems: int = 8 * LANE,
                       interpret=None) -> jnp.ndarray:
    """Fused Z = sum_k coeffs[k] * X[k];  X: (K, N) with N % tile == 0."""
    K, N = X.shape
    assert N % block_elems == 0, (N, block_elems)
    rows = block_elems // LANE
    kernel = functools.partial(_lincomb_kernel, K=K)
    z = pl.pallas_call(
        kernel,
        grid=(N // block_elems,),
        in_specs=[whole_block((K,)),
                  grid_block((K, rows, LANE), axis=1)],
        out_specs=grid_block((rows, LANE), axis=0),
        out_shape=jax.ShapeDtypeStruct((N // LANE, LANE), X.dtype),
        interpret=resolve_interpret(interpret),
    )(coeffs, as_rows(X))
    return z.reshape(N)


def _scale_add_multi_kernel(c_ref, x_ref, y_ref, z_ref, *, K: int):
    """z[k] tile = c[k] * x tile + y[k] tile.  x read once per tile."""
    xt = x_ref[...]
    for k in range(K):
        z_ref[k] = c_ref[k] * xt + y_ref[k]


def scale_add_multi(coeffs: jnp.ndarray, x: jnp.ndarray, Y: jnp.ndarray, *,
                    block_elems: int = 8 * LANE,
                    interpret=None) -> jnp.ndarray:
    """Fused Z[k] = coeffs[k]*x + Y[k];  x:(N,), Y:(K,N), N % tile == 0."""
    K, N = Y.shape
    assert x.shape == (N,) and N % block_elems == 0, (x.shape, Y.shape)
    rows = block_elems // LANE
    kernel = functools.partial(_scale_add_multi_kernel, K=K)
    Z = pl.pallas_call(
        kernel,
        grid=(N // block_elems,),
        in_specs=[whole_block((K,)),
                  grid_block((rows, LANE), axis=0),
                  grid_block((K, rows, LANE), axis=1)],
        out_specs=grid_block((K, rows, LANE), axis=1),
        out_shape=jax.ShapeDtypeStruct((K, N // LANE, LANE), Y.dtype),
        interpret=resolve_interpret(interpret),
    )(coeffs, as_rows(x), as_rows(Y))
    return Z.reshape(K, N)


def _lane_sum(v):
    """(R, LANE) -> (1, LANE): the sublane reduction stays in vregs."""
    return jnp.sum(v, axis=0, keepdims=True)


def _wrms_kernel(x_ref, w_ref, out_ref):
    xw = x_ref[...] * w_ref[...]
    out_ref[0] = _lane_sum(xw * xw)


def _reduce(kernel, operands, tile: int, n_out: int, dtype, interpret):
    """Run a lane-partial reduction over ``(…, N)`` operands: one
    ``(n_out, LANE)`` row block per grid program -> (grid, n_out, LANE).
    The reduced (last) axis of every operand is tiled, leading axes
    stay whole."""
    N = operands[0].shape[-1]
    assert N % tile == 0, (N, tile)
    rows = tile // LANE
    grid = N // tile
    in_specs = [grid_block(op.shape[:-1] + (rows, LANE), axis=-2)
                for op in operands]
    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=grid_block((1, n_out, LANE), axis=0),
        out_shape=jax.ShapeDtypeStruct((grid, n_out, LANE), dtype),
        interpret=resolve_interpret(interpret),
    )(*(as_rows(op) for op in operands))


def wrms_partial(x: jnp.ndarray, w: jnp.ndarray, *,
                 reduce_tile: int = 64 * LANE,
                 interpret=None) -> jnp.ndarray:
    """Per-tile lane partials of sum((x*w)^2), shape (grid, 1, LANE);
    the final sum is done by the caller."""
    return _reduce(_wrms_kernel, (x, w), reduce_tile, 1, x.dtype,
                   interpret)


def _wrms_mask_kernel(x_ref, w_ref, m_ref, out_ref):
    xwm = x_ref[...] * w_ref[...] * m_ref[...]
    out_ref[0] = _lane_sum(xwm * xwm)


def wrms_mask_partial(x: jnp.ndarray, w: jnp.ndarray, m: jnp.ndarray, *,
                      reduce_tile: int = 64 * LANE,
                      interpret=None) -> jnp.ndarray:
    """Per-tile lane partials of sum((x*w*m)^2) (N_VWrmsNormMask)."""
    return _reduce(_wrms_mask_kernel, (x, w, m), reduce_tile, 1, x.dtype,
                   interpret)


def _dot_kernel(x_ref, y_ref, out_ref):
    out_ref[0] = _lane_sum(x_ref[...] * y_ref[...])


def dot_partial(x: jnp.ndarray, y: jnp.ndarray, *,
                reduce_tile: int = 64 * LANE,
                interpret=None) -> jnp.ndarray:
    """Per-tile lane partials of <x, y>, shape (grid, 1, LANE)."""
    return _reduce(_dot_kernel, (x, y), reduce_tile, 1, x.dtype, interpret)


def _multidot_kernel(x_ref, y_ref, out_ref, *, K: int):
    """out[0, k] = lane partials of <x tile, Y[k] tile>; x read once."""
    xt = x_ref[...]
    for k in range(K):
        out_ref[0, k:k + 1, :] = _lane_sum(xt * y_ref[k])


def multi_dot_partial(x: jnp.ndarray, Y: jnp.ndarray, *,
                      reduce_tile: int = 64 * LANE,
                      interpret=None) -> jnp.ndarray:
    """Per-tile lane partials of d_k = <x, Y[k]>, shape (grid, K, LANE)
    (N_VDotProdMulti); d = partials.sum(axis=(0, 2))."""
    K, N = Y.shape
    assert x.shape == (N,)
    kernel = functools.partial(_multidot_kernel, K=K)
    return _reduce(kernel, (x, Y), reduce_tile, K, x.dtype, interpret)
