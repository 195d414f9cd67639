"""Pallas TPU kernel: batched small-block linear solve (cuSolver batchQR analog).

Solves nb independent b-by-b systems A_j x_j = r_j — the submodel
use-case Newton solve with the Fig.-1 block-diagonal Jacobian.

TPU-native layout (DESIGN.md §2 hardware adaptation): the GPU batched-QR
assigns one block per thread-block; on TPU we use a *structure-of-arrays*
layout with the **batch on the lane dimension**:

    A : (b, b, NB)   — A[i, j, :] is the (i,j) entry of every block
    r : (b, NB)

so every elimination operation is an elementwise vector op across 128
lanes (VPU), and the b^2 loop structure is fully unrolled at trace time
(b is static and small — the paper's 3x3 chemistry blocks, up to ~16).
The elimination sequence is *identical for every block* — the TPU
expression of the paper's shared-sparsity/shared-factorization-structure
point (the symbolic offline-generated Gauss-Jordan of ref. [21]).

No pivoting: Newton matrices M = I - gamma*J of chemical-kinetics blocks
are strongly diagonally dominant for acceptable gamma (same assumption
as the paper's embedded symbolic solver).  A diagonal-scaling variant is
exposed for robustness.  ``ref.py`` holds the pure-jnp oracle.

Two elimination kernels, selected by block size:

* ``b <= UNROLL_MAX_B`` — the fully-unrolled form above: every block
  entry is its own live lane-vector (b^2 of them), which is the fastest
  shape while they all fit in vector registers;
* ``b > UNROLL_MAX_B``  — a **row-tiled** elimination: the b^2 live
  vectors of the unrolled form spill registers at b=16 (256 vectors per
  tile — the BENCH_ensemble.json regression this replaces), so the
  matrix instead lives in ONE ``(b, b, TN)`` VMEM-resident accumulator
  and each of the b pivot steps is a handful of whole-array VPU ops
  (normalize pivot row, mask it out of the factor column, one rank-1
  update, and a select that writes the pivot row back — Pallas TPU has
  no scatter, so no ``.at[].set``).  Every intermediate keeps rank 3
  (``(1, b, TN)`` rows, ``(b, 1, TN)`` columns), so the rank-1 update
  is a pair of broadcasts and never a sublane reshape.  ``ops.py``
  additionally shrinks the bundle tile with b^2 so the accumulator
  stays inside a fixed VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import LANE, grid_block, resolve_interpret

# largest block size the fully-unrolled kernels handle before register
# pressure wins over unrolling (b^2 live lane-vectors; 64 at b=8 is
# fine, 256 at b=16 spills — measured in BENCH_ensemble.json)
UNROLL_MAX_B = 8


def _gj_kernel(a_ref, r_ref, x_ref, *, b: int, scale_rows: bool):
    """Gauss-Jordan elimination, unrolled over the (static) block size.

    a_ref: (b, b, TN) VMEM tile;  r_ref: (b, TN);  x_ref: (b, TN) out.
    """
    # load rows into registers (lists of (TN,) vectors — fully unrolled)
    A = [[a_ref[i, j, :] for j in range(b)] for i in range(b)]
    r = [r_ref[i, :] for i in range(b)]

    if scale_rows:
        for i in range(b):
            m = jnp.maximum(
                functools.reduce(jnp.maximum,
                                 [jnp.abs(A[i][j]) for j in range(b)]),
                1e-30)
            inv = 1.0 / m
            A[i] = [A[i][j] * inv for j in range(b)]
            r[i] = r[i] * inv

    for k in range(b):
        piv = A[k][k]
        inv_piv = 1.0 / piv
        # normalize pivot row
        A[k] = [A[k][j] * inv_piv for j in range(b)]
        r[k] = r[k] * inv_piv
        # eliminate column k from every other row
        for i in range(b):
            if i == k:
                continue
            f = A[i][k]
            A[i] = [A[i][j] - f * A[k][j] for j in range(b)]
            r[i] = r[i] - f * r[k]

    for i in range(b):
        x_ref[i, :] = r[i]


def _gj_inverse_kernel(a_ref, x_ref, *, b: int, scale_rows: bool):
    """Gauss-Jordan inversion: eliminate [A | I] -> [I | A^{-1}].

    a_ref: (b, b, TN) VMEM tile; x_ref: (b, b, TN) out = A^{-1} in the
    same SoA layout.  Same unrolled shared-structure elimination as
    :func:`_gj_kernel` with the b-column identity as the right-hand side
    — this is the lsetup product of the batched-BDF ensemble pipeline
    (factor once here, then every Newton iteration is one spmv).
    """
    A = [[a_ref[i, j, :] for j in range(b)] for i in range(b)]
    one = jnp.ones_like(A[0][0])
    zero = jnp.zeros_like(A[0][0])
    R = [[one if i == j else zero for j in range(b)] for i in range(b)]

    if scale_rows:
        for i in range(b):
            m = jnp.maximum(
                functools.reduce(jnp.maximum,
                                 [jnp.abs(A[i][j]) for j in range(b)]),
                1e-30)
            inv = 1.0 / m
            A[i] = [A[i][j] * inv for j in range(b)]
            R[i] = [R[i][j] * inv for j in range(b)]

    for k in range(b):
        inv_piv = 1.0 / A[k][k]
        A[k] = [A[k][j] * inv_piv for j in range(b)]
        R[k] = [R[k][j] * inv_piv for j in range(b)]
        for i in range(b):
            if i == k:
                continue
            fkt = A[i][k]
            A[i] = [A[i][j] - fkt * A[k][j] for j in range(b)]
            R[i] = [R[i][j] - fkt * R[k][j] for j in range(b)]

    for i in range(b):
        for j in range(b):
            x_ref[i, j, :] = R[i][j]


def _row_scale(a):
    """1 / max_j |a[i, j]| per row of every block, as a (b, 1, TN)
    column (the diagonal-scaling variant's D)."""
    return 1.0 / jnp.maximum(jnp.max(jnp.abs(a), axis=1, keepdims=True),
                             1e-30)


def _gj_tiled_kernel(a_ref, r_ref, x_ref, *, b: int, scale_rows: bool):
    """Row-tiled Gauss-Jordan for large blocks (b > UNROLL_MAX_B).

    a_ref: (b, b, TN);  r_ref / x_ref: (b, 1, TN) (ops.py passes the
    right-hand side with a unit sublane axis so it broadcasts against
    the factor column without a reshape).  Each pivot step is a rank-1
    update of A and r plus two selects that write the normalized pivot
    row back, so the live set is O(b*TN) (one pivot row + one factor
    column) rather than the unrolled kernel's O(b^2*TN) registers.  The
    accumulator is held as a functional value: Mosaic materializes it
    in VMEM either way, and under interpret emulation an explicit
    ``scratch_shapes`` ref measures 3-7x slower.
    """
    a = a_ref[...]
    rr = r_ref[...]
    if scale_rows:
        inv_m = _row_scale(a)
        a = a * inv_m
        rr = rr * inv_m
    rows = lax.broadcasted_iota(jnp.int32, (b, 1, 1), 0)
    for k in range(b):
        pivot_row = rows == k
        inv = 1.0 / a[k:k + 1, k:k + 1, :]              # (1, 1, TN)
        rowk = a[k:k + 1] * inv                         # (1, b, TN)
        rk = rr[k:k + 1] * inv                          # (1, 1, TN)
        f = jnp.where(pivot_row, 0.0, a[:, k:k + 1, :])  # (b, 1, TN)
        a = jnp.where(pivot_row, rowk, a - f * rowk)
        rr = jnp.where(pivot_row, rk, rr - f * rk)
    x_ref[...] = rr


def _gj_tiled_inverse_kernel(a_ref, x_ref, *, b: int, scale_rows: bool):
    """Row-tiled in-place Gauss-Jordan inversion (b > UNROLL_MAX_B).

    Classic in-place GJ: the inverse replaces A in the same (b, b, TN)
    accumulator (no [A | I] augmentation, so the working set is half the
    unrolled kernel's).  Per pivot step: normalized pivot row with the
    pivot slot replaced by 1/piv, rank-1 update, then column k is
    rewritten as -f/piv (the in-place bookkeeping for the identity
    columns the augmented form would carry).
    """
    a = a_ref[...]
    if scale_rows:
        # rows of A are pre-scaled by D = diag(inv_m): S = (D A)^-1
        # = A^-1 D^-1, so the COLUMNS are post-scaled below.  The same
        # scales are needed once as a column (b, 1, TN) and once laid
        # along the columns (1, b, TN); both come straight from the
        # reduction, with no transpose.
        inv_m = _row_scale(a)
        inv_cols = 1.0 / jnp.maximum(jnp.max(jnp.abs(a), axis=1),
                                     1e-30)[None]
        a = a * inv_m
    S = a
    rows = lax.broadcasted_iota(jnp.int32, (b, 1, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, b, 1), 1)
    for k in range(b):
        pivot_row = rows == k
        inv = 1.0 / S[k:k + 1, k:k + 1, :]              # (1, 1, TN)
        rowk = jnp.where(cols == k, inv, S[k:k + 1] * inv)   # (1, b, TN)
        f = jnp.where(pivot_row, 0.0, S[:, k:k + 1, :])      # (b, 1, TN)
        S = jnp.where(pivot_row, rowk, S - f * rowk)
        S = jnp.where(cols == k, jnp.where(pivot_row, inv, -f * inv), S)
    if scale_rows:
        S = S * inv_cols
    x_ref[...] = S


def block_inverse_soa(A: jnp.ndarray, *, batch_tile: int = 4 * LANE,
                      interpret=None,
                      scale_rows: bool = True) -> jnp.ndarray:
    """Invert every block: A:(b,b,NB) -> Ainv:(b,b,NB), NB % tile == 0
    (ops.py pads).  b <= UNROLL_MAX_B uses the unrolled [A | I] kernel
    (2*b*b*tile VMEM words); larger b the row-tiled IN-PLACE inversion
    (b*b*tile words) — ops.py additionally shrinks the tile with b^2 to
    hold a fixed VMEM budget."""
    b, b2, NB = A.shape
    assert b == b2
    assert NB % batch_tile == 0, (NB, batch_tile)
    grid = (NB // batch_tile,)
    kern = _gj_inverse_kernel if b <= UNROLL_MAX_B \
        else _gj_tiled_inverse_kernel
    kernel = functools.partial(kern, b=b, scale_rows=scale_rows)
    blocks = grid_block((b, b, batch_tile))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[blocks],
        out_specs=blocks,
        out_shape=jax.ShapeDtypeStruct((b, b, NB), A.dtype),
        interpret=resolve_interpret(interpret),
    )(A)


def block_solve_soa(A: jnp.ndarray, r: jnp.ndarray, *,
                    batch_tile: int = 4 * LANE, interpret=None,
                    scale_rows: bool = True) -> jnp.ndarray:
    """Solve with SoA layout A:(b,b,NB), r:(b,NB) -> x:(b,NB).

    NB must be a multiple of ``batch_tile`` (ops.py pads).  Each grid
    program owns a (b, b, batch_tile) VMEM tile: for b=8, tile=512 that
    is 8*8*512*4B = 128 KiB of A — comfortably inside ~16 MiB VMEM.
    b > UNROLL_MAX_B routes to the row-tiled kernel, whose (b, b+1,
    tile) working set (matrix plus right-hand side) ops.py keeps under
    GJ_VMEM_BYTES by shrinking the tile with b^2.
    """
    b, b2, NB = A.shape
    assert b == b2 and r.shape == (b, NB)
    assert NB % batch_tile == 0, (NB, batch_tile)
    grid = (NB // batch_tile,)
    tiled = b > UNROLL_MAX_B
    kern = _gj_tiled_kernel if tiled else _gj_kernel
    kernel = functools.partial(kern, b=b, scale_rows=scale_rows)
    # the row-tiled kernel takes r as (b, 1, NB), see _gj_tiled_kernel
    rshape = (b, 1) if tiled else (b,)
    rhs = grid_block(rshape + (batch_tile,))
    x = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[grid_block((b, b, batch_tile)), rhs],
        out_specs=rhs,
        out_shape=jax.ShapeDtypeStruct(rshape + (NB,), A.dtype),
        interpret=resolve_interpret(interpret),
    )(A, r.reshape(rshape + (NB,)))
    return x.reshape(b, NB)
