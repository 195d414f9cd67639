"""Pallas TPU kernels: sparse SpMV (CSR + ensemble shared-pattern BSR).

The paper pairs its GPU vectors with ``SUNMATRIX_CUSPARSE`` — a CSR
matrix plus a *low-storage block-diagonal / block-sparse* variant where
every block shares one sparsity pattern and the index arrays are stored
once.  The TPU adaptation keeps that shared-pattern idea and pushes it
further: because the pattern is shared across the whole ensemble it is
**static at trace time**, so the kernels below carry no index arrays at
all — the sparsity structure is compiled into the instruction stream
(the "symbolic offline-generated" elimination idea of the batched GJ
kernels, applied to SpMV):

* :func:`csr_spmv_ell` — scalar CSR SpMV in ELL form: rows ride the
  128-wide lane axis, the (static) max-row-length loop is unrolled, and
  each step is one gather + one fused multiply-add across lanes.
* :func:`bsr_spmv_soa` — ensemble block-sparse SpMV, SoA layout with
  the **system batch on the lane axis** (same convention as
  block_solve.py): values ``(nnzb, b, b, NB)``, x ``(nblk, b, NB)``.
  The block pattern (``brows``/``bcols``) is a static tuple, so the
  e-loop over nonzero blocks and the b^2 inner products are fully
  unrolled elementwise vector ops — no gather at all.

The per-block diagonal inverse (``bsr_block_jacobi_inverse_soa``) needs
no new kernel: ops.py statically gathers the diagonal blocks and reuses
the Gauss-Jordan inverse kernel from block_solve.py over the flattened
``nblk * NB`` batch.

``ref.py`` holds the pure-jnp oracles both kernels are parity-tested
against.  The BSR kernel compiles for TPU.  The CSR kernel's lane
gather (``jnp.take`` from a VMEM-resident x) does not: Mosaic lowers
only 2-D gathers, so :func:`csr_spmv_ell` runs in interpret mode only
and raises :class:`NotImplementedError` when asked to compile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import LANE, grid_block, resolve_interpret, whole_block


def _csr_ell_kernel(d_ref, c_ref, x_ref, y_ref, *, kmax: int):
    """ELL-form CSR SpMV tile: d/c are (kmax, TR) with rows on lanes,
    x is fully VMEM-resident, y is (TR,).  Padded slots carry d == 0
    (and col 0), so they contribute nothing."""
    xv = x_ref[:]
    acc = d_ref[0, :] * jnp.take(xv, c_ref[0, :], axis=0)
    for k in range(1, kmax):
        acc = acc + d_ref[k, :] * jnp.take(xv, c_ref[k, :], axis=0)
    y_ref[:] = acc


def csr_spmv_ell(data_ell: jnp.ndarray, cols_ell: jnp.ndarray,
                 x: jnp.ndarray, *, row_tile: int = 8 * LANE,
                 interpret=None) -> jnp.ndarray:
    """y = A @ x with A in lane-major ELL form.

    data_ell : (kmax, NR) — NR lane-padded row count, NR % row_tile == 0
    cols_ell : (kmax, NR) int32 column of each slot (0 where padded)
    x        : (NC,) the full input vector (stays resident per program)
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "Pallas op csr_spmv (kernel csr_spmv_ell) does not compile "
            "for TPU: its per-lane gather from the VMEM-resident x is "
            "not lowered by Mosaic ('Only 2D gather is supported').  "
            "Route this op to the jnp backend, e.g. "
            "policy.override(csr_spmv='jnp').")
    kmax, NR = data_ell.shape
    assert cols_ell.shape == (kmax, NR)
    assert NR % row_tile == 0, (NR, row_tile)
    (NC,) = x.shape
    grid = (NR // row_tile,)
    kernel = functools.partial(_csr_ell_kernel, kmax=kmax)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[grid_block((kmax, row_tile)),
                  grid_block((kmax, row_tile)),
                  whole_block((NC,))],
        out_specs=grid_block((row_tile,)),
        out_shape=jax.ShapeDtypeStruct((NR,), data_ell.dtype),
        interpret=resolve_interpret(interpret),
    )(data_ell, cols_ell, x)


def _bsr_spmv_kernel(v_ref, x_ref, y_ref, *, b: int, nblk: int,
                     brows: tuple, bcols: tuple):
    """Shared-pattern block-sparse SpMV, fully unrolled.

    v_ref: (nnzb, b, b, TN);  x_ref/y_ref: (nblk, b, TN).  The pattern
    (brows, bcols) is static, so every accumulation below is a plain
    lane-wide FMA — the TPU expression of storing the index arrays once
    for all ensemble members (here: zero times, they are compiled in).
    """
    acc = [[None] * b for _ in range(nblk)]
    for e, (bi, bj) in enumerate(zip(brows, bcols)):
        for i in range(b):
            contrib = v_ref[e, i, 0, :] * x_ref[bj, 0, :]
            for j in range(1, b):
                contrib = contrib + v_ref[e, i, j, :] * x_ref[bj, j, :]
            if acc[bi][i] is None:
                acc[bi][i] = contrib
            else:
                acc[bi][i] = acc[bi][i] + contrib
    zeros = jnp.zeros_like(x_ref[0, 0, :])
    for bi in range(nblk):
        for i in range(b):
            y_ref[bi, i, :] = zeros if acc[bi][i] is None else acc[bi][i]


def bsr_spmv_soa(values: jnp.ndarray, x: jnp.ndarray, *, brows: tuple,
                 bcols: tuple, nblk: int, batch_tile: int = 4 * LANE,
                 interpret=None) -> jnp.ndarray:
    """y_I = sum_{e: brows[e]=I} A_e @ x_{bcols[e]} for every ensemble
    member: values (nnzb, b, b, NB), x (nblk, b, NB) -> y (nblk, b, NB).
    NB % batch_tile == 0 (ops.py pads; zero-padded systems yield zeros).
    """
    nnzb, b, b2, NB = values.shape
    assert b == b2 and x.shape == (nblk, b, NB)
    assert len(brows) == len(bcols) == nnzb
    assert NB % batch_tile == 0, (NB, batch_tile)
    grid = (NB // batch_tile,)
    kernel = functools.partial(_bsr_spmv_kernel, b=b, nblk=nblk,
                               brows=tuple(brows), bcols=tuple(bcols))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[grid_block((nnzb, b, b, batch_tile)),
                  grid_block((nblk, b, batch_tile))],
        out_specs=grid_block((nblk, b, batch_tile)),
        out_shape=jax.ShapeDtypeStruct((nblk, b, NB), values.dtype),
        interpret=resolve_interpret(interpret),
    )(values, x)
