"""Pallas TPU kernel: block-diagonal matrix-vector product.

The paper's SUNMatrix_cuSparse provides a custom low-storage
block-diagonal SpMV.  TPU version in the SoA layout of block_solve.py:
A:(b,b,NB), x:(b,NB) -> y:(b,NB); the b^2 multiply-adds are unrolled and
every operation is a LANE-wide elementwise op — memory-bound streaming,
exactly one read of A and x per element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import LANE, grid_block, resolve_interpret


def _spmv_kernel(a_ref, x_ref, y_ref, *, b: int):
    for i in range(b):
        acc = a_ref[i, 0, :] * x_ref[0, :]
        for j in range(1, b):
            acc = acc + a_ref[i, j, :] * x_ref[j, :]
        y_ref[i, :] = acc


def blockdiag_spmv_soa(A: jnp.ndarray, x: jnp.ndarray, *,
                       batch_tile: int = 4 * LANE,
                       interpret=None) -> jnp.ndarray:
    b, b2, NB = A.shape
    assert b == b2 and x.shape == (b, NB)
    assert NB % batch_tile == 0
    grid = (NB // batch_tile,)
    kernel = functools.partial(_spmv_kernel, b=b)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[grid_block((b, b, batch_tile)),
                  grid_block((b, batch_tile))],
        out_specs=grid_block((b, batch_tile)),
        out_shape=jax.ShapeDtypeStruct((b, NB), A.dtype),
        interpret=resolve_interpret(interpret),
    )(A, x)
