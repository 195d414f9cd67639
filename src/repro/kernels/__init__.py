"""Pallas TPU kernels for the hot spots the paper fuses by hand.

Kernel bodies live in one module per family (``vecops``, ``newton``,
``block_solve``, ``blockdiag_spmv``, ``sparse``), their jit'd padding
wrappers in ``ops`` and the pure-jnp oracles in ``ref``.  The helpers
below are shared by every ``pallas_call`` in the package.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret mode as given, or derived from where the program
    runs when ``None``: compiled to Mosaic on a TPU, interpreted on any
    other backend (the CPU has no Mosaic lowering)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def grid_block(block_shape, axis: int = -1) -> pl.BlockSpec:
    """BlockSpec for a 1-D grid that steps along ``axis`` of the array
    and holds every other axis whole.

    The whole-axis block indices are int32 zeros: a Python ``0`` traces
    to int64 under ``jax_enable_x64``, and Mosaic then fails to legalize
    the index map (``failed to legalize operation 'func.return'``)."""
    nd = len(block_shape)
    axis = axis % nd

    def index_map(g):
        zero = jnp.int32(0)
        return tuple(g if d == axis else zero for d in range(nd))

    return pl.BlockSpec(tuple(block_shape), index_map)


def whole_block(shape) -> pl.BlockSpec:
    """BlockSpec that hands every grid program the whole (small) array,
    with int32 block indices for the same reason as :func:`grid_block`."""
    nd = len(shape)
    return pl.BlockSpec(tuple(shape),
                        lambda g: (jnp.int32(0),) * nd)


def as_rows(x: jnp.ndarray) -> jnp.ndarray:
    """View the lane-padded last axis as ``(rows, LANE)`` — the 2-D form
    Mosaic tiles, and the same bytes as XLA's 1-D tiled layout."""
    return x.reshape(x.shape[:-1] + (x.shape[-1] // LANE, LANE))
