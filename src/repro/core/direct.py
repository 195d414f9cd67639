"""Batched block-diagonal direct linear solver.

The SUNLinearSolver_cuSolverSp_batchQR analog: solves n independent
small systems A_j x_j = b_j in one batched call.  The factorization
structure is shared across blocks (the paper's shared-sparsity /
shared-QR-pattern point); on TPU we express that as one vectorized
elimination whose control flow is identical for every block (DESIGN.md
§2 — symbolic Gauss-Jordan ≙ unrolled vectorized GJ).

Two backends, selected by ExecPolicy:
* 'jnp'    — jnp.linalg LU solve (XLA batched) or our vectorized GJ;
* 'pallas' — repro.kernels.block_solve (VMEM-tiled, lane-major layout).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .matrix import BlockDiagMatrix
from .policies import ExecPolicy, XLA_FUSED


class DirectStats(NamedTuple):
    nblocks: int
    block_size: int


def gauss_jordan_batched(A: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Vectorized Gauss-Jordan with partial pivoting over a block batch.

    A: (nb, n, n), b: (nb, n) -> x: (nb, n).  The elimination sequence is
    identical across blocks (shared structure); pivoting is a vectorized
    row swap per block.  Unrolled over n (n is small and static).
    """
    nb, n, _ = A.shape
    # augmented system
    M = jnp.concatenate([A, b[:, :, None]], axis=2)  # (nb, n, n+1)
    for k in range(n):
        # partial pivot: pick argmax |M[:, k:, k]| per block
        piv_rel = jnp.argmax(jnp.abs(M[:, k:, k]), axis=1)        # (nb,)
        piv = piv_rel + k
        rows = jnp.arange(n)[None, :]                             # (1, n)
        batch = jnp.arange(nb)
        # swap rows k and piv (vectorized gather-based permutation)
        perm = jnp.where(rows == k, piv[:, None],
                         jnp.where(rows == piv[:, None], k, rows))  # (nb, n)
        M = M[batch[:, None], perm, :]
        # eliminate column k from all other rows
        pivval = M[:, k, k]                                       # (nb,)
        pivrow = M[:, k, :] / pivval[:, None]                     # (nb, n+1)
        factors = M[:, :, k]                                      # (nb, n)
        M = M - factors[:, :, None] * pivrow[:, None, :]
        M = M.at[:, k, :].set(pivrow)
    return M[:, :, n]


def block_solve(A: BlockDiagMatrix, b: jnp.ndarray,
                policy: ExecPolicy = XLA_FUSED) -> jnp.ndarray:
    """Solve the block-diagonal system; b flat (nb*bs,) or (nb, bs)."""
    nb, bs = A.nblocks, A.block_size
    data = A.data if A.mask is None else A.data * A.mask[None]
    bb = b.reshape(nb, bs)
    if policy.backend == "pallas":
        from repro.kernels import ops as kops
        xb = kops.block_solve(data, bb, batch_tile=policy.batch_tile,
                              interpret=policy.interpreted())
    else:
        xb = gauss_jordan_batched(data, bb)
    return xb.reshape(b.shape)


def block_lu_factor(A: BlockDiagMatrix):
    """Factor once / solve many (SUNLinSolSetup / SUNLinSolSolve split)."""
    data = A.data if A.mask is None else A.data * A.mask[None]
    lu, piv = jax.vmap(jax.scipy.linalg.lu_factor)(data)
    return lu, piv


def block_lu_solve(factors, b: jnp.ndarray, block_size: int) -> jnp.ndarray:
    lu, piv = factors
    nb = lu.shape[0]
    bb = b.reshape(nb, block_size)
    xb = jax.vmap(lambda l, p, r: jax.scipy.linalg.lu_solve((l, p), r))(lu, piv, bb)
    return xb.reshape(b.shape)
