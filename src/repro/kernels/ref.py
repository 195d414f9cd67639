"""Pure-jnp oracles for every Pallas kernel (the ref.py contract).

Each function must be the semantic ground truth the kernels are tested
against with assert_allclose over shape/dtype sweeps.

Every contraction asks for ``HIGHEST`` precision: at the default, a TPU
rounds float32 matmul operands to bfloat16, which leaves too few digits
for a Newton iteration on stiff blocks (every lane of a float32
Robertson ensemble then fails to converge).  The CPU ignores the flag.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def block_solve_ref(A: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """Batched solve, AoS layout A:(nb,b,b), r:(nb,b) -> (nb,b)."""
    return jnp.linalg.solve(A, r[..., None])[..., 0]


def block_solve_soa_ref(A: jnp.ndarray, r: jnp.ndarray) -> jnp.ndarray:
    """SoA layout A:(b,b,NB), r:(b,NB) -> x:(b,NB)."""
    Aaos = jnp.transpose(A, (2, 0, 1))
    raos = jnp.transpose(r, (1, 0))
    x = jnp.linalg.solve(Aaos, raos[..., None])[..., 0]
    return jnp.transpose(x, (1, 0))


def linear_combination_ref(coeffs: jnp.ndarray, X: jnp.ndarray) -> jnp.ndarray:
    """Z = sum_k c_k X[k];  X:(K,N), coeffs:(K,) -> (N,)."""
    return jnp.einsum("k,kn->n", coeffs, X, precision=HIGHEST)


def scale_add_multi_ref(coeffs: jnp.ndarray, x: jnp.ndarray,
                        Y: jnp.ndarray) -> jnp.ndarray:
    """Z[k] = c_k x + Y[k];  x:(N,), Y:(K,N) -> (K,N)."""
    return coeffs[:, None] * x[None, :] + Y


def wrms_partial_ref(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """sum((x*w)^2) over the whole array -> scalar."""
    return jnp.sum((x * w) ** 2)


def wrms_mask_partial_ref(x: jnp.ndarray, w: jnp.ndarray,
                          m: jnp.ndarray) -> jnp.ndarray:
    """sum((x*w*m)^2) over the whole array -> scalar."""
    return jnp.sum((x * w * m) ** 2)


def dot_prod_multi_ref(x: jnp.ndarray, Y: jnp.ndarray) -> jnp.ndarray:
    """d_k = <x, Y[k]>;  x:(N,), Y:(K,N) -> (K,)."""
    return jnp.matmul(Y, x, precision=HIGHEST)


def dot_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return jnp.vdot(x, y, precision=HIGHEST)


def blockdiag_spmv_soa_ref(A: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """y = blockdiag(A) @ x in SoA; A:(b,b,NB), x:(b,NB) -> y:(b,NB)."""
    return jnp.einsum("ijn,jn->in", A, x, precision=HIGHEST)


def block_inverse_soa_ref(A: jnp.ndarray) -> jnp.ndarray:
    """Per-block inverse in SoA; A:(b,b,NB) -> A^{-1}:(b,b,NB)."""
    Ainv = jnp.linalg.inv(jnp.transpose(A, (2, 0, 1)))
    return jnp.transpose(Ainv, (1, 2, 0))


def newton_residual_soa_ref(z: jnp.ndarray, fval: jnp.ndarray,
                            psi: jnp.ndarray, gamma: jnp.ndarray,
                            negate: bool = False) -> jnp.ndarray:
    """g = z - gamma*f - psi in SoA; z/f/psi (n, NB), gamma (NB,).
    ``negate=True`` returns -g (the Newton rhs); the sign flip is
    applied to the computed g so both variants round identically."""
    g = z - gamma[None, :] * fval - psi
    return -g if negate else g


def masked_update_wrms_soa_ref(z: jnp.ndarray, dz: jnp.ndarray,
                               w: jnp.ndarray, mask: jnp.ndarray):
    """(z_new, dn): z_new = where(mask, z+dz, z); dn = per-system WRMS
    of dz (over ALL systems, masked or not); SoA (n, NB) / mask (NB,)."""
    z_new = jnp.where(mask[None, :] != 0, z + dz, z)
    t = dz * w
    return z_new, jnp.sqrt(jnp.mean(t * t, axis=0))


def history_rescale_soa_ref(W: jnp.ndarray, Z: jnp.ndarray,
                            active: jnp.ndarray) -> jnp.ndarray:
    """Z_new[j,k,s] = sum_i W[j,i,s] Z[i,k,s] where active[s], else
    Z[j,k,s];  W (q1,q1,NB), Z (q1,n,NB), active (NB,).

    The contraction is evaluated as the AoS einsum on transposed views
    (exact layout changes XLA folds into the contraction) so the jnp
    backend reproduces the pre-SoA integrator's accumulation order
    bitwise — a reformulated sum reassociates and breaks the
    bitwise-trajectory pin (tests/test_soa_carry.py).
    """
    Waos = jnp.transpose(W, (2, 0, 1))
    Zaos = jnp.transpose(Z, (2, 0, 1))
    R = jnp.transpose(jnp.einsum("sji,sik->sjk", Waos, Zaos,
                               precision=HIGHEST), (1, 2, 0))
    return jnp.where(active[None, None, :] != 0, R, Z)


def wrms_soa_ref(v: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Per-system WRMS over the state axis: v/w (n, NB) -> (NB,)."""
    t = v * w
    return jnp.sqrt(jnp.mean(t * t, axis=0))


def csr_spmv_ref(data: jnp.ndarray, x: jnp.ndarray, indptr,
                 indices) -> jnp.ndarray:
    """y = A @ x for CSR A with static (indptr, indices); data:(nnz,)."""
    import numpy as np
    ip = np.asarray(indptr)
    n = len(ip) - 1
    seg = jnp.asarray(np.repeat(np.arange(n), np.diff(ip)))
    cols = jnp.asarray(np.asarray(indices, np.int32))
    return jax.ops.segment_sum(data * x[cols], seg, num_segments=n)


def bsr_spmv_soa_ref(values: jnp.ndarray, x: jnp.ndarray, brows, bcols,
                     nblk: int) -> jnp.ndarray:
    """Shared-pattern ensemble BSR SpMV oracle: values (nnzb, b, b, NB),
    x (nblk, b, NB) -> y (nblk, b, NB)."""
    bc = jnp.asarray(bcols)
    contrib = jnp.einsum("eijn,ejn->ein", values, x[bc],
                         precision=HIGHEST)
    return jax.ops.segment_sum(contrib, jnp.asarray(brows),
                               num_segments=nblk)


def bsr_diag_inverse_soa_ref(values: jnp.ndarray, brows, bcols,
                             nblk: int) -> jnp.ndarray:
    """Inverse of every diagonal block of the shared pattern:
    values (nnzb, b, b, NB) -> (b, b, nblk*NB), block (I, sys) ordered
    with the block index major (matches the op's flattened SoA batch)."""
    diag_idx = []
    for I in range(nblk):
        hits = [e for e, (i, j) in enumerate(zip(brows, bcols))
                if i == I and j == I]
        assert hits, f"pattern lacks diagonal block ({I},{I})"
        diag_idx.append(hits[0])
    D = values[jnp.asarray(diag_idx)]                # (nblk, b, b, NB)
    Dinv = jnp.linalg.inv(jnp.transpose(D, (0, 3, 1, 2)))
    b = values.shape[1]
    return jnp.transpose(Dinv, (2, 3, 0, 1)).reshape(b, b, -1)
