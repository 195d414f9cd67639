"""Pallas TPU kernels for the ensemble Newton hot loop (SoA layout).

The batched-BDF corrector runs these three ops on every Newton
iteration / step over ``(n, NB)`` state arrays with the system batch on
the 128-wide lane axis (the repo's SoA-everywhere convention, nsys
LAST).  Unfused, each costs one HBM pass per constituent op; fused,
each is exactly one pass:

* :func:`newton_residual` — ``g = z - gamma*f - psi`` (three streaming
  operands, one output; ``negate=True`` emits the Newton right-hand
  side ``-g`` directly, folding the sign flip into the same pass);
* :func:`masked_update_wrms` — the masked iterate update
  ``z += dz (where mask)`` FUSED with the per-system WRMS of ``dz``:
  the correction is read once from HBM instead of once for the update
  and once for the convergence-rate reduction;
* :func:`lagrange_rescale` — the Lagrange history rebuild
  ``Z_new[j] = sum_i W[j,i] * Z[i]`` from each lane's step ratio and
  valid depth: the weights are made in the kernel, so one pass reads
  and writes Z and nothing of W touches HBM; lanes that keep their
  step (inactive, or eta == 1) pass through unchanged, and a bundle
  with no other lane copies Z through instead of running the
  (QMAX+1)^2 multiply-add sweep;
* :func:`wrms_soa` — the per-system WRMS reduction ``(n, NB) -> (NB,)``
  (the batched row of the N_VWrmsNorm family; the BDF error test and
  the DIRK residual checks go through it).

Like the block kernels, the n (state) axis rides the sublanes and is
small/static; ``ops.py`` pads the batch axis to the bundle tile.
Per-system scalars (gamma, the masks, the WRMS outputs) travel as
``(1, NB)`` rows: a rank-1 ``(NB,)`` operand gets XLA's 1-D tiled
layout, which Mosaic refuses unless the bundle tile happens to match
it.
"""
from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from . import LANE, grid_block, resolve_interpret


def _row(v: jnp.ndarray) -> jnp.ndarray:
    """(NB,) per-system scalars -> the (1, NB) row the kernels take."""
    return v.reshape(1, v.shape[0])


def _newton_residual_kernel(z_ref, f_ref, psi_ref, gam_ref, out_ref, *,
                            negate: bool):
    g = z_ref[...] - gam_ref[...] * f_ref[...] - psi_ref[...]
    out_ref[...] = -g if negate else g


def newton_residual(z: jnp.ndarray, fval: jnp.ndarray, psi: jnp.ndarray,
                    gamma: jnp.ndarray, *, batch_tile: int = 4 * LANE,
                    interpret=None,
                    negate: bool = False) -> jnp.ndarray:
    """Fused g = z - gamma*f - psi; all of z/f/psi are (n, NB), gamma is
    (NB,).  ``negate=True`` returns -g (the Newton rhs) in the same
    pass."""
    n, NB = z.shape
    assert fval.shape == (n, NB) and psi.shape == (n, NB)
    assert gamma.shape == (NB,) and NB % batch_tile == 0
    kernel = functools.partial(_newton_residual_kernel, negate=negate)
    state = grid_block((n, batch_tile))
    return pl.pallas_call(
        kernel,
        grid=(NB // batch_tile,),
        in_specs=[state, state, state, grid_block((1, batch_tile))],
        out_specs=state,
        out_shape=jax.ShapeDtypeStruct((n, NB), z.dtype),
        interpret=resolve_interpret(interpret),
    )(z, fval, psi, _row(gamma))


def _masked_update_wrms_kernel(z_ref, dz_ref, w_ref, m_ref, zout_ref,
                               dn_ref, *, n: int):
    m = m_ref[...] > 0.5                     # (1, TN) float mask
    dz = dz_ref[...]
    zout_ref[...] = jnp.where(m, z_ref[...] + dz, z_ref[...])
    t = dz * w_ref[...]
    dn_ref[...] = jnp.sqrt(jnp.sum(t * t, axis=0, keepdims=True) / n)


def masked_update_wrms(z: jnp.ndarray, dz: jnp.ndarray, w: jnp.ndarray,
                       mask: jnp.ndarray, *, batch_tile: int = 4 * LANE,
                       interpret=None):
    """Fused masked iterate update + per-system WRMS of the correction.

    z/dz/w: (n, NB), mask: (NB,) (nonzero = update) ->
    ``(z_new, dn)`` with z_new = where(mask, z+dz, z) and
    dn[s] = sqrt(mean_k (dz[k,s]*w[k,s])^2).  The norm is over ALL
    systems (masked systems still report their dn; the caller decides
    what to keep), matching the unfused update-then-wrms pair.
    """
    n, NB = z.shape
    assert dz.shape == (n, NB) and w.shape == (n, NB)
    assert mask.shape == (NB,) and NB % batch_tile == 0
    kernel = functools.partial(_masked_update_wrms_kernel, n=n)
    state = grid_block((n, batch_tile))
    lanes = grid_block((1, batch_tile))
    z_new, dn = pl.pallas_call(
        kernel,
        grid=(NB // batch_tile,),
        in_specs=[state, state, state, lanes],
        out_specs=[state, lanes],
        out_shape=[
            jax.ShapeDtypeStruct((n, NB), z.dtype),
            jax.ShapeDtypeStruct((1, NB), z.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(z, dz, w, _row(mask))
    return z_new, dn.reshape(NB)


def reciprocal_denominators(q1: int, dtype) -> list:
    """``r[q][i]``: one over the Lagrange denominator of old node ``i``
    at depth ``q``, ``prod_{k != i, k <= q} (k - i) = (-1)^i i! (q-i)!``,
    rounded once to ``dtype``; 0 for columns ``i > q``.

    Each denominator is an integer of at most 120, exact in ``dtype``,
    and ``den * r`` rounds to exactly 1: a weight whose numerator
    product equals its denominator (the diagonal at eta = 1, the
    ``(0, 0)`` weight at any eta) comes out exactly 1."""
    import numpy as np
    one = np.ones((), dtype)
    return [[float(one / np.asarray((-1) ** i * math.factorial(i) *
                                    math.factorial(q - i), dtype))
             if i <= q else 0.0 for i in range(q1)] for q in range(q1)]


def _lagrange_rescale_kernel(eta_ref, q_ref, z_ref, out_ref, *, rden):
    """Z_new[j] = sum_i W[j,i] Z[i] with each lane's weights made here.

    The new node index j rides the sublanes, so every weight column
    ``W[:, i]`` is one (q1, TN) value:
    ``W[j,i] = prod_{k != i, k <= q} (k - j*eta) * r[q][i]``, with rows
    ``j > q`` the identity.  Lanes at eta == 1 (unchanged step, or
    inactive: the wrapper sends them at eta 1) copy Z through, and a
    tile with no other lane skips the arithmetic."""
    q1, _, tn = z_ref.shape
    eta = eta_ref[...]                        # (1, TN)
    live = eta != 1.0
    any_live = jnp.max(live.astype(jnp.float32))

    @pl.when(any_live > 0.5)
    def _():
        q = q_ref[...]                        # (1, TN), the valid depth
        j = lax.broadcasted_iota(jnp.int32, (q1, tn), 0).astype(eta.dtype)
        p = j * eta                           # new nodes sit at -j*eta
        # the numerator's factors, 1 beyond the valid depth
        f = [jnp.where(q >= k, k - p, 1.0) for k in range(q1)]
        z = [z_ref[i] for i in range(q1)]
        cols = []
        for i in range(q1):
            num = functools.reduce(operator.mul,
                                   [f[k] for k in range(q1) if k != i])
            r = jnp.zeros_like(eta)
            for qv in range(i, q1):
                r = jnp.where(q == qv, rden[qv][i], r)
            cols.append(jnp.where(j > q, (j == i).astype(eta.dtype),
                                  num * r))
        for jj in range(q1):
            acc = cols[0][jj:jj + 1] * z[0]
            for i in range(1, q1):
                acc = acc + cols[i][jj:jj + 1] * z[i]
            out_ref[jj] = jnp.where(live, acc, z[jj])

    @pl.when(any_live <= 0.5)
    def _():
        out_ref[...] = z_ref[...]


def lagrange_rescale(eta: jnp.ndarray, q: jnp.ndarray, Z: jnp.ndarray,
                     *, batch_tile: int = 4 * LANE,
                     interpret=None) -> jnp.ndarray:
    """Lane-parallel Lagrange history rebuild from per-lane (eta, q).

    eta: (NB,) step ratio (1 where a lane is not to change), q: (NB,)
    valid history depth (as Z's dtype), Z: (q1, n, NB) history ->
    Z_new[j,k,s] = sum_i W(eta_s, q_s)[j,i] * Z[i,k,s], where W is the
    Lagrange matrix that moves the old nodes -i onto the new nodes
    -j*eta (the weights are made in the kernel: no (q1, q1, NB) weight
    tensor is read).  Lanes at eta == 1 return Z bit-exactly; a bundle
    tile with no other lane skips the weights and the multiply-adds.
    """
    q1, n, NB = Z.shape
    assert eta.shape == (NB,) and q.shape == (NB,)
    assert NB % batch_tile == 0
    kernel = functools.partial(
        _lagrange_rescale_kernel,
        rden=reciprocal_denominators(q1, Z.dtype))
    hist = grid_block((q1, n, batch_tile))
    lanes = grid_block((1, batch_tile))
    return pl.pallas_call(
        kernel,
        grid=(NB // batch_tile,),
        in_specs=[lanes, lanes, hist],
        out_specs=hist,
        out_shape=jax.ShapeDtypeStruct((q1, n, NB), Z.dtype),
        interpret=resolve_interpret(interpret),
    )(_row(eta), _row(q), Z)


def _wrms_soa_kernel(v_ref, w_ref, out_ref, *, n: int):
    t = v_ref[...] * w_ref[...]
    out_ref[...] = jnp.sqrt(jnp.sum(t * t, axis=0, keepdims=True) / n)


def wrms_soa(v: jnp.ndarray, w: jnp.ndarray, *,
             batch_tile: int = 4 * LANE,
             interpret=None) -> jnp.ndarray:
    """Per-system WRMS: v/w (n, NB) -> (NB,), one fused pass (the
    sublane reduction stays inside the tile, so no partials)."""
    n, NB = v.shape
    assert w.shape == (n, NB) and NB % batch_tile == 0
    kernel = functools.partial(_wrms_soa_kernel, n=n)
    state = grid_block((n, batch_tile))
    out = pl.pallas_call(
        kernel,
        grid=(NB // batch_tile,),
        in_specs=[state, state],
        out_specs=grid_block((1, batch_tile)),
        out_shape=jax.ShapeDtypeStruct((1, NB), v.dtype),
        interpret=resolve_interpret(interpret),
    )(v, w)
    return out.reshape(NB)
