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
* :func:`history_rescale` — the Lagrange history rebuild
  ``Z_new[j] = sum_i W[j,i] * Z[i]`` as a lane-parallel kernel that
  SHORT-CIRCUITS inactive systems: a bundle whose systems are all
  masked (finished, or unclipped steps with identity W) copies Z
  through instead of running the (QMAX+1)^2 multiply-add sweep, and
  inactive lanes inside a live bundle pass through unchanged;
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

import jax
import jax.numpy as jnp
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


def _history_rescale_kernel(w_ref, z_ref, a_ref, out_ref, *, q1: int):
    act = a_ref[...] > 0.5                   # (1, TN)

    @pl.when(jnp.max(a_ref[...]) > 0.5)
    def _():
        for j in range(q1):
            acc = w_ref[j, 0:1, :] * z_ref[0]
            for i in range(1, q1):
                acc = acc + w_ref[j, i:i + 1, :] * z_ref[i]
            out_ref[j] = jnp.where(act, acc, z_ref[j])

    @pl.when(jnp.max(a_ref[...]) <= 0.5)
    def _():
        out_ref[...] = z_ref[...]


def history_rescale(W: jnp.ndarray, Z: jnp.ndarray, active: jnp.ndarray,
                    *, batch_tile: int = 4 * LANE,
                    interpret=None) -> jnp.ndarray:
    """Lane-parallel Lagrange history rebuild with inactive short-circuit.

    W: (q1, q1, NB) per-system rescale matrices, Z: (q1, n, NB) history,
    active: (NB,) (nonzero = rescale) -> Z_new with
    Z_new[j,k,s] = sum_i W[j,i,s] * Z[i,k,s] where active, else Z[j,k,s].
    A bundle tile with NO active system skips the q1^2 multiply-add
    sweep entirely and copies Z through (the common case between step
    rejections and once most systems reach tf).
    """
    q1, q1b, NB = W.shape
    _, n, _ = Z.shape
    assert q1 == q1b and Z.shape == (q1, n, NB)
    assert active.shape == (NB,) and NB % batch_tile == 0
    kernel = functools.partial(_history_rescale_kernel, q1=q1)
    hist = grid_block((q1, n, batch_tile))
    return pl.pallas_call(
        kernel,
        grid=(NB // batch_tile,),
        in_specs=[grid_block((q1, q1, batch_tile)), hist,
                  grid_block((1, batch_tile))],
        out_specs=hist,
        out_shape=jax.ShapeDtypeStruct((q1, n, NB), Z.dtype),
        interpret=resolve_interpret(interpret),
    )(W, Z, _row(active))


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
