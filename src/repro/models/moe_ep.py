"""Expert-parallel MoE via shard_map + all_to_all (the production path).

Mapping of the paper's design to MoE (DESIGN.md §5): expert FFNs are the
"submodel" pattern — many independent small systems batched for device
saturation, with a block-diagonal structure (Fig. 1: each expert's weights
are one block).  The dispatch/combine is the MPIPlusX contract taken to
its limit: local routing decisions + exactly two collectives (all_to_all
out and back) over the 'model' mesh axis.

Two token layouts:
* ``split``      — tokens are partitioned over the EP axis too (sequence
  split inside the MoE block).  Dispatch = all_to_all. Used for
  train/prefill shapes.
* ``replicated`` — tokens replicated over the EP axis (decode: too few
  tokens to split).  Each shard computes only items routed to ITS local
  experts; the combine is one psum.  No all_to_all.

Both paths use capacity buffers with drop (standard GShard/Switch
semantics; cf = cfg.moe_cap_factor) and are validated against the dense
oracle ``moe_dense_apply`` in tests (tokens under capacity -> exact).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .config import ArchConfig
from . import layers



def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _scatter_to_buffer(values, dest, pos, nbuckets, cap):
    """Scatter values (N, ...) into (nbuckets, cap, ...) at [dest, pos],
    dropping items with pos >= cap.  Collision-free by construction
    (pos is a rank within its bucket)."""
    valid = pos < cap
    d = jnp.where(valid, dest, 0)
    s = jnp.where(valid, pos, 0)
    buf = jnp.zeros((nbuckets, cap) + values.shape[1:], values.dtype)
    vmask = valid.reshape((-1,) + (1,) * (values.ndim - 1))
    return buf.at[d, s].add(values * vmask)


def _rank_in_bucket(dest: jnp.ndarray, nbuckets: int) -> jnp.ndarray:
    """pos[i] = number of j<i with dest[j]==dest[i]  (cumsum of one-hot)."""
    onehot = jax.nn.one_hot(dest, nbuckets, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - 1
    return jnp.take_along_axis(ranks, dest[:, None], axis=1)[:, 0]


def _expert_ffn(xe, w1, w3, w2):
    """xe: (E_loc, C, d); w*: (E_loc, d, f)/(E_loc, f, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1)) * \
        jnp.einsum("ecd,edf->ecf", xe, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _moe_local(cfg: ArchConfig, ep: int, cap: int, cap_e: int,
               x_loc, router, w1, w3, w2, *, axis_name: str,
               replicated_tokens: bool):
    """Per-device MoE body (runs inside shard_map).

    x_loc: (T_loc, d) local tokens; w*: (E_loc, ...) local experts.
    """
    T, d = x_loc.shape
    E_loc = w1.shape[0]
    k = cfg.experts_per_tok
    my_shard = lax.axis_index(axis_name)

    logits = jnp.einsum("td,de->te", x_loc.astype(jnp.float32), router)
    wgt, ids = layers.router_topk(logits, k, cfg.router_impl)  # (T,k)

    # flatten routed items
    item_tok = jnp.repeat(jnp.arange(T), k)              # (N,)
    item_eid = ids.reshape(-1)                            # global expert id
    item_w = wgt.reshape(-1)

    if replicated_tokens:
        # keep only items owned by my shard; combine with psum at the end
        mine = (item_eid // E_loc) == my_shard
        eloc = jnp.where(mine, item_eid % E_loc, 0)
        pos = _rank_in_bucket(jnp.where(mine, eloc, E_loc), E_loc + 1)
        pos = jnp.where(mine, pos, cap_e)                 # drop foreign items
        xe = _scatter_to_buffer(x_loc[item_tok], eloc, pos, E_loc, cap_e)
        ye = _expert_ffn(xe, w1, w3, w2)                  # (E_loc, cap_e, d)
        got = ye[jnp.where(pos < cap_e, eloc, 0),
                 jnp.where(pos < cap_e, pos, 0)]          # (N, d)
        got = got * ((pos < cap_e) & mine)[:, None]
        out = jnp.zeros((T, d), jnp.float32).at[item_tok].add(
            got.astype(jnp.float32) * item_w[:, None])
        out = lax.psum(out, axis_name)
        return out.astype(x_loc.dtype)

    # ---- split tokens: all_to_all dispatch ----
    dest = item_eid // E_loc                              # destination shard
    pos = _rank_in_bucket(dest, ep)                       # rank within dest
    x_send = _scatter_to_buffer(x_loc[item_tok], dest, pos, ep, cap)
    eid_send = _scatter_to_buffer(item_eid[:, None] + 1, dest, pos, ep,
                                  cap)[..., 0]            # 0 = invalid
    # fp8 dispatch (DeepSeek-V3-style): quantize the OUT leg of the
    # all_to_all to e4m3 — halves dispatch ICI traffic; the combine leg
    # (expert outputs) stays bf16 for quality.  §Perf 'dsv3-fp8-dispatch'.
    import os as _os
    fp8 = _os.environ.get("REPRO_MOE_FP8", "0") == "1"
    if fp8:
        x_recv = lax.all_to_all(x_send.astype(jnp.float8_e4m3fn),
                                axis_name, 0, 0,
                                tiled=False).astype(x_loc.dtype)
    else:
        x_recv = lax.all_to_all(x_send, axis_name, 0, 0, tiled=False)
    eid_recv = lax.all_to_all(eid_send, axis_name, 0, 0, tiled=False)
    R = ep * cap
    xr = x_recv.reshape(R, d)
    er = eid_recv.reshape(R)
    rvalid = er > 0
    eloc = jnp.where(rvalid, (er - 1) % E_loc, 0)
    pos2 = _rank_in_bucket(jnp.where(rvalid, eloc, E_loc), E_loc + 1)
    pos2 = jnp.where(rvalid, pos2, cap_e)
    xe = _scatter_to_buffer(xr, eloc, pos2, E_loc, cap_e)
    ye = _expert_ffn(xe, w1, w3, w2)                      # (E_loc, cap_e, d)
    yr = ye[jnp.where(pos2 < cap_e, eloc, 0),
            jnp.where(pos2 < cap_e, pos2, 0)]
    yr = yr * ((pos2 < cap_e) & rvalid)[:, None]
    y_back = lax.all_to_all(yr.reshape(ep, cap, d), axis_name, 0, 0,
                            tiled=False)                  # (ep, cap, d)
    # item i finds its result at y_back[dest_i, pos_i] (if not dropped)
    got = y_back[jnp.where(pos < cap, dest, 0),
                 jnp.where(pos < cap, pos, 0)]
    got = got * (pos < cap)[:, None]
    out = jnp.zeros((T, d), jnp.float32).at[item_tok].add(
        got.astype(jnp.float32) * item_w[:, None])
    return out.astype(x_loc.dtype)


def moe_ep_apply(p: Dict, cfg: ArchConfig, x: jnp.ndarray, mesh, *,
                 dp_axes: Tuple[str, ...] = ("data",),
                 ep_axis="model",
                 cst: Callable = layers._id_cst,
                 token_layout: str = "split") -> jnp.ndarray:
    """Expert-parallel MoE layer.  x: (B, S, d) global array under jit.

    ``ep_axis`` may be one mesh axis ('model') or a TUPLE — e.g.
    ('model','data') gives 256-way EP on the 16x16 pod where every chip
    *owns* its experts outright (E_loc = E/256): expert weights never
    move (no FSDP all-gather), only tokens do (two all_to_alls).  This is
    the weights-stationary layout (§Perf iteration 'dsv3-ep256').

    Token layouts:
    * 'split'      — train/prefill: tokens partitioned over dp_axes
                     (batch) and 'model' (sequence).
    * 'replicated' — decode: sequence length 1 cannot split over 'model'.
      Single-axis EP uses the psum-combine path; multi-axis EP reuses the
      all_to_all path with tokens replicated over 'model' (each model
      replica dispatches its copy — duplicated expert compute, negligible
      at decode token counts, and zero weight movement).
    """
    B, S, d = x.shape
    ep_axes = (ep_axis,) if isinstance(ep_axis, str) else tuple(ep_axis)
    ep = 1
    for a in ep_axes:
        ep *= mesh.shape[a]
    E = cfg.n_experts
    assert E % ep == 0, (E, ep)
    E_loc = E // ep
    k = cfg.experts_per_tok
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]

    multi_axis = len(ep_axes) > 1
    if token_layout == "split":
        assert S % mesh.shape["model"] == 0 and B % dp == 0, (B, S, dp)
        T_loc = (B // dp) * (S // mesh.shape["model"])
        x_spec = P(dp_axes, "model", None)
        use_a2a = True
        dup = 1
    else:
        assert B % dp == 0
        T_loc = (B // dp) * S
        x_spec = P(dp_axes, None, None)
        use_a2a = multi_axis          # single-axis: psum-combine path
        dup = mesh.shape["model"] if multi_axis else 1

    n_items = T_loc * k
    cap = _round_up(max(int(n_items / ep * cfg.moe_cap_factor * dup), 8), 8)
    cap_e = _round_up(max(int(n_items / max(E_loc, 1) *
                              cfg.moe_cap_factor), 8), 8) \
        if not use_a2a else \
        _round_up(max(int(ep * cap / max(E_loc, 1) * 1.25), 8), 8)

    coll_axes = ep_axes if use_a2a else ep_axes[0]
    local = functools.partial(
        _moe_local, cfg, ep, cap, cap_e, axis_name=coll_axes,
        replicated_tokens=not use_a2a)

    def body(x_l, router, w1, w3, w2):
        Bl, Sl, _ = x_l.shape
        out = local(x_l.reshape(Bl * Sl, d), router, w1, w3, w2)
        return out.reshape(Bl, Sl, d)

    w_spec = P(ep_axes if multi_axis else ep_axes[0], None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=x_spec, check_vma=False,
    )
    out = fn(x, p["router"], p["w1"], p["w3"], p["w2"])
    if "shared" in p:
        out = out + layers.swiglu_apply(p["shared"], x, cst=cst)
    return cst(out, ("batch", "seq", "embed"))
