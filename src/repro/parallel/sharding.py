"""Logical-axis sharding rules -> NamedSharding (the distribution core).

Params and activations carry *logical* axis names (models/spec.py); two
rule tables map them onto mesh axes:

* PARAM_RULES — FSDP over ('pod','data') on a non-TP dim + tensor/expert
  parallelism over 'model'.  Every large matrix is sharded on two dims.
* ACT_RULES   — batch over ('pod','data'), heads/mlp/vocab over 'model'.

``spec_for`` degrades gracefully: a dim that is not divisible by its mesh
axes, or whose mesh axis is already used by an earlier dim, falls back to
replication — this is what lets tiny smoke configs, odd head counts
(e.g. 36 heads on a 16-way model axis -> replicated) and batch=1 decode
shapes lower on any mesh without per-arch special-casing.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalAxes = Tuple[Optional[str], ...]

PARAM_RULES: Dict[str, Tuple[str, ...]] = {
    "embed": ("pod", "data"),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    # experts are OWNED per chip when E divides model*data (weights-
    # stationary EP — §Perf 'dsv3-ep256'); spec_for shrinks to ('model',)
    # when it does not divide (e.g. dbrx's 16 experts).
    "experts": ("model", "data"),
    "expert_mlp": None,
    "q_lora": ("pod", "data"),
    "kv_lora": ("pod", "data"),
    "head_dim": None,
    "heads_x": ("model",),
    "embed_out": None,
    "layers": None,
}

ACT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": None,
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
    "embed": None,
    "head_dim": None,
    "experts": ("model",),
    "layers": None,
}

# Cache-only rules (decode path): head_dim takes 'model' when
# heads/kv_heads could not (axis uniqueness) — this shards the GQA KV
# cache for archs whose kv-head count doesn't divide the model axis
# (kv=8 on a 16-way axis -> 86.6 GB/chip replicated without it; the
# extra psum of contracting a sharded head_dim is negligible at Sq=1).
# NOT applied to train/prefill activations: there the induced score
# psums are (B,S,H,S)-scale and catastrophic (deepseek-coder train went
# 15s -> 457s collective when this was tried globally — §Perf).
def cache_rules_from(act_rules: Dict) -> Dict:
    out = dict(act_rules)
    out["head_dim"] = ("model",)
    return out

# --- pure-FSDP profile (no tensor parallelism): every parameter matrix is
# sharded on its d_model ('embed') dim across ALL chips; activations shard
# batch over (pod,data) and sequence over 'model'.  Removes the per-layer
# activation all-reduces of Megatron-style TP at the cost of per-layer
# weight all-gathers — the winning trade for dense decoder training at
# these shapes (§Perf 'qwen72b-fsdp').
FSDP_PARAM_RULES: Dict[str, Tuple[str, ...]] = {
    "embed": ("pod", "data", "model"),
    "vocab": None,
    "heads": None,
    "kv_heads": None,
    "mlp": None,
    "experts": ("model", "data"),
    "expert_mlp": None,
    "q_lora": ("pod", "data", "model"),
    "kv_lora": ("pod", "data", "model"),
    "head_dim": None,
    "heads_x": None,
    "embed_out": None,
    "layers": None,
}

FSDP_ACT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": ("model",),
    "heads": None,
    "kv_heads": None,
    "mlp": None,
    "vocab": None,
    "embed": None,
    "head_dim": None,
    "experts": None,
    "layers": None,
}

PROFILES = {
    "tp_fsdp": (PARAM_RULES, ACT_RULES),
    "fsdp": (FSDP_PARAM_RULES, FSDP_ACT_RULES),
}


def _mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(shape: Sequence[int], axes: LogicalAxes, mesh: Mesh,
             rules: Dict[str, Tuple[str, ...]]) -> P:
    """Build a PartitionSpec honoring divisibility + axis-uniqueness."""
    sizes = _mesh_axis_sizes(mesh)
    used = set()
    out = []
    for dim, name in zip(shape, axes):
        entry = rules.get(name) if name is not None else None
        if not entry:
            out.append(None)
            continue
        # drop mesh axes already used or absent from this mesh
        cand = tuple(a for a in entry if a in sizes and a not in used)
        if not cand:
            out.append(None)
            continue
        prod = math.prod(sizes[a] for a in cand)
        if dim % prod != 0:
            # try shrinking from the right (e.g. ('pod','data') -> ('pod',))
            while cand and dim % math.prod(sizes[a] for a in cand) != 0:
                cand = cand[:-1]
            if not cand:
                out.append(None)
                continue
        used.update(cand)
        out.append(cand if len(cand) > 1 else cand[0])
    # trim trailing Nones for tidiness
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shardings_for_tree(shapes_tree, axes_tree, mesh: Mesh,
                       rules: Dict = None):
    """shapes_tree: tree of ShapeDtypeStruct (or arrays); axes_tree: tree
    of logical-axes tuples with identical structure."""
    rules = rules or PARAM_RULES
    return jax.tree_util.tree_map(
        lambda s, ax: NamedSharding(mesh, spec_for(s.shape, ax, mesh, rules)),
        shapes_tree, axes_tree,
        is_leaf=lambda x: isinstance(x, (tuple,)) and all(
            isinstance(e, (str, type(None))) for e in x))


def param_shardings(abstract_tree, axes, mesh: Mesh, rules: Dict = None):
    rules = rules or PARAM_RULES
    flat_a, treedef = jax.tree_util.tree_flatten(abstract_tree)
    flat_x = treedef.flatten_up_to(axes)
    out = [NamedSharding(mesh, spec_for(a.shape, x, mesh, rules))
           for a, x in zip(flat_a, flat_x)]
    return jax.tree_util.tree_unflatten(treedef, out)


def make_cst(mesh: Optional[Mesh], rules: Dict = None):
    """Activation sharding-constraint applier: cst(x, logical_axes)."""
    rules = rules or ACT_RULES
    if mesh is None:
        return lambda x, axes: x

    def cst(x, axes):
        if len(axes) != x.ndim:
            return x
        spec = spec_for(x.shape, tuple(axes), mesh, rules)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return cst


# --- cache logical axes (for serve-path in_shardings) -----------------------


def cache_axes_like(cache_specs, cfg) -> Any:
    """Return a logical-axes tree matching the cache spec tree."""

    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(leaf.shape)
        if name in ("k", "v"):
            return ("layers", "batch", "seq", "kv_heads", "head_dim")[:nd]
        if name in ("c_kv", "k_rope"):
            return ("layers", "batch", "seq", None)[:nd]
        if name == "pos":
            return ("layers",) * nd   # () unstacked, (L,) when stacked
        if name == "conv":
            return ("layers", "batch", None, "mlp")[:nd]
        if name == "ssm":
            return ("layers", "batch", "heads", "head_dim", None)[:nd]
        if name in ("C",):
            return ("layers", "batch", "heads", None, None)[:nd]
        if name in ("n", "m", "c", "h"):
            # xlstm scalar states: (pairs, B, ...) — shard batch
            return (("layers", "batch") + (None,) * (nd - 2))[:nd]
        return (None,) * nd

    return jax.tree_util.tree_map_with_path(one, cache_specs)
