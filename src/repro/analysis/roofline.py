"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md §Roofline).

Terms (TPU v5e constants; per-chip quantities from the SPMD module):
  compute    = HLO_FLOPs_per_chip / peak_FLOPs        [s]
  memory     = HLO_bytes_per_chip / HBM_bw            [s]
  collective = collective_operand_bytes_per_chip / link_bw   [s]

``cost_analysis()`` reports the per-device program (post-SPMD), so no
division by chip count is needed.  collective bytes are parsed from the
compiled HLO text: the sum of operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute op.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Device:
    """One roofline device entry (per chip).

    The physical ceilings (``peak_flops``/``hbm_bw``/``ici_bw``) drive
    :meth:`Roofline.finalize`; the remaining fields parameterize the
    per-op dispatch cost model in :mod:`repro.analysis.opcost`:

    vmem_bytes        : working-set budget for row-tiled Pallas
                        accumulators (the ``GJ_VMEM_BYTES`` knob, now a
                        device property); ``None`` = uncapped (interpret
                        mode has no VMEM — it pays per-step interpreter
                        overhead instead).
    jnp_bw/pallas_bw  : effective streamed bandwidth each backend
                        sustains on this device (<= hbm_bw; on the
                        ``interpret`` pseudo-device these are host-RAM
                        figures calibrated against ``--tune`` data).
    jnp_launch        : per-dispatch overhead on the jnp side [s] —
                        one fused XLA kernel launch on compiled
                        devices; on the ``interpret`` pseudo-device it
                        is the eager per-primitive dispatch cost the
                        oracle pays ``jnp_kernels`` times (opcost
                        counts the oracle's primitive dispatches).
    pallas_call       : fixed pallas_call entry overhead [s].
    pallas_step       : per-grid-step cost [s] — compiled program
                        prologue, or the interpreter's per-step Python
                        loop on the pseudo-device.
    interp_op         : interpret mode only: per kernel-body primitive
                        per grid step [s] (numpy dispatch overhead);
                        0.0 on compiled devices.
    interpret         : True for the CPU-emulation pseudo-device.
    """

    name: str
    peak_flops: float
    hbm_bw: float
    ici_bw: float
    vmem_bytes: Optional[int] = 2 * 1024 * 1024
    jnp_bw: float = 0.0          # 0 -> defaults to hbm_bw
    pallas_bw: float = 0.0       # 0 -> defaults to hbm_bw
    jnp_launch: float = 2e-6
    pallas_call: float = 2e-6
    pallas_step: float = 1e-7
    interp_op: float = 0.0
    interpret: bool = False

    def bw(self, backend: str) -> float:
        eff = self.jnp_bw if backend == "jnp" else self.pallas_bw
        return eff or self.hbm_bw


DEVICES: Dict[str, Device] = {
    # TPU v5e per chip (bf16 peak) — the paper-model target.
    "tpu_v5e": Device(name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9,
                      ici_bw=50e9),
    # TPU v4 per chip: larger part, same model structure.
    "tpu_v4": Device(name="tpu_v4", peak_flops=275e12, hbm_bw=1228e9,
                     ici_bw=100e9),
    # The interpret/CPU pseudo-device: Pallas kernels run under the
    # interpreter (numpy per grid step), jnp runs through XLA:CPU.  The
    # effective-rate and overhead constants are calibrated against the
    # committed .autotune/interpret.json measurements on this host
    # class (weighted relative-error fit over the 62-entry grid); they
    # exist to rank backends, not to predict wall time.  jnp_launch is
    # the eager per-primitive dispatch cost — the oracle's fixed
    # overhead scales with opcost's jnp_kernels dispatch counts.
    "interpret": Device(name="interpret", peak_flops=5e9, hbm_bw=10e9,
                        ici_bw=10e9, vmem_bytes=None,
                        jnp_bw=7e9, pallas_bw=9e9,
                        jnp_launch=70e-6, pallas_call=20e-6,
                        pallas_step=10e-6, interp_op=2e-6,
                        interpret=True),
}


#: ``jax.Device.device_kind`` -> roofline device name.  A compiled
#: policy on a chip missing here fails instead of borrowing another
#: chip's row.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v4": "tpu_v4",
}


def device_for_kind(kind: str) -> str:
    """The roofline device name of a ``jax.Device.device_kind``."""
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(f"no roofline device for device_kind {kind!r}; "
                         f"known kinds: {sorted(DEVICE_KINDS)}") from None


def get_device(name: str) -> Device:
    try:
        return DEVICES[name]
    except KeyError:
        raise ValueError(f"unknown roofline device {name!r}; "
                         f"known: {sorted(DEVICES)}") from None


# Back-compat module constants (TPU v5e per chip) — Roofline.finalize
# and older callers read these; they alias the device-table entry.
PEAK_FLOPS = DEVICES["tpu_v5e"].peak_flops    # bf16
HBM_BW = DEVICES["tpu_v5e"].hbm_bw            # bytes/s
ICI_BW = DEVICES["tpu_v5e"].ici_bw            # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|"
                       r"f64|c64|c128)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,\s]*)\}")
_WHILE_RE = re.compile(
    r"while\([^)]*\),\s*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_COMP_START_RE = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s+\([^)]*\)\s*->\s*.+\{\s*$")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _out_bytes(line: str) -> int:
    """Sum of output shape bytes (lhs of '=', layouts stripped)."""
    s = re.sub(r"\{[0-9,\s]*\}", "", line)  # strip layout annotations
    eq = s.find("=")
    par = s.find("(", eq)
    region = s[eq + 1: par if par > eq else None]
    return sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(region))


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))          # [num_groups, group_size]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


def _line_traffic(line: str, base: str, n_devices: int) -> int:
    """Estimated per-device ICI bytes moved by one execution of this op.

    Ring-algorithm models: all-gather out*(g-1)/g; all-reduce
    2*size*(g-1)/g; reduce-scatter in ~ out*(g-1); all-to-all
    size*(g-1)/g; collective-permute size.
    """
    size = _out_bytes(line)
    g = max(_group_size(line, n_devices), 1)
    if base == "all-gather":
        return int(size * (g - 1) / g)
    if base == "all-reduce":
        return int(2 * size * (g - 1) / g)
    if base == "reduce-scatter":
        return int(size * (g - 1))
    if base == "all-to-all":
        return int(size * (g - 1) / g)
    return size                           # collective-permute


def _parse_computations(hlo_text: str):
    """name -> list of body lines (flat, no nesting in HLO text)."""
    comps = {}
    cur, buf = None, []
    for line in hlo_text.splitlines():
        m = _COMP_START_RE.match(line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            buf = []
            comps[cur] = buf
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                buf.append(line)
    return comps


def collective_bytes(hlo_text: str, n_devices: int = 1) -> Dict[str, int]:
    """Per-device collective traffic, loop-aware.

    Collectives inside while bodies (scan over layers / microbatches)
    execute trip-count times but appear once in the text; we walk the
    call graph and multiply by the loop bound parsed from the condition
    computation (max integer constant — correct for lax.scan loops).
    """
    comps = _parse_computations(hlo_text)

    def comp_direct(name):
        """(per-kind bytes dict, count, list of (trip, body) sub-loops)."""
        per = {k: 0 for k in _COLLECTIVES}
        cnt = 0
        loops = []
        for line in comps.get(name, ()):
            s = line.strip()
            wm = _WHILE_RE.search(s)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trip = 1
                consts = [int(c) for cl in comps.get(cond, ())
                          for c in _CONST_RE.findall(cl)]
                if consts:
                    trip = max(consts)
                loops.append((trip, body))
                continue
            for base in _COLLECTIVES:
                if f" {base}(" in s or f" {base}-start(" in s:
                    per[base] += _line_traffic(s, base, n_devices)
                    cnt += 1
                    break
        return per, cnt, loops

    memo = {}

    def total(name, depth=0):
        if name in memo:
            return memo[name]
        if depth > 12:
            return ({k: 0 for k in _COLLECTIVES}, 0)
        per, cnt, loops = comp_direct(name)
        for trip, body in loops:
            sub, subcnt = total(body, depth + 1)
            for k in _COLLECTIVES:
                per[k] += trip * sub[k]
            cnt += trip * subcnt
        memo[name] = (per, cnt)
        return memo[name]

    # entry = the computation containing other computations' calls; HLO
    # marks it ENTRY but our parser drops the marker — find the one that
    # is not referenced as a fusion/branch target, or just sum over the
    # computation named like 'main'.
    entry = None
    for name in comps:
        if name.startswith("main"):
            entry = name
            break
    if entry is None:  # fallback: computation with most lines
        entry = max(comps, key=lambda n: len(comps[n])) if comps else ""
    per, cnt = total(entry)
    out = dict(per)
    out["count"] = cnt
    out["total"] = sum(per[k] for k in _COLLECTIVES)
    out["entry"] = entry
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float             # per chip
    hlo_bytes: float             # per chip
    coll_bytes: float            # per chip
    model_flops: float           # analytic 6ND (dense) / 6 N_active D
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    bottleneck: str = ""
    useful_ratio: float = 0.0    # MODEL_FLOPS / (chips * HLO_FLOPs)
    mfu_bound: float = 0.0       # model_flops/chips/peak / max(terms)
    coll_detail: Optional[Dict] = None
    memory_per_chip: Optional[Dict] = None

    def finalize(self, device: str = "tpu_v5e"):
        dev = get_device(device)
        self.t_compute = self.hlo_flops / dev.peak_flops
        self.t_memory = self.hlo_bytes / dev.hbm_bw
        self.t_collective = self.coll_bytes / dev.ici_bw
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        self.bottleneck = max(terms, key=terms.get)
        total_hlo = self.hlo_flops * self.chips
        self.useful_ratio = (self.model_flops / total_hlo
                             if total_hlo else 0.0)
        t_dom = max(terms.values())
        ideal = self.model_flops / self.chips / dev.peak_flops
        self.mfu_bound = ideal / t_dom if t_dom > 0 else 0.0
        return self

    def to_dict(self):
        return dataclasses.asdict(self)


def active_param_count(cfg) -> int:
    """Parameters touched per token: experts scaled by top-k/E."""
    from repro.models import Model
    from repro.models.spec import ParamSpec
    import jax

    specs = Model(cfg).specs()
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, ParamSpec)):
        n = 1
        for d in leaf.shape:
            n *= d
        if "experts" in leaf.axes:
            n = int(n * cfg.experts_per_tok / max(cfg.n_experts, 1))
        total += n
    return total


def model_flops_for(cfg, shape_cfg) -> float:
    """6*N_active*D for train; 2*N_active*tokens for decode/prefill fwd."""
    n_active = active_param_count(cfg)
    if shape_cfg.kind == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6.0 * n_active * tokens
    if shape_cfg.kind == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape_cfg.global_batch


def summarize(rows):
    """Markdown table for EXPERIMENTS.md."""
    hdr = ("| arch | shape | mesh | t_compute | t_memory | t_collective | "
           "bottleneck | useful | MFU-bound |\n"
           "|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r.arch} | {r.shape} | {r.mesh} | {r.t_compute:.3e} | "
            f"{r.t_memory:.3e} | {r.t_collective:.3e} | {r.bottleneck} | "
            f"{r.useful_ratio:.2f} | {r.mfu_bound:.2%} |")
    return "\n".join(lines)
