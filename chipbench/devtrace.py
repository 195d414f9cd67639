"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The trace (``*.xplane.pb``) holds, per TPU, a line ``XLA Ops`` of
device operations, each named by its HLO instruction as the compiled
program declares it (``%name = type opcode(type %operand, ...), ...``),
and host lines that carry the benchmark's own ``bench.*`` spans.  From
them:

* busy time per device: the union of the intervals of its *leaf* ops
  (an op that contains others, such as a ``while`` or ``conditional``,
  is control flow whose children are the work) and of its asynchronous
  copies (the ``Async XLA Ops`` line), clipped to the window that the
  ``bench.window`` span marks; the idle share is one minus busy over the
  window;
* bytes per op: the compiler's own per-op cost is not in the trace, so
  the bytes are those of the operands and results the instruction
  declares in HBM (memory space 0; arrays the compiler placed in VMEM,
  ``S(1)``, move no HBM bytes), counted at their logical size; inside a
  fusion, a parameter that is only sliced counts the slices it reads,
  and a parameter updated in place by the fusion's root
  ``dynamic-update-slice`` counts only the update;
* the HBM roofline share: the bytes of every leaf compute op over peak
  HBM bandwidth is the least time those ops could take; divided by
  their summed device time.  Control flow and the issue and wait of an
  asynchronous copy are left out (their declared operands are not moved
  in their own time).  An op whose own share exceeds 100% is a
  miscount, and is flagged, not clipped;
* the breakdown: the compute ops that took most device time, and the
  longest idle gaps named by the benchmark span the host was in.

Each op is given the program it ran in (the ``XLA Modules`` line:
``jit_<function>``).  Where the driver names the timed programs, the
roofline and the breakdown's ops cover only theirs: the benchmark's own
small programs in the window (the sampling of answers, the drawing of
inputs) are not the system under test.  Busy time covers every op.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
# ops whose declared operands are not bytes they move in their own time:
# control flow (its children are the work) and the issue and wait of an
# asynchronous copy (the copy itself runs on the async line)
_NOT_COUNTED = ("while", "conditional", "call")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
}
_TYPE = re.compile(r"\b(" + "|".join(sorted(_DTYPE_BYTES, key=len,
                                            reverse=True))
                   + r")\[([0-9,]*)\](\{[^}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_SLICES = ("slice", "dynamic-slice")


@dataclass
class Op:
    """One leaf device op: ``text`` is its HLO instruction; ``copy`` marks
    an asynchronous copy (the trace's async line)."""

    device: int
    start: float   # seconds on the trace clock
    end: float
    text: str
    copy: bool = False
    module: str = ""   # the program it ran in, e.g. "jit_call"

    @property
    def name(self) -> str:
        head = self.text.split(" = ", 1)[0]
        return head.lstrip("%").strip()


@dataclass
class Span:
    name: str
    start: float
    end: float


def type_bytes(text: str) -> float:
    """Logical bytes of every HBM-resident array type in ``text``."""
    total = 0.0
    for dtype, dims, layout in _TYPE.findall(text):
        space = _SPACE.search(layout or "")
        if space and int(space.group(1)) != 0:
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _balanced(text: str, i: int) -> int:
    """Index just past the parenthesis group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def split_instruction(text: str) -> Tuple[str, str, List[str], str]:
    """``(result_type, opcode, operand_texts, attributes)`` of one HLO
    instruction ``%name = result opcode(operands), attributes``."""
    rhs = text.split(" = ", 1)[1] if " = " in text else text
    if rhs.startswith("("):
        k = _balanced(rhs, 0)
    else:
        k = rhs.find(" ")
    result, rest = rhs[:k], rhs[k:].lstrip()
    p = rest.find("(")
    opcode = rest[:p].strip()
    q = _balanced(rest, p)
    inner = rest[p + 1:q - 1]
    operands, depth, cur = [], 0, []
    for ch in inner:
        if ch in "({[":
            depth += 1
        elif ch in ")}]":
            depth -= 1
        if ch == "," and depth == 0:
            operands.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if "".join(cur).strip():
        operands.append("".join(cur).strip())
    return result, opcode, operands, rest[q:]


@dataclass
class FusedBody:
    """What a fused computation does with each parameter."""

    slice_bytes: Dict[int, float] = field(default_factory=dict)
    inplace: Dict[int, float] = field(default_factory=dict)  # update bytes


def parse_fusions(hlo_text: str) -> Dict[str, FusedBody]:
    """Fused computations of an optimized HLO module (``as_text()`` of a
    compiled executable), by computation name."""
    bodies: Dict[str, FusedBody] = {}
    name, params, users, defs, root = None, {}, defaultdict(list), {}, None
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith("%") and s.endswith("{") and " -> " in s:
            name = s.split(" ", 1)[0].lstrip("%")
            params, users, defs, root = {}, defaultdict(list), {}, None
            continue
        if name is None:
            continue
        if s == "}":
            body = FusedBody()
            for pname, idx in params.items():
                uses = users.get(pname, [])
                if uses and all(op in _SLICES for op, _, _ in uses):
                    body.slice_bytes[idx] = sum(type_bytes(r)
                                                for _, r, _ in uses)
                elif (root is not None and root[1] == "dynamic-update-slice"
                      and root[2] and root[2][0] == pname
                      and len(uses) == 1):
                    upd = root[2][1] if len(root[2]) > 1 else ""
                    body.inplace[idx] = type_bytes(defs.get(upd, ""))
            bodies[name] = body
            name = None
            continue
        if " = " not in s:
            continue
        is_root = s.startswith("ROOT ")
        s = s[5:] if is_root else s
        lhs = s.split(" = ", 1)[0].lstrip("%")
        result, opcode, operands, _ = split_instruction(s)
        defs[lhs] = result
        refs = [o.split()[-1].lstrip("%") for o in operands if o]
        if opcode == "parameter":
            m = re.match(r"\s*(\d+)", operands[0] if operands else "")
            if m:
                params[lhs] = int(m.group(1))
        for r in refs:
            users[r].append((opcode, result, refs))
        if is_root:
            root = (lhs, opcode, refs)
    return bodies


def op_bytes(text: str, fusions: Optional[Dict[str, FusedBody]] = None
             ) -> Optional[float]:
    """HBM bytes one execution of the op ``text`` reads and writes;
    ``None`` for control flow and the issue or wait of an asynchronous
    copy, which the roofline leaves out."""
    result, opcode, operands, attrs = split_instruction(text)
    if opcode in _NOT_COUNTED or opcode.endswith(("-start", "-done")):
        return None
    body = None
    if opcode == "fusion" and fusions:
        m = re.search(r"calls=%([\w.\-]+)", attrs)
        body = fusions.get(m.group(1)) if m else None
    total = 0.0
    inplace = False
    for i, operand in enumerate(operands):
        full = type_bytes(operand)
        if body is not None and i in body.slice_bytes:
            total += min(full, body.slice_bytes[i])
        elif body is not None and i in body.inplace:
            # updated in place: only the update's region is written
            inplace = True
            total += body.inplace[i] if full else 0.0
        else:
            total += full
    if not inplace:
        total += type_bytes(result)
    return total


def _leaves(events: Sequence[Tuple[float, float, str]]
            ) -> List[Tuple[float, float, str]]:
    """The events of one line that contain no other event."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    parent = [False] * len(events)
    stack: List[int] = []
    eps = 1e-9
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s + eps:
            stack.pop()
        if stack and e <= events[stack[-1]][1] + eps:
            parent[stack[-1]] = True
        stack.append(i)
    return [events[i] for i in range(len(events)) if not parent[i]]


def union_length(intervals: Iterable[Tuple[float, float]], lo: float,
                 hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Iterable[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    gaps, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


@dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    fusions: Dict[str, FusedBody] = field(default_factory=dict)
    # programs whose ops the roofline and top ops cover (None: all)
    timed: Optional[frozenset] = None

    @property
    def devices(self) -> List[int]:
        return sorted({op.device for op in self.ops})

    @property
    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not w:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        return w[0].start, w[-1].end

    def window_s(self) -> float:
        lo, hi = self.window
        return hi - lo

    def busy_s(self) -> Dict[int, float]:
        """Busy seconds of each device inside the window."""
        lo, hi = self.window
        by_dev = defaultdict(list)
        for op in self.ops:
            by_dev[op.device].append((op.start, op.end))
        return {d: union_length(iv, lo, hi) for d, iv in by_dev.items()}

    def in_window(self) -> List[Op]:
        """The window's compute ops (not the asynchronous copies) of the
        timed programs."""
        lo, hi = self.window
        return [op for op in self.ops
                if not op.copy and op.start >= lo and op.end <= hi
                and (self.timed is None or op.module in self.timed)]

    def hbm_roofline(self, hbm_bytes_per_s: float):
        """``(share %, flagged)`` over the leaf ops of the window; flagged
        lists ``(op name, own share %)`` of ops reading over 100%.
        ``None`` when the window holds no op."""
        per: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0])
        cost: Dict[str, Optional[float]] = {}
        for op in self.in_window():
            if op.text not in cost:
                cost[op.text] = op_bytes(op.text, self.fusions)
            if cost[op.text] is None:
                continue
            acc = per[op.name]
            acc[0] += cost[op.text]
            acc[1] += op.end - op.start
        total_b = sum(b for b, _ in per.values())
        total_t = sum(t for _, t in per.values())
        if total_t <= 0:
            return None
        flagged = sorted(
            ((name, 100.0 * b / hbm_bytes_per_s / t)
             for name, (b, t) in per.items()
             if t > 0 and b / hbm_bytes_per_s > t), key=lambda x: -x[1])
        return 100.0 * total_b / hbm_bytes_per_s / total_t, flagged

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` ops that took most device time in the window,
        averaged over the devices."""
        per: Dict[str, float] = defaultdict(float)
        for op in self.in_window():
            per[op.name] += op.end - op.start
        ndev = max(1, len(self.devices))
        top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t / ndev] for name, t in top]

    def top_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of any device in the window, each
        named by the benchmark span that overlaps it most."""
        lo, hi = self.window
        by_dev = defaultdict(list)
        for op in self.ops:
            by_dev[op.device].append((op.start, op.end))
        gaps = [g for iv in by_dev.values() for g in idle_gaps(iv, lo, hi)]
        gaps.sort(key=lambda g: g[0] - g[1])
        inner = [s for s in self.spans if s.name != WINDOW_SPAN]
        out = []
        for s, e in gaps[:k]:
            best, name = 0.0, "host outside benchmark spans"
            for sp in inner:
                ov = min(e, sp.end) - max(s, sp.start)
                if ov > best:
                    best, name = ov, sp.name
            out.append([name, e - s])
        return out


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _attribute(ops: List[Op], modules: List[Tuple[float, float, str]]):
    """Give each op of one device the program whose interval holds its
    start."""
    modules.sort()
    starts = [m[0] for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < modules[i][1]:
            op.module = modules[i][2]


def load(path: str, hlo_texts: Sequence[str] = (),
         timed: Optional[Iterable[str]] = None) -> Trace:
    """Read an ``.xplane.pb`` into leaf device ops and benchmark spans;
    ``hlo_texts`` (optimized modules of the timed executables) give the
    fused computations' parameter use; ``timed`` names their programs
    (``jit_<function>``) for the roofline and the top ops."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            dev_ops: List[Op] = []
            modules: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.extend((e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9,
                                    e.name.split("(", 1)[0])
                                   for e in line.events)
                if line.name not in (OPS_LINE, ASYNC_LINE):
                    continue
                evs = [(e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9, e.name)
                       for e in line.events]
                if line.name == ASYNC_LINE:
                    dev_ops.extend(Op(dev, s, e, t, copy=True)
                                   for s, e, t in evs)
                else:
                    dev_ops.extend(Op(dev, s, e, t)
                                   for s, e, t in _leaves(evs))
            _attribute(dev_ops, modules)
            ops.extend(dev_ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name, e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns)
                                          * 1e-9))
    spans.sort(key=lambda s: s.start)
    fusions: Dict[str, FusedBody] = {}
    for text in hlo_texts:
        fusions.update(parse_fusions(text))
    return Trace(ops=ops, spans=spans, fusions=fusions,
                 timed=None if timed is None else frozenset(timed))
