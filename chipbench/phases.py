"""Device time and idle time of the ensemble-BDF step, from a trace.

The integrator runs each phase of its step under a named scope
(``ensemble_bdf.rescale``, ``.predict``, ``.lsetup``, ``.newton``,
``.error_test``, ``.update``).  The compiled instructions carry it in
their ``op_name`` metadata, which the trace's op events do not: the
names are read from the optimized HLO text of the timed programs, keyed
by instruction name, and looked up only for ops of a timed program
(instruction names repeat across programs).  Each op of the window is
charged to the innermost phase in its name, or to ``unscoped``.

* :func:`phase_time`: device seconds of the window's compute ops of the
  timed programs by phase, averaged over the devices; the phases and
  ``unscoped`` sum to the timed programs' device time;
* :func:`in_call_idle_s`: the idle time inside the timed programs'
  executions: each stretch of the window in which a device runs no op
  (compute or asynchronous copy), where the op that ends just before it
  and the op that starts just after it both belong to a timed program.
  A gap between an execution's edge and its first or last op is missed
  (a few microseconds a call on a v5e), and so would be the gap between
  two executions with no other program between them; the ensemble
  cell's window runs its input draw and its summary between calls;
* :func:`idle_by_phase`: that idle time by the phase of the op that
  ends each gap.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

PHASE_PREFIX = "ensemble_bdf."
UNSCOPED = "unscoped"
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?[ ,]metadata=\{'
                      r'[^}]*?op_name="([^"]*)"', re.M)


def op_names(hlo_texts: Iterable[str]) -> Dict[str, str]:
    """``{instruction name: op_name}`` of optimized HLO module texts."""
    names: Dict[str, str] = {}
    for text in hlo_texts:
        names.update(_OP_NAME.findall(text))
    return names


def phase(op_name: str) -> str:
    """The innermost ``ensemble_bdf.*`` component of an op_name."""
    found = [c for c in op_name.split("/") if c.startswith(PHASE_PREFIX)]
    return found[-1] if found else UNSCOPED


def _timed(trace, op) -> bool:
    return trace.timed is not None and op.module in trace.timed


def phase_of(trace, names: Dict[str, str], op) -> str:
    """The phase of a trace op; names are looked up only for ops of a
    timed program."""
    return phase(names.get(op.name, "")) if _timed(trace, op) else UNSCOPED


def phase_time(trace, names: Dict[str, str]) -> Dict[str, float]:
    """Device seconds of the window's compute ops of the timed programs
    by phase, averaged over the devices."""
    out: Dict[str, float] = defaultdict(float)
    ndev = max(1, len(trace.devices))
    for op in trace.in_window():
        out[phase_of(trace, names, op)] += (op.end - op.start) / ndev
    return dict(out)


def _gaps(trace) -> List[Tuple[float, float, object]]:
    """``(start, end, op that ends it)`` of every idle stretch inside an
    execution of a timed program, clipped to the window."""
    lo, hi = trace.window
    by_dev = defaultdict(list)
    for op in trace.ops:
        by_dev[op.device].append(op)
    out = []
    for ops in by_dev.values():
        ops.sort(key=lambda o: o.start)
        last = None             # the op that ends the busy stretch so far
        for op in ops:
            if last is not None and op.start > last.end:
                s, e = max(last.end, lo), min(op.start, hi)
                if e > s and _timed(trace, last) and _timed(trace, op):
                    out.append((s, e, op))
            if last is None or op.end > last.end:
                last = op
    return out


def in_call_idle_s(trace) -> float:
    """Idle seconds inside the timed programs' executions, averaged
    over the devices."""
    ndev = max(1, len(trace.devices))
    return sum(e - s for s, e, _ in _gaps(trace)) / ndev


def idle_by_phase(trace, names: Dict[str, str]) -> Dict[str, float]:
    """:func:`in_call_idle_s` by the phase of the op that ends each
    gap."""
    out: Dict[str, float] = defaultdict(float)
    ndev = max(1, len(trace.devices))
    for s, e, end in _gaps(trace):
        out[phase_of(trace, names, end)] += (e - s) / ndev
    return dict(out)
