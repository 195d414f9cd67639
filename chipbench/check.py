"""The comparison that decides ``correct``: each number beside its limit.

``worst_err`` is the largest distance of a compared answer from the
plain float64 reference, in units of the tolerance the deployment
states (``rtol * |y_ref| + atol``, per component; atol one number or
one per component).  A non-finite answer
reads infinitely far.  Counts of failed systems or requests are exact
comparisons with the limit 0.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


def worst_err(y, y_ref, rtol: float, atol: float) -> float:
    """max |y - y_ref| / (rtol |y_ref| + atol) over every component."""
    y = np.asarray(y, np.float64)
    y_ref = np.asarray(y_ref, np.float64)
    atol = np.asarray(atol, np.float64)
    if y.size == 0:
        return float("inf")
    err = np.abs(y - y_ref) / (rtol * np.abs(y_ref) + atol)
    err = np.where(np.isfinite(err), err, np.inf)
    return float(err.max())


def report(checks: List[Check]) -> dict:
    """Print each check on standard error and return them as the result
    line's ``checks`` entry."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def against_reference(problem, cfg: dict, y, y0, params) -> float:
    """``worst_err`` of the answers ``y`` to systems ``(y0, params)``
    against the problem's plain reference in float64, at the reference
    tolerances the deployment's ``check`` section gives."""
    if len(y) == 0:
        return float("inf")
    ref = cfg["check"]["reference"]
    y_ref, reached = problem.reference(
        y0, params, float(cfg["t0"]), float(cfg["tf"]), rtol=ref["rtol"],
        atol=ref["atol"])
    if not reached.all():
        raise RuntimeError("the float64 reference did not reach tf")
    return worst_err(y, y_ref, cfg["rtol"], cfg["atol"])
