"""Largest per-chip busy time over their mean, in the traced window
(``Trace.busy_s()``: the union of each device's ops): how far the
busiest chip of the mesh runs ahead of the others, 1.0 when every chip
does the same work (device layer, across chips)."""


def read(rec):
    if rec.trace is None or not rec.trace.ops:
        return None
    busy = rec.trace.busy_s()
    mean = sum(busy.values()) / len(busy)
    return max(busy.values()) / mean if mean > 0 else None
