"""Share of the window in which no op ran on the device: one minus the
union of the device's leaf-op intervals over the window, averaged over
the cell's chips (``chipbench.devtrace``)."""


def read(rec):
    if rec.trace is None or not rec.trace.ops:
        return None
    busy = rec.trace.busy_s()
    mean = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean / rec.trace.window_s())
