"""Newton iterations per system integrated: the sum of
``EnsembleStats.nni`` over every call of the window, over the systems
those calls completed (integrator layer, ``core/batched``)."""


def read(rec):
    c = rec.counters
    if not c.get("cells") or "nni" not in c:
        return None
    return c["nni"] / c["cells"]
