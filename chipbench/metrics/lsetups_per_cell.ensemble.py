"""Linear-solver setups (Jacobian + block inverse) per system
integrated: the sum of ``EnsembleStats.nsetups`` over the window's
calls, over the systems they completed (integrator layer,
``core/batched``)."""


def read(rec):
    c = rec.counters
    if not c.get("cells") or "nsetups" not in c:
        return None
    return c["nsetups"] / c["cells"]
