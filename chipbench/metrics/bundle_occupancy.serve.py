"""Live lanes over padded lanes of the bundles the server executed in
the window, from ``SolverServer.metrics()`` before and after it
(serving layer, ``serve/solver``)."""


def read(rec):
    c = rec.counters
    if not c.get("padded_lanes"):
        return None
    return 100.0 * c["live_lanes"] / c["padded_lanes"]
