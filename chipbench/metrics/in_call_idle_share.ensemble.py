"""Share of the window in which the device sat idle inside the timed
program's executions (``jit_call``, the whole integration): the idle
stretches whose neighbouring ops on both sides belong to that program,
averaged over the cell's chips, over the window
(``chipbench.phases.in_call_idle_s``).  The rest of
``device_idle_share.ensemble`` lies between calls."""
from chipbench import phases


def read(rec):
    trace = rec.trace
    if trace is None or not trace.ops or not trace.timed:
        return None
    return 100.0 * phases.in_call_idle_s(trace) / trace.window_s()
