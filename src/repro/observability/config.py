"""ObservabilityConfig — the single switchboard for the SUNLogger/
SUNProfiler analogs.

Everything is OFF by default, and the disabled path is contractually
free: with the default config, ``integrate`` takes exactly the code
path it took before this subsystem existed, so the jitted hot-loop
jaxprs are *identical* to a no-observability build (statically checked
by sunlint's ``telemetry-purity`` rule).  What the enabled path costs
on a TPU is measured in PERF.md.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class ObservabilityConfig:
    """Per-:class:`~repro.core.context.Context` observability switches.

    profile            : enable the SUNProfiler analog
                         (``ctx.profiler``): region timers around
                         lower/compile/execute in ``integrate`` and the
                         serving pump stages, plus Chrome-trace export.
    profile_sync       : block on an enqueued device token at region
                         exit so async device work is attributed to the
                         region that launched it (SUNProfiler's
                         device-sync semantics).  Turn off for pure
                         host-side region timing.
    telemetry          : record in-loop step telemetry (a bounded ring
                         buffer threaded through the BDF/DIRK step-loop
                         carries), surfaced as ``Solution.telemetry``.
    telemetry_capacity : ring slots per integration.  Reconciliation
                         with the Solution aggregates is exact while
                         the loop runs fewer attempts than this; older
                         records are overwritten past it (the wrapper
                         flags ``truncated``).
    log_level          : enable the SUNLogger analog (``ctx.logger``)
                         at this level ("ERROR" | "WARNING" | "INFO" |
                         "DEBUG"); None keeps it disabled.
    log_path           : optional JSON-lines sink for logger events
                         (events are always kept in a bounded
                         in-memory deque as well).
    """

    profile: bool = False
    profile_sync: bool = True
    telemetry: bool = False
    telemetry_capacity: int = 512
    log_level: Optional[str] = None
    log_path: Optional[str] = None

    @property
    def enabled(self) -> bool:
        """Any instrumentation on at all?"""
        return bool(self.profile or self.telemetry
                    or self.log_level is not None)
