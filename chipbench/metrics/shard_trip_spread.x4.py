"""Largest per-shard step-loop trip count over their mean: each chip of
the sharded ensemble runs its own step loop, and the call waits for
the slowest, so 1.0 means no chip stepped more than the others
(integrator layer, sharded: ``core/batched``; the counters
``shard_trips_max`` and ``shard_trips_mean`` of the mesh driver, from
``EnsembleStats.trips``, one entry per shard, summed over the
window's calls)."""


def read(rec):
    c = rec.counters
    if not c.get("shard_trips_mean") or "shard_trips_max" not in c:
        return None
    return c["shard_trips_max"] / c["shard_trips_mean"]
