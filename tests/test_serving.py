"""Serving front-end suite: admission queue, trace cache, padded-lane
stats hygiene, warm-start continuation, and the end-to-end server.

Scaled down (tiny buckets, short horizons) so the whole file stays
compile-bound at a few traces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched import SolverSession, ensemble_bdf_integrate
from repro.core.context import Context
from repro.core.ivp import IVP, integrate
from repro.core.problems import (batched_robertson, batched_robertson_soa,
                                 decay_chain_family, robertson_family)
from repro.serve.solver import (AdmissionQueue, IVPRequest, ProblemFamily,
                                RetryAfter, SolverServer, TraceCache,
                                TraceKey, bucket_key, tolerance_class)

ROB_PARAMS = {"k1": 0.04, "k2": 1.2e4, "k3": 3e7}


def _req(family="robertson", n=3, rtol=1e-6, atol=1e-9, tf=0.2,
         method="ensemble_bdf"):
    return IVPRequest(family=family, y0=jnp.zeros(n), t0=0.0, tf=tf,
                      rtol=rtol, atol=atol, method=method)


# ---------------------------------------------------------------------------
# admission queue
# ---------------------------------------------------------------------------

class TestAdmissionQueue:
    def test_tolerance_class(self):
        assert tolerance_class(1e-6, 1e-9) == (-6, -9)
        assert tolerance_class(5e-6, 2e-9) == (-6, -9)  # same decade
        assert tolerance_class(1e-7, 1e-9) == (-7, -9)  # tighter decade
        with pytest.raises(ValueError):
            tolerance_class(0.0, 1e-9)
        with pytest.raises(ValueError):
            tolerance_class(1e-6, 2.0)

    def test_bucketing_key_splits(self):
        d = "float64"
        base = bucket_key(_req(), d)
        assert bucket_key(_req(rtol=3e-6), d) == base     # same decade
        assert bucket_key(_req(rtol=1e-4), d) != base     # other decade
        assert bucket_key(_req(family="x"), d) != base
        assert bucket_key(_req(n=6), d) != base
        assert bucket_key(_req(method="ensemble_dirk"), d) != base

    def test_flush_on_max_batch(self):
        q = AdmissionQueue(bucket_sizes=(4, 8), max_batch=4,
                           clock=lambda: 0.0)
        for _ in range(6):
            q.offer(_req(), now=0.0)
        bundles = q.poll(now=0.0)        # full chunk only; 2 remain fresh
        assert len(bundles) == 1 and bundles[0].live == 4
        assert bundles[0].nsys == 4 and q.depth == 2

    def test_flush_on_max_wait_and_padding(self):
        q = AdmissionQueue(bucket_sizes=(4, 8), max_batch=8,
                           max_wait=1e-3)
        for _ in range(3):
            q.offer(_req(), now=0.0)
        assert q.poll(now=5e-4) == []              # not stale yet
        bundles = q.poll(now=2e-3)                 # stale: partial flush
        assert len(bundles) == 1
        b = bundles[0]
        assert b.live == 3 and b.nsys == 4         # padded to bucket size
        assert b.occupancy == pytest.approx(0.75)
        assert q.depth == 0

    def test_staleness_clock_restarts_at_new_head(self):
        # after a full-chunk flush the REMAINING head's arrival drives
        # the stale timer — not the flushed (older) head's
        q = AdmissionQueue(bucket_sizes=(2, 4), max_batch=2,
                           max_wait=1.0)
        q.offer(_req(), now=0.0)
        q.offer(_req(), now=0.0)
        q.offer(_req(), now=0.9)                   # becomes the new head
        assert len(q.poll(now=0.95)) == 1          # the full chunk only
        assert q.poll(now=1.5) == []               # head is 0.6s old
        assert len(q.poll(now=2.0)) == 1           # now stale

    def test_backpressure_retry_after(self):
        q = AdmissionQueue(bucket_sizes=(64,), max_depth=2, max_wait=1e-3)
        q.offer(_req(), now=0.0)
        q.offer(_req(), now=0.0)
        with pytest.raises(RetryAfter) as ei:
            q.offer(_req(), now=0.0)
        assert ei.value.retry_after > 0 and ei.value.depth == 2
        assert q.rejected == 1
        q.poll(now=1.0)                            # drain
        q.offer(_req(), now=1.0)                   # admits again
        assert q.depth == 1


# ---------------------------------------------------------------------------
# trace cache
# ---------------------------------------------------------------------------

class TestTraceCache:
    def _key(self, i):
        return TraceKey(bucket=bucket_key(_req(n=3 + i), "float64"),
                        nsys=8, policy=None)

    def test_hit_miss_evict_counters(self):
        c = TraceCache(maxsize=2)
        built = []
        c.get(self._key(0), lambda: built.append(0) or "a")
        entry, hit = c.get(self._key(0), lambda: built.append(1) or "b")
        assert entry == "a" and hit and built == [0]
        c.get(self._key(1), lambda: "c")
        c.get(self._key(2), lambda: "d")           # evicts LRU key(0)
        assert self._key(0) not in c and len(c) == 2
        assert c.stats() == {"hits": 1, "misses": 3, "evictions": 1,
                             "size": 2, "hit_rate": 0.25}

    def test_lru_touch_refreshes(self):
        c = TraceCache(maxsize=2)
        c.get(self._key(0), lambda: "a")
        c.get(self._key(1), lambda: "b")
        c.get(self._key(0))                        # touch -> key(1) is LRU
        c.get(self._key(2), lambda: "c")
        assert self._key(0) in c and self._key(1) not in c

    def test_miss_without_builder_raises(self):
        with pytest.raises(KeyError):
            TraceCache().get(self._key(0))

    def test_context_surfaces_cache(self):
        ctx = Context()
        assert "trace_cache" not in ctx.dispatch_report()
        ctx.trace_cache = TraceCache()
        assert ctx.dispatch_report()["trace_cache"]["hits"] == 0


# ---------------------------------------------------------------------------
# padded-lane stats hygiene (satellite a)
# ---------------------------------------------------------------------------

class TestPaddedLanes:
    def test_padding_invariance_and_masked_stats(self):
        # 13 live systems padded to 16 (NOT a lane multiple): live
        # lanes must take the IDENTICAL discrete path (exact step
        # counts) with trajectories matching to ULP-level tolerance —
        # XLA fuses the nsys=16 program differently than the nsys=13
        # one, so last-bit float equality across the two programs is
        # not guaranteed — and live=-masked aggregates must exclude
        # the dead lanes
        live_n, pad_n, tf = 13, 16, 0.3
        f, jac, y0 = batched_robertson(live_n)
        f_soa, jac_soa = batched_robertson_soa(live_n)
        sol_ref = integrate(IVP(f=f, jac=jac, f_soa=f_soa,
                                jac_soa=jac_soa, y0=y0),
                            0.0, tf, "ensemble_bdf")

        def pad(fn, in_axis, out_axis):
            def wrapped(t, y):
                t_live = t[:live_n] if getattr(t, "ndim", 0) else t
                out = fn(t_live,
                         jnp.take(y, jnp.arange(live_n), axis=in_axis))
                pad_width = [(0, 0)] * out.ndim
                pad_width[out_axis] = (0, pad_n - live_n)
                return jnp.pad(out, pad_width, mode="edge")
            return wrapped

        # the padded problem replicates the LAST live system's physics
        # into the dead lanes (edge padding), matching the serving
        # convention of replicating the last live request
        y0p = jnp.concatenate(
            [y0, jnp.broadcast_to(y0[-1], (pad_n - live_n, 3))])
        tfv = jnp.where(jnp.arange(pad_n) < live_n, tf, 0.0)
        mask = np.arange(pad_n) < live_n
        sol_pad = integrate(IVP(f=pad(f, 0, 0), jac=pad(jac, 0, 0),
                                f_soa=pad(f_soa, 1, 1),
                                jac_soa=pad(jac_soa, 1, 2), y0=y0p),
                            0.0, tfv, "ensemble_bdf", live=mask)

        assert np.allclose(np.asarray(sol_pad.y[:live_n]),
                           np.asarray(sol_ref.y), rtol=1e-9, atol=1e-12)
        st_p, st_r = sol_pad.stats, sol_ref.stats
        assert np.array_equal(np.asarray(st_p.steps[:live_n]),
                              np.asarray(st_r.steps))
        # dead lanes zeroed by the mask, forced successful
        assert np.all(np.asarray(st_p.steps[live_n:]) == 0)
        assert np.all(np.asarray(st_p.nni[live_n:]) == 0)
        assert np.all(np.asarray(st_p.success[live_n:]))
        # aggregates count live work only
        assert int(sol_pad.nni) == int(sol_ref.nni)
        assert int(jnp.sum(sol_pad.nsetups)) == int(jnp.sum(sol_ref.nsetups))
        assert bool(sol_pad.success) == bool(sol_ref.success)

    def test_live_mask_rejected_for_scalar_methods(self):
        with pytest.raises(ValueError, match="live"):
            integrate(IVP(f=lambda t, y: -y, y0=jnp.ones(2)),
                      0.0, 1.0, "erk:dopri5", live=np.array([True]))


# ---------------------------------------------------------------------------
# warm-start continuation (satellite b)
# ---------------------------------------------------------------------------

class TestWarmStart:
    def test_split_matches_uninterrupted_and_beats_cold_restart(self):
        nsys, tm, tf, rtol = 4, 0.3, 0.8, 1e-6
        f, jac, y0 = batched_robertson(nsys)
        f_soa, jac_soa = batched_robertson_soa(nsys)
        prob = IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa, y0=y0)

        full = integrate(prob, 0.0, tf, "ensemble_bdf")
        leg1 = integrate(prob, 0.0, tm, "ensemble_bdf",
                         return_session=True)
        assert isinstance(leg1.session, SolverSession)
        leg2 = integrate(IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa,
                             y0=leg1.y),
                         tm, tf, "ensemble_bdf",
                         session=leg1.session, return_session=True)
        # parity: the split trajectory agrees with the uninterrupted
        # one to O(rtol) (different step sequences, same tolerance)
        rel = np.max(np.abs(np.asarray(leg2.y) - np.asarray(full.y)) /
                     (np.abs(np.asarray(full.y)) + 1e-30))
        assert rel < 100 * rtol
        assert bool(leg2.success)

        # the warm leg re-enters at terminal order/step: strictly fewer
        # steps than restarting the same leg cold from y(tm)
        cold = integrate(IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa,
                             y0=leg1.y), tm, tf, "ensemble_bdf")
        warm_steps = int(jnp.sum(leg2.stats.steps))
        cold_steps = int(jnp.sum(cold.stats.steps))
        assert warm_steps < cold_steps

        # session accounting: cumulative steps, per-call stats
        assert np.all(np.asarray(leg2.session.steps) ==
                      np.asarray(leg1.session.steps) +
                      np.asarray(leg2.stats.steps))
        assert np.allclose(np.asarray(leg2.session.t), tf)

    def test_cold_session_start_is_value_exact(self):
        # integrating WITH a cold session must match integrating
        # without one bitwise (the h<=0 sentinel path is the cold path)
        nsys = 3
        f, jac, y0 = batched_robertson(nsys)
        plain_y, plain_st = ensemble_bdf_integrate(
            f, jac, y0, 0.0, 0.2)
        sess_y, sess_st, _ = ensemble_bdf_integrate(
            f, jac, y0, 0.0, 0.2,
            session=SolverSession.cold(y0, 0.0), return_session=True)
        assert np.array_equal(np.asarray(plain_y), np.asarray(sess_y))
        assert np.array_equal(np.asarray(plain_st.steps),
                              np.asarray(sess_st.steps))

    def test_session_lanes_concat_roundtrip(self):
        y0 = jnp.arange(12.0).reshape(4, 3)
        s = SolverSession.cold(y0, 1.5)
        assert (s.nsys, s.n) == (4, 3)
        lanes = [s.lanes(slice(i, i + 1)) for i in range(4)]
        assert lanes[0].nsys == 1
        back = SolverSession.concat(lanes)
        for a, b in zip(jax.tree_util.tree_leaves(back),
                        jax.tree_util.tree_leaves(s)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_sharded_rejects_session(self):
        from repro.core.batched import ensemble_bdf_integrate_sharded
        f, jac, y0 = batched_robertson(2)
        with pytest.raises(ValueError, match="session"):
            ensemble_bdf_integrate_sharded(
                f, jac, y0, 0.0, 0.1,
                session=SolverSession.cold(y0, 0.0))


# ---------------------------------------------------------------------------
# end-to-end server (tentpole)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def server():
    fr = robertson_family()
    fd = decay_chain_family(6)
    srv = SolverServer(
        [ProblemFamily("robertson", 3, fr[0], fr[1], fr[2], fr[3]),
         ProblemFamily("decay6", 6, fd[0], fd[1], fd[2], fd[3])],
        bucket_sizes=(4,), max_batch=4, max_wait=1e-3,
        warmup_bundles=4)
    yield srv
    srv.stop()


def _submit_rob(srv, tf=0.2, session=None, y0=(1.0, 0.0, 0.0), t0=0.0):
    return srv.submit("robertson", list(y0), t0, tf, params=ROB_PARAMS,
                      session=session)


class TestSolverServer:
    def test_mixed_bundle_end_to_end(self, server):
        futs = [_submit_rob(server) for _ in range(3)]
        futs.append(server.submit("decay6", np.ones(6), 0.0, 0.5,
                                  params={"k": np.linspace(0.5, 3.0, 6)}))
        assert server.drain() == 2                 # one bundle per family
        sols = [f.result(timeout=5) for f in futs]
        assert all(bool(s.success) for s in sols)
        assert sols[0].y.shape == (3,) and sols[-1].y.shape == (6,)
        # identical requests -> identical lane results
        assert np.array_equal(np.asarray(sols[0].y), np.asarray(sols[1].y))
        # per-request result matches a direct integrate of the same IVP
        # (params as (nsys,) arrays — the batch form the family expects)
        fr = robertson_family()
        pb = {k: jnp.full((1,), v) for k, v in ROB_PARAMS.items()}
        direct = integrate(
            IVP(f=lambda t, y: fr[0](t, y, pb),
                jac=lambda t, y: fr[1](t, y, pb),
                y0=jnp.asarray([[1.0, 0.0, 0.0]])),
            0.0, 0.2, "ensemble_bdf")
        assert np.allclose(np.asarray(sols[0].y),
                           np.asarray(direct.y[0]), rtol=1e-10, atol=1e-12)

    def test_timings_and_cache_reuse(self, server):
        stats0 = server.cache.stats()
        futs = [_submit_rob(server) for _ in range(4)]
        server.drain()
        s = futs[0].result(timeout=5)
        assert set(s.timings) == {"queue_wait", "compile", "execute"}
        assert s.timings["queue_wait"] >= 0.0
        assert s.timings["execute"] > 0.0
        # the robertson@4 trace was compiled by the previous test:
        # this bundle must be a pure hit with NO compile time billed
        assert s.timings["compile"] == 0.0
        stats1 = server.cache.stats()
        assert stats1["hits"] == stats0["hits"] + 1
        assert stats1["misses"] == stats0["misses"]
        assert server.metrics()["steady_misses"] == 0

    def test_warm_start_via_server(self, server):
        f1 = _submit_rob(server, tf=0.4)
        server.drain()
        s1 = f1.result(timeout=5)
        assert s1.session is not None and s1.session.nsys == 1
        leg = dict(tf=float(s1.t) + 0.4, y0=np.asarray(s1.y),
                   t0=float(s1.t))
        f_warm = _submit_rob(server, session=s1.session, **leg)
        f_cold = _submit_rob(server, **leg)
        server.drain()
        warm, cold = f_warm.result(timeout=5), f_cold.result(timeout=5)
        assert int(warm.stats.steps) < int(cold.stats.steps)
        assert bool(warm.success) and bool(cold.success)
        # warm+cold rode ONE bundle: same trace, occupancy accounted
        assert np.allclose(np.asarray(warm.y), np.asarray(cold.y),
                           rtol=1e-4)

    def test_default_bucket_sizes(self):
        fr = robertson_family()
        srv = SolverServer([ProblemFamily("robertson", 3, fr[0], fr[1])])
        assert srv.queue.bucket_sizes == (64, 128, 256, 512)

    def test_backpressure_propagates(self):
        fr = robertson_family()
        srv = SolverServer(
            [ProblemFamily("robertson", 3, fr[0], fr[1])],
            bucket_sizes=(4,), max_batch=4, max_depth=2)
        _submit_rob(srv)
        _submit_rob(srv)
        with pytest.raises(RetryAfter):
            _submit_rob(srv)

    def test_submit_validation(self, server):
        with pytest.raises(ValueError, match="unknown family"):
            server.submit("nope", np.ones(3), 0.0, 1.0)
        with pytest.raises(ValueError, match="y0 shape"):
            server.submit("robertson", np.ones(4), 0.0, 1.0)

    def test_metrics_and_dispatch_report(self, server):
        m = server.metrics()
        for k in ("queue_depth", "rejected", "requests", "bundles",
                  "occupancy", "latency_p50_s", "latency_p99_s",
                  "steady_misses", "trace_cache"):
            assert k in m
        assert 0.0 < m["occupancy"] <= 1.0
        assert m["trace_cache"]["hits"] > 0
        rep = server.ctx.dispatch_report()
        assert rep["trace_cache"] == server.cache.stats()

    def test_async_facade(self, server):
        with server:                               # start()/stop()
            fut = _submit_rob(server)
            sol = fut.result(timeout=30)           # background pump
        assert bool(sol.success)
