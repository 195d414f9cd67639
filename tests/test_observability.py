"""Observability suite: profiler regions, structured event log, metrics
registry, the in-loop step-telemetry ring, and the serving Prometheus /
span surface.

The load-bearing assertions are the *exact* reconciliations: recorded
ring-buffer telemetry must sum to the very counters the Solution
reports (steps, Newton iterations, lsetups, and the batch's loop trips)
— per system, including padded dead lanes and the warm-start
continuation leg.  The structural zero-overhead contract (disabled
config leaves the hot-loop jaxpr byte-identical) is checked statically
by the ``telemetry-purity`` sunlint rule.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.context import Context
from repro.core.ivp import IVP, integrate
from repro.core.problems import (batched_robertson, batched_robertson_soa,
                                 robertson_family)
from repro.observability import (Counter, EventLogger, Gauge, Histogram,
                                 MetricsRegistry, ObservabilityConfig,
                                 Profiler, StepTelemetry, context_metrics,
                                 ring_init, ring_record)
from repro.serve.solver import ProblemFamily, SolverServer
from repro.serve.solver.server import _LatencyRing

ROB_PARAMS = {"k1": 0.04, "k2": 1.2e4, "k3": 3e7}


# ---------------------------------------------------------------------------
# config + profiler + logger
# ---------------------------------------------------------------------------

class TestConfig:
    def test_defaults_are_all_off(self):
        cfg = ObservabilityConfig()
        assert not cfg.profile and not cfg.telemetry
        assert cfg.log_level is None and not cfg.enabled
        assert ObservabilityConfig(profile=True).enabled
        assert ObservabilityConfig(telemetry=True).enabled
        assert ObservabilityConfig(log_level="INFO").enabled

    def test_context_lazy_surfaces(self):
        ctx = Context()
        assert not ctx.profiler.enabled and not ctx.logger.enabled
        ctx2 = Context(observability=ObservabilityConfig(
            profile=True, log_level="DEBUG"))
        assert ctx2.profiler.enabled and ctx2.logger.enabled_for("DEBUG")


class TestProfiler:
    def test_disabled_is_a_shared_noop(self):
        """A disabled profiler stores no span, though its regions (nested,
        synced) are entered and a span is added."""
        p = Profiler(enabled=False)
        with p.region("a"):
            with p.region("b", sync=True):
                pass
        p.add_span("x", 0.0, 1.0)
        assert p.spans == []

    def test_nesting_summary_and_render(self):
        clock = iter(float(i) for i in range(100))
        p = Profiler(enabled=True, sync=False,
                     clock=lambda: next(clock))
        with p.region("outer"):
            with p.region("inner"):
                pass
            with p.region("inner"):
                pass
        names = [(s.name, s.depth) for s in p.spans]
        assert names == [("inner", 1), ("inner", 1), ("outer", 0)]
        s = p.summary()
        assert s["inner"]["count"] == 2 and s["outer"]["count"] == 1
        assert s["outer"]["total_s"] > s["inner"]["total_s"]
        assert "outer" in p.render() and "count" in p.render()

    def test_sync_fn_called_on_exit(self):
        calls = []
        p = Profiler(enabled=True, sync=True,
                     sync_fn=lambda: calls.append(1))
        with p.region("r"):
            pass
        with p.region("nosync", sync=False):
            pass
        assert calls == [1]

    def test_regions_reach_the_jax_profiler_trace(self, tmp_path):
        """A region, enabled or not, is a host span of the JAX profiler's
        trace: on the device ops' clock, in the same file."""
        import glob

        import jax
        from jax.profiler import ProfileData

        on, off = Profiler(enabled=True, sync=False), Profiler(enabled=False)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with on.region("serve.execute"):
                with off.region("serve.resolve"):
                    pass
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        names = {e.name for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/host:")
                 for line in p.lines for e in line.events}
        assert {"serve.execute", "serve.resolve"} <= names
        assert [s.name for s in on.spans] == ["serve.execute"]
        assert off.spans == []

    def test_chrome_trace_export(self, tmp_path):
        p = Profiler(enabled=True, sync=False)
        p.add_span("a", 10.0, 10.5, cat="serve", args={"k": 1})
        p.add_span("b", 10.2, 10.3)
        path = p.export_chrome_trace(str(tmp_path / "trace.json"))
        doc = json.loads(open(path).read())
        ev = doc["traceEvents"]
        assert len(ev) == 2
        assert all(e["ph"] == "X" and e["dur"] >= 0 for e in ev)
        # microseconds relative to the first span
        assert min(e["ts"] for e in ev) == 0.0
        a = next(e for e in ev if e["name"] == "a")
        assert a["cat"] == "serve" and a["args"] == {"k": 1}


class TestEventLogger:
    def test_threshold_filtering(self):
        log = EventLogger(level="WARNING")
        log.debug("d"); log.info("i"); log.warning("w"); log.error("e")
        assert [r["event"] for r in log.events] == ["w", "e"]
        assert log.enabled_for("ERROR") and not log.enabled_for("INFO")

    def test_disabled_drops_everything(self):
        log = EventLogger()
        log.error("boom")
        assert not log.enabled and len(log.events) == 0

    def test_jsonl_sink(self, tmp_path):
        path = tmp_path / "events.jsonl"
        log = EventLogger(level="INFO", path=str(path),
                          clock=lambda: 12.5)
        log.info("step.done", steps=3, method="bdf")
        log.debug("dropped")
        log.close()
        lines = path.read_text().splitlines()
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec == {"ts": 12.5, "level": "INFO",
                       "event": "step.done", "steps": 3,
                       "method": "bdf"}

    def test_unknown_level_raises(self):
        with pytest.raises(ValueError, match="level"):
            EventLogger(level="CHATTY")


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_render(self):
        reg = MetricsRegistry()
        c = reg.counter("repro_reqs", "requests")
        c.inc(); c.inc(2.0, family="rob")
        g = reg.gauge("repro_depth", "queue depth")
        g.set(3)
        text = reg.render()
        assert "# TYPE repro_reqs_total counter" in text
        assert "repro_reqs_total 1" in text
        assert 'repro_reqs_total{family="rob"} 2' in text
        assert "repro_depth 3" in text
        with pytest.raises(ValueError):
            c.inc(-1.0)

    def test_histogram_cumulative_buckets(self):
        h = Histogram("lat", "latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        lines = h.render()
        assert 'lat_bucket{le="0.1"} 1' in lines
        assert 'lat_bucket{le="1"} 2' in lines
        assert 'lat_bucket{le="+Inf"} 3' in lines
        assert "lat_count 3" in lines
        with pytest.raises(ValueError, match="bucket counts"):
            h.set_counts([1, 2], 0.0, 3)     # needs 3 (incl +Inf)

    def test_registry_idempotent_and_kind_clash(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(ValueError, match="registered"):
            reg.gauge("x")

    def test_context_metrics_export(self):
        ctx = Context()
        f, jac, y0 = batched_robertson(2)
        f_soa, jac_soa = batched_robertson_soa(2)
        integrate(IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa,
                      y0=y0), 0.0, 0.05, "ensemble_bdf", ctx=ctx)
        reg = MetricsRegistry()
        context_metrics(reg, ctx)
        text = reg.render()
        assert "repro_context_integrations_total 1" in text


class TestLatencyRing:
    def test_window_and_lifetime_split(self):
        r = _LatencyRing(size=4)
        for v in (1.0, 2.0, 3.0):
            r.observe(v)
        assert r.window() == [1.0, 2.0, 3.0] and r.count == 3
        assert r.clear() == [1.0, 2.0, 3.0]
        assert r.window() == [] and r.count == 0
        # lifetime aggregates survive the window clear
        assert r.total == 3 and r.sum_s == pytest.approx(6.0)

    def test_wraparound_keeps_newest_oldest_first(self):
        r = _LatencyRing(size=3)
        for v in (1.0, 2.0, 3.0, 4.0, 5.0):
            r.observe(v)
        assert r.window() == [3.0, 4.0, 5.0]
        assert r.count == 3 and r.total == 5

    def test_bucket_counts_cumulate_correctly(self):
        r = _LatencyRing(size=8, buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0, 0.01):
            r.observe(v)
        assert list(r.bucket_counts) == [2, 1, 1]   # <=0.1, <=1, +Inf


# ---------------------------------------------------------------------------
# telemetry ring (unit level)
# ---------------------------------------------------------------------------

def _rec(i, nsys=None):
    shape = () if nsys is None else (nsys,)
    f = lambda v, dt=jnp.float64: jnp.full(shape, v, dt)
    return (f(float(i)), f(0.1), f(2, jnp.int32), f(i, jnp.int32),
            f(0.5), f(i % 2 == 0, bool), f(True, bool), f(True, bool),
            f(True, bool))


class TestTelemetryRing:
    def test_record_and_chronological_wrap(self):
        ring = ring_init(3, (), jnp.float64)
        for i in range(5):                   # wraps: keeps 2, 3, 4
            ring = ring_record(ring, _rec(i))
        tel = StepTelemetry(ring)
        assert tel.truncated and tel.records == 3
        assert tel.total_records == 5
        assert tel.t.tolist() == [2.0, 3.0, 4.0]
        assert tel.newton_iters.tolist() == [2, 3, 4]

    def test_untruncated_prefix_only(self):
        ring = ring_init(8, (), jnp.float64)
        for i in range(3):
            ring = ring_record(ring, _rec(i))
        tel = StepTelemetry(ring)
        assert not tel.truncated and tel.records == 3
        assert tel.t.shape == (3,)

    def test_live_mask_zeroes_dead_lanes(self):
        ring = ring_init(4, (3,), jnp.float64)
        for i in range(2):
            ring = ring_record(ring, _rec(i, nsys=3))
        tel = StepTelemetry(ring, live=[True, False, True])
        assert tel.newton_iters[:, 1].tolist() == [0, 0]
        assert not tel.accepted[:, 1].any()
        assert tel.steps().tolist() == [2, 0, 2]
        assert tel.attempts().tolist() == [2, 0, 2]

    def test_capacity_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            ring_init(0, (), jnp.float64)


# ---------------------------------------------------------------------------
# integrate() telemetry: exact reconciliation with Solution aggregates
# ---------------------------------------------------------------------------

def _rob_prob(nsys):
    f, jac, y0 = batched_robertson(nsys)
    f_soa, jac_soa = batched_robertson_soa(nsys)
    return IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa, y0=y0)


class TestIntegrateTelemetry:
    def test_ensemble_bdf_reconciles_exactly(self):
        prob = _rob_prob(4)
        plain = integrate(prob, 0.0, 0.2, "ensemble_bdf")
        sol = integrate(prob, 0.0, 0.2, "ensemble_bdf", telemetry=512)
        tel = sol.telemetry
        assert isinstance(tel, StepTelemetry) and not tel.truncated
        # telemetry must not perturb the integration
        assert np.array_equal(np.asarray(sol.y), np.asarray(plain.y))
        st = sol.stats
        assert tel.steps().tolist() == np.asarray(st.steps).tolist()
        assert tel.attempts().tolist() == \
            np.asarray(st.attempts).tolist()
        assert tel.newton_iters_total().tolist() == \
            np.asarray(st.nni).tolist()
        assert tel.lsetups().tolist() == np.asarray(st.nsetups).tolist()
        s = tel.summary()
        assert s["steps"] == int(jnp.sum(st.steps))
        assert s["h_hist_log10"]["counts"] and s["order_occupancy"]

    def test_config_driven_telemetry(self):
        ctx = Context(observability=ObservabilityConfig(
            telemetry=True, telemetry_capacity=512))
        sol = integrate(_rob_prob(2), 0.0, 0.1, "ensemble_bdf", ctx=ctx)
        assert sol.telemetry is not None
        assert sol.telemetry.steps().tolist() == \
            np.asarray(sol.stats.steps).tolist()
        # config must not force telemetry onto non-capable families
        sol_erk = integrate(IVP(f=lambda t, y: -y, y0=jnp.ones(2)),
                            0.0, 1.0, "erk:dopri5", ctx=ctx)
        assert sol_erk.telemetry is None

    def test_scalar_bdf_reconciles_exactly(self):
        f, jac, y0b = batched_robertson(1)
        y0 = np.asarray(y0b)[0]
        sf = lambda t, y: f(jnp.asarray(t)[None], y[None, :])[0]
        sjac = lambda t, y: jac(jnp.asarray(t)[None], y[None, :])[0]
        sol = integrate(IVP(f=sf, jac=sjac, y0=y0), 0.0, 0.2, "bdf",
                        telemetry=1024)
        tel = sol.telemetry
        assert not tel.truncated
        assert int(tel.steps()) == int(sol.stats.steps)
        assert int(tel.attempts()) == int(sol.stats.attempts)
        assert int(tel.newton_iters_total()) == int(sol.stats.nni)

    def test_ensemble_dirk_reconciles_exactly(self):
        sol = integrate(_rob_prob(3), 0.0, 0.05,
                        "ensemble_dirk:sdirk2", telemetry=2048)
        tel = sol.telemetry
        assert not tel.truncated
        st = sol.stats
        assert tel.steps().tolist() == np.asarray(st.steps).tolist()
        assert tel.newton_iters_total().tolist() == \
            np.asarray(st.nni).tolist()

    def test_telemetry_rejected_for_explicit_methods(self):
        with pytest.raises(ValueError, match="telemetry"):
            integrate(IVP(f=lambda t, y: -y, y0=jnp.ones(2)),
                      0.0, 1.0, "erk:dopri5", telemetry=64)

    def test_padded_bundle_masks_dead_lanes(self):
        live_n, pad_n, tf = 3, 4, 0.1
        prob = _rob_prob(pad_n)
        tfv = jnp.where(jnp.arange(pad_n) < live_n, tf, 0.0)
        mask = np.arange(pad_n) < live_n
        sol = integrate(prob, 0.0, tfv, "ensemble_bdf", live=mask,
                        telemetry=512)
        tel = sol.telemetry
        st = sol.stats                       # already live-masked
        assert tel.steps().tolist() == np.asarray(st.steps).tolist()
        assert tel.steps()[live_n:].tolist() == [0]
        assert tel.newton_iters_total()[live_n:].tolist() == [0]
        assert tel.newton_iters_total().sum() == int(sol.nni)

    def test_warm_start_leg_reconciles(self):
        prob = _rob_prob(2)
        leg1 = integrate(prob, 0.0, 0.1, "ensemble_bdf",
                         return_session=True, telemetry=512)
        assert leg1.telemetry.steps().tolist() == \
            np.asarray(leg1.stats.steps).tolist()
        leg2 = integrate(IVP(f=prob.f, jac=prob.jac, f_soa=prob.f_soa,
                             jac_soa=prob.jac_soa, y0=leg1.y),
                         0.1, 0.3, "ensemble_bdf",
                         session=leg1.session, return_session=True,
                         telemetry=512)
        tel = leg2.telemetry
        # the leg's ring records the LEG's work, not the cumulative
        # session counters
        assert tel.steps().tolist() == \
            np.asarray(leg2.stats.steps).tolist()
        assert tel.newton_iters_total().tolist() == \
            np.asarray(leg2.stats.nni).tolist()


def _rob_family_prob(k3):
    """Robertson ensemble with per-lane k3 (the standard k1, k2)."""
    f, jac, f_soa, jac_soa = robertson_family()
    nsys = k3.shape[0]
    p = {"k1": jnp.full((nsys,), ROB_PARAMS["k1"]),
         "k2": jnp.full((nsys,), ROB_PARAMS["k2"]), "k3": k3}
    y0 = jnp.tile(jnp.asarray([1.0, 0.0, 0.0]), (nsys, 1))
    return IVP(f=lambda t, y: f(t, y, p), jac=lambda t, y: jac(t, y, p),
               f_soa=lambda t, z: f_soa(t, z, p),
               jac_soa=lambda t, z: jac_soa(t, z, p), y0=y0)


@pytest.fixture(scope="module")
def spread_sol():
    """256 lanes whose k3 spans four decades: their step and Newton
    counts diverge, so the batch's loops run past most lanes."""
    prob = _rob_family_prob(jnp.logspace(5.0, 9.0, 256))
    return integrate(prob, 0.0, 1.0, "ensemble_bdf", telemetry=2048)


class TestLoopTrips:
    """The batch's loop trips against the per-lane step records."""

    def test_trips_reconcile_with_telemetry(self, spread_sol):
        st, tel = spread_sol.stats, spread_sol.telemetry
        assert not tel.truncated
        att = np.asarray(st.attempts)
        assert att.min() < att.max()          # the lanes did diverge
        for name in ("trips", "newton_trips", "setup_trips"):
            v = np.asarray(getattr(st, name))
            assert v.shape == (256,) and (v == v[0]).all(), name
        # one record per step-loop trip; a Newton trip runs while any
        # lane iterates; the lsetup branch runs when any lane needs it
        assert int(st.trips[0]) == att.max() == tel.records
        assert int(st.newton_trips[0]) == \
            int(tel.newton_iters.max(axis=1).sum())
        assert int(st.setup_trips[0]) == \
            int(tel.lsetup_fired.any(axis=1).sum())
        # lane occupancy of both loops is below one
        nsys = att.size
        assert att.sum() < int(st.trips[0]) * nsys
        assert int(np.asarray(st.nni).sum()) < \
            int(st.newton_trips[0]) * nsys

    def test_identical_lanes_run_every_trip(self):
        sol = integrate(_rob_family_prob(jnp.full((8,), 3e7)), 0.0, 1.0,
                        "ensemble_bdf")
        st = sol.stats
        assert np.asarray(st.attempts).tolist() == \
            np.asarray(st.trips).tolist()

    def test_masked_passes_trips_through(self, spread_sol):
        st = spread_sol.stats
        live = np.arange(256) < 100
        m = st.masked(live)
        for name in ("trips", "newton_trips", "setup_trips"):
            assert np.array_equal(np.asarray(getattr(m, name)),
                                  np.asarray(getattr(st, name))), name
        assert np.asarray(m.attempts)[100:].sum() == 0


class TestTimedIntegrate:
    def test_direct_timings_reported(self):
        sol = integrate(_rob_prob(2), 0.0, 0.05, "ensemble_bdf",
                        timed=True)
        assert set(sol.timings) == {"lower", "compile", "execute"}
        assert all(v >= 0.0 for v in sol.timings.values())
        assert sol.timings["compile"] > 0.0
        assert bool(sol.success)

    def test_untimed_has_no_timings(self):
        sol = integrate(_rob_prob(2), 0.0, 0.05, "ensemble_bdf")
        assert sol.timings is None

    def test_profile_config_records_regions_and_logs(self):
        ctx = Context(observability=ObservabilityConfig(
            profile=True, profile_sync=False, log_level="INFO"))
        sol = integrate(_rob_prob(2), 0.0, 0.05, "ensemble_bdf",
                        ctx=ctx)
        assert sol.timings is not None
        names = {s.name for s in ctx.profiler.spans}
        assert {"integrate.lower", "integrate.compile",
                "integrate.execute"} <= names
        assert any(e["event"] == "integrate.done"
                   for e in ctx.logger.events)


# ---------------------------------------------------------------------------
# serving surface: Prometheus text, bundle spans, queue events
# ---------------------------------------------------------------------------

@pytest.fixture(scope="class")
def obs_server():
    fr = robertson_family()
    ctx = Context(observability=ObservabilityConfig(
        profile=True, profile_sync=False, log_level="DEBUG"))
    srv = SolverServer(
        [ProblemFamily("robertson", 3, fr[0], fr[1], fr[2], fr[3])],
        ctx=ctx, bucket_sizes=(4,), max_batch=4, max_wait=1e-3,
        warmup_bundles=0, latency_window=8)
    futs = [srv.submit("robertson", [1.0, 0.0, 0.0], 0.0, 0.2,
                       params=ROB_PARAMS) for _ in range(6)]
    bundles = srv.drain()
    for f in futs:
        assert bool(f.result(timeout=30).success)
    yield srv, bundles
    srv.stop()


class TestServerObservability:
    def test_prometheus_exposition(self, obs_server):
        srv, _ = obs_server
        text = srv.metrics_prometheus()
        assert "# TYPE repro_serve_requests_total counter" in text
        assert "repro_serve_requests_total 6" in text
        assert "repro_serve_bundles_total 2" in text
        assert "repro_serve_latency_seconds_count 6" in text
        assert 'le="+Inf"' in text
        assert ('repro_serve_bucket_requests_total'
                '{family="robertson",n="3",nsys="4"} 6') in text
        assert "repro_context_integrations_total" in text
        assert "repro_serve_occupancy" in text

    def test_bundle_spans_cover_every_bundle(self, obs_server):
        srv, bundles = obs_server
        spans = srv.ctx.profiler.spans
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        for name in ("serve.bundle.queue_wait", "serve.execute",
                     "serve.resolve"):
            assert len(by_name[name]) == bundles, name
        # compiled once per cache miss, in the compile itself
        assert len(by_name["serve.compile"]) == srv.cache.misses >= 1
        assert "serve.bundle.compile" not in by_name
        # queue wait must precede execute on the shared timebase
        qw = by_name["serve.bundle.queue_wait"][0]
        ex = by_name["serve.execute"][0]
        assert qw.t0 <= ex.t1
        trace = srv.ctx.profiler.chrome_trace()
        assert all(e["ph"] == "X" for e in trace["traceEvents"])

    def test_queue_and_bundle_events_logged(self, obs_server):
        srv, bundles = obs_server
        events = [e["event"] for e in srv.ctx.logger.events]
        assert events.count("queue.admit") == 6
        assert events.count("queue.flush") == bundles
        assert events.count("serve.bundle") == bundles

    def test_latency_window_vs_lifetime(self, obs_server):
        srv, _ = obs_server
        m = srv.metrics()
        assert m["latency_samples"] == 6 and m["latency_observed"] == 6
        taken = srv.take_latencies()
        assert len(taken) == 6
        m2 = srv.metrics()
        assert m2["latency_samples"] == 0
        assert m2["latency_observed"] == 6   # lifetime survives
        # the Prometheus histogram is lifetime-backed: still 6
        assert ("repro_serve_latency_seconds_count 6"
                in srv.metrics_prometheus())
