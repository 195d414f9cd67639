"""Serving front-end benchmark: throughput/latency of the dynamic-
batching IVP server (``repro.serve.solver``) under load.

Drives a :class:`~repro.serve.solver.server.SolverServer` with
mixed-shape kinetics traffic (parametric Robertson n=3 + linear decay
chain n=6 — distinct buckets, so the trace cache is exercised across
families) at three load points per backend and reports per-point
p50/p99 latency, systems/sec, and batch occupancy.  The table lands in
``BENCH_serving.json`` via the ``json_artifact`` contract of
``benchmarks/run.py``.

Backends: ``jnp`` (XLA-fused dispatch, the performance-relevant CPU
path) at real load; ``pallas-interpret`` at reduced counts/horizons
(interpret mode is a correctness emulation — its rows validate that the
serving stack composes with the kernel backend, not TPU performance).

``smoke()`` is the CI acceptance run (``--smoke``): >= 10^4 mixed-shape
requests through one server, asserting the serving invariants —
trace-cache hit rate >= 95% with ZERO steady-state recompiles after the
warmup window, batch occupancy >= 80%, warm-start continuations taking
strictly fewer steps than a cold restart of the same leg, and a short
pallas-interpret burst solving successfully.  It then validates the
observability surface: the Prometheus text exposition must parse and
reconcile with ``metrics()``, and a profiled mini-run must produce a
Chrome-trace/Perfetto timeline carrying queue-wait / compile / execute
spans for EVERY flushed bundle.

``check()`` is the ``--check`` gate hook: a scaled-down smoke whose
functional invariants (hit rate / steady misses / occupancy /
warm-start win) gate CI deterministically; latency/throughput rows are
always informational (they are host properties, per the
REPRO_PERF_CHECK rationale in ensemble_bench).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.context import Context
from repro.core.policies import ExecPolicy, XLA_FUSED
from repro.core.problems import decay_chain_family, robertson_family
from repro.serve.solver import ProblemFamily, RetryAfter, SolverServer

LOAD_POINTS_JNP = (256, 1024, 4096)       # requests per load point
LOAD_POINTS_PALLAS = (8, 16, 32)          # interpret mode: emulation cost
TF_JNP = 0.4
TF_PALLAS = 0.02
SMOKE_REQUESTS = 10_240                   # >= 10^4 acceptance floor
SMOKE_HIT_RATE = 0.95
SMOKE_OCCUPANCY = 0.80

# module-global artifact picked up by benchmarks/run.py after run()
json_artifact = None


def _families():
    fr = robertson_family()
    fd = decay_chain_family(6)
    return (ProblemFamily("robertson", 3, fr[0], fr[1], fr[2], fr[3]),
            ProblemFamily("decay6", 6, fd[0], fd[1], fd[2], fd[3]))


def _make_server(policy: ExecPolicy, bucket_sizes, max_batch,
                 max_wait: float = 1e-3, max_depth: int = 4096
                 ) -> SolverServer:
    # warmup window: a saturated poll drains one family's full chunk
    # run before touching the next bucket, so the second family's
    # first-touch compile can land ~max_depth/(2*max_batch) bundles in
    return SolverServer(list(_families()), Context(policy=policy),
                        bucket_sizes=bucket_sizes, max_batch=max_batch,
                        max_wait=max_wait, max_depth=max_depth,
                        warmup_bundles=max(16, max_depth // max_batch))


def _submit_mixed(srv: SolverServer, nreq: int, tf: float, seed: int,
                  decay_every: int = 2):
    """Submit ``nreq`` mixed-family requests with per-request physics,
    pumping the server whenever admission pushes back."""
    rng = np.random.default_rng(seed)
    futs = []
    for i in range(nreq):
        if decay_every and i % decay_every == 1:
            kw = dict(family="decay6", y0=np.ones(6), t0=0.0, tf=tf,
                      params={"k": rng.uniform(0.1, 5.0, 6)})
        else:
            kw = dict(family="robertson", y0=[1.0, 0.0, 0.0], t0=0.0,
                      tf=tf,
                      params={"k1": 0.04,
                              "k2": 1e4 * (0.5 + rng.random()),
                              "k3": 3e7 * 10.0 ** rng.uniform(-1, 1)})
        while True:
            try:
                futs.append(srv.submit(**kw))
                break
            except RetryAfter:
                srv.pump()          # backpressure: drain, then retry
    return futs


def _load_point(srv: SolverServer, nreq: int, tf: float, seed: int,
                decay_every: int = 2) -> dict:
    """One measured point: submit ``nreq`` requests open-loop, drain,
    report wall clock, percentiles, and occupancy over the point."""
    m0 = srv.metrics()
    srv.take_latencies()
    t0 = time.perf_counter()
    futs = _submit_mixed(srv, nreq, tf, seed, decay_every)
    srv.drain()
    wall = time.perf_counter() - t0
    ok = all(bool(f.result().success) for f in futs)
    lat = sorted(srv.take_latencies())
    m1 = srv.metrics()
    live = m1["live_lanes"] - m0["live_lanes"]
    padded = m1["padded_lanes"] - m0["padded_lanes"]
    q = SolverServer._quantile
    return {"requests": nreq, "wall_s": wall,
            "systems_per_sec": nreq / wall,
            "latency_p50_ms": 1e3 * q(lat, 0.50),
            "latency_p99_ms": 1e3 * q(lat, 0.99),
            "occupancy": (live / padded) if padded else 0.0,
            "all_success": ok}


def run():
    global json_artifact
    rows = []
    table = {"workload": "dynamic-batching IVP serving "
                         "(robertson n=3 + decay chain n=6)",
             "units": "systems_per_sec / latency_ms",
             "note": ("pallas rows are interpret-mode CPU emulation "
                      "(stack-composition check, not TPU perf); load "
                      "points are open-loop request counts per backend"),
             "backends": {}}
    configs = (
        # (name, policy, load points, tf, bucket sizes, max_batch,
        #  decay_every) — pallas runs robertson-only (decay_every=0):
        # interpret-mode compiles are minutes-scale, one trace is enough
        # for the composition check
        ("jnp", XLA_FUSED, LOAD_POINTS_JNP, TF_JNP, (32, 64, 128), 128, 2),
        ("pallas_interpret",
         ExecPolicy(backend="pallas", interpret=True),
         LOAD_POINTS_PALLAS, TF_PALLAS, (8,), 8, 0),
    )
    for name, policy, points, tf, sizes, max_batch, mix in configs:
        srv = _make_server(policy, sizes, max_batch)
        # warmup: populate the trace cache so load points measure
        # steady-state serving, not first-touch compiles
        warm = _submit_mixed(srv, 2 * max_batch, tf, seed=0,
                             decay_every=mix)
        srv.drain()
        [f.result() for f in warm]
        entries = []
        for i, nreq in enumerate(points):
            res = _load_point(srv, nreq, tf, seed=i + 1, decay_every=mix)
            entries.append(res)
            rows.append((f"serving.{name}.n{nreq}",
                         1e6 * res["wall_s"] / nreq,
                         f"sys_per_s={res['systems_per_sec']:.3e},"
                         f"p50_ms={res['latency_p50_ms']:.2f},"
                         f"p99_ms={res['latency_p99_ms']:.2f},"
                         f"occ={res['occupancy']:.2f}"))
        m = srv.metrics()
        table["backends"][name] = {
            "load_points": entries,
            "trace_cache": m["trace_cache"],
            "steady_misses": m["steady_misses"],
            "occupancy_cumulative": m["occupancy"]}
    json_artifact = ("BENCH_serving.json", table)
    return rows


def _validate_prometheus(text: str, m: dict) -> None:
    """The scrape must be well-formed text exposition AND reconcile
    with the dict ``metrics()`` reports."""
    lines = [ln for ln in text.splitlines() if ln]
    assert lines, "empty Prometheus exposition"
    seen_types = {}
    for ln in lines:
        if ln.startswith("# TYPE "):
            _, _, name, kind = ln.split(" ", 3)
            seen_types[name] = kind
        else:
            assert ln.startswith("#") or " " in ln, f"malformed: {ln!r}"
    assert seen_types.get("repro_serve_requests_total") == "counter"
    assert seen_types.get("repro_serve_latency_seconds") == "histogram"
    assert seen_types.get("repro_serve_occupancy") == "gauge"
    assert f"repro_serve_requests_total {m['requests']}" in text
    assert f"repro_serve_bundles_total {m['bundles']}" in text
    assert ("repro_serve_latency_seconds_count "
            f"{m['latency_observed']}") in text
    assert 'repro_serve_latency_seconds_bucket' in text
    assert 'le="+Inf"' in text
    # failure-path counters reconcile with metrics() (zero on a clean
    # run; the chaos suite exercises the nonzero side)
    assert (f"repro_serve_degraded_total {m['degraded']}") in text
    for reason, count in m["failures"].items():
        assert (f'repro_serve_failures_total{{reason="{reason}"}} '
                f"{count}") in text
    # the Context counters ride the same scrape
    assert "repro_context_integrations_total" in text


def _profiled_trace_smoke(nreq: int = 96, verbose: bool = True) -> None:
    """A profiled mini-run: every flushed bundle must land queue-wait /
    execute / resolve spans on the profiler timeline, each compile its
    own, and the exported Chrome trace must be loadable, well-formed
    JSON."""
    import json as _json
    import os
    import tempfile

    from repro.observability import ObservabilityConfig

    fr = robertson_family()
    ctx = Context(observability=ObservabilityConfig(
        profile=True, profile_sync=False))
    srv = SolverServer(
        [ProblemFamily("robertson", 3, fr[0], fr[1], fr[2], fr[3])],
        ctx=ctx, bucket_sizes=(32,), max_batch=32, max_wait=1e-3,
        warmup_bundles=0)
    futs = _submit_mixed(srv, nreq, TF_JNP, seed=23, decay_every=0)
    bundles = srv.drain()
    assert all(bool(f.result().success) for f in futs)
    spans = {}
    for s in srv.ctx.profiler.spans:
        spans.setdefault(s.name, []).append(s)
    for name in ("serve.bundle.queue_wait", "serve.execute",
                 "serve.resolve"):
        got = len(spans.get(name, ()))
        assert got == bundles, \
            f"{name}: {got} spans for {bundles} flushed bundles"
    assert len(spans.get("serve.compile", ())) == srv.cache.misses
    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        srv.ctx.profiler.export_chrome_trace(path)
        with open(path) as fh:
            doc = _json.load(fh)
        ev = doc["traceEvents"]
        assert all(e["ph"] == "X" and e["dur"] >= 0 and e["ts"] >= 0
                   for e in ev)
        per_bundle = [e for e in ev
                      if e["name"] in ("serve.bundle.queue_wait",
                                       "serve.execute", "serve.resolve")]
        assert len(per_bundle) == 3 * bundles
    finally:
        os.unlink(path)
    if verbose:
        print(f"serving.perfetto,{bundles},spans_per_bundle=3,"
              f"trace_events={len(ev)}", flush=True)


def smoke(nreq: int = SMOKE_REQUESTS, verbose: bool = True,
          hit_rate_floor: float = SMOKE_HIT_RATE) -> dict:
    """The CI acceptance run: >= 10^4 mixed-shape requests through one
    jnp-backed server, then the serving invariants are ASSERTED (not
    just printed).  Returns the final metrics dict.

    ``hit_rate_floor`` defaults to the 95% acceptance bar, which is a
    statement about the >= 10^4-request run (2 cold compiles amortized
    over ~80 bundles); scaled-down runs must scale it too (check()
    does) — steady_misses == 0 is the scale-free invariant either way.
    """
    srv = _make_server(XLA_FUSED, bucket_sizes=(128,), max_batch=128)
    futs = _submit_mixed(srv, nreq, TF_JNP, seed=7)
    srv.drain()
    sols = [f.result() for f in futs]
    assert all(bool(s.success) for s in sols), "some requests failed"
    m = srv.metrics()
    cache = m["trace_cache"]
    assert cache["hit_rate"] >= hit_rate_floor, \
        f"trace-cache hit rate {cache['hit_rate']:.3f} < {hit_rate_floor}"
    assert m["steady_misses"] == 0, \
        f"{m['steady_misses']} steady-state recompiles (want 0)"
    assert m["occupancy"] >= SMOKE_OCCUPANCY, \
        f"occupancy {m['occupancy']:.2f} < {SMOKE_OCCUPANCY}"

    # warm-start win: continue one robertson trajectory via its session
    # handle vs a cold restart of the SAME leg (same bundle, same
    # trace).  The leg keeps the ORIGINAL request's rate constants —
    # the session's Nordsieck history describes THAT chemistry; a
    # continuation under different params is a valid but history-
    # mismatched restart with no step-count guarantee.
    p = {"k1": 0.04, "k2": 1.2e4, "k3": 3e7}
    f0 = srv.submit("robertson", [1.0, 0.0, 0.0], 0.0, TF_JNP, params=p)
    srv.drain()
    s = f0.result()
    leg = dict(family="robertson", y0=np.asarray(s.y), t0=float(s.t),
               tf=float(s.t) + TF_JNP, params=p)
    f_warm = srv.submit(**leg, session=s.session)
    f_cold = srv.submit(**leg)
    srv.drain()
    warm_steps = int(f_warm.result().stats.steps)
    cold_steps = int(f_cold.result().stats.steps)
    assert warm_steps < cold_steps, \
        f"warm-start took {warm_steps} steps vs cold {cold_steps}"

    # pallas-interpret burst: the serving stack composes with the
    # kernel backend (emulation-mode, so tiny horizon and bundle)
    psrv = _make_server(ExecPolicy(backend="pallas", interpret=True),
                        bucket_sizes=(8,), max_batch=8)
    pfuts = _submit_mixed(psrv, 8, TF_PALLAS, seed=11, decay_every=0)
    psrv.drain()
    assert all(bool(f.result().success) for f in pfuts), \
        "pallas-interpret burst failed"

    # observability surface: the Prometheus scrape must reconcile with
    # metrics(), and a profiled run must land per-bundle spans on a
    # valid Perfetto/Chrome-trace timeline
    _validate_prometheus(srv.metrics_prometheus(), srv.metrics())
    _profiled_trace_smoke(verbose=verbose)
    if verbose:
        print(f"serving.smoke,{nreq},hit_rate={cache['hit_rate']:.3f},"
              f"steady_misses={m['steady_misses']},"
              f"occupancy={m['occupancy']:.2f},"
              f"warm_steps={warm_steps},cold_steps={cold_steps}",
              flush=True)
    return m


def check() -> bool:
    """``benchmarks/run.py --check`` hook: the functional serving
    invariants gate at a scaled-down request count (deterministic on
    any host); latency is printed as INFO only — wall-clock serving
    numbers are host properties, same rationale as the
    REPRO_PERF_CHECK demotion in ensemble_bench."""
    try:
        # 2048 requests = 16 bundles -> 2 cold compiles cap the hit
        # rate at 14/16; the scale-free gates (zero steady-state
        # recompiles, occupancy, warm-start win) are unchanged
        m = smoke(nreq=2048, verbose=False, hit_rate_floor=0.85)
    except AssertionError as e:
        print(f"check.serving.smoke,FAIL,{e}", flush=True)
        return False
    cache = m["trace_cache"]
    print(f"check.serving.smoke,PASS,"
          f"hit_rate={cache['hit_rate']:.3f},"
          f"steady_misses={m['steady_misses']},"
          f"occupancy={m['occupancy']:.2f}", flush=True)
    print(f"check.serving.latency,INFO,"
          f"p50_s={m['latency_p50_s']:.4f},"
          f"p99_s={m['latency_p99_s']:.4f}", flush=True)
    return True


if __name__ == "__main__":
    import json
    import sys
    jax.config.update("jax_enable_x64", True)
    if "--smoke" in sys.argv[1:]:
        smoke()
        sys.exit(0)
    for row in run():
        print(",".join(str(x) for x in row))
    if json_artifact:
        path, payload = json_artifact
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"wrote {path}")
