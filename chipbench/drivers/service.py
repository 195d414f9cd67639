"""Open loop of single-system requests through ``SolverServer``.

Independent clients each send one IVP of the deployment's problem (its
own parameters, cold: no session) at Poisson arrival times fixed by the
seed (``chipbench.gen.poisson_schedule``).  The server runs as users
run it: ``start()`` (its pump on a background thread), ``submit()``
returning a Future, its own bucket sizes and ``max_wait``.

Latency is taken on the client side, from each request's *due* time to
its Future resolving, so a stall delays every request behind it and a
late generator shows as latency; how late the generator ran is printed
apart.  Every request due in the window is counted: one that fails, is
refused or is not answered within a minute of the window's close is
infinitely late.

A per-component atol is handed to the server in units of each
component's atol (:mod:`chipbench.units`): requests carry ``y0`` in
those units and the scalar atol, answers are read back in the source's.

Set-up warms only the shapes this traffic uses, through the server's
own ``submit``: one bundle of each live count up to ``warm_live`` (the
traffic file's bound on one bundle's live requests), of requests that
end where they start, then one request integrated to ``tf``.  The
window counts the compiles it holds, expected 0, so a count the warm-up
left cold shows.
"""
from __future__ import annotations

import concurrent.futures as cf
import functools
import time

import numpy as np

from chipbench import check as _check
from chipbench import gen, layout, units
from chipbench.devtrace import WINDOW_SPAN
from chipbench.harness import Window, clock

DRAIN_S = 60.0       # how long past the window's close an answer may come


class _CompileCounter:
    """Programs built while ``active``: every lowering, which comes
    before a compile and before a load from the persistent cache alike
    (a JAX monitoring listener).  An eager operation whose program was
    evicted from JAX's in-memory caches is lowered again and counts."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.count += 1


def _stamp(done, i, fut):
    done[i] = clock()


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-quantile (infinite values stay infinite)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return float("inf")
    k = int(np.ceil(q * v.size)) - 1
    return float(v[min(max(k, 0), v.size - 1)])


class Driver:
    def __init__(self, cell, seed, devs, *, seconds, span, log):
        self.cell, self.seed, self.devs = cell, int(seed), devs
        self.span, self.log = span, log
        self.cfg, self.traffic = cell.config, cell.traffic
        self.problem = layout.problem(cell.root, self.cfg["problem"])
        if cell.traffic["generator"] != "poisson":
            raise ValueError(f"the {cell.config['driver']} driver drives "
                             f"poisson traffic, not "
                             f"{cell.traffic['generator']!r}")
        self.due = gen.poisson_schedule(self.seed,
                                        float(self.traffic["rate_per_s"]),
                                        seconds)
        self._answers = []

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core.context import Context
        from repro.core.policies import ExecPolicy
        from repro.serve.solver import (ProblemFamily, RetryAfter,
                                        SolverServer)

        cfg = self.cfg
        self._refused = RetryAfter
        self.dtype = jnp.dtype(cfg["dtype"])
        self.atol, self.scale = units.split(cfg["atol"])
        self.z0 = (np.asarray(cfg["y0"], np.float64) / self.scale).astype(
            self.dtype)
        fam = ProblemFamily(cfg["problem"], self.z0.size,
                            *units.family_in_units(self.problem.family(),
                                                   self.scale, self.dtype))
        self.srv = SolverServer(
            fam, Context(policy=ExecPolicy(backend=cfg["backend"])))
        self.compiles = _CompileCounter()
        # the queue's observability hook also reports each flushed
        # bundle's live requests
        self.flushed = []
        forward = self.srv.queue.on_event

        def on_event(event, fields):
            if event == "queue.flush":
                self.flushed.append(fields["live"])
            if forward is not None:
                forward(event, fields)

        self.srv.queue.on_event = on_event
        self.params = self.request_params(len(self.due), self.seed)
        self._warm(int(self.traffic["warm_live"]))

    def request_params(self, count: int, seed: int):
        """Each request's parameters (Python floats, as a client sends
        them), drawn on the device from ``seed`` in one call."""
        import jax

        params = jax.device_get(jax.jit(
            functools.partial(gen.draw_params, n=count,
                              spec=self.traffic["params"],
                              dtype=self.dtype))(gen.seed_key(seed)))
        return [{k: float(v[i]) for k, v in params.items()}
                for i in range(count)]

    def _request(self, params, tf):
        cfg = self.cfg
        return self.srv.submit(cfg["problem"], self.z0, float(cfg["t0"]),
                               tf, rtol=cfg["rtol"], atol=self.atol,
                               params=params)

    def _warm(self, live: int) -> None:
        """One bundle of each live count up to ``live``, of requests
        that end where they start (``tf = t0``), then one request
        integrated to ``tf``, so that the window holds no first
        execution of the step loop's body."""
        t0 = float(self.cfg["t0"])
        for count in range(1, live + 1):
            futs = [self._request(self.params[0], t0) for _ in range(count)]
            self.srv.drain()
            for fut in futs:
                fut.result()
        fut = self._request(self.params[0], float(self.cfg["tf"]))
        self.srv.drain()
        fut.result()

    def timed_programs(self):
        """The server's bundle programs (``SolverServer._compile`` jits
        its ``run``)."""
        return ["jit_run"]

    def hlo_texts(self):
        return [entry.fn.as_text() for entry in
                (self.srv.cache.get(k, None)[0] for k in self.srv.cache.keys())]

    # -- the window -----------------------------------------------------

    def run_schedule(self, due: np.ndarray, params, close_s: float):
        """Offer ``due`` (s from the start) with ``params`` each; return
        ``(latency_s, late_s, futures, counters)``.  Latency of a failed
        or unanswered request is infinite."""
        n = len(due)
        done = np.full(n, np.nan)
        late = np.zeros(n)
        futs = [None] * n
        srv, cfg = self.srv, self.cfg
        m0 = srv.metrics()
        self.flushed.clear()
        self.compiles.count = 0
        self.compiles.active = True
        srv.start()
        try:
            with self.span(WINDOW_SPAN):
                start = clock()
                for i in range(n):
                    target = start + due[i]
                    wait = target - clock()
                    if wait > 0:
                        time.sleep(wait)
                    with self.span("bench.submit"):
                        late[i] = clock() - target
                        try:
                            fut = self._request(params[i], float(cfg["tf"]))
                        except self._refused as exc:
                            fut = cf.Future()
                            fut.set_exception(exc)
                    fut.add_done_callback(functools.partial(_stamp, done, i))
                    futs[i] = fut
                rest = start + close_s - clock()
                if rest > 0:
                    time.sleep(rest)
            cf.wait(futs, timeout=max(0.0, start + close_s + DRAIN_S
                                      - clock()))
        finally:
            srv.stop()
            self.compiles.active = False
        m1 = srv.metrics()
        lat = np.full(n, np.inf)
        for i, fut in enumerate(futs):
            if fut.done() and fut.exception() is None:
                lat[i] = done[i] - (start + due[i])
        counters = {k: float(m1[k] - m0[k]) for k in
                    ("live_lanes", "padded_lanes", "bundles", "requests")}
        counters["compiles"] = float(self.compiles.count)
        counters["max_live"] = float(max(self.flushed, default=0))
        return lat, late, futs, counters

    def window(self, seconds: float) -> Window:
        lat, late, futs, counters = self.run_schedule(self.due, self.params,
                                                      seconds)
        sols = [(i, f.result()) for i, f in enumerate(futs)
                if f.done() and f.exception() is None]
        self._answers = [(i, np.asarray(sol.y, np.float64) * self.scale)
                         for i, sol in sols]
        failed = len(futs) - len(sols)
        waits = [sol.timings["queue_wait"] for _, sol in sols]
        tails = {q: 1e3 * percentile(lat, q)
                 for q in (0.50, 0.75, 0.90, 0.95, 0.99)}
        notes = [
            "latency from the due time: " + ", ".join(
                f"p{round(100 * q)} {v!r} ms" for q, v in tails.items()),
            f"generator late: p50 {1e3 * percentile(late, 0.5)!r} ms, "
            f"p99 {1e3 * percentile(late, 0.99)!r} ms, max "
            f"{1e3 * float(late.max(initial=0.0))!r} ms",
            f"programs lowered in the window: {int(counters['compiles'])} "
            f"(expected 0); bundles {int(counters['bundles'])}, most live "
            f"requests in one {int(counters['max_live'])} (warmed up to "
            f"{self.traffic['warm_live']}); requests {len(futs)}, failed "
            f"{failed}"]
        return Window(
            end_to_end={"serve_p50_ms": tails[0.50],
                        "serve_p95_ms": tails[0.95]},
            attempted=len(futs), failed=failed, counters=counters,
            samples={"queue_wait_s": waits}, notes=notes)

    def reseed(self, seed: int, seconds: float) -> None:
        """Another seed's schedule and requests, on the same server."""
        self.seed = int(seed)
        self.due = gen.poisson_schedule(self.seed,
                                        float(self.traffic["rate_per_s"]),
                                        seconds)
        self.params = self.request_params(len(self.due), self.seed)

    def release(self) -> None:
        """The server and its programs go; the answers are on the
        host."""
        self.srv = None

    # -- correct --------------------------------------------------------

    def compared(self):
        """``(y, y0, params)`` of the answered requests."""
        idx = [i for i, _ in self._answers]
        n = np.asarray(self.cfg["y0"]).size
        y = np.stack([y for _, y in self._answers]) if idx else \
            np.zeros((0, n))
        params = {k: np.asarray([self.params[i][k] for i in idx])
                  for k in self.params[0]}
        y0 = np.tile(np.asarray(self.cfg["y0"], np.float64), (len(idx), 1))
        return y, y0, params

    def check(self):
        lim = self.cfg["check"]["limits"]
        failed = len(self.due) - len(self._answers)
        err = _check.against_reference(self.problem, self.cfg,
                                       *self.compared())
        return [_check.Check("worst_err", err, lim["worst_err"]),
                _check.Check("failed_requests", float(failed),
                             lim["failed_requests"])]
