"""The serving driver: a synchronous core with an async facade.

``SolverServer`` composes the three serving layers over the solver
stack: the :class:`~repro.serve.solver.queue.AdmissionQueue` groups
requests into shape buckets, the
:class:`~repro.serve.solver.trace_cache.TraceCache` maps each padded
bundle shape to a compiled executable, and every bundle is pumped
through the unified ``IVP.integrate`` front-end with a
:class:`~repro.core.batched.SolverSession` carry — so cold requests
and warm-start continuations mix freely in one bundle under one trace.

The **synchronous core** is :meth:`pump`: flush due bundles, execute
each, resolve its per-request futures.  Tests and benchmarks drive it
directly (deterministic, no threads); the **async facade**
(:meth:`start`/:meth:`stop`) runs the same pump on a background thread
so :meth:`submit` is a non-blocking enqueue returning a
``concurrent.futures.Future``.

Every response is a full :class:`~repro.core.ivp.Solution` restricted
to the request's lane — padded dead lanes never leak into a client's
stats — extended with the serving wall-clock split
(``timings = {"queue_wait", "compile", "execute"}``; compile is the
bundle's trace+compile cost, nonzero only for the bundle that missed
the trace cache) and the warm-start ``session`` handle for follow-up
requests.  :meth:`metrics` reports queue depth, batch occupancy
(live vs padded lanes), p50/p99 latency, and the trace-cache counters.
"""
from __future__ import annotations

import random
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import status as _status
from repro.core.batched import SolverSession
from repro.core.context import Context
from repro.core.ivp import IVP, Solution, integrate
from repro.core.policies import XLA_FUSED

from .queue import AdmissionQueue, Bundle, IVPRequest, RetryAfter
from .trace_cache import TraceCache, TraceKey

__all__ = ["ProblemFamily", "SolverServer", "RetryAfter",
           "SolverError", "DeadlineExceeded"]


class SolverError(RuntimeError):
    """A request's lane ended with a non-success CV_*-style retcode.

    Only the OFFENDING lane's Future fails with this — bundle-mates
    resolve normally (fault containment).  Carries the structured
    status so clients can dispatch on it:

    ``retcode``      — the integer flag (:mod:`repro.core.status`)
    ``retcode_name`` — its symbolic name (``"CONV_FAILURE"``, ...)
    ``stats``        — the lane's :class:`~repro.core.batched.
                       EnsembleStats` slice (steps, attempts, netf,
                       ncfn, ... for THIS lane)
    """

    def __init__(self, message: str, *, retcode: int = 0,
                 stats: Any = None):
        super().__init__(message)
        self.retcode = int(retcode)
        self.retcode_name = _status.retcode_name(retcode)
        self.stats = stats


class DeadlineExceeded(SolverError):
    """The request's deadline passed before its bundle executed; it was
    shed at flush time — no solver compute was spent on it."""


@dataclass(frozen=True)
class ProblemFamily:
    """A served problem class: parametric batched RHS/Jacobian.

    The callables take the bundle's stacked per-request ``params``
    pytree as a third argument (traced data, so new parameter values
    never recompile): ``f(t:(nsys,), y:(nsys,n), params) -> (nsys,n)``,
    ``jac -> (nsys,n,n)``; the optional SoA forms follow the hot-loop
    convention (``f_soa(t, y:(n,nsys), params) -> (n,nsys)``,
    ``jac_soa -> (n,n,nsys)``).  ``params=None`` families close over
    everything.
    """

    name: str
    n: int
    f: Callable
    jac: Callable
    f_soa: Optional[Callable] = None
    jac_soa: Optional[Callable] = None


@dataclass
class _CompiledBundle:
    fn: Any            # AOT-compiled (session, tf, params) -> (y, st, sess)
    compile_s: float   # trace + lower + compile wall clock
    meta: dict         # trace-time Solution fields (method, solver names,
    #                    workspace bytes) reused for every hit


#: request-latency histogram bucket upper bounds (seconds) — the
#: Prometheus exposition adds the implicit +Inf bucket
_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                    0.25, 0.5, 1.0, 2.5, 5.0)


class _LatencyRing:
    """Fixed-size latency sample window + lifetime histogram totals.

    Replaces the grow-then-truncate list: observations land in a
    preallocated ring (O(1) per sample, no 100k-entry spike before the
    truncate) and percentile reads copy out at most ``size`` samples.
    The Prometheus accumulators (per-bucket counts / sum / count) are
    LIFETIME totals and survive :meth:`clear` — scrapes stay monotone
    even when a benchmark drains the percentile window per load point.
    """

    def __init__(self, size: int = 8192,
                 buckets: Tuple[float, ...] = _LATENCY_BUCKETS):
        self.size = int(size)
        if self.size < 1:
            raise ValueError("latency window must hold >= 1 sample")
        self.buckets = tuple(buckets)
        self._slots = [0.0] * self.size
        self._pos = 0
        self._n = 0
        self.total = 0                  # lifetime observation count
        self.sum_s = 0.0                # lifetime latency sum
        # non-cumulative per-bucket counts, last slot = +Inf overflow
        self.bucket_counts = [0] * (len(self.buckets) + 1)

    def observe(self, v: float) -> None:
        self._slots[self._pos] = v
        self._pos = (self._pos + 1) % self.size
        self._n = min(self._n + 1, self.size)
        self.total += 1
        self.sum_s += v
        for i, ub in enumerate(self.buckets):
            if v <= ub:
                self.bucket_counts[i] += 1
                break
        else:
            self.bucket_counts[-1] += 1

    @property
    def count(self) -> int:
        """Samples currently in the percentile window."""
        return self._n

    def window(self) -> List[float]:
        """The window's samples, oldest first."""
        if self._n < self.size:
            return self._slots[:self._n]
        return self._slots[self._pos:] + self._slots[:self._pos]

    def clear(self) -> List[float]:
        """Return the window and reset it (lifetime totals persist)."""
        out = self.window()
        self._pos = 0
        self._n = 0
        return out


class SolverServer:
    """Dynamic-batching IVP server over the ensemble solver stack."""

    def __init__(self, families, ctx: Optional[Context] = None, *,
                 method: str = "ensemble_bdf", order: int = 5,
                 lin_solver=None,
                 bucket_sizes: Optional[Tuple[int, ...]] = None,
                 max_batch: Optional[int] = None,
                 max_wait: float = 2e-3, max_depth: int = 4096,
                 cache_size: int = 32, max_steps: int = 100_000,
                 warmup_bundles: int = 16,
                 clock: Callable[[], float] = time.monotonic,
                 latency_window: int = 8192):
        if isinstance(families, ProblemFamily):
            families = [families]
        self.families: Dict[str, ProblemFamily] = {
            fam.name: fam for fam in families}
        if not self.families:
            raise ValueError("SolverServer needs at least one ProblemFamily")
        self.ctx = ctx if ctx is not None else Context()
        self.method = method
        self.order = order
        self.lin_solver = lin_solver
        self.max_steps = max_steps
        self.clock = clock
        self.dtype = str(jnp.asarray(0.0).dtype)
        if bucket_sizes is None:
            from .queue import bucket_sizes_from_bench
            bucket_sizes = bucket_sizes_from_bench()
        self.queue = AdmissionQueue(bucket_sizes=bucket_sizes,
                                    max_batch=max_batch,
                                    max_wait=max_wait,
                                    max_depth=max_depth,
                                    dtype=self.dtype, clock=clock,
                                    on_event=self._queue_event)
        self.cache = TraceCache(maxsize=cache_size)
        # surface the cache counters through ctx.dispatch_report()
        self.ctx.trace_cache = self.cache
        self.warmup_bundles = int(warmup_bundles)
        self._lock = threading.Lock()       # queue admission/flush
        self._mlock = threading.Lock()      # metrics accumulators
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lat = _LatencyRing(latency_window)
        self._requests = 0
        self._bundles = 0
        self._live_lanes = 0
        self._padded_lanes = 0
        self._steady_misses = 0
        # fault-containment accumulators: failed requests by reason
        # (retcode name / "deadline" / "exec_error") and bundles
        # re-pumped under the jnp oracle policy (backend fallback)
        self._failures: Dict[str, int] = {}
        self._degraded = 0
        # per-bucket throughput: (family, n, nsys) -> accumulators
        self._bucket_stats: Dict[Tuple[str, int, int], dict] = {}

    def _queue_event(self, event: str, fields: dict) -> None:
        """AdmissionQueue observability hook -> the context logger
        (rejects are WARNING — they shed client load; the rest DEBUG)."""
        log = self.ctx.logger
        if not log.enabled:
            return
        if event == "queue.reject":
            log.warning(event, **fields)
        else:
            log.debug(event, **fields)

    # ------------------------------------------------------------------
    # submission (async facade surface)
    # ------------------------------------------------------------------

    def submit(self, family: str, y0, t0: float, tf: float, *,
               rtol: float = 1e-6, atol: float = 1e-9,
               params: Any = None, session: Any = None,
               method: Optional[str] = None,
               deadline: Optional[float] = None) -> Future:
        """Enqueue one IVP; returns a Future resolving to its
        :class:`~repro.core.ivp.Solution` (with ``timings`` and a
        warm-start ``session``).  Raises :class:`RetryAfter` when the
        queue is at depth — resubmit after ``exc.retry_after`` seconds.

        ``deadline`` is a RELATIVE budget in seconds: if the request is
        still queued when its bundle flushes past ``now + deadline``,
        it is shed with :class:`DeadlineExceeded` before any compute.
        A lane that fails inside the solver resolves its Future with a
        typed :class:`SolverError` (retcode + per-lane stats) while its
        bundle-mates resolve normally.
        """
        fam = self.families.get(family)
        if fam is None:
            raise ValueError(f"unknown family {family!r}; registered: "
                             f"{sorted(self.families)}")
        y0 = jnp.asarray(y0, self.dtype)
        if y0.shape != (fam.n,):
            raise ValueError(f"family {family!r} serves n={fam.n} "
                             f"systems; got y0 shape {tuple(y0.shape)}")
        if session is not None and (session.n != fam.n or
                                    session.nsys != 1):
            raise ValueError(
                f"session must be a single-lane handle for n={fam.n} "
                f"(got n={session.n}, nsys={session.nsys})")
        abs_deadline = None
        if deadline is not None:
            if deadline <= 0:
                raise ValueError(f"deadline must be > 0 (relative "
                                 f"seconds); got {deadline!r}")
            abs_deadline = self.clock() + float(deadline)
        req = IVPRequest(family=family, y0=y0, t0=float(t0),
                         tf=float(tf), rtol=rtol, atol=atol,
                         method=method or self.method, params=params,
                         session=session, deadline=abs_deadline,
                         future=Future())
        with self._lock:
            self.queue.offer(req)      # may raise RetryAfter
        self._wake.set()
        return req.future

    def submit_with_retry(self, family: str, y0, t0: float, tf: float,
                          *, retries: int = 6, jitter: float = 0.5,
                          seed: Optional[int] = None,
                          sleep: Callable[[float], None] = time.sleep,
                          **kw) -> Future:
        """:meth:`submit` with jittered exponential backoff on
        :class:`RetryAfter`.

        The rejection's depth-proportional ``retry_after`` hint seeds
        the delay, doubled per consecutive reject and spread by up to
        ``jitter * delay`` of seeded uniform noise so a rejected cohort
        does not re-arrive in lockstep.  ``seed`` makes the jitter
        deterministic (tests/chaos); ``sleep`` is injectable for
        synchronous drivers that pump the server themselves between
        attempts.  Re-raises the final :class:`RetryAfter` once
        ``retries`` rejections have been consumed.
        """
        rng = random.Random(seed)
        for attempt in range(retries + 1):
            try:
                return self.submit(family, y0, t0, tf, **kw)
            except RetryAfter as exc:
                if attempt >= retries:
                    raise
                delay = exc.retry_after * (2.0 ** attempt)
                delay *= 1.0 + jitter * rng.random()
                sleep(delay)
        raise AssertionError("unreachable")

    # ------------------------------------------------------------------
    # the synchronous core
    # ------------------------------------------------------------------

    def pump(self, now: Optional[float] = None,
             flush_all: bool = False) -> int:
        """Flush due bundles and execute them; returns bundles run.
        The deterministic core — tests drive it directly."""
        with self._lock:
            bundles = self.queue.poll(now, flush_all=flush_all)
        if not bundles:
            return 0
        with self.ctx.profiler.region("serve.pump", cat="serve",
                                      sync=False, bundles=len(bundles)):
            for bundle in bundles:
                self._execute(bundle)
        return len(bundles)

    def drain(self) -> int:
        """Pump (flushing partial buckets) until the queue is empty."""
        total = 0
        while self.queue.depth:
            total += self.pump(flush_all=True)
        return total

    # ------------------------------------------------------------------
    # async facade
    # ------------------------------------------------------------------

    def start(self) -> "SolverServer":
        """Run the pump loop on a daemon thread (idempotent)."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                self._wake.wait(timeout=0.5 * self.queue.max_wait)
                self._wake.clear()
                self.pump()
            self.pump(flush_all=True)   # don't strand queued futures

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="solver-serve-pump")
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._wake.set()
        self._thread.join()
        self._thread = None

    def __enter__(self) -> "SolverServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------------
    # bundle execution
    # ------------------------------------------------------------------

    def _assemble(self, bundle: Bundle):
        """Gather per-request lane sessions (warm handles as-is, cold
        lanes built from y0) into one SoA bundle session, pad dead
        lanes by replicating the last live lane with ``tf = t`` (a
        masked no-op from step one), and stack the params pytree."""
        lanes = []
        for req in bundle.requests:
            if req.session is not None:
                lanes.append(req.session)
            else:
                lanes.append(SolverSession.cold(req.y0[None, :], req.t0))
        npad = bundle.nsys - bundle.live
        if npad:
            lanes.extend([lanes[-1]] * npad)
        sess = SolverSession.concat(lanes)
        tf_live = [req.tf for req in bundle.requests]
        # dead lanes: tf == the replicated lane's current t -> inactive
        tfa = jnp.concatenate([
            jnp.asarray(tf_live, sess.t.dtype),
            jnp.broadcast_to(sess.t[-1], (npad,))]) if npad else \
            jnp.asarray(tf_live, sess.t.dtype)
        p0 = bundle.requests[0].params
        if p0 is None:
            if any(r.params is not None for r in bundle.requests):
                raise ValueError("mixed params/None requests in one "
                                 "family bundle")
            params = None
        else:
            stacked = [r.params for r in bundle.requests]
            stacked.extend([stacked[-1]] * npad)
            params = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(
                    [jnp.asarray(x, self.dtype) for x in xs]), *stacked)
        return sess, tfa, params

    def _compile(self, key: TraceKey, sess, tfa, params,
                 policy=None) -> _CompiledBundle:
        """Trace, lower, and AOT-compile one bundle shape (the cache
        miss path); records the compile wall clock and the trace-time
        Solution metadata reused for every subsequent hit.  ``policy``
        overrides the context policy (the backend-fallback path
        recompiles the bundle under the jnp oracle)."""
        fam = self.families[key.bucket.family]
        rtol = 10.0 ** key.bucket.tol_class[0]
        atol = 10.0 ** key.bucket.tol_class[1]
        pol_kw = {} if policy is None else {"policy": policy}
        opts = self.ctx.options(rtol=rtol, atol=atol,
                                max_steps=self.max_steps, **pol_kw)
        method = key.bucket.method
        meta: dict = {}

        def run(sess, tfa, params):
            fb = lambda t, y: fam.f(t, y, params)
            jb = lambda t, y: fam.jac(t, y, params)
            fs = (lambda t, z: fam.f_soa(t, z, params)) \
                if fam.f_soa is not None else None
            js = (lambda t, z: fam.jac_soa(t, z, params)) \
                if fam.jac_soa is not None else None
            prob = IVP(f=fb, jac=jb, f_soa=fs, jac_soa=js,
                       y0=sess.Z[0].T)
            sol = integrate(prob, sess.t[0], tfa, method, ctx=self.ctx,
                            opts=opts, order=self.order,
                            lin_solver=self.lin_solver,
                            session=sess, return_session=True)
            # trace-time capture: these Solution fields are concrete
            # Python values (strings / host ints) even under tracing
            meta.update(method=sol.method, lin_solver=sol.lin_solver,
                        nonlin_solver=sol.nonlin_solver,
                        workspace_bytes=sol.workspace_bytes)
            return sol.y, sol.stats, sol.session

        t0 = time.perf_counter()
        with self.ctx.profiler.region("serve.compile", cat="serve",
                                      family=key.bucket.family,
                                      nsys=key.nsys):
            compiled = jax.jit(run).lower(sess, tfa, params).compile()
        return _CompiledBundle(fn=compiled,
                               compile_s=time.perf_counter() - t0,
                               meta=dict(meta))

    def _count_failures(self, reason: str, k: int = 1) -> None:
        with self._mlock:
            self._failures[reason] = self._failures.get(reason, 0) + k

    def _run_compiled(self, entry: _CompiledBundle, sess, tfa, params):
        """The compiled-executable invocation, isolated so the chaos
        harness can wrap it (simulated executable raise) and the
        fallback path can reuse it."""
        y, st, sess_out = entry.fn(sess, tfa, params)
        jax.block_until_ready(y)
        return y, st, sess_out

    def _needs_fallback(self, y) -> bool:
        """All-NaN bundle state under a non-oracle backend: the kernel
        path itself is implicated (a single diverging system quarantines
        per-lane instead), so the bundle qualifies for the one-shot
        jnp-oracle re-pump."""
        if self.ctx.policy.backend == "jnp":
            return False
        import numpy as np

        arr = np.asarray(y)
        return arr.size > 0 and not np.isfinite(arr).any()

    def _shed_expired(self, bundle: Bundle) -> Optional[Bundle]:
        """Fail expired requests' Futures at FLUSH time (no compute is
        spent on them) and rebuild the bundle from the survivors;
        returns None when nothing is left to execute."""
        now = self.clock()
        if not any(r.deadline is not None and now >= r.deadline
                   for r in bundle.requests):
            return bundle
        live: List[IVPRequest] = []
        shed = 0
        for req in bundle.requests:
            if req.deadline is not None and now >= req.deadline:
                shed += 1
                exc = DeadlineExceeded(
                    f"deadline exceeded before execution "
                    f"(queued {now - req.arrival:.3f}s)")
                if req.future.set_running_or_notify_cancel():
                    req.future.set_exception(exc)
            else:
                live.append(req)
        self._count_failures("deadline", shed)
        log = self.ctx.logger
        if log.enabled_for("WARNING"):
            log.warning("serve.deadline_shed", family=bundle.key.family,
                        shed=shed, live=len(live))
        if not live:
            return None
        return Bundle(key=bundle.key, requests=live,
                      nsys=self.queue.pad_to(len(live)),
                      flushed=bundle.flushed)

    def _degrade(self, bundle: Bundle, sess, tfa, params, exc):
        """One-shot backend fallback: re-pump the bundle under the jnp
        oracle policy (its own TraceKey, so the degraded executable is
        cached too).  A failure HERE propagates — the fallback is not
        retried."""
        fkey = TraceKey(bucket=bundle.key, nsys=bundle.nsys,
                        policy=XLA_FUSED)
        entry, hit = self.cache.get(
            fkey,
            lambda: self._compile(fkey, sess, tfa, params,
                                  policy=XLA_FUSED))
        y, st, sess_out = self._run_compiled(entry, sess, tfa, params)
        with self._mlock:
            self._degraded += 1
        log = self.ctx.logger
        if log.enabled_for("WARNING"):
            log.warning("serve.bundle.degraded",
                        family=bundle.key.family, nsys=bundle.nsys,
                        reason=f"{type(exc).__name__}: {exc}"[:200])
        return y, st, sess_out, entry, hit

    def _execute(self, bundle: Bundle) -> None:
        prof = self.ctx.profiler
        if prof.enabled:
            # the queue stamps arrival/flushed on the SERVER clock
            # (time.monotonic by default); capture both clocks at one
            # instant so queue events can be mapped onto the profiler
            # timebase and merged into the Chrome trace
            p_anchor, s_anchor = prof.now(), self.clock()
        shed = self._shed_expired(bundle)
        if shed is None:
            return
        bundle = shed
        degraded = False
        try:
            with prof.region("serve.assemble", cat="serve", sync=False):
                sess, tfa, params = self._assemble(bundle)
            key = TraceKey(bucket=bundle.key, nsys=bundle.nsys,
                           policy=self.ctx.policy)
            entry, hit = self.cache.get(
                key, lambda: self._compile(key, sess, tfa, params))
            if not hit and self._bundles >= self.warmup_bundles:
                with self._mlock:
                    self._steady_misses += 1
            with prof.region("serve.execute", cat="serve", sync=False,
                             family=bundle.key.family, live=bundle.live,
                             nsys=bundle.nsys):
                t0 = time.perf_counter()
                try:
                    y, st, sess_out = self._run_compiled(entry, sess, tfa,
                                                         params)
                    if self._needs_fallback(y):
                        raise RuntimeError(
                            "bundle state is entirely non-finite under "
                            f"backend {self.ctx.policy.backend!r}")
                except Exception as fallback_exc:
                    y, st, sess_out, entry, hit = self._degrade(
                        bundle, sess, tfa, params, fallback_exc)
                    degraded = True
                exec_s = time.perf_counter() - t0
        except Exception as exc:       # resolve, don't strand, futures
            self._count_failures("exec_error", len(bundle.requests))
            for req in bundle.requests:
                if not req.future.set_running_or_notify_cancel():
                    continue
                req.future.set_exception(
                    exc if isinstance(exc, SolverError) else
                    SolverError(f"bundle execution failed: {exc}"))
            raise
        done = self.clock()
        bkey = (bundle.key.family, bundle.key.n, bundle.nsys)
        with self._mlock:
            self._bundles += 1
            self._requests += bundle.live
            self._live_lanes += bundle.live
            self._padded_lanes += bundle.nsys
            for req in bundle.requests:
                self._lat.observe(done - req.arrival)
            row = self._bucket_stats.setdefault(
                bkey, {"requests": 0, "bundles": 0, "exec_s": 0.0})
            row["requests"] += bundle.live
            row["bundles"] += 1
            row["exec_s"] += exec_s
        if prof.enabled:
            # the bundle's queue wait (arrival -> flush) was stamped on
            # the server clock before this call: mapped onto the
            # profiler timebase and recorded after the fact
            pmap = lambda ts: p_anchor + (ts - s_anchor)
            prof.add_span("serve.bundle.queue_wait",
                          pmap(min(r.arrival for r in bundle.requests)),
                          pmap(bundle.flushed), cat="serve",
                          args={"family": bundle.key.family,
                                "live": bundle.live, "nsys": bundle.nsys})
        log = self.ctx.logger
        if log.enabled_for("INFO"):
            log.info("serve.bundle", family=bundle.key.family,
                     live=bundle.live, nsys=bundle.nsys, cached=hit,
                     compile_s=0.0 if hit else entry.compile_s,
                     exec_s=exec_s)
        # per-lane retcode inspection: only OFFENDING lanes fail (typed
        # SolverError with retcode + per-lane stats); bundle-mates
        # resolve normally — the serving face of quarantine containment
        with prof.region("serve.resolve", cat="serve", sync=False):
            retcodes = None
            if getattr(st, "retcodes", None) is not None:
                import numpy as np

                retcodes = np.asarray(st.retcodes)
            failed_lanes = []
            for i, req in enumerate(bundle.requests):
                rc = int(retcodes[i]) if retcodes is not None else 0
                if rc != 0:
                    lane_stats = jax.tree_util.tree_map(
                        lambda a: a[..., i], st)
                    exc = SolverError(
                        f"lane failed with {_status.retcode_name(rc)} "
                        f"({rc}) [{_status.SUNDIALS_FLAGS.get(rc, '?')}]",
                        retcode=rc, stats=lane_stats)
                    self._count_failures(_status.retcode_name(rc))
                    failed_lanes.append(i)
                    if req.future.set_running_or_notify_cancel():
                        req.future.set_exception(exc)
                    continue
                sol = self._lane_solution(i, req, bundle, y, st, sess_out,
                                          entry, hit, exec_s, degraded)
                if req.future.set_running_or_notify_cancel():
                    req.future.set_result(sol)
        if failed_lanes and log.enabled_for("WARNING"):
            log.warning("serve.lane_failed", family=bundle.key.family,
                        failed=len(failed_lanes), live=bundle.live,
                        lanes=failed_lanes[:16])

    def _lane_solution(self, i: int, req: IVPRequest, bundle: Bundle,
                       y, st, sess_out, entry: _CompiledBundle,
                       hit: bool, exec_s: float,
                       degraded: bool = False) -> Solution:
        """One request's Solution: the bundle result restricted to its
        lane (dead padded lanes never reach a client), plus the serving
        wall-clock split and the warm-start session handle.

        ``degraded`` marks results recomputed under the jnp oracle
        after the configured backend failed (one-shot fallback)."""
        lane_stats = jax.tree_util.tree_map(lambda a: a[..., i], st)
        meta = entry.meta
        timings = {"queue_wait": bundle.flushed - req.arrival,
                   "compile": 0.0 if hit else entry.compile_s,
                   "execute": exec_s}
        rcs = getattr(st, "retcodes", None)
        oks = getattr(st, "ok", None)
        return Solution(
            y=y[i], t=sess_out.t[i], success=st.success[i],
            stats=lane_stats, method=meta["method"],
            lin_solver=meta["lin_solver"],
            nonlin_solver=meta["nonlin_solver"],
            nni=st.nni[i],
            nli=st.nli[i] if st.nli is not None else None,
            nsetups=st.nsetups[i] if st.nsetups is not None else None,
            workspace_bytes=meta["workspace_bytes"],
            high_water_bytes=self.ctx.memory.high_water_bytes,
            npsolves=st.npsolves[i] if st.npsolves is not None else None,
            npsetups=None,
            session=sess_out.lanes(slice(i, i + 1)),
            timings=timings,
            retcodes=rcs[i] if rcs is not None else None,
            ok=oks[i] if oks is not None else None,
            degraded=degraded)

    # ------------------------------------------------------------------
    # observability
    # ------------------------------------------------------------------

    @staticmethod
    def _quantile(sorted_vals: List[float], q: float) -> float:
        if not sorted_vals:
            return 0.0
        idx = min(len(sorted_vals) - 1,
                  max(0, int(round(q * (len(sorted_vals) - 1)))))
        return sorted_vals[idx]

    def take_latencies(self) -> List[float]:
        """Return and clear the request-latency window (seconds) — lets
        a benchmark attribute percentiles to one load point.  The
        lifetime histogram accumulators behind ``metrics_prometheus()``
        are unaffected (scrapes stay monotone)."""
        with self._mlock:
            return self._lat.clear()

    def metrics(self) -> dict:
        """Serving health: queue depth, occupancy (live vs padded
        lanes), latency percentiles over the bounded sample window
        (``latency_samples`` of ``latency_observed`` lifetime
        observations), trace-cache counters, and the
        zero-steady-state-recompiles audit (``steady_misses``)."""
        with self._mlock:
            lat = sorted(self._lat.window())
            live, padded = self._live_lanes, self._padded_lanes
            out = {
                "queue_depth": self.queue.depth,
                "rejected": self.queue.rejected,
                "requests": self._requests,
                "bundles": self._bundles,
                "live_lanes": live,
                "padded_lanes": padded,
                "occupancy": (live / padded) if padded else 0.0,
                "latency_p50_s": self._quantile(lat, 0.50),
                "latency_p99_s": self._quantile(lat, 0.99),
                "latency_samples": self._lat.count,
                "latency_observed": self._lat.total,
                "steady_misses": self._steady_misses,
                "warmup_bundles": self.warmup_bundles,
                "trace_cache": self.cache.stats(),
                "failures": dict(self._failures),
                "degraded": self._degraded,
            }
        return out

    def metrics_prometheus(self) -> str:
        """The same serving health as :meth:`metrics`, rendered in
        Prometheus text exposition format, plus the context counters and
        autotune/trace-cache report (one scrape covers the serving tier
        AND the solver core).  Metric names: ``repro_serve_*`` for the
        serving tier (per-bucket throughput labeled ``{family, n,
        nsys}``), ``repro_context_*`` / ``repro_trace_cache_*`` /
        ``repro_autotune_*`` from :func:`repro.observability.metrics.
        context_metrics`."""
        from repro.observability.metrics import (MetricsRegistry,
                                                 context_metrics)
        reg = MetricsRegistry()
        m = self.metrics()
        reg.counter("repro_serve_requests",
                    "Requests served").set_cumulative(m["requests"])
        reg.counter("repro_serve_bundles",
                    "Bundles executed").set_cumulative(m["bundles"])
        reg.counter("repro_serve_rejected",
                    "Requests rejected at max queue depth"
                    ).set_cumulative(m["rejected"])
        reg.counter("repro_serve_steady_misses",
                    "Trace-cache misses after warmup"
                    ).set_cumulative(m["steady_misses"])
        fail = reg.counter(
            "repro_serve_failures",
            "Requests failed, labeled by reason (retcode name, "
            "deadline, exec_error)")
        for reason, count in sorted(m["failures"].items()):
            fail.set_cumulative(count, reason=reason)
        reg.counter("repro_serve_degraded",
                    "Bundles recomputed under the jnp oracle after a "
                    "backend failure").set_cumulative(m["degraded"])
        reg.counter("repro_serve_live_lanes",
                    "Live lanes executed").set_cumulative(m["live_lanes"])
        reg.counter("repro_serve_padded_lanes",
                    "Total lanes executed incl. padding"
                    ).set_cumulative(m["padded_lanes"])
        reg.gauge("repro_serve_queue_depth",
                  "Queued, unflushed requests").set(m["queue_depth"])
        reg.gauge("repro_serve_occupancy",
                  "Live / padded lane ratio").set(m["occupancy"])
        reg.gauge("repro_serve_latency_p50_seconds",
                  "Window median request latency"
                  ).set(m["latency_p50_s"])
        reg.gauge("repro_serve_latency_p99_seconds",
                  "Window p99 request latency").set(m["latency_p99_s"])
        reg.gauge("repro_serve_latency_samples",
                  "Samples in the percentile window"
                  ).set(m["latency_samples"])
        with self._mlock:
            hist = reg.histogram("repro_serve_latency_seconds",
                                 "Request latency (admission to result)",
                                 buckets=self._lat.buckets)
            hist.set_counts(list(self._lat.bucket_counts),
                            self._lat.sum_s, self._lat.total)
            bucket_rows = {k: dict(v)
                           for k, v in self._bucket_stats.items()}
        breq = reg.counter("repro_serve_bucket_requests",
                           "Requests served per shape bucket")
        bbun = reg.counter("repro_serve_bucket_bundles",
                           "Bundles executed per shape bucket")
        bexe = reg.counter("repro_serve_bucket_exec_seconds",
                           "Execute wall-clock per shape bucket")
        for (family, n, nsys), row in sorted(bucket_rows.items()):
            labels = {"family": family, "n": str(n), "nsys": str(nsys)}
            breq.set_cumulative(row["requests"], **labels)
            bbun.set_cumulative(row["bundles"], **labels)
            bexe.set_cumulative(row["exec_s"], **labels)
        context_metrics(reg, self.ctx)
        return reg.render()
