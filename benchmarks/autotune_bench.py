"""Autotune-cache population and CI verification (the measured half of
cost-model-driven dispatch).

``benchmarks/run.py --tune`` calls :func:`tune`: every OP_TABLE op is
timed on both dispatch backends (best-of-reps MIN, the same noise-robust
statistic as ensemble_bench) over a grid of shape signatures — the
pallas side additionally over a couple of tile candidates — and the
winners land in ``.autotune/interpret.json`` via
:class:`repro.core.autotune.AutotuneCache` (committed like the BENCH
files, so ``backend='auto'`` resolves from measurements, not just the
analytical model).

``benchmarks/run.py --check`` calls :func:`check`: every committed
entry is re-measured and its recorded winner must still win within the
same >20% slack discipline as the BENCH gate — the fresh
loser/winner time ratio must stay above ``REGRESSION_SLACK *
min(committed_ratio, RATIO_CAP)``.  Entries whose tiled axis is below
``GATE_MIN_AXIS`` — or whose committed winner runs in under
``GATE_MIN_TIME`` (a few-hundred-us op flips winner under transient
host load no matter how decisive its committed ratio looks; the axis
threshold alone mis-scores fast streaming ops, which finish ~50x
sooner than a block op over the same axis) — run in timer-noise
territory and are informational, and ``REPRO_PERF_CHECK=info`` demotes
all timing verdicts (mirroring ensemble_bench.check)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import opcost
from repro.core import autotune
from repro.core import dispatch as dp
from repro.core.policies import ExecPolicy, XLA_FUSED

REGRESSION_SLACK = 0.8
RATIO_CAP = 1.25
GATE_MIN_AXIS = 4096        # same rationale as ensemble_bench.GATE_MIN_NSYS
GATE_MIN_TIME = 500e-6      # committed-winner runtime noise floor [s]

DEVICE = "interpret"        # the only measurable device on this host

STREAM_N = (4096, 262144)
GJ_NSYS = (512, 4096, 32768)
SOA_NSYS = (512, 4096, 32768)


def _time(fn, *a, reps=3):
    """Best-of-reps wall time (MIN), each rep synced — see
    ensemble_bench._time for why MIN and not mean."""
    jax.block_until_ready(fn(*a))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        best = min(best, time.perf_counter() - t0)
    return best


def _pallas_policy(op: str, tile: int) -> ExecPolicy:
    kw = {"backend": "pallas", "interpret": True}
    if op in opcost.BATCHED_OPS:
        kw["batch_tile"] = tile
    elif op in opcost.REDUCTION_OPS:
        kw["reduce_tile"] = tile
    else:
        kw["block_elems"] = tile
    return ExecPolicy(**kw)


def _tiles_for(op: str, axis_len: int):
    top = opcost._lane_ceil(axis_len)
    if op in opcost.BATCHED_OPS:
        cands = {min(512, top), top}
    else:
        cands = {min(8 * 128, top), min(top, 1 << 16)}
    return sorted(cands)


def _cases():
    """Yield (op, args) covering every OP_TABLE op over the shape grid.
    ``args`` are the public-wrapper positional arguments — the same
    tuple opcost.signature consumes, so tuner keys and auto-dispatch
    keys agree by construction."""

    def rnd(i, shape):
        return jax.random.normal(jax.random.PRNGKey(i), shape)

    for n in STREAM_N:
        x, y, z = rnd(1, (n,)), rnd(2, (n,)), rnd(3, (n,))
        w = jnp.abs(y) + 0.1
        m = (x > 0).astype(x.dtype)
        coeffs = [0.3, -1.2, 2.5]
        yield "linear_sum", (2.0, x, -0.5, y)
        yield "linear_combination", (coeffs, [x, y, z])
        yield "scale_add_multi", (coeffs, x, [x, y, z])
        yield "axpy", (1.7, x, y)
        yield "dot", (x, y)
        yield "wrms_norm", (x, w)
        yield "wrms_norm_mask", (x, w, m)
        yield "dot_prod_multi", (x, [y, z, w])
        yield "wrms_ss", (x, w)
    for b in (3, 8, 16, 24):
        for nsys in GJ_NSYS:
            A = rnd(b, (b, b, nsys)) * 0.05
            A = jnp.eye(b)[:, :, None] - A        # diagonally dominant
            r = rnd(b + 1, (b, nsys))
            yield "block_solve_soa", (A, r)
            if b <= 16 and nsys <= 4096:
                yield "block_inverse_soa", (A,)
            if b <= 8 and nsys <= 4096:
                yield "blockdiag_spmv_soa", (A, r)
    for n in (3, 8):
        for nsys in SOA_NSYS:
            zz, ff, psi = rnd(20, (n, nsys)), rnd(21, (n, nsys)), \
                rnd(22, (n, nsys))
            gmb = jnp.abs(rnd(23, (nsys,))) + 0.1
            ww = jnp.abs(rnd(24, (n, nsys))) + 0.1
            mb = rnd(25, (nsys,)) > 0.3
            yield "newton_residual_soa", (zz, ff, psi, gmb, True)
            if nsys >= 4096:
                yield "masked_update_wrms_soa", (zz, ff, ww, mb)
                yield "wrms_soa", (zz, ww)
            if nsys == 4096:
                q1 = 6
                eh = jnp.exp(0.5 * rnd(26, (nsys,)))
                qh = jnp.full((nsys,), q1 - 1, jnp.int32)
                Zh = rnd(27, (q1, n, nsys))
                yield "lagrange_rescale_soa", (eh, qh, Zh, mb)
    # sparse: banded CSR + a small shared-pattern BSR ensemble
    from repro.core.sunmatrix import SparseCSR
    for ncsr in (133, 1024):
        band = np.abs(np.arange(ncsr)[:, None] - np.arange(ncsr)) <= 2
        dense = np.asarray(rnd(30, (ncsr, ncsr))) * band
        csr = SparseCSR.from_dense(dense)
        xs = rnd(31, (ncsr,))
        yield "csr_spmv", (csr.data, xs, csr.pattern)
    nblk, bb = 5, 3
    brows, bcols = zip(*[(i, j) for i in range(nblk)
                         for j in range(nblk) if abs(i - j) <= 1])
    bpat = (tuple(brows), tuple(bcols), nblk)
    for nsys in (512, 4096):
        Vb = rnd(32, (len(brows), bb, bb, nsys)) + \
            jnp.where((jnp.asarray(brows) == jnp.asarray(bcols))
                      [:, None, None, None],
                      (bb + 2.0) * jnp.eye(bb)[None, :, :, None], 0.0)
        xb = rnd(33, (nblk, bb, nsys))
        yield "bsr_spmv_soa", (Vb, xb, bpat)
        yield "bsr_block_jacobi_inverse_soa", (Vb, bpat)


def _wrapper(op):
    """The public dispatch wrapper for ``op`` with (args..., policy)."""
    fns = {
        "newton_residual_soa": lambda z, f, p, g, neg, pol:
            dp.newton_residual_soa(z, f, p, g, pol, negate=neg),
        "masked_update_wrms_soa": lambda z, dz, w, m, pol:
            jnp.concatenate([a.ravel() for a in
                             dp.masked_update_wrms_soa(z, dz, w, m, pol)]),
        "scale_add_multi": lambda c, x, ys, pol:
            jnp.stack(dp.scale_add_multi(c, x, ys, pol)),
    }
    if op in fns:
        return fns[op]
    return lambda *a: getattr(dp, op)(*a)


def _measure_case(op, args, reps=3):
    """(t_jnp, t_pallas_best, best_tile) for one (op, args)."""
    call = _wrapper(op)
    sig = opcost.signature(op, args)
    t_jnp = _time(lambda: call(*args, XLA_FUSED), reps=reps)
    best_t, best_tile = float("inf"), 0
    for tile in _tiles_for(op, sig.axis_len):
        t = _time(lambda: call(*args, _pallas_policy(op, tile)), reps=reps)
        if t < best_t:
            best_t, best_tile = t, tile
    return sig, t_jnp, best_t, best_tile


def tune(reps: int = 3, verbose: bool = True):
    """Measure the full grid and (re)write ``.autotune/interpret.json``.
    Returns the cache."""
    cache = autotune.AutotuneCache(DEVICE)
    for op, args in _cases():
        sig, t_jnp, t_pal, tile = _measure_case(op, args, reps=reps)
        entry = autotune.Entry(sig=sig, t_jnp=t_jnp, t_pallas=t_pal,
                               tile=tile)
        cache.put(entry)
        if verbose:
            print(f"tune.{sig.key()},{entry.winner},"
                  f"jnp_us={t_jnp * 1e6:.0f},pallas_us={t_pal * 1e6:.0f},"
                  f"tile={tile}", flush=True)
    path = cache.save()
    audit = autotune.model_audit(cache)
    if verbose:
        print(f"tune.saved,{len(cache.entries)},{path}", flush=True)
        print(f"tune.model_agreement,"
              f"{audit['model_agree']}/{audit['model_total']},"
              f"{audit['model_agreement']:.2f}", flush=True)
    autotune.reset_resolver(DEVICE)       # pick up the fresh cache
    return cache


def check() -> bool:
    """CI gate: every committed autotune entry's recorded winner must
    still win on re-measure, within the BENCH slack discipline (one
    retry; sub-GATE_MIN_AXIS entries and REPRO_PERF_CHECK=info are
    informational)."""
    import os
    soft = os.environ.get("REPRO_PERF_CHECK", "").lower() == "info"
    cache = autotune.AutotuneCache(DEVICE).load()
    if not cache.entries:
        print("check.autotune,FAIL,no committed cache entries "
              "(run: python -m benchmarks.run --tune)", flush=True)
        return False
    ok = True
    for entry in cache.entries.values():
        committed_adv = max(entry.ratio, 1.0 / entry.ratio)
        floor = REGRESSION_SLACK * min(committed_adv, RATIO_CAP)
        gating = (entry.sig.axis_len >= GATE_MIN_AXIS and
                  min(entry.t_jnp, entry.t_pallas) >= GATE_MIN_TIME and
                  not soft)
        args = _args_for(entry.sig)
        if args is None:                  # grid changed under the cache
            print(f"check.autotune.{entry.sig.key()},STALE,"
                  f"no generator for this signature — re-tune", flush=True)
            ok &= not gating
            continue
        good, fresh_adv = False, 0.0
        for _attempt in range(2):
            _sig, t_jnp, t_pal, _tile = _measure_case(entry.sig.op, args,
                                                      reps=2)
            tw, tl = (t_jnp, t_pal) if entry.winner == "jnp" \
                else (t_pal, t_jnp)
            fresh_adv = tl / tw
            good = fresh_adv >= floor
            if good:
                break
        ok &= good or not gating
        verdict = ("PASS" if gating else "INFO") if good else \
            ("FAIL" if gating else "INFO")
        print(f"check.autotune.{entry.sig.key()},{verdict},"
              f"winner={entry.winner},fresh={fresh_adv:.2f},"
              f"floor={floor:.2f}", flush=True)
    return ok


def _args_for(sig: opcost.OpSig):
    """Rebuild the generator args matching ``sig`` (None if the tuning
    grid no longer produces this signature)."""
    for op, args in _cases():
        if op == sig.op and opcost.signature(op, args).key() == sig.key():
            return args
    return None


if __name__ == "__main__":
    import sys
    jax.config.update("jax_enable_x64", True)
    if "--check" in sys.argv:
        sys.exit(0 if check() else 1)
    tune()
