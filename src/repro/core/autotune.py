"""Persisted autotune cache + the ``backend='auto'`` resolver.

The measured half of cost-model-driven dispatch: :func:`tune` times
every OP_TABLE op over a grid of signatures on both backends and writes
the winners to ``.autotune/<device>.json`` (committed with the code)::

    PYTHONPATH=src python -m repro.core.autotune

``<device>`` is the roofline device the program runs on: ``interpret``
on a CPU, where Pallas kernels run in interpret mode, or the chip's row
(``tpu_v5e``) on a TPU.  ``backend='auto'`` dispatch resolves each call
site at trace time:

1. per-op override on the policy (``ExecPolicy.op_overrides``) — forced;
2. exact autotune-cache hit for (op, shape-signature, dtype) — the
   measured winner and its measured-best tile;
3. nearest cache entry (same op/dtype/structural params, closest tiled-
   axis length within 8x) — measurement generalizes along the batch
   axis far better than across block sizes;
4. the analytical model (:mod:`repro.analysis.opcost`) — always
   evaluated anyway, so every decision records whether model and
   measurement agree (``ctx.dispatch_report()`` surfaces mismatches).

Cache files are schema-versioned: a loader seeing a different
``schema`` (or an entry whose key disagrees with its recorded
signature) drops the stale data and falls back to the model — never an
error, exactly like a cold cache.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from pathlib import Path
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import opcost
from repro.analysis.opcost import OpSig
from repro.analysis.roofline import get_device

from .policies import ExecPolicy, XLA_FUSED

#: bump when the key derivation or entry layout changes; mismatched
#: files are discarded wholesale (stale winners are worse than a cold
#: cache — they would silently pin yesterday's loser).
SCHEMA_VERSION = 1

#: nearest-entry fallback range along the tiled axis (log-distance cap).
NEAREST_MAX_FACTOR = 8.0


def default_cache_dir() -> Path:
    """``$REPRO_AUTOTUNE_DIR`` or ``<repo_root>/.autotune`` (resolved
    from this file so tests/benchmarks work from any cwd)."""
    env = os.environ.get("REPRO_AUTOTUNE_DIR")
    if env:
        return Path(env)
    return Path(__file__).resolve().parents[3] / ".autotune"


@dataclasses.dataclass
class Entry:
    """One measured (op, signature) record."""

    sig: OpSig
    t_jnp: float              # best-of-reps seconds
    t_pallas: float
    tile: int = 0             # measured-best pallas tile (0 = default)

    @property
    def winner(self) -> str:
        return "jnp" if self.t_jnp <= self.t_pallas else "pallas"

    @property
    def ratio(self) -> float:
        """Measured jnp/pallas time ratio (>1 -> pallas wins)."""
        return self.t_jnp / max(self.t_pallas, 1e-12)

    def to_json(self) -> dict:
        return {"sig": dataclasses.asdict(self.sig), "t_jnp": self.t_jnp,
                "t_pallas": self.t_pallas, "tile": self.tile,
                "winner": self.winner}

    @classmethod
    def from_json(cls, d: dict) -> "Entry":
        return cls(sig=OpSig(**d["sig"]), t_jnp=float(d["t_jnp"]),
                   t_pallas=float(d["t_pallas"]), tile=int(d.get("tile", 0)))


class AutotuneCache:
    """Schema-versioned, per-device persisted measurement store."""

    def __init__(self, device: str, path: Optional[Path] = None):
        self.device = device
        self.path = Path(path) if path is not None else \
            default_cache_dir() / f"{device}.json"
        self.entries: Dict[str, Entry] = {}
        self.stale = False        # a file existed but was invalidated

    # -- persistence --------------------------------------------------------

    def load(self) -> "AutotuneCache":
        """Read the cache file; schema or key mismatches discard the
        file's (or entry's) data silently — a cold cache, not an error."""
        self.entries = {}
        try:
            payload = json.loads(self.path.read_text())
        except (OSError, json.JSONDecodeError):
            return self
        if payload.get("schema") != SCHEMA_VERSION or \
                payload.get("device") != self.device:
            self.stale = True
            return self
        for key, raw in payload.get("entries", {}).items():
            try:
                entry = Entry.from_json(raw)
            except (KeyError, TypeError, ValueError):
                self.stale = True
                continue
            if entry.sig.key() != key:          # mismatched/corrupt key
                self.stale = True
                continue
            self.entries[key] = entry
        return self

    def save(self) -> Path:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        payload = {"schema": SCHEMA_VERSION, "device": self.device,
                   "note": ("measured best-of-reps seconds per backend; "
                            "regenerate with: PYTHONPATH=src python -m "
                            "repro.core.autotune"),
                   "entries": {k: e.to_json()
                               for k, e in sorted(self.entries.items())}}
        self.path.write_text(json.dumps(payload, indent=2) + "\n")
        return self.path

    # -- lookup -------------------------------------------------------------

    def put(self, entry: Entry) -> None:
        self.entries[entry.sig.key()] = entry

    def get(self, sig: OpSig) -> Optional[Entry]:
        return self.entries.get(sig.key())

    def nearest(self, sig: OpSig) -> Optional[Entry]:
        """Closest entry with the same op/dtype and structural params
        (b, k, nnz), ranked by log-distance along the tiled axis and
        capped at :data:`NEAREST_MAX_FACTOR` — batch size extrapolates;
        block structure does not."""
        best, best_d = None, math.inf
        for e in self.entries.values():
            es = e.sig
            if (es.op, es.dtype, es.b, es.k, es.nnz) != \
                    (sig.op, sig.dtype, sig.b, sig.k, sig.nnz):
                continue
            a, c = max(1, es.axis_len), max(1, sig.axis_len)
            d = abs(math.log(a / c))
            if d < best_d:
                best, best_d = e, d
        if best is not None and best_d <= math.log(NEAREST_MAX_FACTOR):
            return best
        return None


@dataclasses.dataclass
class Decision:
    """One resolved call site (recorded once per unique signature)."""

    sig: OpSig
    backend: str
    source: str               # 'override' | 'cache' | 'near' | 'model'
    tile: int
    model_winner: str
    cached_winner: Optional[str] = None
    hits: int = 1

    @property
    def agree(self) -> Optional[bool]:
        """Model-vs-measurement agreement (None without a measurement)."""
        if self.cached_winner is None:
            return None
        return self.model_winner == self.cached_winner

    def to_dict(self) -> dict:
        return {"op": self.sig.op, "sig": self.sig.key(),
                "backend": self.backend, "source": self.source,
                "tile": self.tile, "model_winner": self.model_winner,
                "cached_winner": self.cached_winner, "agree": self.agree,
                "hits": self.hits}


class Resolver:
    """Per-device decision engine for ``backend='auto'`` dispatch."""

    def __init__(self, device: str, cache: Optional[AutotuneCache] = None):
        self.device = device
        self.cache = cache if cache is not None else \
            AutotuneCache(device).load()
        self.decisions: Dict[str, Decision] = {}

    def decide(self, sig: OpSig, requested_tile: Optional[int] = None,
               override: Optional[str] = None) -> Decision:
        """Resolve one call site; memoized per unique signature."""
        key = sig.key()
        hit = self.decisions.get(key)
        if hit is not None and override is None:
            hit.hits += 1
            return hit
        pred = opcost.predict(sig, self.device, requested_tile)
        entry = self.cache.get(sig)
        near = None if entry is not None else self.cache.nearest(sig)
        measured = entry or near
        if override:
            backend, source = override, "override"
        elif entry is not None:
            backend, source = entry.winner, "cache"
        elif near is not None:
            backend, source = near.winner, "near"
        else:
            backend, source = pred.winner, "model"
        tile = pred.tile
        if measured is not None and measured.tile and backend == "pallas":
            tile = min(measured.tile,
                       opcost._lane_ceil(max(1, sig.axis_len)))
        dec = Decision(sig=sig, backend=backend, source=source, tile=tile,
                       model_winner=pred.winner,
                       cached_winner=measured.winner if measured else None)
        self.decisions[key] = dec
        return dec

    # -- reporting ----------------------------------------------------------

    def report(self) -> dict:
        """Decisions so far + a model-vs-cache audit over the *whole*
        cache (the >=80%-agreement acceptance metric), mispredictions
        listed explicitly."""
        audit = model_audit(self.cache)
        return {"device": self.device,
                "cache_path": str(self.cache.path),
                "cache_entries": len(self.cache.entries),
                "cache_stale": self.cache.stale,
                "decisions": [d.to_dict()
                              for d in self.decisions.values()],
                **audit}


def model_audit(cache: AutotuneCache) -> dict:
    """Compare the analytical model's predicted winner against every
    measured cache entry."""
    agree, mispredictions = 0, []
    for e in cache.entries.values():
        pred = opcost.predict(e.sig, cache.device)
        if pred.winner == e.winner:
            agree += 1
        else:
            mispredictions.append(
                {"sig": e.sig.key(), "measured": e.winner,
                 "predicted": pred.winner,
                 "measured_ratio": round(e.ratio, 3),
                 "predicted_ratio": round(pred.ratio, 3)})
    total = len(cache.entries)
    return {"model_agreement": (agree / total) if total else None,
            "model_agree": agree, "model_total": total,
            "mispredictions": mispredictions}


# ---------------------------------------------------------------------------
# Process-wide resolver registry.  ExecPolicy stays a frozen hashable
# value type (it keys jit caches), so it carries only the device *name*;
# the mutable resolver/cache state lives here and Context fronts it.
# ---------------------------------------------------------------------------

_RESOLVERS: Dict[str, Resolver] = {}


def get_resolver(device: str) -> Resolver:
    get_device(device)                      # validate the name early
    res = _RESOLVERS.get(device)
    if res is None:
        res = _RESOLVERS[device] = Resolver(device)
    return res


def reset_resolver(device: Optional[str] = None) -> None:
    """Drop memoized resolvers (tests; after regenerating a cache)."""
    if device is None:
        _RESOLVERS.clear()
    else:
        _RESOLVERS.pop(device, None)


def resolve(op: str, policy, *args):
    """Trace-time entry point for ``backend='auto'`` dispatch: extract
    the call-site signature, decide, and run the chosen implementation
    under a concretized policy.  Imported lazily by
    :func:`repro.core.dispatch.dispatch` to avoid an import cycle."""
    from . import dispatch as dp
    sig = opcost.signature(op, args)
    res = get_resolver(policy.device_name())
    dec = res.decide(sig, requested_tile=None)
    fields = {"backend": dec.backend}
    if dec.backend == "pallas":
        if op in opcost.BATCHED_OPS:
            fields["batch_tile"] = dec.tile
        elif op in opcost.REDUCTION_OPS:
            fields["reduce_tile"] = dec.tile
        else:
            fields["block_elems"] = dec.tile
    concrete = dataclasses.replace(policy, op_overrides=(), **fields)
    fn = dp.OP_TABLE[op].get(dec.backend, dp.OP_TABLE[op]["jnp"])
    return fn(*args, policy=concrete)


def decisions_report(policy) -> dict:
    """Report for the resolver belonging to ``policy``'s device."""
    return get_resolver(policy.device_name()).report()


# ---------------------------------------------------------------------------
# The sweep that fills the cache.  Every OP_TABLE op is timed on both
# dispatch backends (best-of-reps MIN) over a grid of shape signatures,
# the pallas side over a couple of tile candidates as well.
# ---------------------------------------------------------------------------

STREAM_N = (4096, 262144)
GJ_NSYS = (512, 4096, 32768)
SOA_NSYS = (512, 4096, 32768)


def _time(fn, reps=3):
    """Best-of-reps wall time (MIN), each rep synced: the minimum is the
    statistic least disturbed by transient host load."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _pallas_policy(op: str, tile: int) -> ExecPolicy:
    kw = {"backend": "pallas"}
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
    from .sunmatrix import SparseCSR

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
    from . import dispatch as dp
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
    """(sig, t_jnp, t_pallas_best, best_tile) for one (op, args)."""
    call = _wrapper(op)
    sig = opcost.signature(op, args)
    t_jnp = _time(lambda: call(*args, XLA_FUSED), reps=reps)
    best_t, best_tile = float("inf"), 0
    for tile in _tiles_for(op, sig.axis_len):
        t = _time(lambda: call(*args, _pallas_policy(op, tile)), reps=reps)
        if t < best_t:
            best_t, best_tile = t, tile
    return sig, t_jnp, best_t, best_tile


def tune(reps: int = 3, verbose: bool = True) -> AutotuneCache:
    """Measure the full grid on the device this program runs on and
    (re)write its ``.autotune/<device>.json``.  Returns the cache."""
    device = ExecPolicy(backend="pallas").device_name()
    cache = AutotuneCache(device)
    for op, args in _cases():
        sig, t_jnp, t_pal, tile = _measure_case(op, args, reps=reps)
        entry = Entry(sig=sig, t_jnp=t_jnp, t_pallas=t_pal, tile=tile)
        cache.put(entry)
        if verbose:
            print(f"tune.{sig.key()},{entry.winner},"
                  f"jnp_us={t_jnp * 1e6:.0f},pallas_us={t_pal * 1e6:.0f},"
                  f"tile={tile}", flush=True)
    path = cache.save()
    audit = model_audit(cache)
    if verbose:
        print(f"tune.saved,{len(cache.entries)},{path}", flush=True)
        print(f"tune.model_agreement,"
              f"{audit['model_agree']}/{audit['model_total']},"
              f"{audit['model_agreement']:.2f}", flush=True)
    reset_resolver(device)                # pick up the fresh cache
    return cache


if __name__ == "__main__":
    jax.config.update("jax_enable_x64", True)
    tune()
