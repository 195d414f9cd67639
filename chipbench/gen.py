"""Traffic generation from ``--seed``: per-system parameters drawn on the
device, and open-loop arrival schedules.

A traffic file gives each parameter as a distribution::

    {"dist": "const", "value": 0.04}
    {"dist": "uniform", "low": 5e3, "high": 1.5e4}
    {"dist": "log_uniform", "low": 3e6, "high": 3e8}

and an open loop as a ``rate_per_s``.
"""
from __future__ import annotations

import math

import numpy as np


def seed_key(seed: int):
    """A JAX PRNG key that depends on all of ``seed`` (any size: a plain
    ``PRNGKey`` keeps only its low 32 bits)."""
    import jax.numpy as jnp

    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(state, jnp.uint32)


def draw_params(key, n: int, spec: dict, dtype):
    """``{name: (n,) array}`` drawn on the device from ``key``; traceable,
    so one jitted call makes every parameter of a batch."""
    import jax
    import jax.numpy as jnp

    out = {}
    for i, (name, d) in enumerate(sorted(spec.items())):
        k = jax.random.fold_in(key, i)
        kind = d["dist"]
        if kind == "const":
            out[name] = jnp.full((n,), d["value"], dtype)
        elif kind == "uniform":
            out[name] = jax.random.uniform(k, (n,), dtype, d["low"],
                                           d["high"])
        elif kind == "log_uniform":
            lo, hi = math.log(d["low"]), math.log(d["high"])
            out[name] = jnp.exp(jax.random.uniform(k, (n,), jnp.float32,
                                                   lo, hi)).astype(dtype)
        else:
            raise ValueError(f"unknown distribution {kind!r} for {name!r}")
    return out


def poisson_schedule(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop at ``rate``.

    The gaps are the ``N = rate * seconds`` quantiles of the exponential
    distribution, in an order drawn from ``seed``: every seed offers the
    same requests in the same time, arranged differently, so seeds
    differ in arrival pattern and not in load."""
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    np.random.default_rng(int(seed)).shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]
