"""Closed loop of ensemble integrations through the system's entry
points.

One caller (a reacting-flow code's chemistry substep) integrates the
deployment's ``ngroups`` independent systems per call, calls back to
back: each call generates its systems' parameters on the device from
(seed, call index), runs the compiled integration and waits for it
(``block_until_ready``).  A call is ``integrate(IVP(f, jac, f_soa,
jac_soa, y0), t0, tf, method, lin_solver=..., opts=ODEOptions(rtol,
atol, policy=ExecPolicy(backend)))``, with a per-component atol handed
over in units of each component's atol (:mod:`chipbench.units`).

``cells_per_s`` is every system completed in the window over the wall
time of the window's whole calls.  Right after each call a small
program takes what ``correct`` needs from its output (failed systems
counted over all lanes, counter sums, and a sample of lanes drawn from
the seed), so that no call's output outlives it; the sampled lanes are
compared with the plain reference once the window has closed.
"""
from __future__ import annotations

import numpy as np

from chipbench import check as _check
from chipbench import gen, layout, units
from chipbench.devtrace import WINDOW_SPAN
from chipbench.harness import Window, clock


class Driver:
    def __init__(self, cell, seed, devs, *, seconds, span, log):
        self.cell, self.seed, self.devs = cell, int(seed), devs
        self.span, self.log = span, log
        cfg = self.cfg = cell.config
        self.nsys = int(cfg["ngroups"])
        self.atol, self.scale = units.split(cfg["atol"])
        self.per_call = int(cfg["check"]["lanes_per_call"])
        self.problem = layout.problem(cell.root, cfg["problem"])
        if cell.traffic["generator"] != "closed_loop":
            raise ValueError(f"the {cell.config['driver']} driver drives "
                             f"closed_loop traffic, not "
                             f"{cell.traffic['generator']!r}")
        self._samples = []

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding
        from repro.core import linsol
        from repro.core.arkode import ODEOptions
        from repro.core.ivp import IVP, integrate
        from repro.core.policies import ExecPolicy

        cfg, nsys = self.cfg, self.nsys
        dtype = jnp.dtype(cfg["dtype"])
        policy = ExecPolicy(backend=cfg["backend"])
        opts = ODEOptions(rtol=cfg["rtol"], atol=self.atol, policy=policy)
        ls = getattr(linsol, cfg["lin_solver"])()
        t0, tf = float(cfg["t0"]), float(cfg["tf"])
        f, jac, f_soa, jac_soa = units.family_in_units(
            self.problem.family(), self.scale, dtype)
        z0row = (np.asarray(cfg["y0"], np.float64) / self.scale).astype(dtype)
        spec = self.cell.traffic["params"]
        place = SingleDeviceSharding(self.devs[0])

        def call(y0, p):
            prob = IVP(f=lambda t, y: f(t, y, p),
                       jac=lambda t, y: jac(t, y, p),
                       f_soa=lambda t, y: f_soa(t, y, p),
                       jac_soa=lambda t, y: jac_soa(t, y, p), y0=y0)
            sol = integrate(prob, t0, tf, cfg["method"], lin_solver=ls,
                            opts=opts)
            return sol.y, sol.retcodes, sol.stats.nni, sol.nsetups

        def inputs(key, i):
            p = gen.draw_params(jax.random.fold_in(key, i), nsys, spec, dtype)
            y0 = jnp.broadcast_to(jnp.asarray(z0row), (nsys, z0row.size))
            return y0, p

        # int32 partial sums over 64 blocks, added exactly on the host
        blocks = 64 if nsys % 64 == 0 else 1

        def total(x):
            return jnp.sum(x.astype(jnp.int32).reshape(blocks, -1), axis=1)

        def summarize(out, p, idx):
            y, rc, nni, nsetups = out
            return {"nni": total(nni), "nsetups": total(nsetups),
                    "failed": total(rc != 0), "y": y[idx],
                    "rc": rc[idx], "p": {k: v[idx] for k, v in p.items()}}

        self.key = jax.device_put(gen.seed_key(self.seed), place)
        i0 = np.int32(0)
        self._inputs = jax.jit(inputs, out_shardings=place).lower(
            self.key, i0).compile()
        y0, p = jax.block_until_ready(self._inputs(self.key, i0))
        self._call = jax.jit(call).lower(y0, p).compile()
        idx = np.zeros(self.per_call, np.int32)
        self._summarize = jax.jit(summarize).lower(
            self._call.out_info, p, idx).compile()
        del y0, p

    def hlo_texts(self):
        return [self._call.as_text()]

    def timed_programs(self):
        """The programs of the system under test in the trace; the
        window's other two (drawing inputs, sampling answers) are the
        benchmark's."""
        return ["jit_call"]

    # -- the window -----------------------------------------------------

    def lanes(self, i: int) -> np.ndarray:
        """The lanes of call ``i`` compared with the reference, drawn
        from (seed, i)."""
        rng = np.random.default_rng([self.seed, i])
        return np.sort(rng.choice(self.nsys, self.per_call,
                                  replace=False)).astype(np.int32)

    def window(self, seconds: float) -> Window:
        import jax

        summaries = []
        with self.span(WINDOW_SPAN):
            t0 = clock()
            while True:
                i = np.int32(len(summaries))
                with self.span("bench.generate"):
                    y0, p = self._inputs(self.key, i)
                with self.span("bench.call"):
                    out = jax.block_until_ready(self._call(y0, p))
                with self.span("bench.summarize"):
                    summaries.append(self._summarize(out, p, self.lanes(i)))
                del y0, p, out
                if clock() - t0 >= seconds:
                    break
            elapsed = clock() - t0
        calls = len(summaries)
        self._samples = jax.device_get(summaries)
        count = lambda key: int(sum(np.asarray(s[key], np.int64).sum()
                                    for s in self._samples))
        failed = count("failed")
        cells = calls * self.nsys
        counters = {"cells": float(cells), "calls": float(calls),
                    "nni": float(count("nni")),
                    "nsetups": float(count("nsetups")),
                    "failed": float(failed)}
        return Window(end_to_end={"cells_per_s": cells / elapsed},
                      attempted=cells, failed=failed, counters=counters,
                      notes=[f"window: {calls} calls of {self.nsys} systems "
                             f"in {elapsed!r} s"])

    def reseed(self, seed: int, seconds: float) -> None:
        """Another seed's systems, on the same compiled programs."""
        import jax

        self.seed = int(seed)
        self.key = jax.device_put(gen.seed_key(self.seed),
                                  self.key.sharding)

    def release(self) -> None:
        """Drop the compiled programs; the samples are on the host."""
        self._call = self._inputs = self._summarize = None

    # -- correct --------------------------------------------------------

    def compared(self):
        """``(y, y0, params)`` of the lanes the check compares."""
        y = np.concatenate([s["y"] for s in self._samples]) * self.scale
        params = {k: np.concatenate([s["p"][k] for s in self._samples])
                  for k in self._samples[0]["p"]}
        y0 = np.tile(np.asarray(self.cfg["y0"], np.float64), (len(y), 1))
        return y, y0, params

    def check(self):
        lim = self.cfg["check"]["limits"]
        failed = sum(int(np.asarray(s["failed"], np.int64).sum())
                     for s in self._samples)
        err = _check.against_reference(self.problem, self.cfg,
                                       *self.compared())
        return [_check.Check("worst_err", err, lim["worst_err"]),
                _check.Check("failed_lanes", float(failed),
                             lim["failed_lanes"])]
