"""Closed loop of ensemble integrations over a mesh of chips, through
``integrate(..., mesh=)``.

The deployment's ``ngroups`` systems are split over the cell's chips on
one axis (the configuration's ``mesh``), as a reacting-flow code splits
its box over the accelerators of a node: each chip integrates the
systems it holds, with no collectives.  Everything else is the one-chip
driver's (:mod:`chipbench.drivers.ensemble`): inputs drawn on the
devices per call from (seed, call), one compiled call a window, the
same summary after each call and the same check.  What differs:

* the inputs are placed with a ``NamedSharding`` over the cell's chips;
* a call is ``integrate(IVP(f, jac, f_soa, jac_soa, y0, params=p), t0,
  tf, method, lin_solver=..., opts=..., mesh=mesh)``: the problem's
  native SoA family with the per-system rates in ``IVP.params``, of
  which each chip is handed its slice;
* the compared lanes are drawn from every shard alike;
* each call's step-loop trips (``EnsembleStats.trips``, one entry per
  shard: each chip runs its own loop) are summed per shard over the
  window; the counters ``shard_trips_max`` and ``shard_trips_mean`` are
  the largest and the mean of those sums.
"""
from __future__ import annotations

import numpy as np

from chipbench import gen, layout, units

_ensemble = layout.driver(layout.PACKAGE_DIR.parent, "ensemble")


class Driver(_ensemble.Driver):
    def __init__(self, cell, seed, devs, *, seconds, span, log):
        super().__init__(cell, seed, devs, seconds=seconds, span=span,
                         log=log)
        chips = len(devs)
        if self.nsys % chips or self.per_call % chips:
            raise ValueError(f"{cell.config_name}: ngroups {self.nsys} and "
                             f"lanes_per_call {self.per_call} must split "
                             f"evenly over {chips} chips")
        self.shard = self.nsys // chips

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
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
        axis = cfg["mesh"]["axis"]
        mesh = Mesh(np.asarray(self.devs), (axis,))
        split = NamedSharding(mesh, P(axis))
        shard = self.shard

        def call(y0, p):
            sol = integrate(IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa,
                                y0=y0, params=p), t0, tf, cfg["method"],
                            lin_solver=ls, opts=opts, mesh=mesh)
            return (sol.y, sol.retcodes, sol.stats.nni, sol.nsetups,
                    sol.stats.trips)

        def inputs(key, i):
            p = gen.draw_params(jax.random.fold_in(key, i), nsys, spec, dtype)
            y0 = jnp.broadcast_to(jnp.asarray(z0row), (nsys, z0row.size))
            return y0, p

        # int32 partial sums over 64 blocks, added exactly on the host
        blocks = 64 if nsys % 64 == 0 else 1

        def total(x):
            return jnp.sum(x.astype(jnp.int32).reshape(blocks, -1), axis=1)

        def summarize(out, p, idx):
            y, rc, nni, nsetups, trips = out
            return {"nni": total(nni), "nsetups": total(nsetups),
                    "failed": total(rc != 0), "trips": trips[::shard],
                    "y": y[idx], "rc": rc[idx],
                    "p": {k: v[idx] for k, v in p.items()}}

        self.key = jax.device_put(gen.seed_key(self.seed),
                                  NamedSharding(mesh, P()))
        i0 = np.int32(0)
        self._inputs = jax.jit(inputs, out_shardings=split).lower(
            self.key, i0).compile()
        y0, p = jax.block_until_ready(self._inputs(self.key, i0))
        self._call = jax.jit(call).lower(y0, p).compile()
        idx = np.zeros(self.per_call, np.int32)
        self._summarize = jax.jit(summarize).lower(
            self._call.out_info, p, idx).compile()
        del y0, p

    def lanes(self, i: int) -> np.ndarray:
        """The lanes of call ``i`` compared with the reference: the same
        number from every chip's shard, drawn from (seed, i)."""
        rng = np.random.default_rng([self.seed, i])
        each = self.per_call // len(self.devs)
        return np.concatenate([
            s * self.shard + np.sort(rng.choice(self.shard, each,
                                                replace=False))
            for s in range(len(self.devs))]).astype(np.int32)

    def window(self, seconds: float):
        window = super().window(seconds)
        per_shard = np.sum([np.asarray(s["trips"], np.int64)
                            for s in self._samples], axis=0)
        window.counters["shard_trips_max"] = float(per_shard.max())
        window.counters["shard_trips_mean"] = float(per_shard.mean())
        return window
