#!/usr/bin/env python3
"""Bring-up smoke run of the ensemble-BDF and serving path on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded ensemble on four chips

One chip runs three phases through the user entry points, with the
Pallas kernels compiled for the chip:

* ensemble   -- ``integrate(..., "ensemble_bdf", lin_solver=BlockDiagGJ())``
  over 1,048,576 Robertson systems (one chip's share of a 128x128x64
  reacting-flow mesh, one 3-species stiff system per cell), in float32,
  once under ``backend="pallas"`` and once under ``backend="jnp"``;
* block      -- ``block_solve_soa`` / ``block_inverse_soa`` at b = 16
  and 24 (the row-tiled kernels) against the jnp oracle;
* serving    -- a ``SolverServer`` answering 64 requests and one
  warm-start continuation under the compiled Pallas policy.

``--four-chips`` runs only the sharded ensemble (``integrate(...,
"ensemble_bdf", mesh=...)`` with the native SoA Robertson family and
the rates in ``IVP.params``, 4 x 1,048,576 systems) and the same
systems on one chip to compare with.

Answers are checked against a float64 jnp run of the same systems on the
host CPU.  Everything runs in this one process, which starts no child.
The script exits nonzero when no TPU is found or when any check fails;
only when every phase passed does it print, as its last line, one JSON
object naming the device.  The wall times it prints are the set-up of
one run (compile plus first execution), not rates.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

NSYS = 1 << 20            # 128 x 128 x 64 cells, one Robertson system each
TF = 40.0
RTOL, ATOL = 1e-4, 1e-8   # float32 state: tolerances f32 can meet
# The float64 reference integrates at a tolerance 1e5 x tighter, so its
# own error is negligible against the limits below.
REF_RTOL, REF_ATOL = 1e-9, 1e-14
# Limit on |y - y_ref| / (RTOL*|y_ref| + ATOL), every component.  The
# tolerances bound the local error of each step; the global error of
# this contractive stiff problem stays a small multiple of them.
# float32 runs of the checked lanes on the host CPU came within 4.0
# (jnp) and 3.5 (Pallas, interpret mode); 10 leaves room for the
# chip's own rounding and step sequence.
ERR_MULT = 10.0
N_CHECK = 1024            # lanes compared with the float64 reference
SEED = 0
# |sum(y) - 1| limit.  float32 state does not conserve the mass to
# machine precision: over all NSYS cells in float32 on the host CPU the
# defect reached 7.0e-4 (float64: 1e-7 on the checked lanes).  It is
# held to the accuracy of the state itself, which the component check
# implies: ERR_MULT * (RTOL * sum|y| + 3 * ATOL).
MASS_LIMIT = ERR_MULT * (RTOL * 1.0 + 3 * ATOL)
BLOCK_NSYS = 32768
# |x - x_oracle| / max|x_oracle| limit for the block phase.  The blocks
# are 2b*I + N(0, 1): diagonally dominant, condition number below ~3,
# so two float32 eliminations agree to about b * eps32 * cond ~ 1e-5.
BLOCK_LIMIT = 1e-4
SERVE_REQUESTS = 64


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def require_tpu(count: int):
    """The device check: a TPU with ``count`` chips, or exit nonzero.

    The float64 reference runs on the host CPU device, so a platform
    list that names backends without the CPU gets it added (the TPU
    stays the default backend)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: the default JAX device is "
                 f"{devs[0].platform!r} ({devs[0].device_kind}); this "
                 f"script runs only on a TPU chip and never falls back "
                 f"to the CPU")
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    check(len(devs) >= count, f"need {count} TPU chips, found {len(devs)}")
    return devs


def import_repro():
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as exc:
        sys.exit(f"chip_smoke: the repro package was not found under "
                 f"{os.path.join(here, 'src')}: {exc}")


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def in_use(device) -> int:
    return int(device.memory_stats()["bytes_in_use"])


def worst_err(y, y_ref) -> float:
    """Largest |y - y_ref| in units of RTOL*|y_ref| + ATOL."""
    y, y_ref = np.asarray(y, np.float64), np.asarray(y_ref, np.float64)
    return float(np.max(np.abs(y - y_ref) / (RTOL * np.abs(y_ref) + ATOL)))


def reference(rates, y0, t1: float) -> np.ndarray:
    """float64 jnp ensemble-BDF run of the given Robertson systems on the
    host CPU device: ``rates`` maps k1/k2/k3 to (m,) arrays, ``y0`` is
    (m, 3); returns y(t1), (m, 3)."""
    import jax
    import jax.numpy as jnp
    from repro.core import problems
    from repro.core.arkode import ODEOptions
    from repro.core.ivp import IVP, integrate
    from repro.core.linsol import BlockDiagGJ

    f, jac, f_soa, jac_soa = problems.robertson_family()
    with jax.enable_x64(True), \
            jax.default_device(jax.devices("cpu")[0]):
        p = {k: jnp.asarray(np.asarray(v), jnp.float64)
             for k, v in rates.items()}
        prob = IVP(f=lambda t, y: f(t, y, p),
                   jac=lambda t, y: jac(t, y, p),
                   f_soa=lambda t, y: f_soa(t, y, p),
                   jac_soa=lambda t, y: jac_soa(t, y, p),
                   y0=jnp.asarray(np.asarray(y0), jnp.float64))
        sol = integrate(prob, 0.0, t1, "ensemble_bdf",
                        lin_solver=BlockDiagGJ(),
                        opts=ODEOptions(rtol=REF_RTOL, atol=REF_ATOL))
        rc = np.asarray(sol.retcodes)
        check(bool((rc == 0).all()),
              f"float64 reference: retcodes {np.unique(rc)}")
        return np.asarray(sol.y, np.float64)


def pallas_policy(what: str):
    """The compiled Pallas policy: ``ExecPolicy(backend="pallas")`` with
    its interpret mode derived from the device, which must say
    compiled."""
    from repro.core.policies import ExecPolicy
    policy = ExecPolicy(backend="pallas")
    check(not policy.interpreted(),
          f"{what}: Pallas would run in interpret mode")
    return policy


def require_kernels(text: str, what: str) -> None:
    check("tpu_custom_call" in text,
          f"{what}: the lowered program holds no tpu_custom_call, so no "
          f"Pallas kernel was compiled for the chip")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def ensemble_phase(backend: str, nsys: int, idx, y_ref, device) -> None:
    """One ensemble-BDF run over ``nsys`` Robertson cells under
    ``backend``; checks retcodes, mass and the ``idx`` lanes against
    ``y_ref``."""
    import jax
    from repro.core import problems
    from repro.core.arkode import ODEOptions
    from repro.core.ivp import IVP, integrate
    from repro.core.linsol import BlockDiagGJ
    from repro.core.policies import ExecPolicy

    tag = f"ensemble {backend}"
    policy = pallas_policy(tag) if backend == "pallas" else \
        ExecPolicy(backend=backend)
    f, jac, y0 = problems.batched_robertson(nsys)
    f_soa, jac_soa = problems.batched_robertson_soa(nsys)
    opts = ODEOptions(rtol=RTOL, atol=ATOL, policy=policy)

    def run(y0):
        sol = integrate(IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa,
                            y0=y0), 0.0, TF, "ensemble_bdf",
                        lin_solver=BlockDiagGJ(), opts=opts)
        return sol.y, sol.retcodes, sol.stats.steps, sol.stats.nni

    t0 = time.perf_counter()
    lowered = jax.jit(run).lower(y0)
    if backend == "pallas":
        require_kernels(lowered.as_text(), tag)
    y, rc, steps, nni = jax.block_until_ready(lowered.compile()(y0))
    setup_s = time.perf_counter() - t0
    y = np.asarray(y)
    rc = np.asarray(rc)
    check(y.dtype == np.float32, f"{tag}: state dtype {y.dtype}")
    check(bool((rc == 0).all()),
          f"{tag}: {int((rc != 0).sum())} lanes failed, retcodes "
          f"{np.unique(rc)}")
    mass = float(np.max(np.abs(y.astype(np.float64).sum(axis=1) - 1.0)))
    err = worst_err(y[idx], y_ref)
    steps = np.asarray(steps)
    log(f"[{tag}] nsys={nsys} steps max={int(steps.max())} "
        f"mean={float(steps.mean()):.2f} newton_iters="
        f"{int(np.asarray(nni, np.int64).sum())} worst_err={err:.4f} "
        f"(limit {ERR_MULT}, units of rtol*|y|+atol, {len(idx)} lanes "
        f"vs float64 CPU) mass_err={mass:.3e} (limit {MASS_LIMIT:.3e}) "
        f"setup_s={setup_s:.2f} (compile + first run, not a rate) "
        f"peak_bytes_in_use={peak_bytes(device)}")
    check(err <= ERR_MULT, f"{tag}: worst error {err} > {ERR_MULT}")
    check(mass <= MASS_LIMIT, f"{tag}: mass error {mass} > {MASS_LIMIT}")


def block_phase(nsys: int, device) -> None:
    """Row-tiled Gauss-Jordan solve and inverse at b = 16, 24 against
    the jnp oracle, both on the chip in float32."""
    import jax
    import jax.numpy as jnp
    from repro.core import dispatch as dv
    from repro.core.policies import XLA_FUSED

    pallas = pallas_policy("block")
    rng = np.random.default_rng(SEED)
    for b in (16, 24):
        A = (rng.standard_normal((b, b, nsys))
             + 2.0 * b * np.eye(b)[:, :, None]).astype(np.float32)
        r = rng.standard_normal((b, nsys)).astype(np.float32)
        A, r = jnp.asarray(A), jnp.asarray(r)
        tag = f"block b={b}"
        t0 = time.perf_counter()
        solve = jax.jit(lambda A, r: dv.block_solve_soa(A, r, pallas))
        inverse = jax.jit(lambda A: dv.block_inverse_soa(A, pallas))
        ls, li = solve.lower(A, r), inverse.lower(A)
        require_kernels(ls.as_text(), f"{tag} solve")
        require_kernels(li.as_text(), f"{tag} inverse")
        x, Ainv = jax.block_until_ready((ls.compile()(A, r),
                                         li.compile()(A)))
        setup_s = time.perf_counter() - t0
        x_o = dv.block_solve_soa(A, r, XLA_FUSED)
        Ainv_o = dv.block_inverse_soa(A, XLA_FUSED)
        e_x = float(jnp.max(jnp.abs(x - x_o)) / jnp.max(jnp.abs(x_o)))
        e_i = float(jnp.max(jnp.abs(Ainv - Ainv_o))
                    / jnp.max(jnp.abs(Ainv_o)))
        log(f"[{tag}] nsys={nsys} solve_err={e_x:.3e} "
            f"inverse_err={e_i:.3e} (limit {BLOCK_LIMIT:.0e}, relative to "
            f"max|oracle|, jnp oracle on chip) setup_s={setup_s:.2f} "
            f"(compile + first run, not a rate) "
            f"peak_bytes_in_use={peak_bytes(device)}")
        check(e_x <= BLOCK_LIMIT and e_i <= BLOCK_LIMIT,
              f"{tag}: error {e_x}, {e_i} > {BLOCK_LIMIT}")


def serving_phase(device) -> None:
    """A SolverServer under the compiled Pallas policy: 64 seeded
    requests, then one warm-start continuation of the first."""
    from repro.core import problems
    from repro.core.context import Context
    from repro.serve.solver import ProblemFamily, SolverServer

    fam = ProblemFamily("robertson", 3, *problems.robertson_family())
    srv = SolverServer(fam, Context(policy=pallas_policy("serving")),
                       bucket_sizes=(SERVE_REQUESTS,),
                       max_batch=SERVE_REQUESTS)
    rng = np.random.default_rng(SEED + 1)
    m = SERVE_REQUESTS
    rates = {"k1": np.full(m, 0.04),
             "k2": 1e4 * (0.5 + rng.random(m)),
             "k3": 3e7 * 10.0 ** rng.uniform(-1.0, 1.0, m)}
    rates = {k: v.astype(np.float32) for k, v in rates.items()}
    y0 = np.array([1.0, 0.0, 0.0], np.float32)

    def params(i):
        return {k: float(v[i]) for k, v in rates.items()}

    t0 = time.perf_counter()
    futs = [srv.submit("robertson", y0, 0.0, TF, rtol=RTOL, atol=ATOL,
                       params=params(i)) for i in range(m)]
    srv.drain()
    sols = [fut.result() for fut in futs]     # a failed Future raises
    first_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    fut = srv.submit("robertson", sols[0].y, TF, 2 * TF, rtol=RTOL,
                     atol=ATOL, params=params(0),
                     session=sols[0].session)
    srv.drain()
    cont = fut.result()
    cont_s = time.perf_counter() - t1

    y = np.stack([np.asarray(s.y) for s in sols])
    y_ref = reference(rates, np.tile(y0, (m, 1)), TF)
    y_ref_cont = reference({k: v[:1] for k, v in rates.items()},
                           y0[None, :], 2 * TF)
    err = worst_err(y, y_ref)
    err_cont = worst_err(np.asarray(cont.y)[None, :], y_ref_cont)
    met = srv.metrics()
    texts = [srv.cache.get(key, None)[0].fn.as_text()
             for key in srv.cache.keys()]
    steps = sum(int(s.stats.steps) for s in sols)
    nni = sum(int(s.nni) for s in sols)
    log(f"[serving] requests={m}+1 bundles={met['bundles']} steps="
        f"{steps} newton_iters={nni} worst_err={err:.4f} "
        f"continuation_err={err_cont:.4f} (limit {ERR_MULT}, units of "
        f"rtol*|y|+atol vs float64 CPU) degraded={met['degraded']} "
        f"failures={met['failures']} setup_s={first_s:.2f} "
        f"(compile + first bundle, not a rate) continuation_s="
        f"{cont_s:.2f} peak_bytes_in_use={peak_bytes(device)}")
    check(met["degraded"] == 0, f"serving: {met['degraded']} bundles "
          f"fell back to the jnp oracle")
    check(not met["failures"], f"serving: failures {met['failures']}")
    for text in texts:
        require_kernels(text, "serving")
    check(err <= ERR_MULT, f"serving: worst error {err} > {ERR_MULT}")
    check(err_cont <= ERR_MULT,
          f"serving: continuation error {err_cont} > {ERR_MULT}")


def sharded_phase(devs) -> None:
    """4 x NSYS systems with per-system params on the default
    ('systems',) mesh, through ``integrate(..., mesh=)`` with the native
    SoA family, against the same systems on one chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import problems
    from repro.core.arkode import ODEOptions
    from repro.core.ivp import IVP, integrate
    from repro.core.linsol import BlockDiagGJ
    from repro.launch.mesh import make_ensemble_mesh

    nsys = 4 * NSYS
    policy = pallas_policy("sharded")
    opts = ODEOptions(rtol=RTOL, atol=ATOL, policy=policy)
    f, jac, f_soa, jac_soa = problems.robertson_family()
    # inputs are held on the host, so that the memory each device holds
    # before the sharded run is only its baseline
    params = dict(zip(("k1", "k2", "k3"),
                      map(np.asarray, problems.robertson_rates(nsys))))
    y0 = np.zeros((nsys, 3), np.float32)
    y0[:, 0] = 1.0
    kw = dict(opts=opts, lin_solver=BlockDiagGJ())

    mesh = make_ensemble_mesh()
    shard = NamedSharding(mesh, P("systems"))
    before = [in_use(d) for d in devs]
    y0_s, params_s = jax.device_put((y0, params), shard)

    def solve(y0, params, mesh=None):
        sol = integrate(IVP(f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa,
                            y0=y0, params=params), 0.0, TF, "ensemble_bdf",
                        mesh=mesh, **kw)
        return sol.y, sol.retcodes

    def sharded(y0, params):
        return solve(y0, params, mesh)

    t0 = time.perf_counter()
    lowered = jax.jit(sharded).lower(y0_s, params_s)
    require_kernels(lowered.as_text(), "sharded")
    y_sh, rc_sh = jax.block_until_ready(lowered.compile()(y0_s, params_s))
    sh_s = time.perf_counter() - t0
    after = [in_use(d) for d in devs]
    shard_devs = {s.device for s in y_sh.addressable_shards}

    one = jax.sharding.SingleDeviceSharding(devs[0])
    y0_1, params_1 = jax.device_put((y0, params), one)
    t1 = time.perf_counter()
    y_1, rc_1 = jax.block_until_ready(jax.jit(solve)(y0_1, params_1))
    one_s = time.perf_counter() - t1
    y_sh, y_1 = np.asarray(y_sh), np.asarray(y_1)
    rc_sh, rc_1 = np.asarray(rc_sh), np.asarray(rc_1)
    err = worst_err(y_sh, y_1)
    log(f"[sharded] nsys={nsys} shards_on={len(shard_devs)} distinct "
        f"devices bytes_in_use before={before} after={after} "
        f"max_err_vs_one_chip={err:.4g} (limit {ERR_MULT}, units of "
        f"rtol*|y|+atol) bitwise_equal={bool(np.array_equal(y_sh, y_1))} "
        f"setup_s sharded={sh_s:.2f} one_chip={one_s:.2f} (compile + "
        f"first run, not rates)")
    check(len(shard_devs) == len(devs) == 4,
          f"sharded: output shards on {len(shard_devs)} devices")
    check(all(a > b for a, b in zip(after, before)),
          f"sharded: memory in use did not grow on every device: "
          f"{before} -> {after}")
    check(bool((rc_sh == 0).all()) and bool((rc_1 == 0).all()),
          "sharded: failed lanes")
    check(err <= ERR_MULT, f"sharded: error vs one chip {err} > {ERR_MULT}")


def one_chip(dev) -> None:
    """The one-chip phases, each against its reference."""
    import jax
    from repro.core import problems

    k1, k2, k3 = problems.robertson_rates(NSYS)
    idx = np.sort(np.random.default_rng(SEED).choice(
        NSYS, N_CHECK, replace=False))
    rates = {"k1": np.asarray(k1)[idx], "k2": np.asarray(k2)[idx],
             "k3": np.asarray(k3)[idx]}
    y0 = np.tile(np.array([1.0, 0.0, 0.0]), (N_CHECK, 1))
    t0 = time.perf_counter()
    y_ref = reference(rates, y0, TF)
    log(f"[reference] float64 jnp on {jax.devices('cpu')[0]} "
        f"lanes={N_CHECK} rtol={REF_RTOL} atol={REF_ATOL} "
        f"seconds={time.perf_counter() - t0:.2f}")
    for backend in ("pallas", "jnp"):
        ensemble_phase(backend, NSYS, idx, y_ref, dev)
    block_phase(BLOCK_NSYS, dev)
    serving_phase(dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded ensemble on four chips and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)

    devs = require_tpu(4 if args.four_chips else 1)
    import_repro()
    import jax
    from repro.launch.cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")

    if args.four_chips:
        sharded_phase(devs[:4])
    else:
        one_chip(devs[0])
    log(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
