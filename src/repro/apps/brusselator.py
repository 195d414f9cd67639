"""The paper's demonstration problem (§7): 1D advection-reaction brusselator.

    u_t = -c u_x + A - (w+1) u + v u^2
    v_t = -c v_x + w u - v u^2
    w_t = -c w_x + (B - w)/eps - w u

First-order upwind on a periodic uniform mesh; IMEX integration with
ARKODE's ARK3(2)4L[2]SA: advection explicit, stiff reactions implicit.

Two nonlinear-solver configurations, exactly the paper's:

* **task-local** — Newton where the linear solve is the batched 3x3
  block-diagonal direct solve (reactions are point-local, so the stage
  Jacobian is Fig. 1's block-diagonal matrix).  The ONLY global
  communication in the solve is the WRMS norm reduction — the paper's
  "requires no parallel communication" property.  The 3x3 solves use
  the vectorized Gauss-Jordan (= the paper's offline-generated symbolic
  solver [21]) or the Pallas block-solve kernel.

* **global** — Newton + GMRES on the full system with the block solve
  as preconditioner (the paper's fallback for pre-custom-solver
  SUNDIALS versions).

On a mesh, the state shards over the 'data' axis; the upwind stencil's
``jnp.roll`` becomes a halo exchange (collective-permute) — the direct
analog of the paper's GPU-GPU NVLink point-to-point transfers.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import arkode, butcher, direct, krylov, matrix
from repro.core.arkode import ODEOptions
from repro.core.policies import ExecPolicy, XLA_FUSED


@dataclasses.dataclass(frozen=True)
class BrusselatorConfig:
    """Problem and solver settings of the paper's brusselator (§7)."""

    name: str = "brusselator1d"
    nx: int = 512            # mesh points (paper: up to 1.536e8)
    c: float = 0.01          # advection speed
    A: float = 1.0
    B: float = 3.5
    eps: float = 5e-6        # stiffness parameter
    b_domain: float = 10.0   # domain size (paper b in {10..2560})
    t_final: float = 10.0
    alpha: float = 0.1       # initial-bump amplitude
    rtol: float = 1e-6
    atol: float = 1e-9
    solver: str = "task-local"   # 'task-local' | 'global'


def initial_state(cfg: BrusselatorConfig) -> jnp.ndarray:
    """y: (nx, 3) with the gaussian-bump initial condition."""
    x = jnp.linspace(0.0, cfg.b_domain, cfg.nx, endpoint=False)
    mu, sigma = cfg.b_domain / 2.0, cfg.b_domain / 4.0
    p = cfg.alpha * jnp.exp(-((x - mu) ** 2) / (2 * sigma ** 2))
    u = cfg.A + p
    v = cfg.B / cfg.A + p
    w = 3.0 + p
    return jnp.stack([u, v, w], axis=1)


def advection_rhs(cfg: BrusselatorConfig, cst: Callable = lambda x, a: x):
    dx = cfg.b_domain / cfg.nx

    def fe(t, y):
        # first-order upwind (c > 0), periodic: the roll is the halo
        # exchange (collective-permute under sharding)
        ym1 = jnp.roll(y, 1, axis=0)
        return -(cfg.c / dx) * (y - ym1)

    return fe


def reaction_rhs(cfg: BrusselatorConfig):
    def fi(t, y):
        u, v, w = y[:, 0], y[:, 1], y[:, 2]
        du = cfg.A - (w + 1.0) * u + v * u * u
        dv = w * u - v * u * u
        dw = (cfg.B - w) / cfg.eps - w * u
        return jnp.stack([du, dv, dw], axis=1)

    return fi


def reaction_jacobian(cfg: BrusselatorConfig):
    """Analytic per-point 3x3 Jacobian blocks: (nx, 3, 3)."""

    def jac(t, y):
        u, v, w = y[:, 0], y[:, 1], y[:, 2]
        z = jnp.zeros_like(u)
        row0 = jnp.stack([-(w + 1.0) + 2.0 * v * u, u * u, -u], axis=1)
        row1 = jnp.stack([w - 2.0 * v * u, -u * u, u], axis=1)
        row2 = jnp.stack([-w, z, -1.0 / cfg.eps - u], axis=1)
        return jnp.stack([row0, row1, row2], axis=1)

    return jac


def task_local_lin_solver(cfg: BrusselatorConfig,
                          policy: ExecPolicy = XLA_FUSED):
    """(t, z, gamma, rhs) -> dz via batched 3x3 block elimination."""
    jac = reaction_jacobian(cfg)

    def solve(t, z, gamma, rhs):
        J = jac(t, z)                               # (nx, 3, 3)
        M = matrix.bd_scale_addi(-gamma, matrix.BlockDiagMatrix(J))
        return direct.block_solve(M, rhs, policy=policy)

    return solve


def global_gmres_lin_solver(cfg: BrusselatorConfig,
                            policy: ExecPolicy = XLA_FUSED):
    """(t, z, gamma, rhs) -> dz via GMRES with block-Jacobi preconditioner
    (the paper's 'global' configuration)."""
    fi = reaction_rhs(cfg)
    jac = reaction_jacobian(cfg)

    def solve(t, z, gamma, rhs):
        def matvec(v):
            _, jv = jax.jvp(lambda zz: fi(t, zz), (z,), (v,))
            return v - gamma * jv

        J = jac(t, z)
        M = matrix.bd_scale_addi(-gamma, matrix.BlockDiagMatrix(J))

        def precond(v):
            return direct.block_solve(M, v, policy=policy)

        dz, _ = krylov.gmres(matvec, rhs, tol=1e-4, restart=16,
                             max_restarts=2, precond=precond)
        return dz

    return solve


def integrate(cfg: BrusselatorConfig, *, t_final: Optional[float] = None,
              policy: ExecPolicy = XLA_FUSED,
              opts: Optional[ODEOptions] = None):
    """Run the IMEX integration; returns (y_final, stats)."""
    tf = t_final if t_final is not None else cfg.t_final
    y0 = initial_state(cfg)
    fe = advection_rhs(cfg)
    fi = reaction_rhs(cfg)
    if cfg.solver == "task-local":
        lin = task_local_lin_solver(cfg, policy)
    else:
        lin = global_gmres_lin_solver(cfg, policy)
    o = opts or ODEOptions(rtol=cfg.rtol, atol=cfg.atol, max_steps=100_000,
                           newton_max=6)
    return arkode.imex_integrate(fe, fi, y0, 0.0, tf, butcher.ARK324,
                                 o, lin_solver=lin)


def reference_solution(cfg: BrusselatorConfig, t_final: float,
                       n_steps: int = 20000):
    """Fine fixed-step explicit reference (expensive; small tf only)."""
    y0 = initial_state(cfg)
    fe = advection_rhs(cfg)
    fi = reaction_rhs(cfg)

    def f(t, y):
        return fe(t, y) + fi(t, y)

    return arkode.erk_fixed(f, y0, 0.0, t_final, n_steps,
                            butcher.DORMAND_PRINCE)
