"""Shared ensemble test problems (paper Fig. 5 submodel workload).

The batched Robertson kinetics problem is the canonical driver of the
ensemble subsystem: the example (``examples/batched_kinetics.py``), the
chip benchmark (``chipbench/``) and the test suite all integrate the
SAME problem, so it lives here once instead of as copies that could
drift apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def robertson_rates(nsys: int):
    """Per-cell Robertson rate constants ``(k1, k2, k3)``, each
    ``(nsys,)`` in the default float dtype, drawn from fixed PRNG keys:
    k2 spans [0.5e4, 1.5e4) and k3 two decades around 3e7.  Every
    Robertson ensemble below closes over these, so a reference run can
    rebuild exactly the same cells from them."""
    key = jax.random.PRNGKey(0)
    k1 = 0.04 * jnp.ones((nsys,))
    k2 = 1e4 * (0.5 + jax.random.uniform(key, (nsys,)))
    k3 = 3e7 * 10.0 ** jax.random.uniform(jax.random.PRNGKey(1), (nsys,),
                                          minval=-1.0, maxval=1.0)
    return k1, k2, k3


def batched_robertson(nsys: int):
    """Robertson kinetics with per-cell rate constants — ``nsys``
    independent 3-species systems whose stiffness varies cell to cell
    (k3 spans two orders of magnitude), the "large variations in
    stiffness" regime the paper warns about.

    Returns ``(f, jac, y0)``: ``f(t, y) -> (nsys, 3)`` and
    ``jac(t, y) -> (nsys, 3, 3)`` are vectorized over the batch with the
    rates closed over; ``y0`` is the standard ``[1, 0, 0]`` start.
    """
    k1, k2, k3 = robertson_rates(nsys)

    def f(t, y):  # y: (nsys, 3)
        a, b, c = y[:, 0], y[:, 1], y[:, 2]
        r1, r2, r3 = k1 * a, k2 * b * c, k3 * b * b
        return jnp.stack([-r1 + r2, r1 - r2 - r3, r3], axis=1)

    def jac(t, y):
        a, b, c = y[:, 0], y[:, 1], y[:, 2]
        z = jnp.zeros_like(a)
        return jnp.stack([
            jnp.stack([-k1, k2 * c, k2 * b], axis=1),
            jnp.stack([k1, -k2 * c - 2 * k3 * b, -k2 * b], axis=1),
            jnp.stack([z, 2 * k3 * b, z], axis=1)], axis=1)

    y0 = jnp.concatenate([jnp.ones((nsys, 1)), jnp.zeros((nsys, 2))],
                         axis=1)
    return f, jac, y0


def batched_robertson_soa(nsys: int):
    """Native SoA companions to :func:`batched_robertson` — the same
    per-cell rates (identical PRNG keys), with the system axis LAST:
    ``f_soa(t, y:(3,nsys)) -> (3,nsys)`` and ``jac_soa -> (3,3,nsys)``.

    Passing these to ``ensemble_bdf``/``ensemble_dirk`` (directly or via
    ``IVP(f_soa=..., jac_soa=...)``) makes the Newton hot loop fully
    conversion-free: the arithmetic is expression-for-expression the
    AoS form's, only the stacking axes differ, so trajectories stay
    bitwise-identical to the wrapped-AoS path (tests/test_soa_carry.py).
    """
    k1, k2, k3 = robertson_rates(nsys)

    def f_soa(t, y):  # y: (3, nsys)
        a, b, c = y[0], y[1], y[2]
        r1, r2, r3 = k1 * a, k2 * b * c, k3 * b * b
        return jnp.stack([-r1 + r2, r1 - r2 - r3, r3], axis=0)

    def jac_soa(t, y):  # -> (3, 3, nsys)
        a, b, c = y[0], y[1], y[2]
        z = jnp.zeros_like(a)
        return jnp.stack([
            jnp.stack([-k1, k2 * c, k2 * b], axis=0),
            jnp.stack([k1, -k2 * c - 2 * k3 * b, -k2 * b], axis=0),
            jnp.stack([z, 2 * k3 * b, z], axis=0)], axis=0)

    return f_soa, jac_soa


def robertson_family():
    """Parametric Robertson kinetics for the serving front-end: the same
    3-species problem as :func:`batched_robertson`, but with the rate
    constants supplied as *per-request data* instead of closed over —
    ``params = {"k1": (nsys,), "k2": (nsys,), "k3": (nsys,)}`` rides the
    bundle as a traced argument, so requests with different chemistry
    share ONE trace-cache entry (the shape-bucketed jit cache never
    recompiles on new rate constants).

    Returns ``(f, jac, f_soa, jac_soa)`` with signatures
    ``f(t:(nsys,), y:(nsys,3), params) -> (nsys,3)`` etc.; state size
    n = 3.
    """

    def f(t, y, p):  # y: (nsys, 3)
        a, b, c = y[:, 0], y[:, 1], y[:, 2]
        r1, r2, r3 = p["k1"] * a, p["k2"] * b * c, p["k3"] * b * b
        return jnp.stack([-r1 + r2, r1 - r2 - r3, r3], axis=1)

    def jac(t, y, p):
        a, b, c = y[:, 0], y[:, 1], y[:, 2]
        k1, k2, k3 = p["k1"], p["k2"], p["k3"]
        z = jnp.zeros_like(a)
        return jnp.stack([
            jnp.stack([-k1, k2 * c, k2 * b], axis=1),
            jnp.stack([k1, -k2 * c - 2 * k3 * b, -k2 * b], axis=1),
            jnp.stack([z, 2 * k3 * b, z], axis=1)], axis=1)

    def f_soa(t, y, p):  # y: (3, nsys)
        a, b, c = y[0], y[1], y[2]
        r1, r2, r3 = p["k1"] * a, p["k2"] * b * c, p["k3"] * b * b
        return jnp.stack([-r1 + r2, r1 - r2 - r3, r3], axis=0)

    def jac_soa(t, y, p):  # -> (3, 3, nsys)
        a, b, c = y[0], y[1], y[2]
        k1, k2, k3 = p["k1"], p["k2"], p["k3"]
        z = jnp.zeros_like(a)
        return jnp.stack([
            jnp.stack([-k1, k2 * c, k2 * b], axis=0),
            jnp.stack([k1, -k2 * c - 2 * k3 * b, -k2 * b], axis=0),
            jnp.stack([z, 2 * k3 * b, z], axis=0)], axis=0)

    return f, jac, f_soa, jac_soa


def decay_chain_family(n: int = 6):
    """Parametric linear decay chain (n species) — the serving suite's
    second shape, so mixed-shape traffic exercises distinct buckets:
    ``dy_0/dt = -k_0 y_0``, ``dy_i/dt = k_{i-1} y_{i-1} - k_i y_i``,
    with per-request decay rates ``params = {"k": (nsys, n)}``.  Mildly
    stiff when the rates span decades; the Jacobian is lower bidiagonal.

    Returns ``(f, jac, f_soa, jac_soa)`` in the batch conventions of
    :func:`robertson_family`.
    """

    def f(t, y, p):  # y: (nsys, n)
        r = p["k"] * y
        return -r + jnp.concatenate(
            [jnp.zeros_like(r[:, :1]), r[:, :-1]], axis=1)

    def jac(t, y, p):  # -> (nsys, n, n)
        k = p["k"]
        J = -jax.vmap(jnp.diag)(k)
        sub = jax.vmap(lambda kk: jnp.diag(kk, k=-1))(k[:, :-1])
        return J + sub

    def f_soa(t, y, p):  # y: (n, nsys)
        r = p["k"].T * y
        return -r + jnp.concatenate(
            [jnp.zeros_like(r[:1]), r[:-1]], axis=0)

    def jac_soa(t, y, p):  # -> (n, n, nsys)
        return jnp.transpose(jac(t, y.T, p), (1, 2, 0))

    return f, jac, f_soa, jac_soa


def ensemble_brusselator(nsys: int, nx: int = 16, du: float = 0.02,
                         dv: float = 0.02, a: float = 1.0):
    """An ensemble of 1-D Brusselator reaction-diffusion systems — the
    sparse-Jacobian submodel workload (arXiv:2405.01713's many-
    independent-ODE-systems regime with *banded* per-system Jacobians).

    Each of the ``nsys`` members is the classic 2-species Brusselator
    on ``nx`` cells (no-flux boundaries), with a per-member reaction
    parameter ``b`` spanning the oscillatory threshold, so stiffness
    varies across the ensemble.  State layout is interleaved
    ``[u_0, v_0, u_1, v_1, ...]`` (n = 2*nx), which makes the Jacobian
    banded: dense 2x2 reaction blocks on the diagonal plus
    species-diagonal Laplacian coupling to the neighbor cells —
    fill fraction ~ 4/nx, the exploit-the-sparsity regime.

    Returns ``(f, jac, jac_sparsity, y0)``: batched RHS/Jacobian in the
    ensemble convention (``(t:(nsys,), y:(nsys, n))``), the static
    (n, n) boolean pattern, and a perturbed near-steady start.
    """
    n = 2 * nx
    bpar = jnp.linspace(1.8, 3.2, nsys)
    h2 = 1.0 / ((1.0 / max(nx, 2)) ** 2)

    def lap(w):                       # (nsys, nx), no-flux (reflecting)
        wl = jnp.concatenate([w[:, :1], w[:, :-1]], axis=1)
        wr = jnp.concatenate([w[:, 1:], w[:, -1:]], axis=1)
        return (wl - 2.0 * w + wr) * h2

    def f(t, y):                      # y: (nsys, 2*nx)
        u, v = y[:, 0::2], y[:, 1::2]
        uv2 = u * u * v
        fu = a - (bpar[:, None] + 1.0) * u + uv2 + du * lap(u)
        fv = bpar[:, None] * u - uv2 + dv * lap(v)
        return jnp.stack([fu, fv], axis=2).reshape(y.shape[0], n)

    def f_single(t1, y1, b1):
        u, v = y1[0::2], y1[1::2]
        ul = jnp.concatenate([u[:1], u[:-1]])
        ur = jnp.concatenate([u[1:], u[-1:]])
        vl = jnp.concatenate([v[:1], v[:-1]])
        vr = jnp.concatenate([v[1:], v[-1:]])
        uv2 = u * u * v
        fu = a - (b1 + 1.0) * u + uv2 + du * (ul - 2.0 * u + ur) * h2
        fv = b1 * u - uv2 + dv * (vl - 2.0 * v + vr) * h2
        return jnp.stack([fu, fv], axis=1).reshape(n)

    def jac(t, y):
        # per-member dense (n, n) Jacobians; ensemble BDF compresses
        # them to the banded pattern at lsetup when jac_sparsity is set
        tb = jnp.broadcast_to(jnp.asarray(t), (y.shape[0],))
        return jax.vmap(lambda t1, y1, b1: jax.jacfwd(
            lambda yy: f_single(t1, yy, b1))(y1))(tb, y, bpar)

    import numpy as np
    P = np.zeros((n, n), bool)
    for i in range(nx):
        P[2 * i:2 * i + 2, 2 * i:2 * i + 2] = True    # reaction block
        for j in (i - 1, i + 1):                      # Laplacian coupling
            if 0 <= j < nx:
                P[2 * i, 2 * j] = True                # u_i <- u_j
                P[2 * i + 1, 2 * j + 1] = True        # v_i <- v_j
    x = jnp.linspace(0.0, 1.0, nx)
    u0 = a + 0.1 * jnp.sin(2 * jnp.pi * x)
    v0 = (bpar / a)[:, None] + 0.1 * jnp.cos(2 * jnp.pi * x)[None, :]
    y0 = jnp.stack([jnp.broadcast_to(u0, (nsys, nx)), v0],
                   axis=2).reshape(nsys, n)
    return f, jac, P, y0
