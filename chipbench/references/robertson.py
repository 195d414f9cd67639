"""Plain reference for the Robertson deployments.

Robertson's kinetics (SUNDIALS ``cvRoberts_dns``), one 3-species system
per lane with its own rate constants::

    y1' = -k1 y1 + k2 y2 y3
    y2' =  k1 y1 - k2 y2 y3 - k3 y2^2
    y3' =  k3 y2^2

integrated by linearly implicit Euler extrapolation (Deuflhard's
EULSIM/LIMEX scheme: the harmonic step sequence 1..6 under one
Jacobian per macro step, Aitken-Neville extrapolation to order 6, the
last two diagonal entries as the error estimate), with a step size per
lane.  It is written in NumPy over an array dtype, so the same code
runs as the float64 reference and, in a lower precision, as the
control that ``correct`` has to reject.  It imports nothing of the
system under test.
"""
from __future__ import annotations

import numpy as np

SEQUENCE = (1, 2, 3, 4, 5, 6)


def rhs(y, k1, k2, k3):
    """f(y) for lanes on the last axis: ``y`` is (3, m)."""
    a, b, c = y[0], y[1], y[2]
    r1, r2, r3 = k1 * a, k2 * b * c, k3 * b * b
    return np.stack([-r1 + r2, r1 - r2 - r3, r3])


def jac(y, k1, k2, k3):
    """df/dy, (3, 3, m)."""
    a, b, c = y[0], y[1], y[2]
    z = np.zeros_like(a)
    return np.stack([
        np.stack([-k1, k2 * c, k2 * b]),
        np.stack([k1, -k2 * c - 2 * k3 * b, -k2 * b]),
        np.stack([z, 2 * k3 * b, z])])


def inverse3(A):
    """Inverse of every (3, 3) block of ``A`` (3, 3, m), by cofactors."""
    c00 = A[1, 1] * A[2, 2] - A[1, 2] * A[2, 1]
    c01 = A[1, 2] * A[2, 0] - A[1, 0] * A[2, 2]
    c02 = A[1, 0] * A[2, 1] - A[1, 1] * A[2, 0]
    det = A[0, 0] * c00 + A[0, 1] * c01 + A[0, 2] * c02
    inv = np.stack([
        np.stack([c00, A[0, 2] * A[2, 1] - A[0, 1] * A[2, 2],
                  A[0, 1] * A[1, 2] - A[0, 2] * A[1, 1]]),
        np.stack([c01, A[0, 0] * A[2, 2] - A[0, 2] * A[2, 0],
                  A[0, 2] * A[1, 0] - A[0, 0] * A[1, 2]]),
        np.stack([c02, A[0, 1] * A[2, 0] - A[0, 0] * A[2, 1],
                  A[0, 0] * A[1, 1] - A[0, 1] * A[1, 0]])])
    return inv / det


def matvec(M, v):
    return (M * v[None, :, :]).sum(axis=1, dtype=v.dtype)


def integrate(y0, k1, k2, k3, tf, *, rtol, atol, dtype=np.float64,
              h0=1e-6, max_steps=4000):
    """Integrate every lane from t = 0 to ``tf``.

    ``y0`` is (m, 3); ``k1``..``k3`` are (m,); ``atol`` is one number
    or one per species.  The state and all its arithmetic are held in
    ``dtype``; times and step sizes in float64.
    Returns ``(y, done)``: y(tf) as (m, 3) float64, and whether each
    lane reached ``tf`` within ``max_steps`` macro steps (a lane that
    did not reports the state where it stopped).
    """
    dtype = np.dtype(dtype)
    y = np.asarray(y0, np.float64).T.astype(dtype)
    k = [np.asarray(x, np.float64).astype(dtype) for x in (k1, k2, k3)]
    m = y.shape[1]
    t = np.zeros(m)
    H = np.full(m, float(h0))
    eye = np.eye(3, dtype=dtype)[:, :, None]
    order = len(SEQUENCE)
    atol = np.asarray(atol, np.float64).reshape(-1, 1)
    for _ in range(max_steps):
        live = t < tf
        if not live.any():
            break
        Hs = np.where(live, np.minimum(H, tf - t), 0.0)
        J = jac(y, *k)
        f0 = rhs(y, *k)
        T = []
        for n in SEQUENCE:
            h = (Hs / n).astype(dtype)
            Minv = inverse3(eye - h[None, None, :] * J)
            z = y + matvec(Minv, h * f0)
            for _ in range(n - 1):
                z = z + matvec(Minv, h * rhs(z, *k))
            row = [z]
            for j in range(1, len(T) + 1):
                ratio = dtype.type(n / SEQUENCE[len(T) - j])
                prev = T[-1][j - 1]
                row.append(row[j - 1] + (row[j - 1] - prev) / (ratio - 1))
            T.append(row)
        best, second = T[-1][-1], T[-1][-2]
        scale = atol + rtol * np.maximum(np.abs(y), np.abs(best)).astype(
            np.float64)
        err = np.max(np.abs((best - second).astype(np.float64)) / scale,
                     axis=0)
        ok = live & np.isfinite(err) & (err <= 1.0)
        y = np.where(ok[None, :], best, y)
        t = np.where(ok, t + Hs, t)
        t = np.where(ok & (tf - t <= 1e-12 * tf), tf, t)
        fac = np.where(np.isfinite(err),
                       0.9 * np.maximum(err, 1e-10) ** (-1.0 / order), 0.2)
        H = np.where(live, Hs * np.clip(fac, 0.2, 4.0), H)
    return y.astype(np.float64).T, t >= tf
