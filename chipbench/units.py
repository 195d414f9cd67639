"""A per-component absolute tolerance, given to a program that takes one.

The SUNDIALS examples state atol per component (``cvRoberts_dns``:
1e-8, 1e-14, 1e-6).  The system under test takes one scalar atol
(``ODEOptions.atol``; the server's tolerance class), so a deployment
that states a vector is handed to it in units of each component's atol:
``z_i = y_i / s_i`` with ``s_i = atol_i / atol_0``, integrated at the
scalar ``atol_0``.  The error weight of ``z_i``, ``1 / (rtol |z_i| +
atol_0)``, is ``s_i`` times that of ``y_i``, ``1 / (rtol |y_i| +
atol_i)``, so every weighted norm, and with it every step's error test,
is the one the source states.
"""
from __future__ import annotations

import numpy as np


def split(atol):
    """``(atol_0, s)``: the scalar the program is given and the unit of
    each component (all ones for a scalar ``atol``)."""
    a = np.atleast_1d(np.asarray(atol, np.float64))
    return float(a[0]), a / a[0]


def family_in_units(family, scale, dtype):
    """``(f, jac, f_soa, jac_soa)`` of a parametric family (systems on
    axis 0, or on the last axis for the ``_soa`` forms) for the state
    ``z = y / scale``."""
    import jax.numpy as jnp

    f, jac, f_soa, jac_soa = family
    if np.all(scale == 1.0):
        return family
    s = jnp.asarray(scale, dtype)
    row, col = s[None, :, None], s[None, None, :]

    def fz(t, z, p):
        return f(t, z * s, p) / s

    def jz(t, z, p):
        return jac(t, z * s, p) * col / row

    def fz_soa(t, z, p):
        return f_soa(t, z * s[:, None], p) / s[:, None]

    def jz_soa(t, z, p):
        return (jac_soa(t, z * s[:, None], p) * s[None, :, None]
                / s[:, None, None])

    return fz, jz, fz_soa, jz_soa
