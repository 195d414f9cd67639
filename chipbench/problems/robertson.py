"""Robertson's kinetics (SUNDIALS ``cvRoberts_dns``), n = 3, with the
rate constants k1, k2, k3 as per-system data: the system's own family
(``repro.core.problems.robertson_family``) and the benchmark's plain
reference (:mod:`chipbench.references.robertson`)."""
from __future__ import annotations

import numpy as np

N = 3
PARAMS = ("k1", "k2", "k3")


def family():
    """``(f, jac, f_soa, jac_soa)`` of the system under test, each taking
    the per-system ``params`` as a third argument."""
    from repro.core import problems

    return problems.robertson_family()


def reference(y0, params, t0: float, tf: float, *, rtol: float,
              atol: float, dtype=np.float64):
    """``(y(tf), reached)`` of every system, by the plain reference in
    ``dtype``; ``y0`` is (m, 3), ``params`` maps k1..k3 to (m,)."""
    from chipbench.references import robertson as ref

    if t0 != 0.0:
        raise ValueError("the Robertson reference starts at t = 0")
    return ref.integrate(y0, params["k1"], params["k2"], params["k3"], tf,
                         rtol=rtol, atol=atol, dtype=dtype)
