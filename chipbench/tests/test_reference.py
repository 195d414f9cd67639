"""The plain reference, checked against a second witness (SciPy's
Radau IIA), and the control it gives in a lower precision."""
import ml_dtypes
import numpy as np
import pytest
from scipy.integrate import solve_ivp

from chipbench import check
from chipbench.references import robertson as ref


def systems(m, seed=0):
    rng = np.random.default_rng(seed)
    k = (np.full(m, 0.04), 1e4 * rng.uniform(0.5, 1.5, m),
         3e7 * 10.0 ** rng.uniform(-1.0, 1.0, m))
    return np.tile([1.0, 0.0, 0.0], (m, 1)), k


def test_float64_reference_agrees_with_radau():
    y0, k = systems(6)
    y, reached = ref.integrate(y0, *k, 40.0, rtol=1e-10, atol=1e-16)
    assert reached.all()
    for i in range(len(y0)):
        ki = [np.array([x[i]]) for x in k]
        sol = solve_ivp(lambda t, v: ref.rhs(v[:, None], *ki)[:, 0],
                        (0.0, 40.0), y0[i], method="Radau", rtol=1e-11,
                        atol=1e-18,
                        jac=lambda t, v: ref.jac(v[:, None], *ki)[:, :, 0])
        # in units of the deployments' tolerance (rtol 1e-4, atol 1e-8)
        assert check.worst_err(y[i], sol.y[:, -1], 1e-4, 1e-8) < 1e-3


def test_mass_is_conserved():
    y0, k = systems(64, seed=1)
    y, _ = ref.integrate(y0, *k, 40.0, rtol=1e-10, atol=1e-16)
    assert np.abs(y.sum(axis=1) - 1.0).max() < 1e-9


@pytest.mark.parametrize("dtype,within", [(np.float32, True),
                                          (ml_dtypes.bfloat16, False)])
def test_precision_below_float32_fails_the_limit(dtype, within):
    """In float32 at the deployment's tolerances the reference stays
    within the cells' limit of 10 units; in bfloat16 (the control) it
    cannot hold rtol 1e-4 and does not."""
    y0, k = systems(64, seed=2)
    y_ref, _ = ref.integrate(y0, *k, 40.0, rtol=1e-10, atol=1e-16)
    y, _ = ref.integrate(y0, *k, 40.0, rtol=1e-4, atol=1e-8, dtype=dtype,
                         max_steps=400)
    assert (check.worst_err(y, y_ref, 1e-4, 1e-8) <= 10.0) is within
