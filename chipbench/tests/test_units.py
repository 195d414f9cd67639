"""A per-species atol handed to the program in units of each species'
atol: the weights it implies and the family it integrates."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import units
from chipbench.problems import robertson

ATOL = [1e-8, 1e-14, 1e-6]


def test_weights_in_units_are_the_source_weights():
    atol0, s = units.split(ATOL)
    rtol = 1e-4
    y = np.array([0.7, 3e-5, 0.3])
    z = y / s
    # ||e_y||_{w_y} == ||e_z||_{w_z} for any error e
    e = np.array([1e-6, 1e-12, 1e-7])
    wy = 1.0 / (rtol * np.abs(y) + np.asarray(ATOL))
    wz = 1.0 / (rtol * np.abs(z) + atol0)
    np.testing.assert_allclose(e * wy, (e / s) * wz, rtol=1e-12)
    assert units.split(1e-8)[1].tolist() == [1.0]


def test_family_in_units_is_the_scaled_system():
    with jax.enable_x64(True):
        _, s = units.split(ATOL)
        f, jac, f_soa, jac_soa = robertson.family()
        fz, jz, fz_soa, jz_soa = units.family_in_units(
            (f, jac, f_soa, jac_soa), s, jnp.float64)
        p = {"k1": jnp.full((2,), 0.04), "k2": jnp.full((2,), 1e4),
             "k3": jnp.full((2,), 3e7)}
        y = jnp.array([[0.9, 2e-5, 0.1], [0.5, 1e-5, 0.5]])
        z = y / s
        np.testing.assert_allclose(fz(0.0, z, p) * s, f(0.0, y, p),
                                   rtol=1e-12)
        np.testing.assert_allclose(fz_soa(0.0, z.T, p), fz(0.0, z, p).T,
                                   rtol=1e-12)
        for i in range(2):
            one = {k: v[i:i + 1] for k, v in p.items()}
            auto = jax.jacfwd(lambda v: fz(0.0, v[None], one)[0])(z[i])
            np.testing.assert_allclose(jz(0.0, z, p)[i], auto, rtol=1e-10)
            np.testing.assert_allclose(jz_soa(0.0, z.T, p)[:, :, i], auto,
                                       rtol=1e-10)
