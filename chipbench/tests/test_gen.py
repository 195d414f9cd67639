"""Traffic generation from the seed."""
import jax
import numpy as np

from chipbench import gen

SPEC = {"k1": {"dist": "const", "value": 0.04},
        "k2": {"dist": "uniform", "low": 5e3, "high": 1.5e4},
        "k3": {"dist": "log_uniform", "low": 3e6, "high": 3e8}}


def test_seed_key_uses_every_bit():
    big = 2 ** 33 + 5
    a, b = gen.seed_key(5), gen.seed_key(big)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(gen.seed_key(big)), np.asarray(b))


def test_params_follow_the_traffic_file():
    p = jax.device_get(gen.draw_params(gen.seed_key(3), 4096, SPEC,
                                       np.float32))
    assert (p["k1"] == np.float32(0.04)).all()
    assert p["k2"].min() >= 5e3 and p["k2"].max() <= 1.5e4
    assert p["k3"].min() >= 3e6 * 0.999 and p["k3"].max() <= 3e8 * 1.001
    # log-uniform: about half of the draws below the geometric middle
    assert 0.45 < (p["k3"] < 3e7).mean() < 0.55


def test_schedule_same_load_other_order():
    a = gen.poisson_schedule(1, 20.0, 30.0)
    b = gen.poisson_schedule(2, 20.0, 30.0)
    assert abs(len(a) - len(b)) <= 2 and abs(len(a) - 600) <= 2
    assert not np.array_equal(a[:50], b[:50])
    assert a[0] == 0.0 and a[-1] < 30.0 and (np.diff(a) > 0).all()
