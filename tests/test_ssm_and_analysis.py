"""Chunked-SSD equivalence + analysis-tooling tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep; property tests
from hypothesis import given, settings, strategies as st

from repro.models import ssm


def _random_ssm_inputs(seed, B=2, S=32, nh=3, hd=8, ds=5):
    k = [jax.random.PRNGKey(seed + i) for i in range(6)]
    xs = jax.random.normal(k[0], (B, S, nh, hd))
    Bm = jax.random.normal(k[1], (B, S, ds))
    Cm = jax.random.normal(k[2], (B, S, ds))
    dt = jax.nn.softplus(jax.random.normal(k[3], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(k[4], (nh,)) * 0.5)
    h0 = 0.1 * jax.random.normal(k[5], (B, nh, hd, ds))
    return xs, Bm, Cm, dt, dt * A[None, None], h0


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_ssd_equals_stepwise(chunk):
    xs, Bm, Cm, dt, ld, h0 = _random_ssm_inputs(0)
    y1, h1 = ssm._ssm_scan_stepwise(xs, Bm, Cm, jnp.exp(ld), dt, h0)
    y2, h2 = ssm._ssm_scan_chunked(xs, Bm, Cm, ld, dt, h0, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=2e-4,
                               atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 1000))
def test_chunked_ssd_property(seed):
    xs, Bm, Cm, dt, ld, h0 = _random_ssm_inputs(seed, B=1, S=16, nh=2,
                                                hd=4, ds=3)
    y1, h1 = ssm._ssm_scan_stepwise(xs, Bm, Cm, jnp.exp(ld), dt, h0)
    y2, h2 = ssm._ssm_scan_chunked(xs, Bm, Cm, ld, dt, h0, 4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=5e-4,
                               atol=5e-4)


def test_mamba2_decode_consistent_with_train_path():
    """Prefill via the train path == step-by-step decode with caches."""
    from repro import configs
    from repro.models.spec import init_params
    cfg = configs.get("zamba2-7b-smoke").replace(dtype=jnp.float32)
    p = init_params(ssm.mamba2_spec(cfg), jax.random.PRNGKey(0))
    B, S = 1, 6
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model),
                                jnp.float32)
    y_train, _ = ssm.mamba2_apply(p, cfg, x)          # stepwise (S small)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), ssm.mamba2_cache_spec(cfg, B))
    outs = []
    for t in range(S):
        yt, cache = ssm.mamba2_apply(p, cfg, x[:, t:t + 1], cache=cache)
        outs.append(yt)
    y_dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_train), np.asarray(y_dec),
                               rtol=2e-3, atol=2e-3)


def test_collective_traffic_model():
    """Ring-model byte accounting from synthetic HLO lines."""
    from repro.analysis import hlocost
    hc = hlocost.HloCost("", n_devices=8)
    ag = ('%ag = f32[16,32] all-gather(%x), replica_groups=[2,4]<=[8], '
          'dimensions={0}')
    # out 2048 B, g=4 -> 2048*3/4 = 1536
    assert hc._coll_traffic(ag, "all-gather") == 1536
    ar = '%ar = bf16[64] all-reduce(%x), replica_groups=[1,8]<=[8]'
    # 128 B * 2 * 7/8 = 224
    assert hc._coll_traffic(ar, "all-reduce") == 224


def test_hlocost_collectives_in_loops():
    from repro.analysis import hlocost

    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "i"), None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    from jax.sharding import PartitionSpec as P
    import os
    # single-device "mesh" still emits the loop structure
    mesh = jax.make_mesh((1,), ("i",))
    g = jax.shard_map(f, mesh=mesh, in_specs=P(None), out_specs=P(None))
    txt = jax.jit(g).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile().as_text()
    res = hlocost.analyze(txt, 1)
    # degenerate 1-device psum may be optimized away; the walk must not
    # crash and flops/bytes must be finite
    assert res["bytes"] >= 0 and res["flops"] >= 0


def test_roofline_math():
    from repro.analysis import roofline as rf
    row = rf.Roofline(arch="a", shape="s", mesh="m", chips=256,
                      hlo_flops=197e12, hlo_bytes=819e9, coll_bytes=50e9,
                      model_flops=197e12 * 256).finalize()
    assert abs(row.t_compute - 1.0) < 1e-9
    assert abs(row.t_memory - 1.0) < 1e-9
    assert abs(row.t_collective - 1.0) < 1e-9
    assert abs(row.useful_ratio - 1.0) < 1e-9
    assert abs(row.mfu_bound - 1.0) < 1e-9


def test_active_param_count_moe_scaling():
    from repro.analysis import roofline as rf
    from repro import configs
    dsv3 = configs.get("deepseek-v3-671b")
    total_like = rf.active_param_count(dsv3.replace(experts_per_tok=256))
    active = rf.active_param_count(dsv3)
    assert active < total_like / 10       # top-8 of 256 experts
    dense = configs.get("qwen2-72b")
    n = rf.active_param_count(dense)
    assert 70e9 < n < 82e9                # ~72-80B params as configured
