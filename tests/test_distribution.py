"""Distribution tests: sharding rules, MoE EP vs dense oracle, small-mesh
dry-run — multi-device paths run in subprocesses with their own XLA_FLAGS
(this process must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.parallel import sharding as shd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, devices: int = 8, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"      # children never touch the chip
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


class FakeMesh:
    """Just enough for spec_for without touching jax devices."""

    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as _np
        self.devices = _np.zeros(tuple(sizes.values()))


def test_spec_for_divisibility_fallbacks():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    # fully divisible: both rules apply
    s = shd.spec_for((8192, 64, 128), ("embed", "heads", "head_dim"),
                     mesh, shd.PARAM_RULES)
    assert s[0] == ("pod", "data") and s[1] == "model"
    # 36 heads don't divide 16 -> replicated
    s = shd.spec_for((4608, 36, 128), ("embed", "heads", "head_dim"),
                     mesh, shd.PARAM_RULES)
    assert len(s) < 2 or s[1] is None
    # experts: 256 divides model*data -> owned; 16 shrinks to model-only
    s = shd.spec_for((256, 7168, 2048), ("experts", "embed", "expert_mlp"),
                     mesh, shd.PARAM_RULES)
    assert s[0] == ("model", "data")
    s = shd.spec_for((16, 6144, 10752), ("experts", "embed", "expert_mlp"),
                     mesh, shd.PARAM_RULES)
    assert s[0] == "model"
    # a mesh axis never appears twice (uniqueness)
    s = shd.spec_for((7168, 1536), ("embed", "q_lora"), mesh,
                     shd.PARAM_RULES)
    flat = []
    for e in s:
        if e is None:
            continue
        flat.extend(e if isinstance(e, tuple) else (e,))
    assert len(flat) == len(set(flat))
    # batch=1 decode falls back to replication
    s = shd.spec_for((1, 1), ("batch", "seq"), mesh, shd.ACT_RULES)
    assert all(e is None for e in s) or len(s) == 0


def test_fsdp_profile_rules():
    mesh = FakeMesh({"data": 16, "model": 16})
    prules, arules = shd.PROFILES["fsdp"]
    s = shd.spec_for((8192, 64, 128), ("embed", "heads", "head_dim"),
                     mesh, prules)
    assert s[0] == ("data", "model")   # pod absent -> dropped
    s = shd.spec_for((256, 4096, 8192), ("batch", "seq", "embed"),
                     mesh, arules)
    assert s[0] == "data" and s[1] == "model"


@pytest.mark.slow
def test_moe_ep_matches_dense_oracle():
    """EP (shard_map + all_to_all) == dense MoE when under capacity."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models import Model, ParallelCtx, transformer as T
        from repro.models import layers, moe_ep
        from repro.parallel import sharding as shd
        cfg = configs.get("dbrx-132b-smoke").replace(
            moe_cap_factor=8.0, dtype=jnp.float32)  # no drops
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        m = Model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        lp = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
        x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model),
                                    cfg.dtype)
        dense = layers.moe_dense_apply(lp["ffn"], cfg, x)
        ep = jax.jit(lambda x: moe_ep.moe_ep_apply(
            lp["ffn"], cfg, x, mesh, dp_axes=("data",), ep_axis="model",
            token_layout="split"))(x)
        err = float(jnp.max(jnp.abs(dense - ep)))
        assert err < 2e-4, err
        # multi-axis EP (experts owned per chip: 4 experts / 8 chips -> no;
        # use 8 experts)
        cfg2 = cfg.replace(n_experts=8)
        from repro.models.spec import init_params
        p2 = init_params(layers.moe_spec(cfg2), jax.random.PRNGKey(2))
        dense2 = layers.moe_dense_apply(p2, cfg2, x)
        ep2 = jax.jit(lambda x: moe_ep.moe_ep_apply(
            p2, cfg2, x, mesh, dp_axes=("data",),
            ep_axis=("model", "data"), token_layout="split"))(x)
        err2 = float(jnp.max(jnp.abs(dense2 - ep2)))
        assert err2 < 2e-4, err2
        # decode layout (tokens replicated over model, single-axis psum)
        ep3 = jax.jit(lambda x: moe_ep.moe_ep_apply(
            lp["ffn"], cfg, x, mesh, dp_axes=("data",), ep_axis="model",
            token_layout="replicated"))(x)
        err3 = float(jnp.max(jnp.abs(dense - ep3)))
        assert err3 < 2e-4, err3
        # decode layout, multi-axis (duplicated dispatch path)
        ep4 = jax.jit(lambda x: moe_ep.moe_ep_apply(
            p2, cfg2, x, mesh, dp_axes=("data",),
            ep_axis=("model", "data"), token_layout="replicated"))(x)
        err4 = float(jnp.max(jnp.abs(dense2 - ep4)))
        assert err4 < 2e-4, err4
        print("OK", err, err2, err3, err4)
    """)
    out = _run_py(code, devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_small_mesh_dryrun_and_sharded_equals_single():
    """(a) dry-run machinery on an 8-device debug mesh; (b) sharded train
    step loss == single-device loss (GSPMD correctness)."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.config import ShapeConfig, SHAPES
        SHAPES["tiny_train"] = ShapeConfig("tiny_train", 32, 8, "train")
        SHAPES["tiny_decode"] = ShapeConfig("tiny_decode", 32, 8, "decode")
        from repro import configs
        from repro.launch import dryrun
        from repro.models import Model
        from repro.train import step as tstep
        from repro.parallel import sharding as shd
        from repro.data import pipeline
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        for arch in ("internlm2-1.8b-smoke", "deepseek-v3-671b-smoke"):
            for shape in ("tiny_train", "tiny_decode"):
                res = dryrun.lower_cell(arch, shape, mesh, "debug")
                assert res["ok"], (arch, shape)
                assert res["roofline"]["hlo_flops"] > 0
        # GSPMD equivalence: same data, same init -> same loss
        cfg = configs.get("internlm2-1.8b-smoke").replace(dtype=jnp.float32)
        m = Model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        d = pipeline.DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                global_batch=8)
        b = {k: jnp.asarray(v) for k, v in
             pipeline.synthetic_batch(d, 0).items()}
        loss1 = float(m.loss(params, b))
        pctx = dryrun.make_pctx(cfg, mesh, "train")
        pshd = shd.param_shardings(
            jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype), params), m.param_axes(), mesh)
        params_sh = jax.device_put(params, pshd)
        loss2 = float(jax.jit(lambda p, b: m.loss(p, b, pctx))(params_sh, b))
        assert abs(loss1 - loss2) < 1e-4, (loss1, loss2)
        print("OK", loss1, loss2)
    """)
    out = _run_py(code, devices=8)
    assert "OK" in out


def test_cache_axes_structure_matches():
    from repro import configs
    from repro.models import Model
    for arch in ("qwen2-72b", "deepseek-v3-671b", "zamba2-7b",
                 "xlstm-125m", "whisper-tiny"):
        cfg = configs.get(arch)
        cs = Model(cfg).cache_specs(4, 64)
        ax = shd.cache_axes_like(cs, cfg)
        la = jax.tree_util.tree_leaves(ax, is_leaf=lambda x:
                                       isinstance(x, tuple))
        ls = jax.tree_util.tree_leaves(cs)
        assert len(la) == len(ls)
        for a, s in zip(la, ls):
            assert len(a) == len(s.shape), (arch, a, s.shape)
