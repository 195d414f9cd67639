"""Cost-model-driven dispatch tests: the analytical opcost model, the
persisted autotune cache (round-trip, schema invalidation, model
fallback), the ``backend='auto'`` resolver, the regenerated op-table
docs, and the acceptance criteria (auto trajectory parity, >=80%
model-vs-measurement agreement on the committed cache)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import opcost, roofline
from repro.core import autotune
from repro.core import dispatch as dp
from repro.core import policies
from repro.core.policies import AUTO, XLA_FUSED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sig(op="linear_sum", n=4096, **kw):
    return opcost.OpSig(op=op, dtype="float64", n=n, **kw)


def _entry(sig, t_jnp=1e-3, t_pallas=2e-3, tile=0):
    return autotune.Entry(sig=sig, t_jnp=t_jnp, t_pallas=t_pallas,
                          tile=tile)


# ---------------------------------------------------------------------------
# satellite: unknown-op dispatch error
# ---------------------------------------------------------------------------


def test_dispatch_unknown_op_is_a_named_error():
    with pytest.raises(ValueError) as exc:
        dp.dispatch("frobnicate", XLA_FUSED)
    msg = str(exc.value)
    assert "frobnicate" in msg
    # the error enumerates the valid table so the caller can self-serve
    for op in ("linear_sum", "block_solve_soa", "csr_spmv"):
        assert op in msg


# ---------------------------------------------------------------------------
# satellite: roofline device table
# ---------------------------------------------------------------------------


def test_device_table_and_aliases():
    assert {"tpu_v5e", "tpu_v4", "interpret"} <= set(roofline.DEVICES)
    v5e = roofline.get_device("tpu_v5e")
    assert roofline.PEAK_FLOPS == v5e.peak_flops
    assert roofline.HBM_BW == v5e.hbm_bw
    assert roofline.ICI_BW == v5e.ici_bw
    # the pseudo-device has no VMEM budget and interpreter overheads
    interp = roofline.get_device("interpret")
    assert interp.interpret and interp.vmem_bytes is None
    assert interp.interp_op > 0
    with pytest.raises(ValueError, match="unknown roofline device"):
        roofline.get_device("gtx480")


# ---------------------------------------------------------------------------
# opcost: signatures and the analytical model
# ---------------------------------------------------------------------------


def test_opcost_signature_covers_every_op():
    n, nsys, b = 256, 130, 3
    x = jnp.ones((n,))
    A = jnp.eye(b)[:, :, None] * jnp.ones((1, 1, nsys))
    r = jnp.ones((b, nsys))
    z = jnp.ones((b, nsys))
    gm = jnp.ones((nsys,))
    mk = jnp.ones((nsys,), bool)
    eh = jnp.ones((nsys,))
    qh = jnp.full((nsys,), 5, jnp.int32)
    Zh = jnp.ones((6, b, nsys))
    data = jnp.ones((17,))
    pat = (tuple(range(5)), tuple(range(5)), 5)
    Vb = jnp.ones((5, b, b, nsys))
    xb = jnp.ones((5, b, nsys))
    args = {
        "linear_sum": (2.0, x, -0.5, x), "axpy": (1.7, x, x),
        "linear_combination": ([1.0, 2.0], [x, x]),
        "scale_add_multi": ([1.0, 2.0], x, [x, x]),
        "dot": (x, x), "wrms_norm": (x, x), "wrms_ss": (x, x),
        "wrms_norm_mask": (x, x, x), "dot_prod_multi": (x, [x, x]),
        "block_solve_soa": (A, r), "block_inverse_soa": (A,),
        "blockdiag_spmv_soa": (A, r),
        "newton_residual_soa": (z, z, z, gm, True),
        "masked_update_wrms_soa": (z, z, z, mk),
        "lagrange_rescale_soa": (eh, qh, Zh, mk), "wrms_soa": (z, z),
        "csr_spmv": (data, x, None), "bsr_spmv_soa": (Vb, xb, pat),
        "bsr_block_jacobi_inverse_soa": (Vb, pat),
    }
    assert set(args) == set(dp.OP_TABLE)
    for op, a in args.items():
        sig = opcost.signature(op, a)
        assert sig.op == op
        assert sig.axis_len > 0
        cost = opcost.op_cost(sig)
        assert cost.flops > 0 and cost.jnp_bytes > 0
        pred = opcost.predict(sig, "interpret")
        assert pred.winner in ("jnp", "pallas")
        assert pred.tile % 128 == 0
    with pytest.raises(ValueError, match="frobnicate"):
        opcost.signature("frobnicate", (x,))
    with pytest.raises(ValueError, match="frobnicate"):
        opcost.op_cost(_sig(op="frobnicate"))


def test_tile_for_vmem_budget_vs_interpret():
    sig = opcost.OpSig(op="block_solve_soa", dtype="float64",
                       n=16, nsys=32768, b=16)
    # interpret: one big lane-padded step, capped at 2^16
    interp = opcost.tile_for(sig, roofline.get_device("interpret"))
    assert interp == 32768
    # compiled: VMEM-bounded — (b x width x tile x 8B) <= vmem_bytes
    v5e = roofline.get_device("tpu_v5e")
    comp = opcost.tile_for(sig, v5e)
    rows = opcost.op_cost(sig).vmem_rows
    assert rows * comp * sig.itemsize <= v5e.vmem_bytes
    assert comp < interp
    # a requested tile clamps further
    assert opcost.tile_for(sig, v5e, requested=256) <= 256


# ---------------------------------------------------------------------------
# satellite: autotune cache persistence + invalidation + fallback
# ---------------------------------------------------------------------------


def test_cache_round_trip(tmp_path):
    path = tmp_path / "interpret.json"
    cache = autotune.AutotuneCache("interpret", path=path)
    e1 = _entry(_sig(), t_jnp=1e-4, t_pallas=9e-4)              # jnp wins
    e2 = _entry(_sig(op="block_solve_soa", n=3, nsys=512, b=3),
                t_jnp=5e-3, t_pallas=1e-4, tile=512)            # pallas wins
    cache.put(e1)
    cache.put(e2)
    assert cache.save() == path
    fresh = autotune.AutotuneCache("interpret", path=path).load()
    assert not fresh.stale
    assert set(fresh.entries) == {e1.sig.key(), e2.sig.key()}
    got = fresh.get(e2.sig)
    assert got.winner == "pallas" and got.tile == 512
    assert got.sig == e2.sig
    assert fresh.get(e1.sig).winner == "jnp"


def test_cache_schema_bump_invalidates(tmp_path):
    path = tmp_path / "interpret.json"
    cache = autotune.AutotuneCache("interpret", path=path)
    cache.put(_entry(_sig()))
    cache.save()
    payload = json.loads(path.read_text())
    payload["schema"] = autotune.SCHEMA_VERSION + 1
    path.write_text(json.dumps(payload))
    stale = autotune.AutotuneCache("interpret", path=path).load()
    assert stale.entries == {} and stale.stale
    # wrong device in the payload is equally stale
    payload["schema"] = autotune.SCHEMA_VERSION
    payload["device"] = "tpu_v4"
    path.write_text(json.dumps(payload))
    wrong = autotune.AutotuneCache("interpret", path=path).load()
    assert wrong.entries == {} and wrong.stale


def test_cache_corrupt_entries_dropped_not_fatal(tmp_path):
    path = tmp_path / "interpret.json"
    cache = autotune.AutotuneCache("interpret", path=path)
    good = _entry(_sig())
    cache.put(good)
    cache.save()
    payload = json.loads(path.read_text())
    # a key that disagrees with its recorded signature, and raw garbage
    payload["entries"]["mismatched-key"] = good.to_json()
    payload["entries"]["garbage"] = {"no": "fields"}
    path.write_text(json.dumps(payload))
    loaded = autotune.AutotuneCache("interpret", path=path).load()
    assert loaded.stale
    assert set(loaded.entries) == {good.sig.key()}
    # a missing file is a clean cold cache, not stale and not an error
    cold = autotune.AutotuneCache("interpret",
                                  path=tmp_path / "nope.json").load()
    assert cold.entries == {} and not cold.stale


def test_resolver_cache_miss_falls_back_to_model(tmp_path):
    empty = autotune.AutotuneCache("interpret",
                                   path=tmp_path / "none.json").load()
    res = autotune.Resolver("interpret", cache=empty)
    dec = res.decide(_sig())
    assert dec.source == "model"
    assert dec.backend in ("jnp", "pallas")
    assert dec.cached_winner is None and dec.agree is None
    # memoized per signature; hit count tracks call sites
    again = res.decide(_sig())
    assert again is dec and dec.hits == 2


def test_resolver_cache_hit_near_and_override(tmp_path):
    cache = autotune.AutotuneCache("interpret",
                                   path=tmp_path / "c.json")
    meas = _entry(_sig(op="wrms_soa", n=3, nsys=4096),
                  t_jnp=5e-4, t_pallas=1e-4, tile=4096)
    cache.put(meas)
    res = autotune.Resolver("interpret", cache=cache)
    # exact hit: measured winner + measured tile (clamped to the axis)
    dec = res.decide(_sig(op="wrms_soa", n=3, nsys=4096))
    assert (dec.source, dec.backend) == ("cache", "pallas")
    assert dec.tile <= 4096
    # nearest: same op/dtype/structure, axis within 8x
    near = res.decide(_sig(op="wrms_soa", n=3, nsys=8192))
    assert (near.source, near.backend) == ("near", "pallas")
    # beyond 8x: back to the model
    far = res.decide(_sig(op="wrms_soa", n=3, nsys=4096 * 32))
    assert far.source == "model"
    # an override pins regardless of cache
    forced = res.decide(_sig(op="wrms_soa", n=3, nsys=4096),
                        override="jnp")
    assert (forced.source, forced.backend) == ("override", "jnp")
    # report carries the decisions and the model audit fields
    rep = res.report()
    assert rep["cache_entries"] == 1
    assert {"model_agreement", "mispredictions"} <= set(rep)
    assert any(d["source"] == "near" for d in rep["decisions"])


def test_policy_op_overrides_pin_without_resolver():
    pol = AUTO.override(dot="jnp", block_solve_soa="pallas")
    assert pol.backend_for("dot") == "jnp"
    assert pol.backend_for("block_solve_soa") == "pallas"
    assert pol.backend_for("axpy") == "auto"
    assert pol.backend == "auto" and hash(pol) is not None
    # a pinned op dispatches directly — the resolver is never consulted
    autotune.reset_resolver("interpret")
    x = jnp.arange(8.0)
    got = dp.dot(x, x, AUTO.override(dot="jnp"))
    np.testing.assert_allclose(np.asarray(got), float(jnp.dot(x, x)))
    assert "interpret" not in autotune._RESOLVERS


def test_auto_dispatch_matches_jnp_and_works_under_jit():
    nsys, b = 516, 3
    A = jax.random.normal(jax.random.PRNGKey(0), (b, b, nsys)) + \
        (b + 2.0) * jnp.eye(b)[:, :, None]
    r = jax.random.normal(jax.random.PRNGKey(1), (b, nsys))
    ref = dp.block_solve_soa(A, r, XLA_FUSED)
    got = dp.block_solve_soa(A, r, AUTO)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-10)
    jitted = jax.jit(lambda A, r: dp.block_solve_soa(A, r, AUTO))
    np.testing.assert_allclose(np.asarray(jitted(A, r)), np.asarray(ref),
                               atol=1e-10)
    x = jnp.linspace(0.0, 1.0, 4096)
    np.testing.assert_allclose(
        float(dp.wrms_norm(x, x + 1.0, AUTO)),
        float(dp.wrms_norm(x, x + 1.0, XLA_FUSED)), rtol=1e-12)


def test_gj_batch_tile_vmem_override():
    from repro.kernels import ops
    base = ops._gj_batch_tile(4096, 4096, b=16, width=17, itemsize=8,
                              interpret=False)
    assert base == 512                      # the pinned default-budget tile
    bigger = ops._gj_batch_tile(4096, 4096, b=16, width=17, itemsize=8,
                                interpret=False,
                                vmem_bytes=4 * 1024 * 1024)
    assert bigger > base
    # interpret mode ignores the budget entirely
    assert ops._gj_batch_tile(4096, 4096, b=16, width=17, itemsize=8,
                              interpret=True,
                              vmem_bytes=1024) == 4096


# ---------------------------------------------------------------------------
# satellite: regenerated op-table docs
# ---------------------------------------------------------------------------


def test_op_table_docs_are_generated_and_complete():
    rows = dp.op_table_rows()
    assert {r[0] for r in rows} == set(dp.OP_TABLE)
    # the policies docstring embeds the rst rendering verbatim
    assert dp.render_op_table("rst") in policies.__doc__
    # the README embeds the markdown rendering verbatim
    with open(os.path.join(REPO, "README.md")) as fh:
        readme = fh.read()
    assert dp.render_op_table("md") in readme
    # every OP_TABLE op appears by name in both renderings
    for op in dp.OP_TABLE:
        assert op in dp.render_op_table("rst")
        assert op in dp.render_op_table("md")


# ---------------------------------------------------------------------------
# acceptance: committed cache vs model, auto trajectory
# ---------------------------------------------------------------------------


def _committed_cache():
    cache = autotune.AutotuneCache("interpret").load()
    if not cache.entries:
        pytest.skip("no committed autotune cache "
                    "(run: python -m repro.core.autotune)")
    return cache


def test_model_agrees_with_committed_cache():
    cache = _committed_cache()
    audit = autotune.model_audit(cache)
    assert audit["model_total"] == len(cache.entries)
    assert audit["model_agreement"] >= 0.8
    # mispredictions (if any) are itemized with both ratios
    for m in audit["mispredictions"]:
        assert {"sig", "measured", "predicted"} <= set(m)


def test_context_dispatch_report_surfaces_audit():
    from repro.core.context import Context
    autotune.reset_resolver("interpret")
    ctx = Context(policy=AUTO)
    x = jnp.linspace(0.0, 1.0, 4096)
    dp.dot(x, x, ctx.policy)
    rep = ctx.dispatch_report()
    assert rep["device"] == "interpret"
    assert rep["cache_entries"] > 0
    assert any(d["op"] == "dot" for d in rep["decisions"])
    assert rep["model_agreement"] is not None
    assert "mispredictions" in rep


def test_auto_ensemble_bdf_matches_fixed_backend_trajectory():
    """IVP.integrate under backend='auto' must land on the same
    trajectory as the fixed jnp backend (same tolerance discipline as
    the jnp-vs-pallas parity test)."""
    from repro.core.context import Context
    from repro.core.ivp import IVP, integrate
    from repro.core.problems import batched_robertson

    nsys = 130
    f, jac, y0 = batched_robertson(nsys)
    prob = IVP(f=f, jac=jac, y0=y0)
    ctx_j = Context(policy=XLA_FUSED)
    ctx_a = Context(policy=AUTO)
    kw = dict(rtol=1e-5, atol=1e-10, max_steps=100_000)
    sol_j = integrate(prob, 0.0, 10.0, "ensemble_bdf", ctx=ctx_j,
                      opts=ctx_j.options(**kw))
    sol_a = integrate(prob, 0.0, 10.0, "ensemble_bdf", ctx=ctx_a,
                      opts=ctx_a.options(**kw))
    assert bool(jnp.all(sol_j.success)) and bool(jnp.all(sol_a.success))
    np.testing.assert_allclose(np.asarray(sol_a.y), np.asarray(sol_j.y),
                               rtol=100 * kw["rtol"], atol=100 * kw["atol"])
    rep = ctx_a.dispatch_report()
    assert rep["decisions"], "auto dispatch resolved no call sites"
