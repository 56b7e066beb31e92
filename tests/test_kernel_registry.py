"""Kernel registry: platform dispatch, policy overrides, tuning-cache
consultation, constraint fallbacks, and the promoted embed_lookup_q8 op."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.kernels import KernelPolicy, tune
from repro.kernels.dequant_matmul.ops import _pad_to, default_tiles


def _dm_inputs(m=4, k=256, n=256, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    wq = jnp.asarray(rng.integers(-127, 127, (k, n)), jnp.int8)
    sc = jnp.asarray(rng.random(n) * 0.01 + 1e-4, jnp.float32)
    return x, wq, sc


def test_all_ops_registered():
    assert set(kernels.available_ops()) >= {
        "rd_quant", "dequant_matmul", "flash_attention", "embed_lookup_q8"}


def test_platform_dispatch_defaults():
    op = kernels.get("dequant_matmul")
    x, wq, sc = _dm_inputs()
    assert op.plan(x, wq, sc, policy=KernelPolicy(platform="tpu")).impl \
        == "pallas"
    assert op.plan(x, wq, sc, policy=KernelPolicy(platform="cpu")).impl \
        == "ref"
    fa = kernels.get("flash_attention")
    q = jnp.zeros((1, 64, 2, 32)); kv = jnp.zeros((1, 64, 2, 32))
    qpos = jnp.broadcast_to(jnp.arange(64), (1, 64))
    assert fa.plan(q, kv, kv, qpos,
                   policy=KernelPolicy(platform="tpu")).impl == "pallas"
    assert fa.plan(q, kv, kv, qpos,
                   policy=KernelPolicy(platform="cpu")).impl == "scan"


def test_policy_impl_override_and_equivalence():
    op = kernels.get("dequant_matmul")
    x, wq, sc = _dm_inputs(m=5, k=200, n=130)   # non-multiple-of-block
    ref = np.asarray(op(x, wq, sc, policy=KernelPolicy().override(
        "dequant_matmul", "ref")))
    interp = np.asarray(op(x, wq, sc, policy=KernelPolicy().override(
        "dequant_matmul", "interpret")))
    np.testing.assert_allclose(interp, ref, rtol=2e-4,
                               atol=2e-4 * np.abs(ref).max())


def test_unknown_impl_raises():
    op = kernels.get("dequant_matmul")
    x, wq, sc = _dm_inputs()
    with pytest.raises(KeyError, match="unknown impl"):
        op.plan(x, wq, sc, policy=KernelPolicy().override(
            "dequant_matmul", "nope"))


def test_decode_tiles_clamp_no_pad():
    """Satellite: a 1-8 row decode matmul must not pad rows to 256."""
    t = default_tiles(4, 512, 512)
    assert t["bm"] == 8
    assert default_tiles(1, 512, 512)["bm"] == 8
    assert default_tiles(300, 512, 512)["bm"] == 256
    # no-pad fast path: m == bm -> the padded operand IS the operand
    x = jnp.ones((8, 512))
    assert _pad_to(x, (t["bm"], t["bk"])).shape == (8, 512)
    assert _pad_to(x, (t["bm"], t["bk"])) is x
    # dispatch plan reflects the clamped tile
    plan = kernels.get("dequant_matmul").plan(
        *_dm_inputs(m=8, k=512, n=512),
        policy=KernelPolicy(platform="tpu", use_tuning_cache=False))
    assert dict(plan.tiles)["bm"] == 8


def test_decode_shape_numerics_small_bm():
    op = kernels.get("dequant_matmul")
    for m in (1, 3, 8):
        x, wq, sc = _dm_inputs(m=m, seed=m)
        got = np.asarray(op(x, wq, sc, policy=KernelPolicy().override(
            "dequant_matmul", "interpret")))
        want = np.asarray(kernels.spec("dequant_matmul").oracle(x, wq, sc))
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-4 * np.abs(want).max())


def test_tuning_cache_hit_vs_default_tiles(tmp_path, monkeypatch):
    monkeypatch.setenv(tune.ENV_VAR, str(tmp_path / "tune.json"))
    tune.invalidate_cache()
    op = kernels.get("dequant_matmul")
    x, wq, sc = _dm_inputs(m=4)
    pol = KernelPolicy(platform="cpu").override("dequant_matmul", "interpret")

    cold = op.plan(x, wq, sc, policy=pol)
    assert not cold.cache_hit
    assert dict(cold.tiles) == default_tiles(4, 256, 256)

    res = tune.autotune("dequant_matmul", [(4, 256, 256)], impl="interpret",
                        repeats=1, warmup=1, force=True)
    assert (tmp_path / "tune.json").exists()
    (entry,) = res.values()
    warm = op.plan(x, wq, sc, policy=pol)
    assert warm.cache_hit
    assert dict(warm.tiles) == entry["tiles"]
    # same pow2 bucket (m=4 -> bucket m4? no: pow2_bucket(3)=4) serves m=3
    assert op.plan(*_dm_inputs(m=3), policy=pol).cache_hit
    # ...and can be ignored by policy
    off = KernelPolicy(platform="cpu", use_tuning_cache=False).override(
        "dequant_matmul", "interpret")
    assert not op.plan(x, wq, sc, policy=off).cache_hit
    # tile pins beat the cache
    pinned = pol.with_tiles("dequant_matmul", bm=16)
    assert dict(op.plan(x, wq, sc, policy=pinned).tiles)["bm"] == 16


def test_flash_non_multiple_shape_falls_back():
    """sq=100 has no power-of-two tile >= 8: pallas constraint fails and
    dispatch downgrades to scan, visibly."""
    fa = kernels.get("flash_attention")
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 100, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 100, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 100, 2, 32)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(100), (1, 100))
    plan = fa.plan(q, k, v, qpos, policy=KernelPolicy(platform="tpu"))
    assert plan.impl == "scan"
    assert "power-of-two" in plan.fallback_reason
    # the fallback still computes correctly (scan == naive oracle)
    got = np.asarray(fa(q, k, v, qpos))
    want = np.asarray(fa(q, k, v, qpos, policy=KernelPolicy().override(
        "flash_attention", "ref")))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_embed_lookup_q8_matches_previous_behavior():
    """The promoted op must reproduce serve/quantized.py's gather exactly."""
    rng = np.random.default_rng(7)
    leaf = {"q8": jnp.asarray(rng.integers(-127, 127, (512, 64)), jnp.int8),
            "q8s": jnp.asarray(rng.random(64) * 0.02 + 1e-4, jnp.float32)}
    toks = jnp.asarray(rng.integers(0, 512, (2, 9)), jnp.int32)
    op = kernels.get("embed_lookup_q8")
    got = np.asarray(op(leaf, toks, jnp.float32))
    # the exact formula embed_lookup_q8 used in serve/quantized.py
    want = np.asarray((jnp.take(leaf["q8"], toks, axis=0).astype(jnp.float32)
                       * leaf["q8s"]).astype(jnp.float32))
    np.testing.assert_array_equal(got, want)
    # ref impl (dequant-then-gather) is bit-identical
    ref = np.asarray(op(leaf, toks, jnp.float32,
                        policy=KernelPolicy().override(
                            "embed_lookup_q8", "ref")))
    np.testing.assert_array_equal(got, ref)
    # non-q8 leaf passes through
    table = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    t2 = jnp.asarray([[0, 3]], jnp.int32)
    np.testing.assert_array_equal(np.asarray(op(table, t2, jnp.float32)),
                                  np.asarray(jnp.take(table, t2, axis=0)))


def test_legacy_config_fields_removed():
    """The PR-3 deprecation shims are gone: per-op pins go through
    KernelPolicy only, and the serve.quantized re-export is dropped."""
    from repro.configs import get_smoke_config
    import repro.serve.quantized as sq
    cfg = get_smoke_config("llama3-8b")
    with pytest.raises(TypeError):
        cfg.replace(attn_impl="naive")
    with pytest.raises(TypeError):
        cfg.replace(q8_matmul_impl="interpret")
    assert not hasattr(sq, "embed_lookup_q8")
    cfg2 = cfg.replace(kernels=KernelPolicy().override(
        "flash_attention", "ref"))
    assert cfg2.kernels.impl_for("flash_attention") == "ref"


def test_dispatch_report_records_default_fallback():
    kernels.clear_dispatch_report()
    fa = kernels.get("flash_attention")
    q = jnp.zeros((1, 8, 2, 16))
    kv = jnp.zeros((1, 8, 2, 16))
    v8 = jnp.zeros((1, 8, 2, 8))     # dv != d
    qpos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    out = fa(q, kv, v8, qpos, policy=KernelPolicy(platform="tpu"))
    assert out.shape == (1, 8, 2, 8)
    (rec,) = [r for r in kernels.dispatch_report()
              if r["op"] == "flash_attention"]
    assert rec["requested"] is None and rec["impl"] == "scan"
    assert "d != dv" in rec["reason"]
    kernels.clear_dispatch_report()
    assert kernels.dispatch_report() == []


def test_noncanonical_qpos_blocks_pallas():
    """The pallas kernel hard-codes right-aligned causal positions; a
    concrete shifted qpos must not silently reach it (review regression)."""
    fa = kernels.get("flash_attention")
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 16, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 16, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 16, 2, 32)), jnp.float32)
    shifted = jnp.maximum(jnp.arange(16) - 4, 0)[None, :]
    pol = KernelPolicy(platform="tpu")
    plan = fa.plan(q, k, v, shifted, policy=pol)
    assert plan.impl == "scan" and "qpos" in plan.fallback_reason
    # the fallback honors the shifted positions (scan == ref oracle)
    got = np.asarray(fa(q, k, v, shifted))
    want = np.asarray(fa(q, k, v, shifted, policy=KernelPolicy().override(
        "flash_attention", "ref")))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # canonical positions keep the kernel eligible
    canon = jnp.broadcast_to(jnp.arange(16), (1, 16))
    assert fa.plan(q, k, v, canon, policy=pol).impl == "pallas"
    # strict + pinned pallas refuses the shifted positions
    with pytest.raises(kernels.KernelDispatchError, match="qpos"):
        fa(q, k, v, shifted, policy=KernelPolicy(
            platform="tpu", strict=True).override(
                "flash_attention", "pallas"))


def test_decode_routes_to_scan_without_fallback_record():
    """Sq==1 is designed routing, not a constraint fallback — it must not
    pollute dispatch_report() on TPU-default policies."""
    fa = kernels.get("flash_attention")
    kernels.clear_dispatch_report()
    q = jnp.zeros((2, 1, 2, 16))
    kv = jnp.zeros((2, 8, 2, 16))
    qpos = jnp.full((2, 1), 7)
    plan = fa.plan(q, kv, kv, qpos, policy=KernelPolicy(platform="tpu"))
    assert plan.impl == "scan" and plan.fallback_reason is None
    fa(q, kv, kv, qpos, policy=KernelPolicy(platform="tpu"),
       kv_len=jnp.asarray([5, 8]))
    assert [r for r in kernels.dispatch_report()
            if r["op"] == "flash_attention"] == []


@pytest.mark.parametrize("op", ["dequant_matmul", "dequant_matmul_grouped",
                                "flash_attention", "rd_quant"])
def test_interpret_never_dispatched_on_tpu(op):
    """Interpret mode is CPU-only: a tpu plan — default, pinned pallas, or
    pinned interpret — never resolves to it, so a refused kernel cannot
    quietly run in the interpreter on the chip."""
    spec = kernels.spec(op)
    assert spec.impls["interpret"].platforms == ("cpu",)
    shape = {"dequant_matmul": (8, 256, 256),
             "dequant_matmul_grouped": (2, 8, 256, 256),
             "flash_attention": (1, 64, 64, 2, 2, 32),
             "rd_quant": (1 << 10,)}[op]
    args, kwargs = spec.example_inputs(shape)
    bound = kernels.get(op)
    tpu = KernelPolicy(platform="tpu")
    for pol in (tpu, tpu.override(op, "pallas"), tpu.override(op, "interpret")):
        assert bound.plan(*args, policy=pol, **kwargs).impl != "interpret"
    cpu = KernelPolicy(platform="cpu").override(op, "interpret")
    assert bound.plan(*args, policy=cpu, **kwargs).impl == "interpret"


def test_attend_impl_aliases_map_to_registry():
    """attend(impl=...) keeps its historical vocabulary, mapped onto
    registry impl names (the ModelConfig string fields are gone)."""
    from repro.models.attention import attend
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 8, 2, 16)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    naive = np.asarray(attend(q, k, v, qpos, impl="naive"))
    scan = np.asarray(attend(q, k, v, qpos, impl="scan"))
    np.testing.assert_allclose(naive, scan, atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="unknown attention impl"):
        attend(q, k, v, qpos, impl="bogus")
