"""Flash-attention Pallas kernel: shape/dtype sweep vs the jnp oracle, and
equivalence with the model's scan-flash path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.models.attention import attend


@pytest.mark.parametrize("b,sq,skv,h,g,d", [
    (2, 256, 256, 4, 2, 64),
    (1, 512, 512, 2, 2, 128),
    (2, 128, 384, 4, 4, 64),     # q shorter than kv (causal offset)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(b, sq, skv, h, g, d, dtype):
    rng = np.random.default_rng(b * 100 + sq)
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)) * 0.5, dtype)
    k = jnp.asarray(rng.standard_normal((b, skv, g, d)) * 0.5, dtype)
    v = jnp.asarray(rng.standard_normal((b, skv, g, d)), dtype)
    ref = np.asarray(flash_attention(q, k, v, use_ref=True),
                     dtype=np.float32)
    out = np.asarray(flash_attention(q, k, v, interpret=True,
                                     bq=128, bk=128), dtype=np.float32)
    tol = 3e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)


def test_flash_kernel_is_differentiable():
    """A train step on TPU runs the kernel forward: its gradient (reference
    backward through the custom VJP) matches the oracle's."""
    rng = np.random.default_rng(3)
    b, s, h, g, d = 1, 64, 2, 1, 32
    q = jnp.asarray(rng.standard_normal((b, s, h, d)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, g, d)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, g, d)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    kern = jax.grad(loss(lambda *a: flash_attention(
        *a, interpret=True, bq=32, bk=32)), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(loss(lambda *a: flash_attention(*a, use_ref=True)),
                   argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(kern, ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_flash_matches_scan_attend():
    """The kernel and the model's scan-flash path agree (same math)."""
    rng = np.random.default_rng(7)
    b, s, h, g, d = 2, 256, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, h, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, g, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, g, d)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(s), (b, s))
    scan = np.asarray(attend(q, k, v, qpos, impl="scan", kv_block=128))
    kern = np.asarray(flash_attention(q, k, v, interpret=True,
                                      bq=128, bk=128))
    np.testing.assert_allclose(kern, scan, atol=2e-5, rtol=2e-5)


def test_flash_ref_is_causal():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 8, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 8, 64)), jnp.float32)
    out1 = flash_attention_ref(q, k, v, causal=True)
    # future keys must not influence earlier outputs
    k2 = k.at[:, -1].set(99.0)
    v2 = v.at[:, -1].set(99.0)
    out2 = flash_attention_ref(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                               np.asarray(out2[:, :-1]), rtol=1e-6)


# ---------------------------------------------------------------------------
# Regression: the old `attend` silently dropped to naive when pallas_flash
# was requested with ragged kv_len or d != dv.  Now the downgrade is
# recorded in kernels.dispatch_report() and raises under strict policies.
# ---------------------------------------------------------------------------

def _ragged_inputs():
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.standard_normal((2, 16, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 16, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 16, 2, 32)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    kv_len = jnp.asarray([9, 16], jnp.int32)
    return q, k, v, qpos, kv_len


def test_pallas_flash_kv_len_fallback_is_recorded():
    from repro import kernels
    kernels.clear_dispatch_report()
    q, k, v, qpos, kv_len = _ragged_inputs()
    pol = kernels.KernelPolicy(platform="tpu").override(
        "flash_attention", "pallas")
    out = attend(q, k, v, qpos, policy=pol, kv_len=kv_len)
    # fell back to a kv_len-aware path, and said so
    want = attend(q, k, v, qpos, impl="naive", kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    recs = [r for r in kernels.dispatch_report()
            if r["op"] == "flash_attention" and r["requested"] == "pallas"]
    assert recs and "kv_len" in recs[0]["reason"]
    kernels.clear_dispatch_report()


def test_pallas_flash_kv_len_strict_raises():
    from repro import kernels
    q, k, v, qpos, kv_len = _ragged_inputs()
    pol = kernels.KernelPolicy(platform="tpu", strict=True).override(
        "flash_attention", "pallas")
    with pytest.raises(kernels.KernelDispatchError, match="kv_len"):
        attend(q, k, v, qpos, policy=pol, kv_len=kv_len)
    # d != dv mismatch raises too
    v8 = v[..., :8]
    with pytest.raises(kernels.KernelDispatchError, match="d != dv"):
        attend(q, k, v8, qpos, policy=pol)
    # interpret mode is CPU-only: on tpu a strict interpret pin refuses
    # instead of hiding the device behind the interpreter
    with pytest.raises(kernels.KernelDispatchError, match="platform 'tpu'"):
        attend(q, k, v, qpos, policy=pol.override(
            "flash_attention", "interpret"))
    # but a satisfiable strict request runs
    cpu_pol = kernels.KernelPolicy(platform="cpu", strict=True)
    out = attend(q, k, v, qpos, policy=cpu_pol.override(
        "flash_attention", "interpret"))
    assert out.shape == q.shape


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
import numpy as np
from repro import kernels
from repro.distributed.sharding import SERVE_RULES
from repro.launch.mesh import make_local_mesh
from repro.models.attention import _attend_on_mesh

mesh = make_local_mesh(data=1, model=4)
pol = kernels.KernelPolicy().override("flash_attention", "ref")
op = lambda q, k, v, qpos, kv_len: kernels.get("flash_attention")(
    q, k, v, qpos, kv_len=kv_len, policy=pol)
rng = np.random.default_rng(0)
for h, g in [(8, 8), (8, 2)]:      # heads split like KV / KV cannot split
    q = jnp.asarray(rng.standard_normal((2, 16, h, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 16, g, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 16, g, 32)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    for kv_len in (None, jnp.asarray([16, 9], jnp.int32)):
        want = op(q, k, v, qpos, kv_len)
        got = jax.jit(lambda *a: _attend_on_mesh(op, *a, mesh, SERVE_RULES)
                      )(q, k, v, qpos, kv_len)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert got.sharding.spec[2] == "model", got.sharding.spec
print("MESH_ATTEND_OK")
"""


def test_attend_on_mesh_splits_heads():
    """The shard_map wrapper that keeps a Pallas kernel on local shards:
    same result as unsharded attention, heads split over the model axis
    even when the KV groups cannot split (4 fake CPU devices)."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_ATTEND_OK" in proc.stdout
