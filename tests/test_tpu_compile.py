"""Compile rehearsal for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached: every Pallas kernel on the serving path, at real
model widths, and the full-width qwen1.5-4b q8 decode step must lower to
``tpu_custom_call`` and fit one chip's 16 GiB.  Nothing runs, so this says
nothing about results or times — it catches what interpret mode cannot
(refused tilings, VMEM limits, programs that do not fit).

The topology is described inside a module-scoped fixture (never at import
time): only one process may load the TPU library, and describing it while
modules are imported would make parallel test workers collect different
tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import KernelPolicy
from repro.kernels.dequant_matmul import (dequant_matmul,
                                          dequant_matmul_grouped)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rd_quant import rd_quant
from repro.models.transformer import decode_step, init_cache, init_params
from repro.serve.quantized import quantize_tree_q8

HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def topology():
    """A described v5e:2x2, with the persistent compile cache off (a
    compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topology):
    """One device of the described v5e:2x2."""
    return SingleDeviceSharding(topology.devices[0])


def _compile(fn, *shapes, chip):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m", [8, 2048], ids=["decode", "prefill"])
def test_dequant_matmul_qwen_widths(chip, m):
    cfg = configs.get("qwen1.5-4b")
    k, n = cfg.d_model, cfg.d_ff
    _compile(dequant_matmul, ((m, k), jnp.bfloat16), ((k, n), jnp.int8),
             ((n,), jnp.float32), chip=chip)


def test_dequant_matmul_grouped_moe_expert_widths(chip):
    cfg = configs.get("deepseek-moe-16b")
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    _compile(dequant_matmul_grouped, ((e, 64, d), jnp.bfloat16),
             ((e, d, f), jnp.int8), ((e, f), jnp.float32), chip=chip)


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_flash_attention_prefill(chip, precision):
    """Also under ``jax.default_matmul_precision("highest")`` (reference
    checks set it): the kernel pins its own MXU precision."""
    cfg = configs.get("qwen1.5-4b")
    h, d, s = cfg.num_heads, cfg.head_dim, 2048
    with jax.default_matmul_precision(precision):
        _compile(flash_attention, ((1, s, h, d), jnp.bfloat16),
                 ((1, s, h, d), jnp.bfloat16), ((1, s, h, d), jnp.bfloat16),
                 chip=chip)


@pytest.mark.parametrize("h,g", [(20, 20), (8, 2)],
                         ids=["qwen-mha", "gqa-kv-cannot-split"])
def test_flash_attention_on_model_mesh(topology, h, g):
    """Under an activation mesh the Mosaic kernel runs on local shards:
    on model=4 each device's kernel sees a quarter of the query heads,
    also when the KV groups (here 2) cannot split four ways."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import SERVE_RULES, activation_sharding
    from repro.models.attention import attend
    mesh = Mesh(np.array(topology.devices).reshape(1, 4), ("data", "model"))
    b, s, d = 1, 512, 128
    rep = NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct((b, s, n, d), jnp.bfloat16, sharding=rep)
            for n in (h, g, g)]
    args.append(jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=rep))
    pol = KernelPolicy(platform="tpu")

    def fn(q, k, v, qpos):
        with activation_sharding(mesh, SERVE_RULES):
            return attend(q, k, v, qpos, policy=pol)
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert calls
    local = f"bf16[{b * h // 4},{s},{d}]"
    assert all(re.findall(r"bf16\[[0-9,]+\]", c)[0] == local
               for c in calls), calls


def test_rd_quant_full_size_tensor(chip):
    from repro.core.quant import nearest_level
    from repro.core.rate_model import estimate_bin_probs
    cfg = configs.get("qwen1.5-4b")
    shape = (cfg.d_model, cfg.d_ff)                # one layer's w_gate
    rng = np.random.default_rng(0)
    sample = (rng.standard_normal(1 << 16) * 0.02).astype(np.float32)
    probs = estimate_bin_probs(nearest_level(sample, 0.002))
    _compile(lambda w, f: rd_quant(w, f, probs, step=0.002, lam=1e-4),
             (shape, jnp.float32), (shape, jnp.float32), chip=chip)


def test_qwen_q8_decode_step_fits_one_chip(chip):
    cfg = configs.get("qwen1.5-4b").replace(
        kernels=KernelPolicy(platform="tpu"))
    batch, max_len = 4, 2048
    params = jax.eval_shape(lambda: quantize_tree_q8(
        init_params(cfg, jax.random.PRNGKey(0))))
    caches = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    on_chip = (lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=chip))
    args = (jax.tree.map(on_chip, params), jax.tree.map(on_chip, caches),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=chip))
    compiled = jax.jit(
        lambda p, c, tok, pos: decode_step(p, cfg, c, pos, tokens=tok)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB > 16 GiB"
