"""work.py's and the dense family's operation counts against XLA's own
cost analysis of the program's code, at smoke sizes on the CPU."""

import jax
import jax.numpy as jnp
import pytest

import spec
import weights
import work
from repro import configs
from repro.kernels.dequant_matmul.ref import dequant_matmul_ref
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.models.transformer import decode_step, init_cache, prefill

DENSE = spec.family("dense")


def flops(fn, *args):
    return jax.jit(fn).lower(*args).cost_analysis()["flops"]


def test_dequant_matmul_counts_the_dot():
    m, k, n = 8, 128, 256
    got = flops(dequant_matmul_ref, jnp.ones((m, k)),
                jnp.ones((k, n), jnp.int8), jnp.ones((n,)))
    want = work.dequant_matmul(m, k, n).flops
    # XLA also counts the per-element dequantize (convert and scale) of
    # the (k, n) weight, which is no part of the matmul's work
    assert want == 2 * m * k * n
    assert want <= got <= want + 2 * k * n


def test_flash_attention_counts_both_products():
    bh, s, d = 4, 64, 32
    q = jnp.ones((bh, s, d))
    got = flops(lambda q, k, v: flash_attention_ref(q, k, v, causal=True),
                q, q, q)
    # the reference computes every (query, key) pair and masks, so it is
    # read against the non-causal count; softmax adds under 10%
    want = work.flash_attention(bh, bh, s, s, d, causal=False).flops
    assert want <= got <= 1.1 * want


def test_causal_pairs():
    assert work.causal_pairs(4, 4) == 10
    assert work.causal_pairs(1, 7) == 7
    assert work.causal_pairs(2, 5) == 4 + 5


@pytest.mark.parametrize("name", ["qwen1.5-4b", "mistral-nemo-12b"])
def test_model_steps(name):
    # one layer: XLA's cost analysis counts a scan body once, whatever
    # the trip count
    cfg = configs.get(name, smoke=True).replace(num_layers=1)
    s = DENSE.smoke_sizes(cfg)
    s["qkv_bias"] = cfg.qkv_bias
    tree = jax.eval_shape(lambda: weights.make_weights(DENSE, s, 0))
    b, t = 4, 64
    caches = jax.eval_shape(lambda: init_cache(cfg, b, t))
    zeros = jnp.zeros((b,), jnp.int32)
    got = flops(lambda p, c, tok, pos: decode_step(p, cfg, c, pos,
                                                   tokens=tok),
                tree, caches, zeros, zeros)
    # decode attends over the whole cache (t keys); XLA adds the weights'
    # dequantize (1/(2b) of the matmuls at b rows), norms and softmax
    want = b * DENSE.decode_row_flops(s, t)
    assert want <= got <= 1.3 * want
    got = flops(lambda p, tok: prefill(p, cfg, tokens=tok), tree,
                jnp.zeros((1, t), jnp.int32))
    # the prefill's attention computes the whole t x t block and masks,
    # against the causal half counted as work
    full = DENSE.prefill_flops(s, t) + 4.0 * s["num_heads"] * s[
        "head_dim"] * (t * t - work.causal_pairs(t, t))
    assert full <= got <= 1.15 * full
