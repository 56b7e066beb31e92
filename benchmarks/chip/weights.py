"""Seeded q8-resident weights for a dense model, made on the device.

One jitted call turns a seed into the whole serving tree, in the form the
q8 backend serves: every projection, the embedding and the head as int8
levels with f32 per-output-channel scales (``{"q8", "q8s"}``), norms and
biases in bf16.  Stacked per-layer leaves and the large vocabulary
matrices are filled block by block inside the program, so only one
block's random draw is ever alive beside the finished tree.

The levels are a clipped, rounded Gaussian (a quantized random init):
``round(z * 127 / 4)``, the per-channel scale ``std * 4 / 127`` jittered
by +-25% across channels, where ``std`` is ``fan_in ** -0.5`` (0.02 for
the embedding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LEVEL_SIGMA = 127.0 / 4.0      # level units per standard deviation
VOCAB_BLOCK = 8192             # rows of embedding / columns of head per draw


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 32 bits)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.key(word, impl="rbg")


def _levels(key, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    return jnp.clip(jnp.round(z * LEVEL_SIGMA), -127, 127).astype(jnp.int8)


def _scales(key, shape, std):
    jitter = jax.random.uniform(key, shape, jnp.float32, 0.75, 1.25)
    return (std / LEVEL_SIGMA) * jitter


def _vector(key, shape, base, spread):
    z = jax.random.normal(key, shape, jnp.float32)
    return (base + spread * z).astype(jnp.bfloat16)


def layer_shapes(s: dict) -> dict:
    """(k, n) of each stacked projection; mirrors ``work.projections``."""
    d, h, g, dh, f = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                      s["head_dim"], s["d_ff"])
    return {"attn": {"wq": (d, h * dh), "wk": (d, g * dh),
                     "wv": (d, g * dh), "wo": (h * dh, d)},
            "mlp": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}}


def _layer(key, s):
    """One layer's leaves (no leading layer axis)."""
    shapes = layer_shapes(s)
    out = {"attn_norm": _vector(jax.random.fold_in(key, 0),
                                (s["d_model"],), 1.0, 0.05),
           "mlp_norm": _vector(jax.random.fold_in(key, 1),
                               (s["d_model"],), 1.0, 0.05)}
    i = 2
    for group, mats in shapes.items():
        out[group] = {}
        for name, (k, n) in mats.items():
            out[group][name] = {
                "q8": _levels(jax.random.fold_in(key, i), (k, n)),
                "q8s": _scales(jax.random.fold_in(key, i + 1), (n,),
                               k ** -0.5)}
            i += 2
    if s.get("qkv_bias"):
        for name, (_, n) in (("bq", shapes["attn"]["wq"]),
                             ("bk", shapes["attn"]["wk"]),
                             ("bv", shapes["attn"]["wv"])):
            out["attn"][name] = _vector(jax.random.fold_in(key, i), (n,),
                                        0.0, 0.1)
            i += 1
    return out


def _fill(key, shape, axis):
    """Levels of ``shape`` drawn VOCAB_BLOCK entries of ``axis`` at a
    time (the last block overlaps its neighbour when the axis is not a
    multiple of the block)."""
    size = shape[axis]
    if size <= VOCAB_BLOCK:
        return _levels(key, shape)
    blk_shape = tuple(VOCAB_BLOCK if i == axis else n
                      for i, n in enumerate(shape))

    def body(b, acc):
        blk = _levels(jax.random.fold_in(key, b), blk_shape)
        start = jnp.minimum(b * VOCAB_BLOCK, size - VOCAB_BLOCK)
        return lax.dynamic_update_slice_in_dim(acc, blk, start, axis=axis)
    return lax.fori_loop(0, -(-size // VOCAB_BLOCK), body,
                         jnp.zeros(shape, jnp.int8))


def make_tree(s: dict, key) -> dict:
    """The serving tree for sizes ``s`` from ``key`` (trace under jit)."""
    L, d, v = s["num_layers"], s["d_model"], s["vocab_size"]
    k_embed, k_head, k_layers, k_misc = jax.random.split(key, 4)
    template = jax.eval_shape(lambda: _layer(k_layers, s))
    stacked0 = jax.tree.map(
        lambda t: jnp.zeros((L,) + t.shape, t.dtype), template)

    def body(l, acc):
        one = _layer(jax.random.fold_in(k_layers, l), s)
        return jax.tree.map(
            lambda a, x: lax.dynamic_update_index_in_dim(a, x, l, 0),
            acc, one)
    layers = lax.fori_loop(0, L, body, stacked0)
    return {
        "embed": {"q8": _fill(k_embed, (v, d), 0),
                  "q8s": _scales(jax.random.fold_in(k_misc, 0), (d,),
                                 0.02)},
        "layers": layers,
        "final_norm": _vector(jax.random.fold_in(k_misc, 1), (d,), 1.0,
                              0.05),
        "head": {"q8": _fill(k_head, (d, v), 1),
                 "q8s": _scales(jax.random.fold_in(k_misc, 2), (v,),
                                d ** -0.5)},
    }


def make_weights(s: dict, seed: int) -> dict:
    """One jitted call: seed -> the q8 serving tree on the default
    device."""
    sizes = dict(s)
    return jax.jit(lambda k: make_tree(sizes, k))(seed_key(seed))


def dequantize(leaf):
    """Levels times per-output-channel scale, in f32 (for the plain
    reference; broadcasts a (L, n) scale over stacked (L, k, n) levels)."""
    q, sc = leaf["q8"].astype(jnp.float32), leaf["q8s"]
    if sc.ndim == 2 and q.ndim == 3:
        sc = sc[:, None, :]
    return q * sc
