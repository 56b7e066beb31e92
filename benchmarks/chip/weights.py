"""Seeded q8-resident weights, made on the device.

One jitted call turns a seed into the whole serving tree, in the form the
q8 backend serves: every projection, the embedding and the head as int8
levels with f32 per-output-channel scales (``{"q8", "q8s"}``), norms and
biases in bf16.  Stacked per-layer leaves and the large vocabulary
matrices are filled block by block inside the program, so only one
block's random draw is ever alive beside the finished tree.

The tree's layout is the model family's: each family is a file
``families/<family>.py`` whose ``make_tree(sizes, key)`` lays the leaves
out as the program's parameters for that architecture, drawing them with
the helpers here.  This module holds what every family shares.

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


def make_weights(family, s: dict, seed: int) -> dict:
    """One jitted call: seed -> the q8 serving tree of ``family`` (a
    module of ``families/``) on the default device."""
    sizes = dict(s)
    return jax.jit(lambda k: family.make_tree(sizes, k))(seed_key(seed))


def dequantize(leaf):
    """Levels times per-output-channel scale, in f32 (for the plain
    reference; broadcasts a (L, n) scale over stacked (L, k, n) levels)."""
    q, sc = leaf["q8"].astype(jnp.float32), leaf["q8s"]
    if sc.ndim == 2 and q.ndim == 3:
        sc = sc[:, None, :]
    return q * sc
