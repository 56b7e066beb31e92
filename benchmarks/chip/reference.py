"""Plain float32 reference of the served models, and its control: what
every model family shares.

Each family (``families/<family>.py``) writes its own forward from the
published architecture in straightforward ``jax.numpy`` with the pieces
here: ``hidden_rows`` runs a whole padded batch of sequences layer by
layer (one jitted layer program, indexed into the stacked weights), and
``head`` the untied head in vocabulary blocks (``_head``), so it fits
beside the served weights on one chip; only the rows asked for reach the
head.  Nothing here imports the program: it reads the benchmark's own q8
tree (``weights.py``) dequantized to f32 and the sizes of the
configuration file, and every matmul runs at
``jax.default_matmul_precision("highest")``.

``quant="fp8"`` is the control: the same forward computed one precision
below the configuration's bf16, with every matmul input (activations
scaled per row, weight levels as they are), the cached K/V and the
attention probabilities rounded to float8 e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from weights import dequantize

Q_BLOCK = 128          # attention query rows per block
V_BLOCK = 16384        # head columns per block
FP8_MAX = 448.0        # largest finite float8 e4m3 value


def _round(x, quant):
    """Round ``x`` through float8 e4m3, scaled per last-axis row."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sc = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / sc).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sc


def _mm(x, leaf, quant):
    if quant is None:
        return x @ dequantize(leaf)
    w = leaf["q8"].astype(jnp.float32).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)
    return (_round(x, quant) @ w) * leaf["q8s"]


def _rms(x, w, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..T-1; x (B, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(q, k, v, quant):
    """Causal GQA attention; q (T, H, D), k/v (T, G, D) -> (T, H*D)."""
    t, h, d = q.shape
    g = k.shape[1]
    qb = Q_BLOCK if t % Q_BLOCK == 0 else t
    q = q.reshape(t // qb, qb, g, h // g, d)
    keys = jnp.arange(t)

    def block(args):
        i, qi = args
        s = jnp.einsum("qgrd,kgd->grqk", qi, k) / jnp.sqrt(jnp.float32(d))
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        p = _round(jax.nn.softmax(s, axis=-1), quant)
        return jnp.einsum("grqk,kgd->qgrd", p, v)
    out = lax.map(block, (jnp.arange(t // qb), q))
    return out.reshape(t, h * d)


@jax.jit
def _embed(embed, tokens):
    rows = jnp.take(embed["q8"], tokens, axis=0).astype(jnp.float32)
    return rows * embed["q8s"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, rows, norm, eps):
    flat = x.reshape(-1, x.shape[-1])
    return _rms(jnp.take(flat, rows, axis=0), norm, eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(hidden, head, targets, quant):
    """Best logit, its id, and the logits at ``targets`` (R, K)."""
    d, v = head["q8"].shape
    blk = min(V_BLOCK, v)
    n_blk = -(-v // blk)

    def body(i, carry):
        best, arg = carry
        start = jnp.minimum(i * blk, v - blk)
        cols = {"q8": lax.dynamic_slice_in_dim(head["q8"], start, blk, 1),
                "q8s": lax.dynamic_slice_in_dim(head["q8s"], start, blk, 0)}
        logits = _mm(hidden, cols, quant)
        b_max = jnp.max(logits, axis=-1)
        b_arg = jnp.argmax(logits, axis=-1) + start
        better = b_max > best
        return jnp.where(better, b_max, best), jnp.where(better, b_arg, arg)
    r = hidden.shape[0]
    best, arg = lax.fori_loop(
        0, n_blk, body, (jnp.full((r,), -jnp.inf), jnp.zeros((r,), jnp.int32)))
    flat = targets.reshape(-1)
    cols = {"q8": jnp.take(head["q8"], flat, axis=1),
            "q8s": jnp.take(head["q8s"], flat, axis=0)}
    w = dequantize(cols).reshape(d, r, -1)
    at = jnp.einsum("rd,drk->rk", hidden, w)
    return best, arg, at


def gaps(family, tree, s: dict, tokens, rows, served, control: bool = False):
    """By how much the reference's logit of each served token lies below
    its best logit (``program``), and, with ``control``, the same gap for
    the token the fp8 control puts first (``control``).  ``family`` (a
    module of ``families/``) runs the forward and the head."""
    h = family.hidden_rows(tree, s, tokens, rows)
    best, _, at = family.head(tree, h, served[:, None])
    out = {"program": best - at[:, 0]}
    if control:
        hc = family.hidden_rows(tree, s, tokens, rows, quant="fp8")
        _, arg_c, _ = family.head(tree, hc, served[:, None], quant="fp8")
        del hc
        _, _, at_c = family.head(tree, h, arg_c[:, None])
        out["control"] = best - at_c[:, 0]
    return out
