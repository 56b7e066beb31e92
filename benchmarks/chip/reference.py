"""Plain float32 reference of the dense decoder family, and its control.

Written from the published architecture (Qwen2 / Mistral: pre-norm
RMSNorm, rotate-half RoPE, grouped-query attention with an optional
q/k/v bias, SwiGLU MLP, untied head) in straightforward ``jax.numpy``.
It imports nothing of the program: it reads the benchmark's own q8 tree
(``weights.py``) dequantized to f32 and the sizes of the configuration
file, and runs every matmul at ``jax.default_matmul_precision("highest")``.

It runs a whole padded batch of sequences layer by layer (one jitted
layer program, indexed into the stacked weights), attention in query
blocks, and the head in vocabulary blocks, so it fits beside the served
weights on one chip.  Only the rows asked for reach the head.

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


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _layer(x, layers, index, s, quant):
    s = dict(s)
    lw = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, index, 0, False),
                      layers)
    b, t, _ = x.shape
    h, g, dh = s["num_heads"], s["num_kv_heads"], s["head_dim"]
    at = lw["attn"]
    a = _rms(x, lw["attn_norm"], s["norm_eps"])
    q, k, v = (_mm(a, at[n], quant) for n in ("wq", "wk", "wv"))
    if s.get("qkv_bias"):
        q = q + at["bq"].astype(jnp.float32)
        k = k + at["bk"].astype(jnp.float32)
        v = v + at["bv"].astype(jnp.float32)
    q = _rope(q.reshape(b, t, h, dh), s["rope_theta"])
    k = _round(_rope(k.reshape(b, t, g, dh), s["rope_theta"]), quant)
    v = _round(v.reshape(b, t, g, dh), quant)
    o = jax.vmap(lambda qq, kk, vv: _attention(qq, kk, vv, quant))(q, k, v)
    x = x + _mm(o, at["wo"], quant)
    m = _rms(x, lw["mlp_norm"], s["norm_eps"])
    mlp = lw["mlp"]
    hid = jax.nn.silu(_mm(m, mlp["w_gate"], quant)) * _mm(m, mlp["w_up"],
                                                          quant)
    return x + _mm(hid, mlp["w_down"], quant)


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


def hidden_rows(tree, s: dict, tokens, rows, quant=None):
    """Final-normed hidden states of ``rows`` (flat indices into the
    (B, T) batch) after a full causal forward over ``tokens``."""
    key = tuple(sorted(s.items()))
    with jax.default_matmul_precision("highest"):
        x = _embed(tree["embed"], tokens)
        for i in range(s["num_layers"]):
            x = _layer(x, tree["layers"], jnp.int32(i), key, quant)
        return _final(x, rows, tree["final_norm"], s["norm_eps"])


def head(tree, hidden, targets, quant=None):
    with jax.default_matmul_precision("highest"):
        return _head(hidden, tree["head"], targets, quant)


def gaps(tree, s: dict, tokens, rows, served, control: bool = False):
    """By how much the reference's logit of each served token lies below
    its best logit (``program``), and, with ``control``, the same gap for
    the token the fp8 control puts first (``control``)."""
    h = hidden_rows(tree, s, tokens, rows)
    best, _, at = head(tree, h, served[:, None])
    out = {"program": best - at[:, 0]}
    if control:
        hc = hidden_rows(tree, s, tokens, rows, quant="fp8")
        _, arg_c, _ = head(tree, hc, served[:, None], quant="fp8")
        del hc
        _, _, at_c = head(tree, h, arg_c[:, None])
        out["control"] = best - at_c[:, 0]
    return out
