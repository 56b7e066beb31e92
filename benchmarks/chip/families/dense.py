"""The dense decoder family: Qwen2 / Mistral / Llama blocks.

Pre-norm RMSNorm, rotate-half RoPE, grouped-query attention with an
optional q/k/v bias, a SwiGLU MLP and an untied head, as the published
architectures write them.  A configuration file whose
``architecture.family`` is ``dense`` is served, checked and counted by
this module (``spec.family`` loads it by its path); what every family
shares lives in ``weights.py``, ``reference.py`` and ``work.py``.

Sizes: the published keys below, ``head_dim`` where the config gives one
(else ``hidden_size / num_attention_heads``), ``qkv_bias`` from the
configuration's ``architecture``, and the configuration's ``reduced``
values last.  Nothing is read from ``assumed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

import work
from reference import (_attention, _embed, _final, _head, _mm, _rms, _rope,
                       _round)
from weights import _fill, _levels, _scales, _vector

# published (Hugging Face) key -> size key the harness uses
_PUBLISHED = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}
# sizes that must equal the program's registry entry
WIDTHS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
          "d_ff", "vocab_size")


# -- sizes --------------------------------------------------------------------

def sizes(config: dict) -> dict:
    """The sizes the harness, the work counts and the reference use, from
    the configuration file's published config."""
    pub = config["published"]
    s = {ours: pub[theirs] for theirs, ours in _PUBLISHED.items()}
    s["head_dim"] = pub.get("head_dim") or s["d_model"] // s["num_heads"]
    s["qkv_bias"] = bool(config["architecture"]["qkv_bias"])
    s.update(config.get("reduced", {}))
    return s


def check_registry(cfg, s: dict) -> dict:
    """{key: (registry, file)} of every width or flag in which the served
    registry entry ``cfg`` differs from the sizes ``s``."""
    want = {k: s[k] for k in WIDTHS}
    want.update(qkv_bias=s["qkv_bias"], family="dense")
    return {k: (getattr(cfg, k), v) for k, v in want.items()
            if getattr(cfg, k) != v}


def smoke_sizes(cfg) -> dict:
    """The widths of the registry's smoke preset ``cfg``, for a
    rehearsal."""
    return {k: getattr(cfg, k) for k in WIDTHS}


# -- weights ------------------------------------------------------------------

def layer_shapes(s: dict) -> dict:
    """(k, n) of each stacked projection; mirrors ``projections``."""
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


def make_tree(s: dict, key) -> dict:
    """The serving tree for sizes ``s`` from ``key`` (trace under jit), in
    the program's dense layout."""
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


# -- plain f32 reference, and its fp8 control ---------------------------------

@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _ref_layer(x, layers, index, s, quant):
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


def hidden_rows(tree, s: dict, tokens, rows, quant=None):
    """Final-normed hidden states of ``rows`` (flat indices into the
    (B, T) batch) after a full causal forward over ``tokens``."""
    key = tuple(sorted(s.items()))
    with jax.default_matmul_precision("highest"):
        x = _embed(tree["embed"], tokens)
        for i in range(s["num_layers"]):
            x = _ref_layer(x, tree["layers"], jnp.int32(i), key, quant)
        return _final(x, rows, tree["final_norm"], s["norm_eps"])


def head(tree, hidden, targets, quant=None):
    """Best logit, its id, and the logits at ``targets`` (R, K)."""
    with jax.default_matmul_precision("highest"):
        return _head(hidden, tree["head"], targets, quant)


# -- work ---------------------------------------------------------------------

def projections(s: dict) -> list[tuple[str, int, int]]:
    """(name, k, n) of every per-layer projection of a dense block."""
    d, h, g, dh, f = (s["d_model"], s["num_heads"], s["num_kv_heads"],
                      s["head_dim"], s["d_ff"])
    return [("wq", d, h * dh), ("wk", d, g * dh), ("wv", d, g * dh),
            ("wo", h * dh, d), ("w_gate", d, f), ("w_up", d, f),
            ("w_down", f, d)]


def matmul_params(s: dict, head: bool = True) -> int:
    per_layer = sum(k * n for _, k, n in projections(s))
    return s["num_layers"] * per_layer + (s["d_model"] * s["vocab_size"]
                                          if head else 0)


def kv_bytes_per_token(s: dict) -> int:
    return (s["num_layers"] * 2 * s["num_kv_heads"] * s["head_dim"]
            * work.KV_BYTES)


def resident_weight_bytes(s: dict) -> int:
    """Bytes of the q8-resident tree: int8 levels of every projection, the
    embedding and the head, their f32 per-channel scales, and the bf16
    norms and biases."""
    L, d, v = s["num_layers"], s["d_model"], s["vocab_size"]
    levels = matmul_params(s) + v * d
    scales = 4 * (L * sum(n for _, _, n in projections(s)) + d + v)
    vectors = work.ACT_BYTES * (2 * L * d + d)
    if s.get("qkv_bias"):
        h, g, dh = s["num_heads"], s["num_kv_heads"], s["head_dim"]
        vectors += work.ACT_BYTES * L * (h + 2 * g) * dh
    return levels + scales + vectors


def attention_flops(s: dict, ctx: int) -> float:
    """QK^T and PV of one query row over ``ctx`` keys, all layers."""
    return 4.0 * s["num_layers"] * s["num_heads"] * s["head_dim"] * ctx


def decode_row_flops(s: dict, ctx: int) -> float:
    """One decoded token whose row attends over ``ctx`` keys."""
    return 2.0 * matmul_params(s) + attention_flops(s, ctx)


def prefill_flops(s: dict, length: int) -> float:
    """A prompt of ``length`` real tokens: every projection per token, the
    head once (the last position), causal attention."""
    return (2.0 * matmul_params(s, head=False) * length
            + 2.0 * s["d_model"] * s["vocab_size"]
            + 4.0 * s["num_layers"] * s["num_heads"] * s["head_dim"]
            * work.causal_pairs(length, length))


def decode_step_bytes(s: dict, ctxs: list[int]) -> float:
    """Least bytes of one decode step over live rows with contexts
    ``ctxs``: the resident weights once, each row's real KV, and one new
    KV token per row."""
    per_tok = kv_bytes_per_token(s)
    return float(resident_weight_bytes(s) + per_tok * sum(ctxs)
                 + per_tok * len(ctxs))


def dequant_matmul_calls(s: dict, rows: int, head_rows: int) -> work.Work:
    """Every ``dequant_matmul`` call of one model pass over ``rows``
    dispatched rows, with the head on ``head_rows`` of them."""
    w = work.ZERO
    for _, k, n in projections(s):
        w = w + work.dequant_matmul(rows, k, n)
    w = work.Work(w.flops * s["num_layers"], w.bytes * s["num_layers"])
    return w + work.dequant_matmul(head_rows, s["d_model"], s["vocab_size"])


def flash_prefill_calls(s: dict, bucket: int) -> work.Work:
    """The flash kernel calls of one admission prefill padded to
    ``bucket``: one per layer."""
    w = work.flash_attention(s["num_heads"], s["num_kv_heads"], bucket,
                             bucket, s["head_dim"])
    return work.Work(w.flops * s["num_layers"], w.bytes * s["num_layers"])


def kernel_work(s: dict, kernel: str, st, bucket) -> work.Work:
    """The least work of ``kernel``'s calls in one step ``st`` (a
    ``loop.Step``): its decode rows at the dispatched batch, each admitted
    prompt at its bucket (``bucket(length)``)."""
    w = work.ZERO
    if kernel == "dequant_matmul":
        if st.rows:
            w = w + dequant_matmul_calls(s, st.rows, st.rows)
        for n in st.prefills:
            w = w + dequant_matmul_calls(s, bucket(n), 1)
    elif kernel == "flash_attention":
        for n in st.prefills:
            w = w + flash_prefill_calls(s, bucket(n))
    else:
        raise ValueError(f"the dense family runs no kernel {kernel!r}")
    return w
