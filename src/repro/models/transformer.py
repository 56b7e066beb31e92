"""Unified decoder-only backbone covering all assigned families.

Layers are *stacked* (leading L axis) and traversed with lax.scan so the
lowered HLO is one layer body regardless of depth — essential for 61-layer
compile times and for the per-layer remat policy.  Families:

  dense  — pre-norm GQA/MLA attention + SwiGLU (llama/qwen/mistral/musicgen/
           qwen2-vl flavours via config flags)
  moe    — attention + capacity-routed MoE (+ leading dense layers)
  ssm    — Mamba2 mixer stack (attention-free)
  hybrid — Mamba2 stack with a *shared* attention block applied every
           `shared_attn_every` layers (Zamba2-style; the shared weights are
           reused at every invocation — DeepCABAC codes them once)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import kernels as _kernels
from ..distributed.sharding import constrain
from ..serve.quantized import dequant_leaf, is_q8
from .attention import gqa_attention, mla_attention
from .config import ModelConfig
from .layers import rms_norm, swiglu_mlp
from .moe import moe_block
from .ssm import mamba2_mixer

# q8 leaves the fused dequant_matmul path consumes in place: attention
# projections (gqa + mla), dense/shared MLP projections, MoE router and
# stacked expert banks.  Anything else (ssm mixer tensors, conv kernels,
# biases that happened to quantize) falls back to a loop-body dequantize —
# explicitly, reported once per tensor via dispatch_report().
_FUSED_ELIGIBLE = frozenset({
    "wq", "wk", "wv", "wo",                       # gqa projections
    "w_dq", "w_uq", "w_dkv", "w_kr", "w_uk", "w_uv",   # mla projections
    "w_gate", "w_up", "w_down",                   # dense MLP / expert banks
    "sh_gate", "sh_up", "sh_down", "router",      # MoE shared + router
})

# (tensor name, reason) pairs already reported — loop-body dequant is a
# per-tensor decision, so report it once, not once per compile per step.
_reported_loop_dequant: set = set()


def _record_loop_dequant(name: str, reason: str) -> None:
    if name in _reported_loop_dequant:
        return
    _reported_loop_dequant.add(name)
    _kernels.record_event(
        op="dequant_matmul", platform=jax.default_backend(),
        impl="loop_dequant", reason=f"{name}: {reason}",
        kind="loop_dequant")


def _fused_layer_params(lp, dt):
    """Per-layer param pass inside the scan body.

    Eligible q8 leaves pass through *intact* — their consumers
    (:func:`~repro.models.layers.q8_einsum`, ``_expert_einsum``) feed the
    int8 levels straight to the fused ``dequant_matmul`` kernels, so the
    stacked parameters are only ever read from HBM as int8.  Ineligible q8
    leaves are dequantized here (the old loop-body path), recorded once per
    tensor with ``kind="loop_dequant"`` so the fallback is loud instead of
    a silent per-step bf16 re-materialization."""
    def visit(path, leaf):
        if not is_q8(leaf):
            return leaf
        name = next((p.key for p in reversed(path)
                     if isinstance(p, jax.tree_util.DictKey)), "<leaf>")
        if name in _FUSED_ELIGIBLE:
            return leaf
        _record_loop_dequant(
            name, "no fused q8 consumer for this tensor (not an "
            "attention/MLP/MoE projection)")
        return dequant_leaf(leaf, dt)

    return jax.tree_util.tree_map_with_path(visit, lp, is_leaf=is_q8)


def _norm(x, p, cfg):
    if isinstance(p, dict):        # layernorm {scale, bias}
        mean = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
        var = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
        y = (x.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        return (y * p["scale"] + p["bias"]).astype(x.dtype)
    return rms_norm(x, p, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _dense(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def _norm_init(cfg, d, dtype):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return jnp.ones((d,), dtype)


def _init_attn(cfg: ModelConfig, key, dtype):
    ks = jax.random.split(key, 8)
    h, g, dh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    if cfg.attention == "mla":
        p = {
            "w_dkv": _dense(ks[0], d, cfg.kv_lora_rank, dtype),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
            "w_uk": _dense(ks[1], cfg.kv_lora_rank,
                           h * cfg.qk_nope_head_dim, dtype),
            "w_uv": _dense(ks[2], cfg.kv_lora_rank,
                           h * cfg.v_head_dim, dtype),
            "w_kr": _dense(ks[3], d, cfg.qk_rope_head_dim, dtype),
            "wo": _dense(ks[4], h * cfg.v_head_dim, d, dtype),
        }
        if cfg.q_lora_rank:
            p["w_dq"] = _dense(ks[5], d, cfg.q_lora_rank, dtype)
            p["q_norm"] = jnp.ones((cfg.q_lora_rank,), dtype)
            p["w_uq"] = _dense(ks[6], cfg.q_lora_rank, h * (
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), dtype)
        else:
            p["w_uq"] = _dense(ks[6], d, h * (
                cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), dtype)
        return p
    p = {
        "wq": _dense(ks[0], d, h * dh, dtype),
        "wk": _dense(ks[1], d, g * dh, dtype),
        "wv": _dense(ks[2], d, g * dh, dtype),
        "wo": _dense(ks[3], h * dh, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), dtype)
        p["bk"] = jnp.zeros((g * dh,), dtype)
        p["bv"] = jnp.zeros((g * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def _init_mlp(cfg, key, dtype, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _dense(k1, cfg.d_model, d_ff, dtype),
            "w_up": _dense(k2, cfg.d_model, d_ff, dtype),
            "w_down": _dense(k3, d_ff, cfg.d_model, dtype)}


def _init_moe(cfg, key, dtype):
    ks = jax.random.split(key, 7)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    p = {
        "router": _dense(ks[0], d, e, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                   * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                 * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                   * f ** -0.5).astype(dtype),
    }
    if cfg.num_shared_experts:
        fs = cfg.num_shared_experts * f
        p["sh_gate"] = _dense(ks[4], d, fs, dtype)
        p["sh_up"] = _dense(ks[5], d, fs, dtype)
        p["sh_down"] = _dense(ks[6], fs, d, dtype)
    return p


def _init_ssm(cfg, key, dtype):
    ks = jax.random.split(key, 9)
    d, d_in = cfg.d_model, cfg.d_inner
    g, n, h = cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    w = cfg.ssm_conv

    def conv_init(key, ch):
        return (jax.random.normal(key, (ch, w), jnp.float32)
                * w ** -0.5).astype(dtype)

    return {
        "w_z": _dense(ks[0], d, d_in, dtype),
        "w_x": _dense(ks[1], d, d_in, dtype),
        "w_b": _dense(ks[2], d, g * n, dtype),
        "w_c": _dense(ks[3], d, g * n, dtype),
        "w_dt": _dense(ks[4], d, h, dtype),
        "conv_x_w": conv_init(ks[5], d_in),
        "conv_x_b": jnp.zeros((d_in,), dtype),
        "conv_b_w": conv_init(ks[6], g * n),
        "conv_b_b": jnp.zeros((g * n,), dtype),
        "conv_c_w": conv_init(ks[7], g * n),
        "conv_c_b": jnp.zeros((g * n,), dtype),
        "a_log": jnp.zeros((h,), jnp.float32),       # A = -1
        "dt_bias": jnp.full((h,), -2.0, jnp.float32),
        "d_skip": jnp.ones((h,), dtype),
        "norm": jnp.ones((d_in,), dtype),
        "out_proj": _dense(ks[8], d_in, d, dtype),
    }


def _init_dense_layer(cfg, key, dtype, d_ff=None):
    k1, k2 = jax.random.split(key)
    return {"attn_norm": _norm_init(cfg, cfg.d_model, dtype),
            "attn": _init_attn(cfg, k1, dtype),
            "mlp_norm": _norm_init(cfg, cfg.d_model, dtype),
            "mlp": _init_mlp(cfg, k2, dtype, d_ff)}


def _init_moe_layer(cfg, key, dtype):
    k1, k2 = jax.random.split(key)
    return {"attn_norm": _norm_init(cfg, cfg.d_model, dtype),
            "attn": _init_attn(cfg, k1, dtype),
            "mlp_norm": _norm_init(cfg, cfg.d_model, dtype),
            "moe": _init_moe(cfg, k2, dtype)}


def _init_ssm_layer(cfg, key, dtype):
    return {"norm": _norm_init(cfg, cfg.d_model, dtype),
            "mixer": _init_ssm(cfg, key, dtype)}


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    params: dict = {}
    if cfg.embed_input:
        params["embed"] = (jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype)

    def stack(init_one, n, key):
        return jax.vmap(lambda k: init_one(cfg, k, dtype))(
            jax.random.split(key, n))

    if cfg.family == "dense":
        params["layers"] = stack(_init_dense_layer, cfg.num_layers, keys[1])
    elif cfg.family == "moe":
        nd = cfg.first_dense_layers
        if nd:
            params["dense_layers"] = jax.vmap(
                lambda k: _init_dense_layer(cfg, k, dtype, cfg.d_ff))(
                jax.random.split(keys[2], nd))
        params["layers"] = stack(_init_moe_layer, cfg.num_layers - nd,
                                 keys[1])
    elif cfg.family == "ssm":
        params["layers"] = stack(_init_ssm_layer, cfg.num_layers, keys[1])
    elif cfg.family == "hybrid":
        params["layers"] = stack(_init_ssm_layer, cfg.num_layers, keys[1])
        params["shared"] = _init_dense_layer(cfg, keys[3], dtype)
    else:
        raise ValueError(cfg.family)

    params["final_norm"] = _norm_init(cfg, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["head"] = _dense(keys[4], cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _attn_dispatch(x, lp, cfg, positions, pos3d, cache, cache_pos,
                   cache_pages=None, layer=None):
    if cfg.attention == "mla":
        return mla_attention(x, lp, cfg, positions, cache=cache,
                             cache_pos=cache_pos, cache_pages=cache_pages,
                             layer=layer)
    return gqa_attention(x, lp, cfg, positions, cache=cache,
                         cache_pos=cache_pos, positions_3d=pos3d,
                         cache_pages=cache_pages, layer=layer)


def _dense_block(x, lp, cfg, positions, pos3d, cache, cache_pos,
                 cache_pages=None, layer=None):
    a, new_cache = _attn_dispatch(_norm(x, lp["attn_norm"], cfg), lp["attn"],
                                  cfg, positions, pos3d, cache, cache_pos,
                                  cache_pages, layer)
    x = constrain(x + a, "batch", "seq", None)
    x = x + swiglu_mlp(_norm(x, lp["mlp_norm"], cfg), lp["mlp"], cfg.act,
                       policy=cfg.kernels)
    return constrain(x, "batch", "seq", None), new_cache, \
        jnp.zeros((), jnp.float32)


def _moe_layer_block(x, lp, cfg, positions, pos3d, cache, cache_pos,
                     cache_pages=None, layer=None):
    a, new_cache = _attn_dispatch(_norm(x, lp["attn_norm"], cfg), lp["attn"],
                                  cfg, positions, pos3d, cache, cache_pos,
                                  cache_pages, layer)
    x = constrain(x + a, "batch", "seq", None)
    m, aux = moe_block(_norm(x, lp["mlp_norm"], cfg), lp["moe"], cfg)
    return constrain(x + m, "batch", "seq", None), new_cache, aux


def _ssm_block(x, lp, cfg, positions, pos3d, cache, cache_pos,
               cache_pages=None, layer=None):
    del positions, pos3d, cache_pos, cache_pages, layer
    m, new_cache = mamba2_mixer(_norm(x, lp["norm"], cfg), lp["mixer"], cfg,
                                cache=cache)
    return constrain(x + m, "batch", "seq", None), new_cache, \
        jnp.zeros((), jnp.float32)


_BLOCKS = {"dense": _dense_block, "moe": _moe_layer_block,
           "ssm": _ssm_block, "hybrid": _ssm_block}


def _maybe_remat(fn, cfg):
    if cfg.remat == "block":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return fn


def _scan_stack(x, stacked, block, cfg, positions, pos3d, caches, cache_pos,
                cache_pages=None):
    """lax.scan over stacked layer params (and per-layer caches).

    q8-quantized serving weights are dequantized *inside* the loop body, so
    HBM reads of the stacked parameters stay int8 (1 B/param).  Under
    paged decode the stacked page *pools* (L, P, page, ...) ride whole in
    the carry, next to the layer index, and each layer writes and reads
    them at that index: a pool passed as ``xs`` would be sliced out per
    layer and re-stacked as ``ys``, copying it every step.  ``cache_pages``
    (shared across layers — one page table entry covers every layer's
    slice of a token page) rides in the closure."""
    dt = jnp.dtype(cfg.compute_dtype)
    body = _maybe_remat(
        functools.partial(block, cfg=cfg, positions=positions, pos3d=pos3d,
                          cache_pos=cache_pos, cache_pages=cache_pages), cfg)

    if caches is None:
        def f(carry, lp):
            h, aux = carry
            h2, _, a = body(h, _fused_layer_params(lp, dt), cache=None)
            return (h2, aux + a), None
        (x, aux), _ = lax.scan(f, (x, jnp.zeros((), jnp.float32)), stacked)
        return x, None, aux

    if cache_pages is not None:
        def f(carry, xs):
            h, aux, pools = carry
            lp, layer = xs
            h2, pools, a = body(h, _fused_layer_params(lp, dt), cache=pools,
                                layer=layer)
            return (h2, aux + a, pools), None
        n_layers = jax.tree.leaves(caches)[0].shape[0]
        (x, aux, pools), _ = lax.scan(
            f, (x, jnp.zeros((), jnp.float32), caches),
            (stacked, jnp.arange(n_layers, dtype=jnp.int32)))
        return x, pools, aux

    def f(carry, xs):
        h, aux = carry
        lp, cache_l = xs
        h2, newc, a = body(h, _fused_layer_params(lp, dt), cache=cache_l)
        return (h2, aux + a), newc
    (x, aux), new_caches = lax.scan(
        f, (x, jnp.zeros((), jnp.float32)), (stacked, caches))
    return x, new_caches, aux


def _hybrid_scan(x, params, cfg, positions, pos3d, caches, cache_pos):
    """Zamba2: groups of `shared_attn_every` mamba layers, then the shared
    attention block (same weights every invocation)."""
    per = cfg.shared_attn_every
    ng = cfg.num_layers // per
    dt = jnp.dtype(cfg.compute_dtype)
    stacked = jax.tree.map(
        lambda a: a.reshape(ng, per, *a.shape[1:]), params["layers"],
        is_leaf=lambda a: hasattr(a, "shape"))
    shared = _fused_layer_params(params["shared"], dt)
    ssm_body = _maybe_remat(
        functools.partial(_ssm_block, cfg=cfg, positions=positions,
                          pos3d=pos3d, cache_pos=cache_pos), cfg)
    attn_body = _maybe_remat(
        functools.partial(_dense_block, cfg=cfg, positions=positions,
                          pos3d=pos3d, cache_pos=cache_pos), cfg)

    ssm_caches = None if caches is None else caches["ssm"]
    attn_caches = None if caches is None else caches["attn"]

    def group(carry, xs):
        h = carry
        if caches is None:
            lps = xs

            def inner(hh, lp):
                h2, _, _ = ssm_body(hh, _fused_layer_params(lp, dt), cache=None)
                return h2, None
            h, _ = lax.scan(inner, h, lps)
            h, _, _ = attn_body(h, shared, cache=None)
            return h, None
        lps, ssm_c, attn_c = xs

        def inner(hh, xs_i):
            lp, c = xs_i
            h2, nc, _ = ssm_body(hh, _fused_layer_params(lp, dt), cache=c)
            return h2, nc
        h, new_ssm = lax.scan(inner, h, (lps, ssm_c))
        h, new_attn, _ = attn_body(h, shared, cache=attn_c)
        return h, (new_ssm, new_attn)

    if caches is None:
        x, _ = lax.scan(group, x, stacked)
        return x, None, jnp.zeros((), jnp.float32)
    ssm_g = jax.tree.map(lambda a: a.reshape(ng, per, *a.shape[1:]),
                         ssm_caches)
    x, (new_ssm, new_attn) = lax.scan(group, x, (stacked, ssm_g, attn_caches))
    new_caches = {"ssm": jax.tree.map(
        lambda a: a.reshape(ng * per, *a.shape[2:]), new_ssm),
        "attn": new_attn}
    return x, new_caches, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Forward / loss / prefill / decode
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            positions=None, pos3d=None, caches=None, cache_pos=None,
            cache_pages=None, last_only: bool = False, last_index=None):
    """Returns (logits, new_caches, aux).

    last_only takes position -1; last_index (B,) int32 gathers one
    per-row position instead (padded-bucket prefill) — both project the
    head on a single position, never the full sequence.

    cache_pages (B, n_max) int32 switches attention to *paged* decode:
    ``caches`` leaves are page pools (L, P, page, ...) and each row's KV
    is scattered/gathered through its page-table row (``repro.serve.kv``).
    Attention families only — an SSM/hybrid state cache has no token axis
    to page."""
    if cfg.embed_input:
        x = _kernels.get("embed_lookup_q8")(params["embed"], tokens,
                                            jnp.dtype(cfg.compute_dtype),
                                            policy=cfg.kernels)
    else:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    x = constrain(x, "batch", "seq", None)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        if cache_pos is None:
            base = 0
        else:
            cp = jnp.asarray(cache_pos)
            # (B,) per-slot offsets (ragged continuous batching) broadcast
            # down the sequence axis; scalars broadcast as before
            base = cp[:, None] if cp.ndim == 1 else cp
        positions = base + jnp.broadcast_to(jnp.arange(s), (b, s))
    if cfg.m_rope and pos3d is None:
        pos3d = jnp.broadcast_to(positions[None], (3, b, s))

    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "hybrid":
        if cache_pages is not None:
            raise ValueError(
                "paged KV decode requires an attention-family cache; the "
                f"{cfg.family!r} state cache has no token axis to page")
        x, new_caches, aux = _hybrid_scan(x, params, cfg, positions, pos3d,
                                          caches, cache_pos)
    else:
        if cache_pages is not None and cfg.family == "ssm":
            raise ValueError(
                "paged KV decode requires an attention-family cache; the "
                "'ssm' state cache has no token axis to page")
        new_caches = {}
        if cfg.family == "moe" and cfg.first_dense_layers:
            dc = None if caches is None else caches["dense"]
            x, ndc, a1 = _scan_stack(x, params["dense_layers"], _dense_block,
                                     cfg, positions, pos3d, dc, cache_pos,
                                     cache_pages)
            aux += a1
            if caches is not None:
                new_caches["dense"] = ndc
        mc = caches if caches is None else (
            caches["main"] if cfg.family == "moe" and cfg.first_dense_layers
            else caches)
        x, nmc, a2 = _scan_stack(x, params["layers"], _BLOCKS[cfg.family],
                                 cfg, positions, pos3d, mc, cache_pos,
                                 cache_pages)
        aux += a2
        if caches is not None:
            if cfg.family == "moe" and cfg.first_dense_layers:
                new_caches["main"] = nmc
            else:
                new_caches = nmc
        else:
            new_caches = None

    x = _norm(x, params["final_norm"], cfg)
    if last_index is not None:
        x = x[jnp.arange(b), last_index][:, None, :]
    elif last_only:
        x = x[:, -1:, :]
    logits = _head_logits(x, params, cfg)
    logits = constrain(logits, "batch", "seq", "vocab")
    return logits, new_caches, aux


def _head_logits(x, params, cfg: ModelConfig):
    """Final projection.  An untied q8 head (d, V) with per-vocab-channel
    scales matches the fused dequant-matmul kernel contract exactly, so the
    fixed-point serving path reads int8 weights from HBM and dequantizes
    in-core (kernels.get("dequant_matmul"); impl/tiles chosen by the
    cfg.kernels policy — decode rows get clamped bm tiles, see
    kernels/dequant_matmul ``default_tiles``)."""
    from ..serve.quantized import is_q8

    head_leaf = params["embed"] if cfg.tie_embeddings else params["head"]
    bsz, s, d = x.shape
    if not cfg.tie_embeddings and is_q8(head_leaf):
        out = _kernels.get("dequant_matmul")(
            x.reshape(bsz * s, d).astype(jnp.float32),
            head_leaf["q8"], head_leaf["q8s"], policy=cfg.kernels)
        return out.reshape(bsz, s, -1)
    if cfg.tie_embeddings and is_q8(head_leaf):
        # transposing the (V, d) embedding puts the per-vocab-row scales on
        # the *input* dim — the kernel contract wants per-output-channel
        # scales, so the tied head is fused-ineligible by design
        _record_loop_dequant(
            "embed.T (tied head)", "tied embedding head transposes "
            "per-vocab-row scales onto the contraction dim")
    head = (dequant_leaf(head_leaf, jnp.float32).T if cfg.tie_embeddings
            else dequant_leaf(head_leaf, jnp.float32))
    return jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                      head.astype(jnp.float32))


def train_loss(params, batch: dict, cfg: ModelConfig):
    """batch: tokens/embeds + labels (B,S) int32 (+ pos3d for m-rope)."""
    logits, _, aux = forward(
        params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
        pos3d=batch.get("pos3d"))
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = -jnp.mean(ll)
    if cfg.family == "moe":
        loss = loss + cfg.router_aux_weight * aux / max(cfg.num_layers, 1)
    return loss


# -- caches -------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Preallocated decode caches, stacked on the layer axis."""
    dt = jnp.int8 if cfg.q8_cache else jnp.dtype(cfg.compute_dtype)
    la = cfg.num_layers

    def attn_cache(n_layers):
        if cfg.attention == "mla":
            return {"ckv": jnp.zeros((n_layers, batch, max_len,
                                      cfg.kv_lora_rank), dt),
                    "kr": jnp.zeros((n_layers, batch, max_len,
                                     cfg.qk_rope_head_dim), dt)}
        return {"k": jnp.zeros((n_layers, batch, max_len, cfg.num_kv_heads,
                                cfg.head_dim), dt),
                "v": jnp.zeros((n_layers, batch, max_len, cfg.num_kv_heads,
                                cfg.head_dim), dt)}

    def ssm_cache(n_layers):
        w1 = cfg.ssm_conv - 1
        gn = cfg.ssm_ngroups * cfg.ssm_state
        cdt = jnp.dtype(cfg.compute_dtype)   # conv tail stays full precision
        return {"conv": {
                    "x": jnp.zeros((n_layers, batch, w1, cfg.d_inner), cdt),
                    "b": jnp.zeros((n_layers, batch, w1, gn), cdt),
                    "c": jnp.zeros((n_layers, batch, w1, gn), cdt)},
                "state": jnp.zeros((n_layers, batch, cfg.ssm_nheads,
                                    cfg.ssm_headdim, cfg.ssm_state),
                                   jnp.float32)}

    if cfg.family == "dense":
        return attn_cache(la)
    if cfg.family == "moe":
        nd = cfg.first_dense_layers
        if nd:
            return {"dense": attn_cache(nd), "main": attn_cache(la - nd)}
        return attn_cache(la)
    if cfg.family == "ssm":
        return ssm_cache(la)
    if cfg.family == "hybrid":
        ng = cfg.num_layers // cfg.shared_attn_every
        return {"ssm": ssm_cache(la), "attn": attn_cache(ng)}
    raise ValueError(cfg.family)


def prefill(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            pos3d=None, max_len: int | None = None):
    """Process the prompt, return (last-position logits (B,V), caches)."""
    b = (tokens if tokens is not None else embeds).shape[0]
    s = (tokens if tokens is not None else embeds).shape[1]
    caches = init_cache(cfg, b, max_len or s)
    logits, new_caches, _ = forward(params, cfg, tokens=tokens, embeds=embeds,
                                    pos3d=pos3d, caches=caches,
                                    last_only=True)
    return logits[:, 0, :], new_caches


def decode_step(params, cfg: ModelConfig, caches, pos, *, tokens=None,
                embeds=None, pos3d=None, cache_pages=None):
    """One token step.  tokens (B,) or embeds (B,1,d).

    pos: scalar int32 (all rows at one offset) or (B,) int32 per-row
    offsets — the ragged continuous-batching path, where each KV-cache
    row is scattered at its own position and masked to its own length.
    cache_pages (B, n_max) int32 selects paged decode over page-pool
    caches (see :func:`forward`).  Returns (logits (B,V), new_caches)."""
    if tokens is not None:
        tokens = tokens[:, None]
    logits, new_caches, _ = forward(params, cfg, tokens=tokens, embeds=embeds,
                                    pos3d=pos3d, caches=caches,
                                    cache_pos=pos, cache_pages=cache_pages,
                                    last_only=True)
    return logits[:, 0, :], new_caches
