"""Attention: GQA (+qk-norm, +bias, +M-RoPE) and MLA.

The attention math itself lives in the kernel registry
(``kernels.get("flash_attention")``): the pure-JAX online-softmax scan, the
naive reference, and the Pallas TPU kernel are registered impls, selected
per platform/shape by the model config's :class:`~repro.kernels.KernelPolicy`
(``cfg.kernels``).  Constraint-driven fallbacks (ragged ``kv_len``,
``d != dv``) are recorded in ``kernels.dispatch_report()`` and raise when a
pinned impl meets ``KernelPolicy(strict=True)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import kernels as _kernels
from ..distributed.sharding import active_mesh, constrain, spec_for
from ..serve.quantized import dequant_cache_value, quantize_cache_value
from .layers import apply_m_rope, apply_rope, q8_einsum, rms_norm


def _cache_store(x, cache_arr, delta):
    """Quantize to the cache's storage dtype (int8 fixed-point serving)."""
    if cache_arr.dtype == jnp.int8:
        return quantize_cache_value(x, delta)
    return x.astype(cache_arr.dtype)


def _cache_load(arr, dtype, delta):
    if arr.dtype == jnp.int8:
        return dequant_cache_value(arr, dtype, delta)
    return arr


def _cache_update(cache_arr, new_vals, cache_pos, delta):
    """Write this step's K/V into the preallocated cache.

    cache_pos scalar: all rows write at the same offset (one-shot batch).
    cache_pos (B,) int32: per-slot ragged positions (continuous batching) —
    each row scatters its single new entry at its own offset.
    """
    vals = _cache_store(new_vals, cache_arr, delta)
    cp = jnp.asarray(cache_pos)
    if cp.ndim == 0:
        return lax.dynamic_update_slice_in_dim(cache_arr, vals, cache_pos,
                                               axis=1)
    assert new_vals.shape[1] == 1, "ragged cache update is decode-only (S=1)"
    b = cache_arr.shape[0]
    return cache_arr.at[jnp.arange(b), cp].set(vals[:, 0])


def _paged_update_load(pools, new_vals, cache_pos, cache_pages, layer,
                       delta, dtype):
    """Paged decode: write one token into layer ``layer`` of the page pool,
    read the batch's logical views of that layer back.

    pools (L, P, page, ...) is every layer's hot-page pool, carried whole
    through the layer scan so the write lands in place; cache_pages
    (B, n_max) int32 maps each row's logical page index to a pool page id
    (one id covers every layer's slice of a token page).  Row ``i``'s new
    K/V lands at ``[layer, cache_pages[i, pos // page], pos % page]``; the
    view is one gather ``pools[layer, cache_pages]`` reshaped to the row's
    contiguous (B, n_max*page, ...) cache, so no layer's pool is ever
    sliced out whole.  Pool page 0 is the scheduler's scratch page:
    padding rows point every logical page at it, so their writes collide
    harmlessly there and never touch a live page.

    The token goes in by rewriting its whole page: on a TPU one token of
    a page can be a partial tile (the pool's default layout tiles
    (page, head_dim) when the KV-head count is not a multiple of 8), and
    writing a partial tile in place makes XLA lay the whole pool out
    anew around the layer loop.  A page is whole tiles in any layout.

    Returns (updated pools, per-row contiguous values in ``dtype``).
    """
    b = cache_pages.shape[0]
    assert new_vals.shape[1] == 1, "paged cache update is decode-only (S=1)"
    page_len = pools.shape[2]
    cp = jnp.asarray(cache_pos, jnp.int32)
    if cp.ndim == 0:
        cp = jnp.broadcast_to(cp, (b,))
    pid = jnp.take_along_axis(cache_pages, (cp // page_len)[:, None],
                              axis=1)[:, 0]
    vals = _cache_store(new_vals, pools, delta)          # (B, 1, ...)
    page = pools.at[layer, pid].get(mode="fill")         # (B, page, ...)
    hit = jnp.arange(page_len)[None, :] == (cp % page_len)[:, None]
    hit = hit.reshape(b, page_len, *(1,) * (page.ndim - 2))
    pools = pools.at[layer, pid].set(jnp.where(hit, vals, page))
    # (B, n_max, page, ...)
    view = pools.at[layer, cache_pages].get(mode="fill")
    view = view.reshape(b, view.shape[1] * page_len, *view.shape[3:])
    return pools, _cache_load(view, dtype, delta)

# attend(impl=...) values -> registry impl names (the historical attend
# vocabulary predates the kernel registry, so "naive"/"pallas_flash"
# alias the registry's "ref"/"pallas")
_ATTN_IMPLS = {"scan": "scan", "naive": "ref",
               "pallas_flash": "pallas", "pallas": "pallas",
               "interpret": "interpret", "ref": "ref"}


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           qpos: jnp.ndarray, *, impl: str | None = None,
           policy=None, kv_block: int = 1024,
           kv_len: jnp.ndarray | None = None) -> jnp.ndarray:
    """q (B,Sq,H,D); k,v (B,Skv,G,D) with G | H.  qpos (B,Sq).

    Dispatches through ``kernels.get("flash_attention")``.  ``policy``
    (normally ``cfg.kernels``) picks the impl per platform; ``impl`` is the
    legacy pin ("scan" / "naive" / "pallas_flash") mapped onto a policy
    override.  Decode (Sq == 1) resolves to the naive path inside the scan
    impl; the Pallas kernel's constraints (no ragged ``kv_len``,
    ``d == dv``) surface via ``kernels.dispatch_report()`` or raise under
    ``KernelPolicy(strict=True)``.
    """
    policy = policy or _kernels.KernelPolicy()
    if impl is not None:
        if impl not in _ATTN_IMPLS:
            raise ValueError(
                f"unknown attention impl {impl!r}; "
                f"one of {sorted(_ATTN_IMPLS)}")
        policy = policy.override("flash_attention", _ATTN_IMPLS[impl])

    flash = _kernels.get("flash_attention")

    def op(q, k, v, qpos, kv_len):
        return flash(q, k, v, qpos, kv_block=kv_block, kv_len=kv_len,
                     policy=policy)
    active = active_mesh()
    if active is None or flash.plan(
            q, k, v, qpos, kv_block=kv_block, kv_len=kv_len,
            policy=policy).impl != "pallas":
        return op(q, k, v, qpos, kv_len)
    return _attend_on_mesh(op, q, k, v, qpos, kv_len, *active)


def _attend_on_mesh(op, q, k, v, qpos, kv_len, mesh, rules):
    """The Mosaic kernel under an activation mesh.  XLA cannot partition
    a Pallas kernel, so it runs inside ``shard_map`` over batch rows and
    query heads — attention is independent per row and per head — and
    only ever sees local shards (the other impls are plain XLA, which the
    partitioner splits itself).  When the KV heads cannot split like the
    query heads, each query head gets its own copy of its KV group (the
    kernel repeats groups the same way), so heads still split."""
    qs = spec_for(q.shape, ("batch", None, "heads", None), mesh, rules)
    ks = spec_for(k.shape, ("batch", None, "kv_heads", None), mesh, rules)
    if qs[2] is not None and ks[2] != qs[2]:
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    qs = P(qs[0], None, qs[2], None)
    if kv_len is None:
        return jax.shard_map(
            lambda q, k, v, qpos: op(q, k, v, qpos, None), mesh=mesh,
            in_specs=(qs, qs, qs, P(qs[0], None)), out_specs=qs,
            check_vma=False)(q, k, v, qpos)
    return jax.shard_map(
        op, mesh=mesh, in_specs=(qs, qs, qs, P(qs[0], None), P(qs[0])),
        out_specs=qs, check_vma=False)(q, k, v, qpos, kv_len)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def gqa_attention(x, p, cfg, positions, *, cache=None, cache_pos=None,
                  positions_3d=None, cache_pages=None, layer=None):
    """x (B,S,d).  Returns (out (B,S,d), new_cache | None).

    Prefill/train: cache None (train) or dict to fill (prefill).
    Decode: S == 1, cache holds (B, Smax, G, D); cache_pos is a scalar
    (whole batch at one offset) or a (B,) int32 vector of per-row offsets
    (ragged continuous batching — see _cache_update).
    Paged decode: cache leaves are every layer's page *pools*
    (L, P, page, G, D), ``layer`` is this layer's index into them, and
    cache_pages (B, n_max) int32 maps logical page index -> pool page id
    (see _paged_update_load; the serving page table lives in
    ``repro.serve.kv``).
    """
    b, s, _ = x.shape
    h, g, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = q8_einsum(x, p["wq"], policy=cfg.kernels)
    k = q8_einsum(x, p["wk"], policy=cfg.kernels)
    v = q8_einsum(x, p["wv"], policy=cfg.kernels)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q.reshape(b, s, h, dh), "batch", "seq", "heads", None)
    k = constrain(k.reshape(b, s, g, dh), "batch", "seq", "kv_heads", None)
    v = constrain(v.reshape(b, s, g, dh), "batch", "seq", "kv_heads", None)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.m_rope:
        q = apply_m_rope(q, positions_3d, cfg.rope_theta, cfg.m_rope_sections)
        k = apply_m_rope(k, positions_3d, cfg.rope_theta, cfg.m_rope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    kv_len = None
    delta = cfg.kv_cache_delta
    if cache is not None and cache_pages is not None:      # paged decode
        ck, k = _paged_update_load(cache["k"], k, cache_pos, cache_pages,
                                   layer, delta, q.dtype)
        cv, v = _paged_update_load(cache["v"], v, cache_pos, cache_pages,
                                   layer, delta, q.dtype)
        new_cache = {"k": ck, "v": cv}
        kv_len = jnp.broadcast_to(
            jnp.asarray(cache_pos, jnp.int32) + s, (b,))
    elif cache is not None and cache_pos is not None:      # decode step
        ck = _cache_update(cache["k"], k, cache_pos, delta)
        cv = _cache_update(cache["v"], v, cache_pos, delta)
        new_cache = {"k": ck, "v": cv}
        k = _cache_load(ck, q.dtype, delta)
        v = _cache_load(cv, q.dtype, delta)
        kv_len = jnp.broadcast_to(
            jnp.asarray(cache_pos, jnp.int32) + s, (b,))
    elif cache is not None:                                 # prefill: fill
        ck = lax.dynamic_update_slice_in_dim(
            cache["k"], _cache_store(k, cache["k"], delta), 0, axis=1)
        cv = lax.dynamic_update_slice_in_dim(
            cache["v"], _cache_store(v, cache["v"], delta), 0, axis=1)
        new_cache = {"k": ck, "v": cv}

    out = attend(q, k, v, positions, policy=cfg.kernels,
                 kv_block=cfg.attn_kv_block, kv_len=kv_len)
    out = q8_einsum(out.reshape(b, s, h * dh), p["wo"], policy=cfg.kernels)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_attention(x, p, cfg, positions, *, cache=None, cache_pos=None,
                  cache_pages=None, layer=None):
    """Latent-cache attention: the KV cache stores only (c_kv, k_rope).

    ``cache_pages`` and ``layer`` select the paged-decode path exactly as
    in :func:`gqa_attention` — the pools are (L, P, page, R) latent
    pages."""
    b, s, _ = x.shape
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    if cfg.q_lora_rank:
        ql = rms_norm(q8_einsum(x, p["w_dq"], policy=cfg.kernels),
                      p["q_norm"], cfg.norm_eps)
        q = q8_einsum(ql, p["w_uq"], policy=cfg.kernels)
    else:
        q = q8_einsum(x, p["w_uq"], policy=cfg.kernels)
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = rms_norm(q8_einsum(x, p["w_dkv"], policy=cfg.kernels),
                   p["kv_norm"], cfg.norm_eps)
    kr = apply_rope(
        q8_einsum(x, p["w_kr"], policy=cfg.kernels)[:, :, None, :],
        positions, cfg.rope_theta)[:, :, 0, :]

    new_cache = None
    kv_len = None
    delta = cfg.kv_cache_delta
    if cache is not None and cache_pages is not None:      # paged decode
        ckv_all, ckv = _paged_update_load(cache["ckv"], ckv, cache_pos,
                                          cache_pages, layer, delta, x.dtype)
        kr_all, kr = _paged_update_load(cache["kr"], kr, cache_pos,
                                        cache_pages, layer, delta, x.dtype)
        new_cache = {"ckv": ckv_all, "kr": kr_all}
        kv_len = jnp.broadcast_to(
            jnp.asarray(cache_pos, jnp.int32) + s, (b,))
    elif cache is not None and cache_pos is not None:      # decode
        ckv_all = _cache_update(cache["ckv"], ckv, cache_pos, delta)
        kr_all = _cache_update(cache["kr"], kr, cache_pos, delta)
        new_cache = {"ckv": ckv_all, "kr": kr_all}
        ckv = _cache_load(ckv_all, x.dtype, delta)
        kr = _cache_load(kr_all, x.dtype, delta)
        kv_len = jnp.broadcast_to(
            jnp.asarray(cache_pos, jnp.int32) + s, (b,))
    elif cache is not None:                                 # prefill
        ckv_all = lax.dynamic_update_slice_in_dim(
            cache["ckv"], _cache_store(ckv, cache["ckv"], delta), 0, axis=1)
        kr_all = lax.dynamic_update_slice_in_dim(
            cache["kr"], _cache_store(kr, cache["kr"], delta), 0, axis=1)
        new_cache = {"ckv": ckv_all, "kr": kr_all}

    # up-project latents (recompute path; absorbed path is a perf option)
    k_nope = q8_einsum(ckv, p["w_uk"],
                       policy=cfg.kernels).reshape(b, -1, h, dn)
    vv = q8_einsum(ckv, p["w_uv"], policy=cfg.kernels).reshape(b, -1, h, dv)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                  (*kr.shape[:2], h, dr))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attend(q_full, k_full, vv, positions, policy=cfg.kernels,
                 kv_block=cfg.attn_kv_block, kv_len=kv_len)
    out = q8_einsum(out.reshape(b, s, h * dv), p["wo"], policy=cfg.kernels)
    return out, new_cache
