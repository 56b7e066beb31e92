"""jit'd wrapper + registry spec: GQA-aware attention over (B,S,H,D).

The registered op ``flash_attention`` covers every attention impl the
model can run:

    ``pallas``     the VMEM-resident TPU kernel (kernel.py)
    ``interpret``  same kernel body, interpreter mode (CPU validation)
    ``scan``       pure-JAX online-softmax scan (compiles everywhere,
                   handles ragged ``kv_len`` and decode)
    ``ref``        naive reference (full score matrix)

The pallas kernel cannot mask ragged per-row ``kv_len`` and requires
``d == dv`` and tile-divisible sequence lengths — those constraints are
declared on the impl, so dispatch falls back to ``scan`` *visibly*
(``registry.dispatch_report()``; raising under ``KernelPolicy(strict=True)``
when pallas was pinned) instead of downgrading silently.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import Impl, OpSpec, register_op
from ..tune import pow2_bucket
from .kernel import DEFAULT_BK, DEFAULT_BQ, flash_attention_pallas
from .ref import flash_attention_ref
from .scan import naive_attend, online_softmax_scan


def pick_block(pref: int, size: int, floor: int = 8) -> int | None:
    """Largest power-of-two tile <= pref that divides ``size`` (None when
    no power of two >= ``floor`` divides it)."""
    t = 1 << max(pref, 1).bit_length() >> 1          # round pref down to pow2
    t = min(t, 1 << (max(size, 1).bit_length() - 1))
    while t >= floor:
        if size % t == 0:
            return t
        t //= 2
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_kernel(qf, kf, vf, causal, bq, bk, interpret):
    """The Pallas kernel made differentiable: ``pallas_call`` has no JVP,
    so the backward pass recomputes attention with the pure-jnp reference
    and differentiates that (a train step on TPU runs the kernel forward)."""
    return flash_attention_pallas(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                                  interpret=interpret)


def _flash_kernel_fwd(qf, kf, vf, causal, bq, bk, interpret):
    out = _flash_kernel(qf, kf, vf, causal, bq, bk, interpret)
    return out, (qf, kf, vf)


def _flash_kernel_bwd(causal, bq, bk, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(flash_attention_ref, causal=causal),
                     *res)
    return vjp(g)


_flash_kernel.defvjp(_flash_kernel_fwd, _flash_kernel_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                              "interpret", "use_ref"))
def _flash(qf, kf, vf, *, causal, bq, bk, interpret, use_ref):
    if use_ref:
        return flash_attention_ref(qf, kf, vf, causal=causal)
    return _flash_kernel(qf, kf, vf, causal, bq, bk, interpret)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = DEFAULT_BQ,
                    bk: int = DEFAULT_BK, interpret: bool = False,
                    use_ref: bool = False) -> jnp.ndarray:
    """q (B, Sq, H, D); k, v (B, Skv, G, D) with G | H -> (B, Sq, H, D).

    KV heads are expanded logically (repeat) before the kernel.  Tile
    sizes are clamped to the largest power-of-two divisor of each sequence
    length; sequence lengths with no such divisor >= 8 raise (the registry
    constraint routes those shapes to the scan impl instead)."""
    b, sq, h, d = q.shape
    skv, g = k.shape[1], k.shape[2]
    rep = h // g
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(
        b * h, skv, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(
        b * h, skv, d)
    bq_eff = pick_block(min(bq, sq), sq)
    bk_eff = pick_block(min(bk, skv), skv)
    if not use_ref and (bq_eff is None or bk_eff is None):
        raise ValueError(
            f"flash_attention: no power-of-two tile >= 8 divides "
            f"sq={sq} / skv={skv}; use the scan impl for these shapes")
    out = _flash(qf, kf, vf, causal=causal, bq=bq_eff or 8, bk=bk_eff or 8,
                 interpret=interpret, use_ref=use_ref)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Registry spec.  Op signature (the model-level contract):
#     (q (B,Sq,H,D), k (B,Skv,G,D), v (B,Skv,G,DV), qpos (B,Sq),
#      *, kv_len=None, kv_block=1024)
# ---------------------------------------------------------------------------

def _qpos_canonical(qpos, sq: int, skv: int) -> bool | None:
    """The pallas kernel hard-codes causal alignment as
    qpos == arange(sq) + (skv - sq).  Returns True/False for concrete
    position arrays, None (unknown, assumed canonical) for tracers — the
    model's jitted forward derives positions from arange, so traced
    positions are canonical by construction for prefill/train shapes."""
    if qpos is None:
        return True
    if isinstance(qpos, jax.core.Tracer):
        return None
    want = np.arange(sq) + (skv - sq)
    return bool(np.all(np.asarray(qpos) == want[None, :]))


def _shape_info(q, k, v, qpos=None, *, kv_len=None, kv_block=1024) -> dict:
    b, sq, h, d = q.shape
    skv = k.shape[1]
    return {"b": b, "sq": sq, "skv": skv, "h": h, "g": k.shape[2],
            "d": d, "dv": v.shape[-1], "ragged": kv_len is not None,
            "qpos_canonical": _qpos_canonical(qpos, sq, skv)}


def _bucket(s: dict) -> str:
    return (f"bh{pow2_bucket(s['b'] * s['h'])}_sq{pow2_bucket(s['sq'])}"
            f"_skv{pow2_bucket(s['skv'])}_d{s['d']}")


def _pallas_constraint(s: dict) -> str | None:
    if s["sq"] <= 1:
        return "decode (Sq == 1): a single-row query tile underfills the MXU"
    if s["ragged"]:
        return "ragged kv_len masking is not implemented in the kernel"
    if s["d"] != s["dv"]:
        return f"d != dv ({s['d']} != {s['dv']})"
    if s["qpos_canonical"] is False:
        return ("qpos is not the canonical right-aligned arange the "
                "kernel's causal mask hard-codes")
    if pick_block(DEFAULT_BQ, s["sq"]) is None:
        return f"sq={s['sq']} has no power-of-two tile >= 8"
    if pick_block(DEFAULT_BK, s["skv"]) is None:
        return f"skv={s['skv']} has no power-of-two tile >= 8"
    return None


def _tile_ok(s: dict, t: dict) -> bool:
    return (t["bq"] <= s["sq"] and s["sq"] % t["bq"] == 0
            and t["bk"] <= s["skv"] and s["skv"] % t["bk"] == 0)


def _default_tiles(s: dict) -> dict:
    return {"bq": pick_block(DEFAULT_BQ, s["sq"]) or DEFAULT_BQ,
            "bk": pick_block(DEFAULT_BK, s["skv"]) or DEFAULT_BK}


def _as_q5(q, k):
    b, sq, h, d = q.shape
    g = k.shape[2]
    return q.reshape(b, sq, g, h // g, d)


def _run_pallas(q, k, v, qpos, *, kv_len=None, kv_block=1024,
                bq=DEFAULT_BQ, bk=DEFAULT_BK):
    del qpos, kv_len, kv_block
    return flash_attention(q, k, v, causal=True, bq=bq, bk=bk)


def _run_interpret(q, k, v, qpos, *, kv_len=None, kv_block=1024,
                   bq=DEFAULT_BQ, bk=DEFAULT_BK):
    del qpos, kv_len, kv_block
    return flash_attention(q, k, v, causal=True, bq=bq, bk=bk,
                           interpret=True)


def _run_scan(q, k, v, qpos, *, kv_len=None, kv_block=1024):
    b, sq, h, _ = q.shape
    q5 = _as_q5(q, k)
    if sq > 1:
        out = online_softmax_scan(q5, k, v, qpos, kv_block, kv_len)
    else:                          # decode: one query row, scan degenerates
        out = naive_attend(q5, k, v, qpos, kv_len)
    return out.reshape(b, sq, h, v.shape[-1])


def _run_ref(q, k, v, qpos, *, kv_len=None, kv_block=1024):
    del kv_block
    b, sq, h, _ = q.shape
    out = naive_attend(_as_q5(q, k), k, v, qpos, kv_len)
    return out.reshape(b, sq, h, v.shape[-1])


def _example_inputs(shape):
    b, sq, skv, h, g, d = shape
    rng = np.random.default_rng(b * 13 + sq + skv + h)
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, g, d)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, g, d)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(sq) + (skv - sq), (b, sq))
    return (q, k, v, qpos), {}


@register_op
def _flash_attention_spec() -> OpSpec:
    return OpSpec(
        name="flash_attention",
        impls={
            "pallas": Impl("pallas", _run_pallas, platforms=("tpu",),
                           constraint=_pallas_constraint),
            "interpret": Impl("interpret", _run_interpret, platforms=("cpu",),
                              constraint=_pallas_constraint),
            "scan": Impl("scan", _run_scan, uses_tiles=False),
            "ref": Impl("ref", _run_ref, uses_tiles=False),
        },
        defaults={"tpu": "pallas", "*": "scan"},
        # decode is *designed* to take the kv_len-aware scan/naive path —
        # route it there instead of reporting a constraint fallback
        route=lambda s, platform: "scan" if s["sq"] <= 1 else None,
        fallbacks=("scan", "ref"),
        tile_space={"bq": (64, 128, 256, 512),
                    "bk": (128, 256, 512, 1024)},
        default_tiles=_default_tiles,
        tile_ok=_tile_ok,
        shape_info=_shape_info,
        bucket=_bucket,
        example_inputs=_example_inputs,
        oracle=flash_attention_ref,
        tune_impls={"tpu": "pallas", "*": "interpret"},
    )
