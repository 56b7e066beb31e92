"""jit'd public wrappers + registry specs for the fused dequantize-matmul.

Two ops live here:

``dequant_matmul``          x (..., K) @ dequant(w_q (K, N), scale (N,))
``dequant_matmul_grouped``  x (E, M, K) @ dequant(w_q (E, K, N),
                            scale (E, N) | (N,)) — one matmul per expert.

Leading activation dims are flattened to the kernel's M and restored on the
way out, so attention projections (B, S, K) and MoE capacity buffers route
through the same pallas kernels as 2-D calls.  Explicit/tuned tiles are
clamped against the padded operand dims at dispatch (a pow2-bucketed cache
winner for m=64 must not ride along verbatim to an m=3 decode batch); every
clamp is recorded in ``dispatch_report()`` with ``kind="tile_clamp"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import Impl, OpSpec, record_event, register_op
from ..tune import pow2_bucket
from .kernel import (BK, BM, BN, dequant_matmul_grouped_pallas,
                     dequant_matmul_pallas)
from .ref import dequant_matmul_grouped_ref, dequant_matmul_ref


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def default_tiles(m: int, k: int, n: int) -> dict:
    """Shape-adaptive tiles.  ``bm`` clamps to the sublane-padded row count
    so a 1-8 row decode matmul pads to 8 rows, not 256; ``bn``/``bk`` clamp
    to the lane-padded layer dims for small heads."""
    return {"bm": min(BM, _round_up(max(m, 1), 8)),
            "bn": min(BN, _round_up(max(n, 1), 128)),
            "bk": min(BK, _round_up(max(k, 1), 128))}


def tile_bounds(m: int, k: int, n: int) -> dict:
    """Hard per-shape ceilings: a tile larger than the padded operand dim
    buys nothing and (for cached/explicit tiles) can exceed the padded
    operand.  Bounds are sublane/lane padded so clamped values stay
    MXU-aligned."""
    return {"bm": max(_round_up(m, 8), 8),
            "bn": _round_up(max(n, 1), 128),
            "bk": _round_up(max(k, 1), 128)}


def _resolve_tiles(requested: dict, m: int, k: int, n: int, *, op: str,
                   impl: str) -> dict:
    """Merge explicit tiles over shape defaults, then clamp to
    :func:`tile_bounds`.  A clamp never crashes the pallas call — it is
    recorded once per trace via :func:`record_event`."""
    tiles = default_tiles(m, k, n)
    tiles.update({p: v for p, v in requested.items() if v is not None})
    bounds = tile_bounds(m, k, n)
    clamped = {p: min(v, bounds[p]) for p, v in tiles.items()}
    if clamped != tiles:
        changed = ", ".join(
            f"{p}={tiles[p]}->{clamped[p]}"
            for p in ("bm", "bn", "bk") if clamped[p] != tiles[p])
        record_event(
            op=op, platform=jax.default_backend(), impl=impl,
            reason=(f"tile clamp for (m={m}, k={k}, n={n}): {changed} "
                    "(cached/explicit tile exceeded padded operand)"),
            kind="tile_clamp")
    return clamped


def _pad_to(x: jnp.ndarray, mult: tuple[int, ...]) -> jnp.ndarray:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mult)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                              "use_ref"))
def _dequant_matmul_jit(x, w_q, scale, *, bm, bn, bk, interpret, use_ref):
    if use_ref:
        return dequant_matmul_ref(x, w_q, scale)
    m, n = x.shape[0], w_q.shape[1]
    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w_q, (bk, bn))
    sp = _pad_to(scale, (bn,))
    out = dequant_matmul_pallas(xp, wp, sp, bm=bm, bn=bn, bk=bk,
                                interpret=interpret)
    return out[:m, :n]


def dequant_matmul(x: jnp.ndarray, w_q: jnp.ndarray, scale: jnp.ndarray, *,
                   bm: int | None = None, bn: int | None = None,
                   bk: int | None = None, interpret: bool = False,
                   use_ref: bool = False) -> jnp.ndarray:
    """Serving matmul against DeepCABAC-quantized weights.

    x (..., K) float, w_q (K, N) int8 levels, scale (N,) per-channel Delta
    -> (..., N) f32.  Leading dims are flattened to the kernel's M.  Tile
    sizes default to :func:`default_tiles`; explicit/tuned tiles are
    clamped to the padded operand (see :func:`_resolve_tiles`).
    """
    x, w_q, scale = jnp.asarray(x), jnp.asarray(w_q), jnp.asarray(scale)
    lead = x.shape[:-1]
    k = x.shape[-1]
    m = int(np.prod(lead)) if lead else 1
    n = w_q.shape[1]
    x2 = x.reshape(m, k)
    if use_ref:
        out = _dequant_matmul_jit(x2, w_q, scale, bm=0, bn=0, bk=0,
                                  interpret=False, use_ref=True)
    else:
        t = _resolve_tiles({"bm": bm, "bn": bn, "bk": bk}, m, k, n,
                           op="dequant_matmul",
                           impl="interpret" if interpret else "pallas")
        out = _dequant_matmul_jit(x2, w_q, scale, bm=t["bm"], bn=t["bn"],
                                  bk=t["bk"], interpret=interpret,
                                  use_ref=False)
    return out.reshape(*lead, n)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                              "use_ref"))
def _dequant_matmul_grouped_jit(x, w_q, scale, *, bm, bn, bk, interpret,
                                use_ref):
    if use_ref:
        return dequant_matmul_grouped_ref(x, w_q, scale)
    _, m, _ = x.shape
    n = w_q.shape[2]
    xp = _pad_to(x, (1, bm, bk))
    wp = _pad_to(w_q, (1, bk, bn))
    sp = _pad_to(scale, (1, bn))
    out = dequant_matmul_grouped_pallas(xp, wp, sp, bm=bm, bn=bn, bk=bk,
                                        interpret=interpret)
    return out[:, :m, :n]


def dequant_matmul_grouped(x: jnp.ndarray, w_q: jnp.ndarray,
                           scale: jnp.ndarray, *, bm: int | None = None,
                           bn: int | None = None, bk: int | None = None,
                           interpret: bool = False,
                           use_ref: bool = False) -> jnp.ndarray:
    """Grouped-expert serving matmul: one independent matmul per expert.

    x (E, M, K) float, w_q (E, K, N) int8 levels, scale (E, N) f32 or (N,)
    (the stacked-MoE wire format — one per-channel Delta shared across the
    layer's experts) -> (E, M, N) f32.
    """
    x, w_q, scale = jnp.asarray(x), jnp.asarray(w_q), jnp.asarray(scale)
    e, m, k = x.shape
    n = w_q.shape[2]
    if scale.ndim == 1:
        scale = jnp.broadcast_to(scale[None, :], (e, n))
    if use_ref:
        return _dequant_matmul_grouped_jit(x, w_q, scale, bm=0, bn=0, bk=0,
                                           interpret=False, use_ref=True)
    t = _resolve_tiles({"bm": bm, "bn": bn, "bk": bk}, m, k, n,
                       op="dequant_matmul_grouped",
                       impl="interpret" if interpret else "pallas")
    return _dequant_matmul_grouped_jit(x, w_q, scale, bm=t["bm"],
                                       bn=t["bn"], bk=t["bk"],
                                       interpret=interpret, use_ref=False)


# ---------------------------------------------------------------------------
# Registry specs
# ---------------------------------------------------------------------------

def _shape_info(x, w_q, scale) -> dict:
    x, w_q = jnp.asarray(x), jnp.asarray(w_q)
    m = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    return {"m": m, "k": x.shape[-1], "n": w_q.shape[1]}


def _bucket(s: dict) -> str:
    # rows are data-dependent (decode m = live batch) -> pow2 bucket;
    # k/n are model dims -> exact
    return f"m{pow2_bucket(s['m'])}_k{s['k']}_n{s['n']}"


def _tile_ok(s: dict, t: dict) -> bool:
    b = tile_bounds(s["m"], s["k"], s["n"])
    return all(t[p] <= b[p] for p in ("bm", "bn", "bk"))


def _example_inputs(shape):
    m, k, n = shape
    rng = np.random.default_rng(m * 31 + k * 7 + n)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    wq = jnp.asarray(rng.integers(-127, 127, (k, n)), jnp.int8)
    sc = jnp.asarray(rng.random(n) * 0.01 + 1e-4, jnp.float32)
    return (x, wq, sc), {}


def _run_pallas(x, w_q, scale, *, bm, bn, bk):
    return dequant_matmul(x, w_q, scale, bm=bm, bn=bn, bk=bk)


def _run_interpret(x, w_q, scale, *, bm, bn, bk):
    return dequant_matmul(x, w_q, scale, bm=bm, bn=bn, bk=bk,
                          interpret=True)


def _run_ref(x, w_q, scale):
    return dequant_matmul(x, w_q, scale, use_ref=True)


@register_op
def _dequant_matmul_spec() -> OpSpec:
    return OpSpec(
        name="dequant_matmul",
        impls={
            "pallas": Impl("pallas", _run_pallas, platforms=("tpu",)),
            "interpret": Impl("interpret", _run_interpret,
                              platforms=("cpu",)),
            "ref": Impl("ref", _run_ref, uses_tiles=False),
        },
        defaults={"tpu": "pallas", "*": "ref"},
        fallbacks=("interpret", "ref"),
        tile_space={"bm": (8, 16, 32, 64, 128, 256),
                    "bn": (128, 256, 512),
                    "bk": (128, 256, 512, 1024)},
        default_tiles=lambda s: default_tiles(s["m"], s["k"], s["n"]),
        tile_ok=_tile_ok,
        shape_info=_shape_info,
        bucket=_bucket,
        example_inputs=_example_inputs,
        oracle=dequant_matmul_ref,
        tune_impls={"tpu": "pallas", "*": "interpret"},
    )


def _grouped_shape_info(x, w_q, scale) -> dict:
    x, w_q = jnp.asarray(x), jnp.asarray(w_q)
    return {"e": x.shape[0], "m": x.shape[1], "k": x.shape[2],
            "n": w_q.shape[2]}


def _grouped_bucket(s: dict) -> str:
    # expert count and k/n are model dims -> exact; per-expert rows are the
    # (static) capacity buffer, but pow2-bucket anyway for robustness
    return f"e{s['e']}_m{pow2_bucket(s['m'])}_k{s['k']}_n{s['n']}"


def _grouped_example_inputs(shape):
    e, m, k, n = shape
    rng = np.random.default_rng(e * 131 + m * 31 + k * 7 + n)
    x = jnp.asarray(rng.standard_normal((e, m, k)), jnp.float32)
    wq = jnp.asarray(rng.integers(-127, 127, (e, k, n)), jnp.int8)
    sc = jnp.asarray(rng.random((e, n)) * 0.01 + 1e-4, jnp.float32)
    return (x, wq, sc), {}


def _run_grouped_pallas(x, w_q, scale, *, bm, bn, bk):
    return dequant_matmul_grouped(x, w_q, scale, bm=bm, bn=bn, bk=bk)


def _run_grouped_interpret(x, w_q, scale, *, bm, bn, bk):
    return dequant_matmul_grouped(x, w_q, scale, bm=bm, bn=bn, bk=bk,
                                  interpret=True)


def _run_grouped_ref(x, w_q, scale):
    return dequant_matmul_grouped(x, w_q, scale, use_ref=True)


@register_op
def _dequant_matmul_grouped_spec() -> OpSpec:
    return OpSpec(
        name="dequant_matmul_grouped",
        impls={
            "pallas": Impl("pallas", _run_grouped_pallas,
                           platforms=("tpu",)),
            "interpret": Impl("interpret", _run_grouped_interpret,
                              platforms=("cpu",)),
            "ref": Impl("ref", _run_grouped_ref, uses_tiles=False),
        },
        defaults={"tpu": "pallas", "*": "ref"},
        fallbacks=("interpret", "ref"),
        tile_space={"bm": (8, 16, 32, 64, 128),
                    "bn": (128, 256),
                    "bk": (128, 256, 512)},
        default_tiles=lambda s: default_tiles(s["m"], s["k"], s["n"]),
        tile_ok=_tile_ok,
        shape_info=_grouped_shape_info,
        bucket=_grouped_bucket,
        example_inputs=_grouped_example_inputs,
        oracle=dequant_matmul_grouped_ref,
        tune_impls={"tpu": "pallas", "*": "interpret"},
    )
