"""jit'd public wrapper + registry spec for the RD-quantization kernel.

Handles flattening/padding to the (M, 1024) tile layout, coefficient packing
from the numpy rate model, and the prev_sig fixed-point iteration (the same
two-pass scheme as core.quant.rd_assign).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core.rate_model import BinProbs
from ..registry import Impl, OpSpec, register_op
from ..tune import pow2_bucket
from .coeffs import pack_coeffs
from .kernel import BLOCK_M, LANES, rd_quant_pallas
from .ref import rd_quant_ref

pack_rate_params = pack_coeffs


def default_block_m(n: int) -> int:
    """Row-block clamped to the sublane-padded row count: small tensors
    (< BLOCK_M * LANES elements) stop padding up to the full row tile."""
    rows = -(-max(int(n), 1) // LANES)
    return min(BLOCK_M, -(-rows // 8) * 8)


def _pad2d(x: jnp.ndarray, fill: float, block_m: int
           ) -> tuple[jnp.ndarray, int]:
    n = x.size
    per_block = block_m * LANES
    m = max((n + per_block - 1) // per_block, 1) * block_m
    padded = jnp.full((m * LANES,), fill, dtype=jnp.float32)
    padded = padded.at[:n].set(x.reshape(-1).astype(jnp.float32))
    return padded.reshape(m, LANES), n


@functools.partial(jax.jit, static_argnames=(
    "step", "lam", "window", "max_level", "num_gr", "passes", "block_m",
    "interpret", "use_ref"))
def _rd_quant_jit(w, fisher, scalars, mag_rate, *, step, lam, window,
                  max_level, num_gr, passes, block_m, interpret, use_ref):
    w2d, n = _pad2d(w, 0.0, block_m)
    f2d, _ = _pad2d(fisher, 1.0, block_m)
    flat_w = w2d.reshape(-1)

    nn = jnp.clip(jnp.round(flat_w / step), -max_level, max_level)
    levels = nn
    for _ in range(max(passes, 1)):
        sig = (levels != 0).astype(jnp.float32)
        ps = jnp.concatenate([jnp.zeros((1,), jnp.float32), sig[:-1]])
        ps2d = ps.reshape(w2d.shape)
        if use_ref:
            out = rd_quant_ref(w2d, f2d, ps2d, scalars, mag_rate, step=step,
                               lam=lam, window=window, max_level=max_level,
                               num_gr=num_gr)
        else:
            out = rd_quant_pallas(w2d, f2d, ps2d, scalars, mag_rate,
                                  step=step, lam=lam, window=window,
                                  max_level=max_level, num_gr=num_gr,
                                  block_m=block_m, interpret=interpret)
        levels = out.reshape(-1).astype(jnp.float32)
    return levels[:n].astype(jnp.int32)


def rd_quant(w, fisher, probs: BinProbs, *, step: float, lam: float,
             window: int = 4, max_level: int = 1 << 20, passes: int = 2,
             block_m: int | None = None, interpret: bool = False,
             use_ref: bool = False) -> jnp.ndarray:
    """RD-quantize a tensor of any shape; returns int32 levels, same shape.

    ``use_ref=True`` routes through the pure-jnp oracle (used on CPU and in
    differential tests); otherwise the Pallas kernel runs (``interpret=True``
    executes the kernel body in Python for validation off-TPU).
    ``block_m`` is the row-block tile (default shape-adaptive).
    """
    scalars, mag_rate = pack_coeffs(probs)
    shape = np.shape(w)
    size = int(np.prod(shape)) if shape else 1
    out = _rd_quant_jit(
        jnp.asarray(w).reshape(-1), jnp.asarray(
            fisher if fisher is not None else np.ones(shape)).reshape(-1),
        jnp.asarray(scalars), jnp.asarray(mag_rate), step=float(step),
        lam=float(lam), window=int(window), max_level=int(max_level),
        num_gr=int(probs.num_gr), passes=int(passes),
        block_m=int(block_m or default_block_m(size)), interpret=interpret,
        use_ref=use_ref)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# Registry spec.  Op signature: (w, fisher, probs, *, step, lam, ...)
# ---------------------------------------------------------------------------

def _shape_info(w, fisher=None, probs=None, **kwargs) -> dict:
    return {"n": int(np.prod(np.shape(w)) or 1)}


def _bucket(s: dict) -> str:
    return f"n{pow2_bucket(s['n'])}"


def _example_inputs(shape):
    from ...core.quant import nearest_level
    from ...core.rate_model import estimate_bin_probs
    n = int(shape[0]) if isinstance(shape, (tuple, list)) else int(shape)
    rng = np.random.default_rng(n)
    w = (rng.standard_normal(n) * 0.05).astype(np.float32)
    w[rng.random(n) < 0.5] = 0
    step = 0.008
    probs = estimate_bin_probs(nearest_level(w, step))
    return (w, None, probs), {"step": step, "lam": 2e-4}


def _run_pallas(w, fisher, probs, *, block_m=None, **kw):
    return rd_quant(w, fisher, probs, block_m=block_m, **kw)


def _run_interpret(w, fisher, probs, *, block_m=None, **kw):
    return rd_quant(w, fisher, probs, block_m=block_m, interpret=True, **kw)


def _run_ref(w, fisher, probs, **kw):
    return rd_quant(w, fisher, probs, use_ref=True, **kw)


@register_op
def _rd_quant_spec() -> OpSpec:
    return OpSpec(
        name="rd_quant",
        impls={
            "pallas": Impl("pallas", _run_pallas, platforms=("tpu",)),
            "interpret": Impl("interpret", _run_interpret,
                              platforms=("cpu",)),
            "ref": Impl("ref", _run_ref, uses_tiles=False),
        },
        defaults={"tpu": "pallas", "*": "ref"},
        fallbacks=("ref",),
        tile_space={"block_m": (8, 64, 128)},
        default_tiles=lambda s: {"block_m": default_block_m(s["n"])},
        shape_info=_shape_info,
        bucket=_bucket,
        example_inputs=_example_inputs,
        oracle=rd_quant_ref,
        tune_impls={"tpu": "pallas", "*": "interpret"},
    )
