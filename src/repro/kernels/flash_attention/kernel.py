"""Pallas TPU flash attention (causal, online softmax).

This is the kernel the §Perf analysis calls for on the training/prefill
memory term: the pure-JAX scan formulation materializes every
(bq, bk) probability block in HBM, while this kernel keeps the score block,
the running max/denominator and the output accumulator in VMEM.

Tiling: grid (BH, Sq/BQ, Skv/BK) with the KV index innermost; the f32
accumulator + softmax stats live in VMEM scratch that persists across the
KV loop (standard revisiting pattern).  Causally-dead KV blocks are skipped
with pl.when.  Block shapes are MXU-aligned (128 multiples).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 256
DEFAULT_BK = 512
# one MXU pass on the operands' own dtype, whatever
# jax.default_matmul_precision says: under "highest", bf16 operands would
# ask Mosaic for an fp32 contraction, which it refuses ("Bad lhs type")
_PRECISION = jax.lax.Precision.DEFAULT


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, bq, bk, causal, offs, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal skip: the first key of this block beyond the last query's reach
    live = (not causal) or (ki * bk <= qi * bq + bq - 1 + offs)

    @pl.when(live)
    def _compute():
        q = q_ref[0]                      # (bq, d)
        k = k_ref[0]                      # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=_PRECISION,
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows + offs, s, NEG_INF)
        m_prev = m_scr[...][:, :1]                         # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_prev = l_scr[...][:, :1]
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=_PRECISION, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, bq: int = DEFAULT_BQ,
                           bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jnp.ndarray:
    """q (BH, Sq, D); k, v (BH, Skv, D).  Sq % bq == Skv % bk == 0."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    grid = (bh, sq // bq, skv // bk)
    scale = 1.0 / (d ** 0.5)
    offs = skv - sq                      # causal alignment (q at the end)
    kernel = functools.partial(
        _flash_kernel, scale=scale, bq=bq, bk=bk, causal=causal, offs=offs,
        n_k=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (col 0)
            pltpu.VMEM((bq, 128), jnp.float32),   # running denominator
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
