"""Operations and least bytes of each kernel call.

Everything here is computed from the shapes a step dispatched.  One
function serves each kernel, so every implementation of that kernel is
read against the same work:

* ``dequant_matmul``: x (m, k) @ dequant(w (k, n) int8, scale (n,) f32).
  Least bytes: the int8 weights, the f32 scales, bf16 activations in and
  out.
* ``flash_attention``: causal attention of ``sq`` queries over ``skv``
  keys.  Least operations: the causally live (query, key) pairs only;
  least bytes: q and the output over the query heads, k and v over the KV
  heads, in bf16.

A model's work (its matmul parameters, its KV bytes, which kernel calls
one step makes) belongs to its family, ``families/<family>.py``, which
counts it from the configuration's sizes with these functions.
"""

from __future__ import annotations

from dataclasses import dataclass

ACT_BYTES = 2          # bf16 activations, the serving compute dtype
KV_BYTES = 2           # bf16 KV cache


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_seconds(self, peaks: dict) -> float:
        """The roofline bound: the larger of operations over the bf16 peak
        and bytes over the HBM bandwidth."""
        return max(self.flops / peaks["bf16_flops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])


ZERO = Work(0.0, 0.0)


def dequant_matmul(m: int, k: int, n: int) -> Work:
    return Work(flops=2.0 * m * k * n,
                bytes=float(k * n + 4 * n + m * k * ACT_BYTES
                            + m * n * ACT_BYTES))


def causal_pairs(sq: int, skv: int) -> int:
    """(query, key) pairs a causal mask keeps when the ``sq`` queries are
    the last ``sq`` of ``skv`` positions."""
    off = skv - sq
    # query i sees keys 0 .. i + off
    return sum(min(skv, i + 1 + off) for i in range(sq))


def flash_attention(heads: int, kv_heads: int, sq: int, skv: int, d: int,
                    causal: bool = True) -> Work:
    pairs = causal_pairs(sq, skv) if causal else sq * skv
    return Work(flops=4.0 * heads * d * pairs,
                bytes=float((2 * heads * sq * d + 2 * kv_heads * skv * d)
                            * ACT_BYTES))
