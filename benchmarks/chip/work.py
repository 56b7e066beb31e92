"""Operations and least bytes of each kernel call and of each model step.

Everything here is computed from the configuration's sizes (the ``sizes``
dict of a configuration file, see ``spec.model_sizes``) and the shapes a
step dispatched.  One function serves each kernel, so every
implementation of that kernel is read against the same work:

* ``dequant_matmul``: x (m, k) @ dequant(w (k, n) int8, scale (n,) f32).
  Least bytes: the int8 weights, the f32 scales, bf16 activations in and
  out.
* ``flash_attention``: causal attention of ``sq`` queries over ``skv``
  keys.  Least operations: the causally live (query, key) pairs only;
  least bytes: q and the output over the query heads, k and v over the KV
  heads, in bf16.

Model work counts matmul parameters (every projection and the head; the
embedding is a gather) and attention over each row's real context.
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


# -- model ------------------------------------------------------------------

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
    return s["num_layers"] * 2 * s["num_kv_heads"] * s["head_dim"] * KV_BYTES


def resident_weight_bytes(s: dict) -> int:
    """Bytes of the q8-resident tree: int8 levels of every projection, the
    embedding and the head, their f32 per-channel scales, and the bf16
    norms and biases."""
    L, d, v = s["num_layers"], s["d_model"], s["vocab_size"]
    levels = matmul_params(s) + v * d
    scales = 4 * (L * sum(n for _, _, n in projections(s)) + d + v)
    vectors = ACT_BYTES * (2 * L * d + d)
    if s.get("qkv_bias"):
        h, g, dh = s["num_heads"], s["num_kv_heads"], s["head_dim"]
        vectors += ACT_BYTES * L * (h + 2 * g) * dh
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
            * causal_pairs(length, length))


def decode_step_bytes(s: dict, ctxs: list[int]) -> float:
    """Least bytes of one decode step over live rows with contexts
    ``ctxs``: the resident weights once, each row's real KV, and one new
    KV token per row."""
    per_tok = kv_bytes_per_token(s)
    return float(resident_weight_bytes(s) + per_tok * sum(ctxs)
                 + per_tok * len(ctxs))


def dequant_matmul_calls(s: dict, rows: int, head_rows: int) -> Work:
    """Every ``dequant_matmul`` call of one model pass over ``rows``
    dispatched rows, with the head on ``head_rows`` of them."""
    w = ZERO
    for _, k, n in projections(s):
        w = w + dequant_matmul(rows, k, n)
    w = Work(w.flops * s["num_layers"], w.bytes * s["num_layers"])
    return w + dequant_matmul(head_rows, s["d_model"], s["vocab_size"])


def flash_prefill_calls(s: dict, bucket: int) -> Work:
    """The flash kernel calls of one admission prefill padded to
    ``bucket``: one per layer."""
    w = flash_attention(s["num_heads"], s["num_kv_heads"], bucket, bucket,
                        s["head_dim"])
    return Work(w.flops * s["num_layers"], w.bytes * s["num_layers"])
