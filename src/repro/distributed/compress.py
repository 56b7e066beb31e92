"""Cross-pod gradient/update compression with error feedback.

The paper names distributed training as the setting where weight-stream
compression matters (§I, §VI); this module applies its quantize-then-code
recipe to the *gradient* stream that crosses the inter-pod boundary — the
scarcest bandwidth in a multi-pod deployment.

Two layers:

1. :func:`ef_compress_update` — error-feedback int8 quantization of the
   update stream (EF-SGD style): runs inside the pjit train step, keeps a
   persistent per-parameter error accumulator, and is exact-in-expectation.
   Wire bytes for the cross-pod hop are accounted with the CABAC rate model
   (the codes are what DeepCABAC would entropy-code on the wire; see
   benchmarks/comm_compression.py).

2. :func:`cross_pod_psum_compressed` — the explicit collective mechanics:
   inside ``jax.shard_map`` each pod quantizes its local contribution to
   int8 codes + blockwise scales, all-gathers the (4x smaller than f32)
   payload over the pod axis, and dequant-sums locally.  This is the
   building block a production hierarchical reduce would use.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..compression.q8 import q8_decode, q8_encode


@dataclass(frozen=True)
class CompressionConfig:
    enabled: bool = False
    ef_decay: float = 1.0          # error-feedback memory (1.0 = full EF)


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def ef_compress_update(grads, ef, cfg: CompressionConfig):
    """Returns (compressed grads, new error-feedback state)."""
    if not cfg.enabled:
        return grads, ef

    def one(g, e):
        t = g.astype(jnp.float32) + cfg.ef_decay * e
        codes, scale = q8_encode(t)
        deq = q8_decode(codes, scale)
        return deq.astype(g.dtype), t - deq

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(ef)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))


def cross_pod_psum_compressed(x: jnp.ndarray, mesh,
                              pod_axis: str = "pod") -> jnp.ndarray:
    """Quantized hierarchical sum over the pod axis (see module docstring).

    Shape contract (explicit; validated):

    * ``x`` has a **leading pod axis** of global size ``mesh.shape[pod_axis]``
      sharded over ``pod_axis`` — slice ``x[i]`` is pod *i*'s partial sum,
      so each pod's local shard is ``(1, ...)``.
    * The result has the **same global shape**: every pod's slice holds the
      dequantized cross-pod sum (replicated content, pod-sharded layout).

    Payload on the inter-pod wire: int8 codes + f32 scales per 128-block =
    ~1.03 B/param vs 4 B/param f32.
    """
    n_pods = mesh.shape[pod_axis]
    if x.ndim < 1 or x.shape[0] != n_pods:
        raise ValueError(
            f"cross_pod_psum_compressed: leading axis of x {x.shape} must "
            f"be the pod axis (size {n_pods}); got "
            f"{x.shape[0] if x.ndim else 'scalar'}")
    in_spec = jax.sharding.PartitionSpec(pod_axis)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(in_spec,), out_specs=in_spec)
    def inner(xp):
        # xp (1, ...): this pod's contribution; drop the size-1 pod slice
        # before encoding so code/scale shapes are position-independent
        part = xp[0].astype(jnp.float32)
        codes, scale = q8_encode(part)
        codes_all = jax.lax.all_gather(codes, pod_axis)    # int8 on the wire
        scale_all = jax.lax.all_gather(scale, pod_axis)
        deq = jax.vmap(q8_decode)(codes_all, scale_all)    # (n_pods, ...)
        return jnp.sum(deq, axis=0)[None]                  # restore pod axis
    return inner(x)


def code_entropy_bits_per_param(codes: jnp.ndarray) -> float:
    """EPMD entropy of int8 codes — the wire rate a CABAC pass achieves
    (upper bound; context adaptation goes below, see core benchmarks)."""
    import numpy as np
    c = np.asarray(codes).ravel()
    _, counts = np.unique(c, return_counts=True)
    p = counts / counts.sum()
    return float(-(p * np.log2(p)).sum())
