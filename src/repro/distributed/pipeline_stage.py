"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Optional PP feature for the pod axis: layers split into `S = |axis|` stages
with stage parameters sharded on the axis; microbatches stream through the
classic GPipe schedule (stage s runs microbatch m at tick t = s + m, bubble
fraction (S-1)/(M+S-1)).  Activations hop stages with a single
`lax.ppermute` per tick — on hardware that is the only inter-pod traffic,
which is why PP is the axis of choice when the cross-pod links are the
scarce resource (DESIGN.md §6).

This is jax-native (no torch.distributed emulation): the schedule is an
unrolled loop inside one shard_map, so XLA overlaps the permute with the
next tick's compute.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def gpipe_apply(stage_fn: Callable, stage_params, x_mb: jnp.ndarray,
                mesh, axis: str = "pod") -> jnp.ndarray:
    """Run `S` parameter stages over `M` microbatches.

    stage_fn(params, x) -> y with x/y of identical shape (a layer block).
    stage_params: pytree with a leading stage dim of size S = mesh.shape[axis]
    (sharded on `axis`).  x_mb: (M, *batch_shape) microbatched input.
    Returns (M, *batch_shape) outputs (after all S stages, in order).
    """
    s = mesh.shape[axis]
    m = x_mb.shape[0]
    ticks = m + s - 1

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_specs, P()), out_specs=P(axis))
    def run(params_local, x_all):
        sid = lax.axis_index(axis)
        local = jax.tree.map(lambda p: p[0], params_local)
        carry = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros((1, *x_all.shape), x_all.dtype)
        perm = [(i, (i + 1) % s) for i in range(s)]
        for t in range(ticks):
            feed_idx = min(max(t, 0), m - 1)
            inp = jnp.where(sid == 0, x_all[feed_idx], carry)
            out = stage_fn(local, inp)
            # the last stage finishes microbatch (t - (S-1)) at tick t
            m_idx = t - (s - 1)
            if 0 <= m_idx < m:
                is_last = sid == (s - 1)
                upd = jnp.where(is_last, out, outputs[0, m_idx])
                outputs = outputs.at[0, m_idx].set(upd)
            carry = lax.ppermute(out, axis, perm)
        return outputs

    stacked = run(stage_params, x_mb)     # (S, M, *batch)
    return stacked[-1]


def split_stages(stacked_layers, n_stages: int):
    """Reshape (L, ...) stacked layer params into (S, L/S, ...) stages."""
    def r(a):
        return a.reshape(n_stages, a.shape[0] // n_stages, *a.shape[1:])
    return jax.tree.map(r, stacked_layers)
