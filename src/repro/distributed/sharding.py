"""Logical-axis sharding rules -> PartitionSpecs (DP / FSDP / TP / EP / SP).

Parameters are matched by tree path against a rule table of *logical* axes;
logical axes resolve to mesh axes through a rules dict.  Every resolved axis
is validated for divisibility against the mesh — a dim that doesn't divide
falls back to replication (e.g. GQA kv-heads with kv < |model|), which keeps
one rule table valid across all 10 architectures and any mesh shape.

Activation constraints use a trace-time context (``activation_sharding``)
so model code stays mesh-agnostic: ``constrain(x, "batch", None, "tp")``
is a no-op outside the context.
"""

from __future__ import annotations

import re
import threading
from contextlib import contextmanager

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),      # DP over pods x data
    "fsdp": "data",                # parameter sharding (ZeRO-3 style)
    "fsdp_pod": ("pod", "data"),   # wider FSDP for the largest models
    "tp": "model",                 # megatron-style tensor parallel
    "expert": "model",             # EP: expert banks
    "vocab": "model",              # embedding/logits vocab dim
    "kv_heads": "model",           # replicated automatically if kv < |model|
    "heads": "model",
    "seq": None,                   # set to "data" to enable SP
    "kv_seq": "model",             # decode KV-cache sequence sharding: no
                                   # assigned arch has kv_heads % 16 == 0, so
                                   # the cache uses the model axis via seq
    "moe_group": ("pod", "data"),  # MoE dispatch groups (== batch rows)
}

# Serving: no optimizer state, so parameters are TP-sharded and *replicated*
# over data (FSDP weight all-gathers would move the whole model per decoded
# token).  MoE expert banks instead span (data x model) = 256-way EP — the
# deepseek-v3 routed experts (1.3 TB bf16) cannot replicate over data.
SERVE_RULES: dict[str, object] = {
    **DEFAULT_RULES,
    "fsdp": None,
    "fsdp_pod": None,
    "expert": ("data", "model"),
    "moe_group": None,             # tokens -> expert owners is the all-to-all
}

# Prefill: like serving (no optimizer, no FSDP) but token counts are large,
# so MoE dispatch groups shard with the batch and experts stay on "model"
# (group-local dispatch, no cross-batch exchange).  deepseek-v3 is the
# exception (launch/dryrun.py): its 1.3 TB expert bank does not fit 16-way,
# so it keeps the SERVE_RULES 256-way EP and pays the dispatch all-to-all.
PREFILL_RULES: dict[str, object] = {
    **SERVE_RULES,
    "expert": "model",
    "moe_group": ("pod", "data"),
}


def _mesh_axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([_mesh_axis_size(mesh, a) for a in axis]))
    return mesh.shape[axis] if axis in mesh.shape else 1


def _present(mesh, axis):
    """Drop mesh axes that don't exist on this mesh (e.g. 'pod' on 1 pod)."""
    if axis is None:
        return None
    if isinstance(axis, tuple):
        kept = tuple(a for a in axis if a in mesh.shape)
        return kept if len(kept) > 1 else (kept[0] if kept else None)
    return axis if axis in mesh.shape else None


def spec_for(shape, logical_axes, mesh, rules=None) -> P:
    """Resolve logical axes for ``shape`` with divisibility fallback.

    Tuple axes degrade gracefully: ("data","model") on a dim of 64 with a
    16x16 mesh falls back to ("model",) (64 % 256 != 0 but 64 % 16 == 0)
    before replicating — e.g. deepseek-moe's 64 experts under 256-way EP.
    """
    rules = rules or DEFAULT_RULES
    out = []
    for dim, name in zip(shape, logical_axes):
        if name is None:
            out.append(None)
            continue
        axis = _present(mesh, rules.get(name))
        candidates = [axis]
        if isinstance(axis, tuple):
            candidates += [axis[i:] if len(axis[i:]) > 1 else axis[-1]
                           for i in range(1, len(axis))]
        chosen = None
        for cand in candidates:
            size = _mesh_axis_size(mesh, cand)
            if cand is not None and size > 1 and dim % size == 0:
                chosen = cand
                break
        out.append(chosen)
    return P(*out)


# ---------------------------------------------------------------------------
# Parameter rules (regex on '/'-joined tree path, innermost dims)
# ---------------------------------------------------------------------------

PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed$", ("vocab", "fsdp")),
    (r"head$", ("fsdp", "vocab")),
    # attention (GQA)
    (r"attn/wq$", ("fsdp", "tp")),
    (r"attn/wk$", ("fsdp", "tp")),
    (r"attn/wv$", ("fsdp", "tp")),
    (r"attn/wo$", ("tp", "fsdp")),
    (r"attn/b[qkv]$", ("tp",)),
    (r"attn/[qk]_norm$", (None,)),
    # attention (MLA)
    (r"attn/w_dq$", ("fsdp", None)),
    (r"attn/w_uq$", (None, "tp")),
    (r"attn/w_dkv$", ("fsdp", None)),
    (r"attn/w_uk$", (None, "tp")),
    (r"attn/w_uv$", (None, "tp")),
    (r"attn/w_kr$", ("fsdp", None)),
    (r"attn/(q_norm|kv_norm)$", (None,)),
    # dense mlp
    (r"mlp/w_gate$", ("fsdp", "tp")),
    (r"mlp/w_up$", ("fsdp", "tp")),
    (r"mlp/w_down$", ("tp", "fsdp")),
    # moe
    (r"moe/router$", ("fsdp", None)),
    (r"moe/w_gate$", ("expert", "fsdp", None)),
    (r"moe/w_up$", ("expert", "fsdp", None)),
    (r"moe/w_down$", ("expert", None, "fsdp")),
    (r"moe/sh_gate$", ("fsdp", "tp")),
    (r"moe/sh_up$", ("fsdp", "tp")),
    (r"moe/sh_down$", ("tp", "fsdp")),
    # ssm
    (r"mixer/w_z$", ("fsdp", "tp")),
    (r"mixer/w_x$", ("fsdp", "tp")),
    (r"mixer/w_b$", ("fsdp", "tp")),
    (r"mixer/w_c$", ("fsdp", "tp")),
    (r"mixer/w_dt$", ("fsdp", "tp")),
    (r"mixer/conv_._w$", ("tp", None)),
    (r"mixer/conv_._b$", ("tp",)),
    (r"mixer/(a_log|dt_bias|d_skip)$", ("tp",)),
    (r"mixer/norm$", ("tp",)),
    (r"mixer/out_proj$", ("tp", "fsdp")),
    # norms / everything 1-D
    (r"(norm|scale|bias)$", (None,)),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


_MOMENT_SUFFIXES = ("/m_q", "/v_q", "/m_s", "/v_s", "/m", "/v")


def logical_axes_for_path(path_str: str, ndim: int) -> tuple:
    # optimizer moments / int8-serving codes live under the param path + a
    # suffix and inherit the param's sharding; q8s / blocked scales keep
    # the trailing axes of the rule (divisibility fallback covers the rest)
    tail_axes = False
    if path_str.endswith("/q8s"):
        path_str = path_str[:-4]
        tail_axes = True
    elif path_str.endswith("/q8"):
        path_str = path_str[:-3]
    else:
        for suf in _MOMENT_SUFFIXES:
            if path_str.endswith(suf):
                path_str = path_str[: -len(suf)]
                break
    for pat, axes in PARAM_RULES:
        if re.search(pat, path_str):
            if tail_axes:              # per-out-channel scale vector(s)
                axes = tuple(axes)[-1:]
            if len(axes) < ndim:       # stacked layer (and scale) lead dims
                return (None,) * (ndim - len(axes)) + tuple(axes)
            return tuple(axes[:ndim])
    return (None,) * ndim


def build_param_specs(params, mesh, rules=None):
    """Pytree of PartitionSpec matching ``params`` (works for opt moments too
    since their tree paths embed the same leaf names)."""
    def leaf_spec(path, leaf):
        ps = _path_str(path)
        axes = logical_axes_for_path(ps, np.ndim(leaf))
        return spec_for(np.shape(leaf), axes, mesh, rules)
    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def named_shardings(specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Activation constraints (trace-time context)
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextmanager
def activation_sharding(mesh, rules=None):
    prev = getattr(_CTX, "active", None)
    _CTX.active = (mesh, rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _CTX.active = prev


def active_mesh():
    """``(mesh, rules)`` of the enclosing :func:`activation_sharding`
    context, or None outside one."""
    return getattr(_CTX, "active", None)


def constrain(x, *logical_axes):
    active = active_mesh()
    if active is None:
        return x
    mesh, rules = active
    spec = spec_for(x.shape, logical_axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
