import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16x16 single-pod, 2x16x16
multi-pod), resolves all input/state shardings, lowers the appropriate step
(train_step for train shapes, prefill for prefill shapes, serve_step for
decode shapes) against ShapeDtypeStruct stand-ins (no allocation), compiles,
and records:

  - memory_analysis()           (proves the per-device footprint)
  - cost_analysis()             (HLO FLOPs / bytes for the roofline)
  - collective bytes            (parsed from the post-SPMD HLO text)

Results land in benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json.
They are compile-time counts from the CPU backend, not device times; those
come from the chip benchmark (benchmarks/chip).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all [--skip-existing]
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .. import configs
from ..configs import ARCH_IDS, SHAPES, shapes_for
from ..distributed.compress import CompressionConfig
from ..distributed.sharding import (DEFAULT_RULES, PREFILL_RULES,
                                    SERVE_RULES)
from ..models.transformer import init_cache, init_params
from ..optim.adamw import AdamWConfig, adamw_init
from ..train.steps import (batch_specs, cache_logical_specs,
                           init_train_state, make_decode_step,
                           make_prefill_step, make_train_step, state_specs)
from .mesh import make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results/dryrun")

# 8-bit Adam moments where the fp32-moment footprint does not fit 16 GB HBM
# at 256 chips (see DESIGN.md §5).
Q8_MOMENT_ARCHS = {"deepseek-v3-671b"}


def opt_config(arch: str) -> AdamWConfig:
    return AdamWConfig(quantized_moments=arch in Q8_MOMENT_ARCHS)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, weak-type-correct, no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(arch: str, shape_name: str) -> dict:
    """Model inputs for one cell, as ShapeDtypeStructs."""
    cfg = configs.get(arch)
    seq, batch, kind = SHAPES[shape_name]
    out: dict = {}
    if kind == "train":
        if cfg.embed_input:
            out["tokens"] = _sds((batch, seq), jnp.int32)
        else:
            out["embeds"] = _sds((batch, seq, cfg.d_model), cfg.compute_dtype)
        out["labels"] = _sds((batch, seq), jnp.int32)
        if cfg.m_rope:
            out["pos3d"] = _sds((3, batch, seq), jnp.int32)
    elif kind == "prefill":
        if cfg.embed_input:
            out["tokens"] = _sds((batch, seq), jnp.int32)
        else:
            out["embeds"] = _sds((batch, seq, cfg.d_model), cfg.compute_dtype)
        if cfg.m_rope:
            out["pos3d"] = _sds((3, batch, seq), jnp.int32)
    else:  # decode: one new token against a seq_len KV/state cache
        if cfg.embed_input:
            out["tokens"] = _sds((batch,), jnp.int32)
        else:
            out["embeds"] = _sds((batch, 1, cfg.d_model), cfg.compute_dtype)
        if cfg.m_rope:
            out["pos3d"] = _sds((3, batch, 1), jnp.int32)
    return out


# ---------------------------------------------------------------------------
# Collective-bytes accounting from post-SPMD HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|"
                       r"f64|c64|c128)\[([0-9,]*)\]")
_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
# defining line: `%name = <result shape(s)> <kind>[-start](operands...)`
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<res>.*?)\s+"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_list_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        n = 1
        if m.group(2):
            for d in m.group(2).split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


def _group_size(line: str, n_chips: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))     # [num_groups, group_size]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return n_chips


def _wire_bytes(kind: str, result_bytes: int, n: int) -> float:
    """Per-device ICI traffic estimate (ring algorithms).

    all-reduce: 2*S*(n-1)/n of the (operand==result) size S;
    all-gather: result holds the gathered array, each device receives
    S*(n-1)/n; reduce-scatter: operand = result*n, wire = result*(n-1);
    all-to-all: each device exchanges (n-1)/n of its data (result size);
    collective-permute: result size.
    """
    if n <= 1:
        return 0.0
    f = (n - 1) / n
    if kind == "all-reduce":
        return 2.0 * result_bytes * f
    if kind == "all-gather":
        return result_bytes * f
    if kind == "reduce-scatter":
        return result_bytes * (n - 1)
    if kind == "all-to-all":
        return result_bytes * f
    return float(result_bytes)     # collective-permute


_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\([^)]*.*\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?condition=%?([\w.\-]+).*?body=%?"
                       r"([\w.\-]+)", re.S)
_S32_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_CALLSITE_RE = re.compile(
    r"(?:condition|body|to_apply|branch_computations=\{)[=%]*%?([\w.\-]+)")


def _split_computations(hlo_text: str) -> tuple[dict[str, str], str]:
    comps: dict[str, list[str]] = {}
    cur = None
    entry = ""
    for line in hlo_text.splitlines():
        m = _COMP_HDR_RE.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return {k: "\n".join(v) for k, v in comps.items()}, entry


def _trip_count(cond_text: str) -> int:
    consts = [int(m.group(1)) for m in _S32_CONST_RE.finditer(cond_text)]
    return max(consts) if consts else 1


def computation_multiplicities(hlo_text: str):
    """(computations, entry_name, multiplicity per executable computation)
    with while-body trip counts propagated through the call graph."""
    comps, entry = _split_computations(hlo_text)
    body_trip: dict[str, int] = {}
    for text in comps.values():
        for m in _WHILE_RE.finditer(text):
            cond, body = m.group(1), m.group(2)
            body_trip[body] = _trip_count(comps.get(cond, ""))
    mult: dict[str, float] = {}
    stack = [(entry, 1.0)]
    while stack:
        name, m = stack.pop()
        if m <= mult.get(name, 0.0):
            continue
        mult[name] = m
        text = comps.get(name, "")
        for cm in _CALLSITE_RE.finditer(text):
            callee = cm.group(1)
            if callee not in comps:
                continue
            factor = body_trip.get(callee, 1)
            stack.append((callee, m * factor))
    return comps, entry, mult


def collective_bytes(hlo_text: str, n_chips: int) -> dict:
    """Per-device collective traffic from the post-SPMD HLO.

    Collectives inside while bodies (the layer scan) are multiplied by the
    loop trip count, extracted from the loop condition's s32 bound.  Only
    defining lines count (`-done` carries no new traffic); result shapes in
    the partitioned module are already per-device.  Records both raw result
    bytes and a ring-algorithm wire estimate per kind.
    """
    comps, entry, mult = computation_multiplicities(hlo_text)

    per_kind = {k: 0.0 for k in _COLL_KINDS}
    wire_kind = {k: 0.0 for k in _COLL_KINDS}
    counts = {k: 0 for k in _COLL_KINDS}
    for name, text in comps.items():
        m = mult.get(name, 1.0)
        for line in text.splitlines():
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            kind = dm.group("kind")
            b = _shape_list_bytes(dm.group("res"))
            if dm.group("start") and kind in ("all-reduce", "reduce-scatter"):
                b //= 2   # async start result carries (operand, result)
            n = _group_size(line, n_chips)
            per_kind[kind] += b * m
            wire_kind[kind] += _wire_bytes(kind, b, n) * m
            counts[kind] += 1
    return {"per_device_bytes": per_kind,
            "wire_bytes": wire_kind,
            "op_counts": counts,
            "total_per_device_bytes": sum(per_kind.values()),
            "total_wire_bytes": sum(wire_kind.values())}


# ---------------------------------------------------------------------------
# Cell builders
# ---------------------------------------------------------------------------

def _shardings(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, mesh, cfg_overrides=None,
               int8_serving: bool = False):
    cfg = configs.get(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    seq, batch, kind = SHAPES[shape_name]
    if kind == "train":
        rules = DEFAULT_RULES
    elif kind == "prefill":
        rules = PREFILL_RULES
        if arch == "deepseek-v3-671b":
            # the 1.3 TB expert bank cannot replicate over data at 16-way
            # EP: shard the expert d/f dims FSDP-style over "data" — the
            # per-layer weight gathers amortize over 1M prefill tokens
            # (§Perf iteration: 256-way-EP serve rules produced 827 s of
            # collectives from unsharded dispatch groups)
            rules = {**PREFILL_RULES, "fsdp": "data"}
    else:
        rules = SERVE_RULES
    inputs = input_specs(arch, shape_name)

    if kind == "train":
        ocfg = opt_config(arch)
        ccfg = CompressionConfig(enabled=False)
        state_shape = jax.eval_shape(
            lambda: init_train_state(cfg, ocfg, ccfg))
        step_fn, _ = make_train_step(cfg, mesh, ocfg, ccfg)
        st_specs = state_specs(state_shape, mesh, rules)
        b_specs = batch_specs(inputs, mesh, rules)
        jitted = jax.jit(step_fn,
                         in_shardings=(_shardings(st_specs, mesh),
                                       _shardings(b_specs, mesh)),
                         donate_argnums=(0,))
        return jitted.lower(state_shape, inputs)

    if int8_serving:
        # fixed-point serving (paper §III-C-1): int8 weights + int8 KV cache
        from ..serve.quantized import quantize_params_for_serving
        cfg = cfg.replace(q8_cache=True)
        params_shape = jax.eval_shape(
            lambda: quantize_params_for_serving(
                init_params(cfg, jax.random.PRNGKey(0))))
    else:
        params_shape = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
    from ..distributed.sharding import build_param_specs
    p_specs = build_param_specs(params_shape, mesh, rules)

    if kind == "prefill":
        step_fn = make_prefill_step(cfg, mesh, rules, max_len=seq)
        b_specs = batch_specs(inputs, mesh, rules)
        cache_shape = jax.eval_shape(lambda: init_cache(cfg, batch, seq))
        c_specs = cache_logical_specs(cache_shape, mesh, rules)
        out_sh = (NamedSharding(mesh, P(None, None)),
                  _shardings(c_specs, mesh))
        jitted = jax.jit(step_fn,
                         in_shardings=(_shardings(p_specs, mesh),
                                       _shardings(b_specs, mesh)),
                         out_shardings=out_sh)
        return jitted.lower(params_shape, inputs)

    # decode
    cache_shape = jax.eval_shape(lambda: init_cache(cfg, batch, seq))
    c_specs = cache_logical_specs(cache_shape, mesh, rules)
    step_fn = make_decode_step(cfg, mesh, rules)
    in_specs = batch_specs(inputs, mesh, rules)
    jitted = jax.jit(
        step_fn,
        in_shardings=(_shardings(p_specs, mesh),
                      _shardings(c_specs, mesh),
                      _shardings(in_specs, mesh), NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, P(None, None)),
                       _shardings(c_specs, mesh)),
        donate_argnums=(1,))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return jitted.lower(params_shape, cache_shape, inputs, pos)


def analyze(lowered, compiled, n_chips: int) -> dict:
    from .hlo_analysis import trip_aware_cost
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax returned [dict]
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text, n_chips)
    comps, _, mult = computation_multiplicities(hlo_text)
    ta = trip_aware_cost(hlo_text, comps, mult)
    return {
        # cost_analysis counts while bodies once (verified); the trip-aware
        # numbers below are the roofline inputs
        "flops_per_device_xla": float(cost.get("flops", 0.0)),
        "bytes_per_device_xla": float(cost.get("bytes accessed", 0.0)),
        "flops_per_device": ta["flops"],
        "bytes_per_device": ta["bytes"],
        "bytes_per_device_bf16": ta["bytes_bf16"],
        "collectives": coll,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
            "live_bytes_est": (mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes
                               - mem.alias_size_in_bytes),
        },
        "n_chips": n_chips,
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             cfg_overrides=None, int8_serving: bool = False) -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_chips = 512 if multi else 256
    t0 = time.time()
    lowered = lower_cell(arch, shape_name, mesh, cfg_overrides, int8_serving)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    print(compiled.memory_analysis())
    cost = compiled.cost_analysis()
    print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})
    res = analyze(lowered, compiled, n_chips)
    res.update({"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2)})
    return res


def cell_path(arch, shape, mesh_kind, suffix=""):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR,
                        f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--int8", action="store_true",
                    help="fixed-point serving (int8 weights + KV cache)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in shapes_for(arch):
                for mesh_kind in ("single", "multi"):
                    cells.append((arch, shape, mesh_kind, False))
                # int8 fixed-point serving variant for the serve shapes
                if SHAPES[shape][2] == "decode":
                    cells.append((arch, shape, "single", True))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape, args.mesh, args.int8)]

    failures = []
    for arch, shape, mesh_kind, int8 in cells:
        suffix = "__int8" if int8 else ""
        path = cell_path(arch, shape, mesh_kind, suffix)
        if args.skip_existing and os.path.exists(path):
            print(f"[skip] {arch} {shape} {mesh_kind}{suffix}")
            continue
        print(f"=== {arch} | {shape} | {mesh_kind}{suffix} ===", flush=True)
        try:
            res = run_cell(arch, shape, mesh_kind, int8_serving=int8)
            res["int8_serving"] = int8
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            print(f"[ok] lower={res['lower_s']}s compile={res['compile_s']}s "
                  f"coll={res['collectives']['total_per_device_bytes']/1e6:.1f}MB/dev",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — record and continue
            failures.append((arch, shape, mesh_kind, repr(e)))
            traceback.print_exc()
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("all cells compiled OK")


if __name__ == "__main__":
    main()
