"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse_compile.py \
        --workload mistral-nemo-12b-q8.chat

At the cell's full width this compiles the benchmark's weight maker and
every program the warm-up compiles on the chip (the paged decode step at
each batch size, the exact and padded admission prefill of each bucket,
the page scatter of each bucket), and prints each one's
``memory_analysis()`` and its total: what the chip's compiler refuses, or
what does not fit one chip's memory, shows here at no chip time.  Every
batch size counts: at one decode row XLA lays the whole KV pool out anew,
so that program, and not the largest batch, needs the most memory.

The topology is described inside ``main``, never at import.  Nothing
runs, so this says nothing about results or times.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def gib(n: int) -> str:
    return f"{n / 2**30:.3f}GiB"


def report(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: args {gib(m.argument_size_in_bytes)} out "
          f"{gib(m.output_size_in_bytes)} alias {gib(m.alias_size_in_bytes)}"
          f" temp {gib(m.temp_size_in_bytes)} code "
          f"{gib(m.generated_code_size_in_bytes)} total {gib(total)}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import spec
    sys.path.insert(0, str(spec.ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import weights
    from repro.models.transformer import (decode_step, forward, init_cache,
                                          prefill)
    from repro.serve.session import ServeSession

    cell = spec.load(args.workload)
    cfg, sizes = harness.model(cell, rehearse=False)
    cfg = cfg.replace(kernels=dataclasses.replace(cfg.kernels,
                                                  platform="tpu"))
    mix, dep = cell.mix, cell.config["deployment"]
    slots = spec.slots(cell.config, mix)
    page = dep["kv_page_size"]
    n_max = -(-mix["max_len"] // page)
    pool_pages = slots * n_max + 1

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=one), tree)

    def compile_(name, fn, *shapes):
        t0 = time.perf_counter()
        c = jax.jit(fn).lower(*shapes).compile()
        report(f"{name} ({time.perf_counter() - t0:.1f}s)", c)

    print(f"{args.workload}: slots={slots} max_len={mix['max_len']} "
          f"pool_pages={pool_pages} page={page}", flush=True)
    key = on_chip(jax.eval_shape(lambda: weights.seed_key(0)))
    make_tree = cell.family.make_tree
    compile_("weights", lambda k: make_tree(sizes, k), key)

    params = on_chip(jax.eval_shape(
        lambda k: make_tree(sizes, k), weights.seed_key(0)))
    pools = on_chip(jax.eval_shape(lambda: init_cache(cfg, pool_pages, page)))
    i32 = jnp.int32

    def arr(shape, dt=i32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    batches = sorted({min(1 << i, slots) for i in range(slots.bit_length()
                                                        + 1)})
    buckets = list(mix["prefill_buckets"])
    for bs in batches:
        compile_(f"decode bs={bs}",
                 lambda p, pl, pg, tok, pos: decode_step(
                     p, cfg, pl, pos, tokens=tok, cache_pages=pg),
                 params, pools, arr((bs, n_max)), arr((bs,)), arr((bs,)))
    for b in buckets:
        cache_len = -(-b // page) * page
        compile_(f"prefill {b}",
                 lambda p, t: prefill(p, cfg, tokens=t, max_len=cache_len),
                 params, arr((1, b)))

        def pad_fn(p, t, last):
            caches = init_cache(cfg, 1, cache_len)
            logits, new, _ = forward(p, cfg, tokens=t, caches=caches,
                                     last_index=last)
            return logits[:, 0, :], new
        compile_(f"prefill {b} padded", pad_fn, params, arr((1, b)),
                 arr((1,)))
        caches = on_chip(jax.eval_shape(lambda: init_cache(cfg, 1,
                                                           cache_len)))
        compile_(f"scatter {b}", ServeSession._scatter_paged_impl, pools,
                 caches, arr((cache_len // page,)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
