"""Kernel-registry benchmark: dispatch every registered op, autotune the
tile spaces, and compare tuned vs legacy-fixed tile configs.

Writes ``BENCH_kernels.json`` so CI accumulates a perf trajectory:

    {"meta": {...}, "registry": {op: dispatch plan}, "autotune": {...},
     "rows": [{"name", "us", ...}]}

``--smoke`` (CI) uses tiny shapes on the interpret impls so the sweep
finishes in seconds on a CPU runner; numbers are regression tracking, not
roofline claims.  The headline comparison: the tuned decode-shape
``dequant_matmul`` config (rows clamped to the live batch) vs the old
fixed ``bm=256, bn=256, bk=512`` tiles that padded every 1-8 row decode
matmul to 256 rows.

Run: PYTHONPATH=src python -m benchmarks.kernel_bench [--smoke] [--out F]
"""

from __future__ import annotations

import argparse
import json
import platform as _platform
import time


def _time_call(fn, *args, repeats=3, warmup=1, **kwargs) -> float:
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_dequant_matmul_tiles(shapes, *, tune_impl: str, repeats: int,
                               rows: list) -> None:
    """Tuned (cache) tiles vs the legacy fixed bm=256,bn=256,bk=512."""
    import numpy as np
    from repro import kernels

    op = kernels.get("dequant_matmul")
    spec = kernels.spec("dequant_matmul")
    impl = spec.impls[tune_impl]
    for m, k, n in shapes:
        (x, wq, sc), _ = spec.example_inputs((m, k, n))
        # the old hard-coded tiles (bn/bk clamped so small layers compile)
        fixed = {"bm": 256, "bn": min(256, -(-n // 128) * 128),
                 "bk": min(512, -(-k // 128) * 128)}
        t_fixed = _time_call(impl.fn, x, wq, sc, repeats=repeats, **fixed)
        pol = kernels.KernelPolicy().override("dequant_matmul", tune_impl)
        plan = op.plan(x, wq, sc, policy=pol)
        tiles = dict(plan.tiles)
        t_tuned = _time_call(impl.fn, x, wq, sc, repeats=repeats, **tiles)
        ref = np.asarray(spec.oracle(x, wq, sc))
        got = np.asarray(impl.fn(x, wq, sc, **tiles))
        np.testing.assert_allclose(got, ref, rtol=2e-4,
                                   atol=2e-4 * np.abs(ref).max())
        rows.append({
            "name": f"dequant_matmul/m{m}_k{k}_n{n}",
            "impl": tune_impl, "fixed_tiles": fixed, "fixed_us":
            round(t_fixed, 1), "tuned_tiles": tiles, "tuned_us":
            round(t_tuned, 1), "cache_hit": plan.cache_hit,
            "tuned_vs_fixed_speedup": round(t_fixed / max(t_tuned, 1e-9), 3),
        })


def bench_registry_dispatch(smoke: bool, rows: list) -> dict:
    """One dispatched call per registered op; records the chosen plan and
    checks the result against the op's oracle."""
    import jax.numpy as jnp
    import numpy as np
    from repro import kernels

    plans: dict = {}

    # dequant_matmul + flash_attention + rd_quant via example_inputs
    examples = {
        "dequant_matmul": (4, 256, 256) if smoke else (8, 2048, 1024),
        "flash_attention": ((1, 64, 64, 2, 2, 32) if smoke
                            else (2, 512, 512, 8, 4, 64)),
        "rd_quant": (1 << 12,) if smoke else (1 << 16,),
    }
    for name, shape in examples.items():
        op = kernels.get(name)
        args, kwargs = kernels.spec(name).example_inputs(shape)
        plan = op.plan(*args, **kwargs)
        us = _time_call(op, *args, repeats=2, **kwargs)
        plans[name] = {"impl": plan.impl, "platform": plan.platform,
                       "tiles": dict(plan.tiles), "cache_hit": plan.cache_hit}
        rows.append({"name": f"{name}/dispatch", "us": round(us, 1),
                     "impl": plan.impl, "shape": list(shape)})

    # embed_lookup_q8 (no example_inputs: tiny inline case)
    rng = np.random.default_rng(0)
    leaf = {"q8": jnp.asarray(rng.integers(-127, 127, (4096, 128)), jnp.int8),
            "q8s": jnp.asarray(rng.random(128) * 0.01 + 1e-4, jnp.float32)}
    toks = jnp.asarray(rng.integers(0, 4096, (4, 64)), jnp.int32)
    op = kernels.get("embed_lookup_q8")
    plan = op.plan(leaf, toks, jnp.float32)
    us = _time_call(op, leaf, toks, jnp.float32, repeats=2)
    got = np.asarray(op(leaf, toks, jnp.float32))
    want = np.asarray(kernels.spec("embed_lookup_q8").oracle(
        leaf, toks, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plans["embed_lookup_q8"] = {"impl": plan.impl, "platform": plan.platform}
    rows.append({"name": "embed_lookup_q8/dispatch", "us": round(us, 1),
                 "impl": plan.impl, "shape": [4, 64]})
    return plans


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny interpret-mode shapes (CI)")
    ap.add_argument("--out", default="BENCH_kernels.json")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    import jax
    from repro import kernels
    from repro.kernels import tune

    backend = jax.default_backend()
    if backend != "tpu" and not args.smoke:
        raise SystemExit(f"kernel_bench: no TPU (backend {backend!r}); "
                         "full-size timings come only from the chip — "
                         "pass --smoke for the interpret-mode CPU sweep")
    tune_impl = "pallas" if backend == "tpu" else "interpret"

    if args.smoke:
        dm_shapes = [(1, 256, 256), (4, 256, 256), (8, 256, 256),
                     (128, 256, 256)]
        dmg_shapes = [(4, 8, 256, 256), (8, 16, 128, 256)]
        fa_shapes = [(1, 64, 64, 2, 2, 32)]
        rd_shapes = [(1 << 12,)]
    else:
        dm_shapes = [(1, 2048, 1024), (8, 2048, 1024), (256, 2048, 1024),
                     (1024, 2048, 1024)]
        dmg_shapes = [(8, 64, 2048, 1024), (64, 32, 1024, 512)]
        fa_shapes = [(2, 512, 512, 8, 4, 64), (1, 2048, 2048, 8, 4, 128)]
        rd_shapes = [(1 << 16,), (1 << 20,)]

    t0 = time.time()
    autotune_results = {
        "dequant_matmul": tune.autotune(
            "dequant_matmul", dm_shapes, impl=tune_impl,
            repeats=args.repeats, force=True),
        "dequant_matmul_grouped": tune.autotune(
            "dequant_matmul_grouped", dmg_shapes, impl=tune_impl,
            repeats=max(args.repeats - 1, 1), force=True),
        "flash_attention": tune.autotune(
            "flash_attention", fa_shapes, impl=tune_impl,
            repeats=max(args.repeats - 1, 1), force=True),
        "rd_quant": tune.autotune(
            "rd_quant", rd_shapes, impl=tune_impl,
            repeats=max(args.repeats - 1, 1), force=True),
    }
    t_tune = time.time() - t0

    rows: list = []
    kernels.clear_dispatch_report()
    plans = bench_registry_dispatch(args.smoke, rows)
    bench_dequant_matmul_tiles(dm_shapes, tune_impl=tune_impl,
                               repeats=args.repeats, rows=rows)

    out = {
        "meta": {
            "backend": backend, "python": _platform.python_version(),
            "jax": jax.__version__, "smoke": bool(args.smoke),
            "autotune_s": round(t_tune, 2),
            "tuning_cache": str(tune.default_cache_path()),
            "ops": kernels.available_ops(),
        },
        "registry": plans,
        "autotune": autotune_results,
        "dispatch_report": kernels.dispatch_report(),
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    decode = [r for r in rows if r["name"].startswith("dequant_matmul/m")
              and int(r["name"].split("/m")[1].split("_")[0]) <= 8]
    for r in decode:
        print(f"{r['name']}: fixed {r['fixed_us']}us -> tuned "
              f"{r['tuned_us']}us (x{r['tuned_vs_fixed_speedup']})")
    print(f"wrote {args.out} ({len(rows)} rows, autotune {t_tune:.1f}s)")


if __name__ == "__main__":
    main()
