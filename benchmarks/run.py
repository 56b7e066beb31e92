"""Benchmark harness — one function per paper table + perf benches.

Prints ``name,us_per_call,derived`` CSV rows.  Perf numbers measured on the
host CPU (the CABAC codec is host-side by design; kernel perf on TPU is
covered by the §Roofline dry-run analysis, not wall-clock here).

Run: PYTHONPATH=src python -m benchmarks.run [--fast]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _row(name: str, us: float, derived: dict):
    print(f"{name},{us:.2f},{json.dumps(derived, default=float)}",
          flush=True)


def bench_table1(fast: bool):
    from .paper_tables import table1
    from .tasks import flat_weights, sparsify_mlp, train_mlp, train_small_lm

    t0 = time.time()
    mlp = train_mlp(steps=200 if fast else 400)
    fixtures = {}

    def mlp_acc(flat):
        return mlp.accuracy({k: np.asarray(v) for k, v in flat.items()})

    fixtures["mlp-dense"] = (flat_weights(mlp.params), None, mlp_acc,
                             mlp.params)
    sp = sparsify_mlp(mlp, steps=250 if fast else 600)
    spw = flat_weights(sp.params)
    nz = np.mean([np.mean(v != 0) for v in spw.values() if v.ndim >= 2])
    fixtures["mlp-sparse"] = (spw, flat_weights(sp.sigma), mlp_acc,
                              sp.params)

    lm = train_small_lm(steps=60 if fast else 150)
    from .tasks import rebuild

    def lm_acc(flat):
        return lm.accuracy(rebuild(lm.params, flat))

    fixtures["small-lm"] = (flat_weights(lm.params), None, lm_acc, lm.params)

    rows = table1(fixtures)
    for r in rows:
        _row(f"table1/{r['model']}", 1e6 * (time.time() - t0), r)
    _row("table1/sparsity", 0.0, {"mlp_sparse_nonzero_frac": float(nz)})
    return fixtures


def bench_table2(fixtures, fast: bool):
    from .paper_tables import table2
    flat, sigma, _, _ = fixtures["mlp-sparse"]
    t0 = time.time()
    rows = table2(flat, sigma)
    for r in rows:
        _row(f"table2/step={r['step']:.4g}", 1e6 * (time.time() - t0), r)


def bench_table3(fixtures, fast: bool):
    from .paper_tables import table3
    for model in ["mlp-dense", "mlp-sparse"]:
        flat = fixtures[model][0]
        t0 = time.time()
        rows = table3(flat)
        for r in rows:
            _row(f"table3/{model}/{r['quantizer']}",
                 1e6 * (time.time() - t0), r)


def bench_fig8(fixtures, fast: bool):
    from .paper_tables import fig8_rate_accuracy
    flat, _, acc_fn, _ = fixtures["mlp-dense"]
    t0 = time.time()
    rows = fig8_rate_accuracy(flat, acc_fn)
    _row("fig8/rate_accuracy", 1e6 * (time.time() - t0), {"points": rows})


def bench_codec_throughput(fast: bool):
    from repro.core import binarization as B
    from repro.core.cabac import RangeDecoder, RangeEncoder
    rng = np.random.default_rng(0)
    n = 100_000 if fast else 400_000
    levels = (rng.standard_t(2, n) * 2).astype(np.int64)
    t0 = time.time()
    enc = RangeEncoder(B.make_contexts())
    B.encode_levels(enc, levels)
    blob = enc.finish()
    t1 = time.time()
    dec = RangeDecoder(blob, B.make_contexts())
    out = B.decode_levels(dec, n)
    t2 = time.time()
    assert np.array_equal(out, levels)
    _row("codec/encode", 1e6 * (t1 - t0),
         {"weights_per_s": n / (t1 - t0),
          "bits_per_param": 8 * len(blob) / n})
    _row("codec/decode", 1e6 * (t2 - t1), {"weights_per_s": n / (t2 - t1)})


def bench_rd_quant_kernel(fast: bool):
    import jax
    from repro import kernels
    from repro.core.quant import nearest_level
    from repro.core.rate_model import estimate_bin_probs
    rd_quant = kernels.get("rd_quant")
    rng = np.random.default_rng(1)
    n = (1 << 18) if fast else (1 << 20)
    w = (rng.standard_normal(n) * 0.05).astype(np.float32)
    probs = estimate_bin_probs(nearest_level(w, 0.01))
    # registry default path (jnp ref on CPU, pallas on TPU)
    out = rd_quant(w, None, probs, step=0.01, lam=1e-4)
    jax.block_until_ready(out)
    t0 = time.time()
    out = rd_quant(w, None, probs, step=0.01, lam=1e-4)
    jax.block_until_ready(out)
    t1 = time.time()
    _row("rd_quant/registry_default", 1e6 * (t1 - t0),
         {"weights_per_s": n / (t1 - t0), "n": n,
          "impl": rd_quant.plan(w, None, probs, step=0.01, lam=1e-4).impl})
    # pallas interpret path — correctness-path timing only (Python-level;
    # TPU times come from the chip benchmark, benchmarks/chip)
    interp = kernels.KernelPolicy().override("rd_quant", "interpret")
    n2 = 1 << 15
    t0 = time.time()
    out = rd_quant(w[:n2], None, probs, step=0.01, lam=1e-4, policy=interp)
    jax.block_until_ready(out)
    t1 = time.time()
    _row("rd_quant/pallas_interpret", 1e6 * (t1 - t0), {"n": n2})


def bench_dequant_matmul(fast: bool):
    import jax
    import jax.numpy as jnp
    from repro import kernels
    dequant_matmul = kernels.get("dequant_matmul")
    rng = np.random.default_rng(2)
    m, k, n = 256, 2048, 1024
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    wq = jnp.asarray(rng.integers(-127, 127, (k, n)), jnp.int8)
    sc = jnp.asarray(rng.random(n) * 0.01, jnp.float32)
    out = dequant_matmul(x, wq, sc)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(10):
        out = dequant_matmul(x, wq, sc)
    jax.block_until_ready(out)
    t1 = time.time()
    us = 1e6 * (t1 - t0) / 10
    _row("dequant_matmul/registry_default", us,
         {"gflops": 2 * m * k * n / 1e9 / (us / 1e6),
          "impl": dequant_matmul.plan(x, wq, sc).impl,
          "weight_bytes_vs_bf16": 0.5})   # int8 weights halve HBM reads


def bench_comm_compression(fast: bool):
    """Wire-rate of the EF-compressed gradient stream (paper §VI)."""
    import jax
    import jax.numpy as jnp
    from repro.compression.q8 import q8_encode
    from repro.distributed.compress import (CompressionConfig,
                                            code_entropy_bits_per_param,
                                            ef_compress_update,
                                            init_error_feedback)
    rng = np.random.default_rng(3)
    g = {"w": jnp.asarray(rng.standard_normal((256, 1024)) * 1e-3,
                          jnp.float32)}
    ef = init_error_feedback(g)
    cfg = CompressionConfig(enabled=True)
    t0 = time.time()
    gq, ef = ef_compress_update(g, ef, cfg)
    jax.block_until_ready(gq)
    t1 = time.time()
    codes, _ = q8_encode(g["w"])
    ent = code_entropy_bits_per_param(codes)
    _row("comm/ef_int8", 1e6 * (t1 - t0),
         {"wire_bits_per_param_int8": 8.0 + 32.0 / 128,
          "cabac_entropy_bits_per_param": ent,
          "f32_baseline_bits": 32.0})


def bench_compression_registry(fast: bool):
    """Compress+decompress one pytree through every registered codec."""
    from repro import compression
    rng = np.random.default_rng(7)
    n = 64 if fast else 128
    tree = {
        "layers": {"blk": {"w": (rng.standard_normal((2, n, 2 * n)) * 0.05
                                 ).astype(np.float32)}},
        "embed": (rng.standard_normal((4 * n, n)) * 0.05).astype(np.float32),
        "norm": np.ones(n, np.float32),
    }
    for name in compression.available():
        codec = compression.get(name)
        t0 = time.time()
        art = codec.compress(tree)
        t1 = time.time()
        codec.decompress(art.blob, like=tree)
        t2 = time.time()
        _row(f"compression/{name}", 1e6 * (t1 - t0),
             {"bits_per_param": art.report["bits_per_param"],
              "ratio_pct": art.report["ratio_pct"],
              "decode_us": 1e6 * (t2 - t1)})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args, _ = ap.parse_known_args()
    print("name,us_per_call,derived")
    fixtures = bench_table1(args.fast)
    bench_table2(fixtures, args.fast)
    bench_table3(fixtures, args.fast)
    bench_fig8(fixtures, args.fast)
    bench_codec_throughput(args.fast)
    bench_rd_quant_kernel(args.fast)
    bench_dequant_matmul(args.fast)
    bench_comm_compression(args.fast)
    bench_compression_registry(args.fast)


if __name__ == "__main__":
    main()
