#!/usr/bin/env python3
"""Bring-up smoke on TPU: serve qwen1.5-4b at its published width.

    python chip_smoke.py              # one chip: the serving path
    python chip_smoke.py --chips 4    # four chips: the multi-chip paths

One process drives everything (a chip belongs to one process at a time).
The model is ``configs.get("qwen1.5-4b")`` — 40 layers, d_model 2560, 20
MHA heads x 128, d_ff 6912, vocab 151936 — with random weights.
Requests go through ``repro.launch.serve``'s ``random_weights`` /
``serve_requests`` after ``configure_runtime`` (strict bf16 rounding,
compile cache), the same code as ``python -m repro.launch.serve``;
weights and prompts come from seed 0.

One chip, phases in order (each raises on failure):

  device     the first JAX device must be a TPU; no CPU fallback.
  params     the q8 tree, made by one jitted init + quantize program.
  q8         slot-KV session: 4 requests x 128 prompt tokens x 32 greedy
             tokens, strict policy, ``dequant_matmul(_grouped)`` pinned to
             pallas; no ``fallback`` in ``dispatch_report()``.  Checked
             against the same requests with ``dequant_matmul`` on ``ref``:
             equal tokens, or first-step logits within LOGIT_RTOL.
  q8-paged   paged-KV session, same requests: tokens equal the slot run.
  bf16       bf16-resident session, same requests; the prefill program
             holds the Pallas flash kernel, and its first-step logits
             match ``flash_attention`` on ``scan`` within LOGIT_RTOL.
  container  the same init pulled to the host, packed by the ``serve-q8``
             codec and served by the ``container`` backend.  Its levels
             and scales are compared with the device-quantized q8 tree
             (at most one step apart, scales within SCALE_RTOL).  Tokens
             equal the q8 session's; only where some level differs may
             first-step logits within LOGIT_RTOL stand in for them.

``--chips 4`` runs only the multi-chip paths and what they are compared
with: a few ``train_loop`` steps on a ``data=4`` mesh (depth cut to
TRAIN_LAYERS so one chip holds the AdamW state of the one-device
comparison) against the same steps on one device, then the sharded
checkpoint that run wrote, cold-started through the bf16 backend onto a
``model=4`` mesh and compared with the saved tensors and with a
one-device session.

Each phase prints wall time, compile time, peak device memory and the
CABAC lane engine (bring-up figures, not benchmark metrics).  The last
stdout line is ``{"ok": true, "device": {...}}``; without a TPU the
script exits nonzero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen1.5-4b"
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, 128, 32
PAGE = 16
# first-step logits of two impls agree when ||a - b|| <= LOGIT_RTOL * ||b||
# (bf16 activations over 40 layers: impls differ only in summation order)
LOGIT_RTOL = 5e-2
# host (serve-q8 codec) vs device q8 scales: rounding only
SCALE_RTOL = 1e-6
TRAIN_LAYERS, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 2, 3, 8, 128
# data=4 vs one-device losses: |a - b| <= LOSS_RTOL * |b| per step
LOSS_RTOL = 2e-3


class CompileClock:
    """Sums XLA backend compile time (persistent-cache reads included)
    and counts cache hits, through JAX's monitoring hooks."""

    def __init__(self):
        import jax.monitoring as mon
        self.secs, self.programs, self.hits = 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.secs, self.programs, self.hits


class Phase:
    """Times one phase and prints its bring-up line; exceptions
    propagate (a failed phase fails the run)."""

    def __init__(self, name, clock, device):
        self.name, self.clock, self.device = name, clock, device

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.clock.snapshot()
        print(f"== {self.name}", flush=True)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            print(f"== {self.name}: FAILED ({exc_type.__name__}: {exc})",
                  flush=True)
            return False
        from repro.core.cabac_vec import resolve_backend
        from repro.core.codec import DecodeOptions
        secs, n, hits = (a - b for a, b in zip(self.clock.snapshot(),
                                                self.c0))
        stats = self.device.memory_stats() or {}
        gib = 2.0 ** 30
        print(f"== {self.name}: ok wall={time.perf_counter() - self.t0:.2f}s"
              f" compile={secs:.2f}s ({n} programs, {hits} from cache)"
              f" peak_hbm={stats.get('peak_bytes_in_use', 0) / gib:.2f}GiB"
              f" in_use={stats.get('bytes_in_use', 0) / gib:.2f}GiB"
              f" cabac_engine="
              f"{resolve_backend(DecodeOptions().backend)}", flush=True)
        return False


def _check_tokens(name, toks, vocab):
    if toks.shape != (N_REQUESTS, NEW_TOKENS):
        raise RuntimeError(f"{name}: tokens shape {toks.shape}")
    if toks.min() < 0 or toks.max() >= vocab:
        raise RuntimeError(f"{name}: token ids outside [0, {vocab})")
    print(f"   {name}: {toks.shape[0]} requests answered; row 0 starts "
          f"{toks[0, :8].tolist()}")


def _check_dispatch(name):
    from repro import kernels
    report = kernels.dispatch_report()
    for rec in report:
        print(f"   {name}: dispatch {rec['kind']}: {rec['op']} "
              f"{rec['requested'] or 'default'} -> {rec['impl']} "
              f"({rec['reason']})")
    fallbacks = [r for r in report if r["kind"] == "fallback"]
    if fallbacks:
        raise RuntimeError(f"{name}: {len(fallbacks)} kernel fallback(s)")
    kernels.clear_dispatch_report()


def _first_logits(cfg, params, prompts, *, want_custom_call=False,
                  mesh=None):
    """Last-position prefill logits (B, V) in f32 — the first sampled
    step — through the model's own ``prefill``; traced under the serving
    mesh's rules when the weights live on one (as a session does)."""
    import contextlib
    import jax
    from repro.distributed.sharding import SERVE_RULES, activation_sharding
    from repro.models.transformer import prefill
    fn = jax.jit(lambda p, t: prefill(p, cfg, tokens=t)[0])
    with (activation_sharding(mesh, SERVE_RULES) if mesh is not None
          else contextlib.nullcontext()):
        lowered = fn.lower(params, prompts)
    if want_custom_call and "tpu_custom_call" not in lowered.as_text():
        raise RuntimeError("prefill program holds no Pallas kernel")
    out = lowered.compile()(params, prompts)
    return np.asarray(out.astype(np.float32))


def _rel_err(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _agree(name, logits, ref, rtol=LOGIT_RTOL):
    err = _rel_err(logits, ref)
    same_top = float(np.mean(logits.argmax(-1) == ref.argmax(-1)))
    print(f"   {name}: first-step logits rel-L2 {err:.3e} (limit {rtol:g}),"
          f" max|diff| {np.abs(logits - ref).max():.3e},"
          f" argmax agreement {same_top:.2f}")
    if not np.isfinite(logits).all() or err > rtol:
        raise RuntimeError(f"{name}: logits disagree (rel-L2 {err:.3e})")


def _flat(tree):
    """``(name, leaf)`` pairs with q8 ``{"q8","q8s"}`` dicts kept whole."""
    import jax
    from repro.compression.tree import _path_key
    from repro.serve.quantized import is_q8
    return [(_path_key(p), leaf) for p, leaf in
            jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_q8)[0]]


def _compare_container_levels(blob, q8_host):
    """Host-packed serve-q8 records against the device-quantized q8 tree:
    levels may differ by one step where the two quantizers round a value
    near half a step differently; scales by rounding only.  Returns
    whether every level and scale is the same."""
    from repro.compression import iter_decompress
    from repro.core.codec import Q8Tensor
    n_diff = n_all = 0
    worst_level = worst_scale = 0.0
    for name, rec in iter_decompress(blob, dequantize=False):
        if not isinstance(rec, Q8Tensor):
            continue
        dev = q8_host.pop(name)
        d = np.abs(rec.levels.astype(np.int16) - dev["q8"].astype(np.int16))
        n_diff += int(np.count_nonzero(d))
        n_all += d.size
        worst_level = max(worst_level, float(d.max()))
        worst_scale = max(worst_scale, float(np.max(
            np.abs(rec.scale - dev["q8s"]) / np.abs(dev["q8s"]))))
    print(f"   container vs device q8 tree: {n_diff} of {n_all} levels "
          f"differ (max |diff| {worst_level:g}); scales max rel diff "
          f"{worst_scale:.3e}")
    if q8_host:
        raise RuntimeError(f"container lacks q8 records {sorted(q8_host)}")
    if worst_level > 1 or worst_scale > SCALE_RTOL:
        raise RuntimeError("host and device quantizers disagree beyond "
                           "rounding")
    return n_diff == 0 and worst_scale == 0


def run_one_chip(device, clock):
    import jax
    from repro import configs, kernels
    from repro.launch.serve import random_weights, serve_requests
    from repro.serve.quantized import is_q8
    from repro.serve.session import ServeConfig

    cfg = configs.get(ARCH)
    print(f"   model {cfg.name}: layers={cfg.num_layers} d_model="
          f"{cfg.d_model} heads={cfg.num_heads}x{cfg.head_dim} d_ff="
          f"{cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.param_dtype}")
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (N_REQUESTS, PROMPT_LEN)).astype(np.int32)
    slot_cfg = ServeConfig(slots=N_REQUESTS, max_len=PROMPT_LEN + NEW_TOKENS)
    pol = (kernels.KernelPolicy(strict=True)
           .override("dequant_matmul", "pallas")
           .override("dequant_matmul_grouped", "pallas"))
    cfg_q8 = cfg.replace(kernels=pol)
    cfg_ref = cfg.replace(kernels=pol.override("dequant_matmul", "ref")
                          .override("dequant_matmul_grouped", "ref"))

    def serve(c, weights, backend, scfg=slot_cfg):
        kernels.clear_dispatch_report()
        return serve_requests(c, weights, prompts, backend=backend,
                              serve_cfg=scfg, max_new_tokens=NEW_TOKENS)

    with Phase("params", clock, device):
        q8 = jax.block_until_ready(random_weights(cfg, "q8"))
        n_bytes = sum(x.nbytes for x in jax.tree.leaves(q8))
        print(f"   q8 tree: {len(jax.tree.leaves(q8))} leaves, "
              f"{n_bytes / 2**30:.2f} GiB")
        # kept on the host for the container phase, which packs the same
        # init with the host-side serve-q8 codec
        q8_host = {name: leaf for name, leaf in _flat(jax.device_get(q8))
                   if is_q8(leaf)}

    with Phase("q8", clock, device):
        toks_q8, sess = serve(cfg_q8, q8, "q8")
        _check_tokens("q8 pallas", toks_q8, cfg.vocab_size)
        _check_dispatch("q8 pallas")
        toks_ref, sess_ref = serve(cfg_ref, q8, "q8")
        _check_tokens("q8 ref", toks_ref, cfg.vocab_size)
        _check_dispatch("q8 ref")
        if np.array_equal(toks_q8, toks_ref):
            print("   q8 pallas vs ref: greedy tokens identical")
        else:
            print(f"   q8 pallas vs ref: {int((toks_q8 != toks_ref).sum())}"
                  f" of {toks_q8.size} tokens differ; checking logits")
            _agree("q8 pallas vs ref",
                   _first_logits(cfg_q8, sess.params, prompts),
                   _first_logits(cfg_ref, sess_ref.params, prompts))
        del sess, sess_ref
        gc.collect()

    with Phase("q8-paged", clock, device):
        paged_cfg = ServeConfig(slots=N_REQUESTS,
                                max_len=PROMPT_LEN + NEW_TOKENS,
                                kv_page_size=PAGE)
        toks_paged, sess = serve(cfg_q8, q8, "q8", paged_cfg)
        sess.close()
        _check_tokens("q8 paged", toks_paged, cfg.vocab_size)
        _check_dispatch("q8 paged")
        if not np.array_equal(toks_paged, toks_q8):
            raise RuntimeError(
                f"paged tokens differ from slot tokens at "
                f"{int((toks_paged != toks_q8).sum())} positions")
        print("   q8 paged vs slot: greedy tokens identical")
        del sess, q8
        gc.collect()

    with Phase("bf16", clock, device):
        toks_bf16, sess = serve(cfg, random_weights(cfg, "bf16"),
                                "bf16")
        _check_tokens("bf16", toks_bf16, cfg.vocab_size)
        _check_dispatch("bf16")
        flash = _first_logits(cfg, sess.params, prompts,
                              want_custom_call=True)
        scan_cfg = cfg.replace(
            kernels=cfg.kernels.override("flash_attention", "scan"))
        _agree("bf16 flash(pallas) vs scan", flash,
               _first_logits(scan_cfg, sess.params, prompts))
        del sess
        gc.collect()

    with Phase("container", clock, device):
        t0 = time.perf_counter()
        blob = random_weights(cfg, "container")
        print(f"   packed serve-q8 container on the host: "
              f"{len(blob) / 2**30:.2f} GiB in "
              f"{time.perf_counter() - t0:.2f}s")
        same_levels = _compare_container_levels(blob, q8_host)
        del q8_host
        toks_c, sess = serve(cfg_q8, blob, "container")
        del blob
        _check_tokens("container", toks_c, cfg.vocab_size)
        _check_dispatch("container")
        if np.array_equal(toks_c, toks_q8):
            print("   container vs q8: greedy tokens identical")
        elif same_levels:
            raise RuntimeError(
                f"container tokens differ from q8 at "
                f"{int((toks_c != toks_q8).sum())} positions, although "
                f"every level and scale is the same")
        else:
            print(f"   container vs q8: {int((toks_c != toks_q8).sum())} of "
                  f"{toks_c.size} tokens differ; checking logits")
            _agree("container vs q8",
                   _first_logits(cfg_q8, sess.params, prompts),
                   _first_logits(cfg_q8, random_weights(cfg, "q8"), prompts))
        del sess
        gc.collect()


def run_four_chips(device, clock):
    import jax
    from repro import configs
    from repro.checkpoint import sharded
    from repro.checkpoint.manager import CheckpointConfig
    from repro.compression.tree import _path_key
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import serve_requests
    from repro.models.transformer import init_params
    from repro.optim.adamw import AdamWConfig
    from repro.serve.backends import get_backend
    from repro.serve.session import ServeConfig
    from repro.train.loop import LoopConfig, train_loop

    cfg = configs.get(ARCH).replace(num_layers=TRAIN_LAYERS)
    print(f"   model {cfg.name} cut to {cfg.num_layers} layers at full "
          f"width (d_model={cfg.d_model}, d_ff={cfg.d_ff}, vocab="
          f"{cfg.vocab_size}); batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, "
          f"{TRAIN_STEPS} steps, f32 AdamW moments")
    loop = LoopConfig(total_steps=TRAIN_STEPS, batch=TRAIN_BATCH,
                      seq=TRAIN_SEQ, ckpt_every=10 ** 9)
    opt = AdamWConfig(lr=1e-3)

    with Phase("train-1", clock, device):
        one = train_loop(cfg, make_local_mesh(1, 1), loop, opt_cfg=opt)
        print(f"   one device: losses {one.losses}")
        gc.collect()

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckdir:
        with Phase("train-4", clock, device):
            mesh = make_local_mesh(data=4, model=1)
            four = train_loop(cfg, mesh, loop, opt_cfg=opt,
                              ckpt_cfg=CheckpointConfig(
                                  ckdir, sharded=True, shard_workers=8))
            print(f"   data=4 mesh: losses {four.losses}")
            for step, (a, b) in enumerate(zip(four.losses, one.losses)):
                if not (np.isfinite(a) and abs(a - b) <= LOSS_RTOL * abs(b)):
                    raise RuntimeError(
                        f"step {step}: loss {a} on data=4 vs {b} on one "
                        f"device (limit {LOSS_RTOL:g} relative)")
            print(f"   losses agree within {LOSS_RTOL:g} relative: max "
                  f"|diff| {max(abs(a - b) for a, b in zip(four.losses, one.losses)):.3e}")
            gc.collect()

        step_dir = str(Path(ckdir) / f"step_{TRAIN_STEPS:08d}")
        prompts = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (N_REQUESTS, PROMPT_LEN)).astype(np.int32)
        scfg = ServeConfig(slots=N_REQUESTS, max_len=PROMPT_LEN + NEW_TOKENS)

        with Phase("restore-4", clock, device):
            manifest = sharded.load_manifest(step_dir)
            print(f"   checkpoint: {len(manifest['files'])} shard files, "
                  f"save mesh {manifest['mesh']}")
            model_mesh = make_local_mesh(data=1, model=4)
            # the reference: a second, independent decode of every tensor
            # whole on the host (restore_flat), run on threads beside the
            # backend's own decode of the mesh slices
            with ThreadPoolExecutor(1) as pool:
                saved = pool.submit(sharded.restore_flat, step_dir,
                                    workers=len(manifest["tensors"]))
                toks_m, sess_m = serve_requests(
                    cfg, step_dir, prompts,
                    backend=get_backend("bf16", mesh=model_mesh),
                    serve_cfg=scfg, max_new_tokens=NEW_TOKENS)
                saved = saved.result()
            flat = jax.tree_util.tree_flatten_with_path(sess_m.params)[0]
            for path, leaf in flat:
                name = _path_key(path)
                spans = len(leaf.sharding.device_set)
                print(f"   {name}: {tuple(leaf.shape)} "
                      f"{type(leaf.sharding).__name__} "
                      f"{getattr(leaf.sharding, 'spec', '')} on {spans} "
                      f"device(s)")
                want = np.asarray(saved[name]).astype(leaf.dtype)
                if not np.array_equal(np.asarray(leaf), want):
                    raise RuntimeError(f"{name}: restored != saved")
                coded = manifest["tensors"][name]["encoding"] == "cabac_v3"
                if coded and spans != 4:
                    raise RuntimeError(
                        f"{name}: entropy-coded leaf restored onto {spans} "
                        f"device(s), not the 4-device mesh")
            print(f"   {len(flat)} restored tensors equal the saved ones")
            template = jax.eval_shape(
                lambda: init_params(cfg, jax.random.PRNGKey(0)))
            one_device = jax.device_put(jax.tree_util.tree_map_with_path(
                lambda p, t: np.asarray(saved[_path_key(p)], t.dtype),
                template), device)
            del saved
            toks_1, sess_1 = serve_requests(
                cfg, one_device, prompts, backend="bf16", serve_cfg=scfg,
                max_new_tokens=NEW_TOKENS)
            _agree("model=4 vs one-device session",
                   _first_logits(cfg, sess_m.params, prompts,
                                 mesh=model_mesh),
                   _first_logits(cfg, sess_1.params, prompts))
            print(f"   greedy tokens equal: {np.array_equal(toks_m, toks_1)}")
            del sess_m, sess_1, one_device
            gc.collect()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths")
    args = ap.parse_args()

    # strict rounding and the compile cache, as every entry point sets them
    # (before JAX starts its backend)
    from repro.launch.runtime import configure_runtime
    cache_dir = configure_runtime()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU device(s); JAX found "
              f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr)
        return 2

    clock = CompileClock()
    with Phase("device", clock, dev):
        print(f"   platform={dev.platform} device_kind={dev.device_kind} "
              f"count={len(devices)} jax={jax.__version__}")
        print(f"   compile cache: {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(dev, clock)
    else:
        run_one_chip(dev, clock)
    secs, n, hits = clock.snapshot()
    print(f"== total: wall={time.perf_counter() - t0:.2f}s compile="
          f"{secs:.2f}s ({n} programs, {hits} from cache)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
