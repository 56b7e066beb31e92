"""Serving entry point: request-level continuous batching over a
pluggable weight backend, optionally from a DeepCABAC container.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --ckpt /tmp/model.dcbc --backend container --batch 4 \
        --prompt-len 16 --steps 32

``--backend``: ``bf16`` (full-precision weights), ``q8`` (in-memory int8
fixed-point matmul weights), ``container`` (stream-decode the DCBC blob;
serve-q8 records stay int8).  Without ``--ckpt`` the weights are random,
made by jitted programs on the default device (:func:`random_weights`);
the container backend packs that tree with the serve-q8 codec
in-process so the streaming load path still runs.

:func:`random_weights` and :func:`serve_requests` are the serving path
``chip_smoke.py`` drives as well — there is no second one.  ``main``
first calls :func:`~repro.launch.runtime.configure_runtime` (compile
cache, strict bf16 rounding), as every entry point does.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from .. import kernels
from .. import configs
from ..configs import ARCH_IDS
from ..models.transformer import init_params
from ..serve.backends import available_backends
from ..serve.quantized import quantize_tree_q8
from ..serve.session import ServeConfig, ServeSession
from .runtime import configure_runtime


def random_weights(cfg, backend: str):
    """Random-init weight source for ``backend`` (seed 0), built on the
    default device by one jitted program so no eager f32 transient (or,
    for q8, the full-precision tree) ever sits in device memory:

    ``bf16``       the ``init_params`` tree;
    ``q8``         ``quantize_tree_q8(init_params(...))`` — the q8 backend
                   loads it as-is (the tree pass is idempotent);
    ``container``  the bf16 tree pulled to the host and packed by the
                   ``serve-q8`` codec (bytes), as users' containers are.
    """
    key = jax.random.PRNGKey(0)
    init = jax.jit(lambda k: init_params(cfg, k))
    if backend == "bf16":
        return init(key)
    if backend == "q8":
        return jax.jit(lambda k: quantize_tree_q8(init_params(cfg, k)))(key)
    if backend == "container":
        from .. import compression
        return compression.get("serve-q8").compress(
            jax.device_get(init(key))).blob
    raise ValueError(f"no random init for backend {backend!r}")


def serve_requests(cfg, weights, prompts, *, backend: str,
                   serve_cfg: ServeConfig, max_new_tokens: int,
                   temperature: float = 0.0):
    """Load ``weights`` through ``backend`` into a :class:`ServeSession`,
    submit one request per prompt row and run them to completion.

    Returns ``(tokens (n, max_new_tokens) int32, session)``; the session
    keeps the loaded weights and caches alive until the caller drops it."""
    session = ServeSession(cfg, weights, backend=backend,
                           serve_cfg=serve_cfg)
    handles = [session.submit(p, max_new_tokens=max_new_tokens,
                              temperature=temperature) for p in prompts]
    session.run()
    return np.stack([h.result() for h in handles]), session


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None,
                    help="DeepCABAC container (.dcbc); random init if unset")
    ap.add_argument("--backend", choices=available_backends(),
                    default="bf16", help="weight backend (see serve/backends)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--slots", type=int, default=0,
                    help="KV slots (0 = one per request)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kernel-impl", action="append", default=[],
                    metavar="OP=IMPL",
                    help="pin a kernel impl (repeatable), e.g. "
                         "flash_attention=pallas dequant_matmul=ref")
    ap.add_argument("--strict-kernels", action="store_true",
                    help="a pinned impl that cannot run raises instead of "
                         "falling back (see kernels.dispatch_report)")
    ap.add_argument("--no-tuning-cache", action="store_true",
                    help="ignore the persistent kernel tuning cache")
    args = ap.parse_args()

    configure_runtime()
    cfg = configs.get(args.arch, smoke=args.smoke)
    pol = cfg.kernels
    for pin in args.kernel_impl:
        op, _, impl = pin.partition("=")
        if op not in kernels.available_ops():
            ap.error(f"--kernel-impl: unknown op {op!r}; "
                     f"available: {kernels.available_ops()}")
        if impl not in kernels.spec(op).impls:
            ap.error(f"--kernel-impl: unknown impl {impl!r} for {op}; "
                     f"available: {sorted(kernels.spec(op).impls)}")
        pol = pol.override(op, impl)
    pol = dataclasses.replace(pol, strict=args.strict_kernels,
                              use_tuning_cache=not args.no_tuning_cache)
    cfg = cfg.replace(kernels=pol)
    if args.ckpt:
        with open(args.ckpt, "rb") as f:
            weights = f.read()
    else:
        weights = random_weights(cfg, args.backend)
        if args.backend == "container":
            print(f"packed serve-q8 container in-process: "
                  f"{len(weights) / 2**20:.1f} MiB")

    scfg = ServeConfig(slots=args.slots or args.batch,
                       max_len=args.prompt_len + args.steps)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)
    out, _ = serve_requests(cfg, weights, prompts, backend=args.backend,
                            serve_cfg=scfg, max_new_tokens=args.steps,
                            temperature=args.temperature)
    print(f"backend={args.backend} slots={scfg.slots}: generated "
          f"{out.shape} tokens; first row tail: "
          f"{out[0, -min(16, out.shape[1]):].tolist()}")
    for rec in kernels.dispatch_report():
        print(f"kernel {rec['kind']}: {rec['op']}: "
              f"{rec['requested'] or 'default'} -> {rec['impl']} "
              f"({rec['reason']})")


if __name__ == "__main__":
    main()
