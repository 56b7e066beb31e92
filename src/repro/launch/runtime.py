"""Process set-up shared by every entry point.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) call :func:`configure_runtime` before their first
JAX computation; importing the library never touches either setting.

Compile cache: ``$JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads
it itself and this module sets nothing.  Otherwise the cache sits at a
fixed directory inside the checkout (``<repo>/.jax_cache``, git-ignored).
The path is part of the cache key, so it never depends on a temp dir, a
PID or the time: a second run on the same checkout reads back what the
first wrote.

Strict rounding: by default XLA may keep bf16 values in f32 inside a
fusion, and which fusions it forms depends on shapes.  A batched B=4
admission prefill and four B=1 prefills then round differently (on TPU
v5e the K/V caches differ from layer 1 on and greedy tokens drift), so
serving output would depend on how requests happened to be batched.
``--xla_allow_excess_precision=false`` makes every program round at its
declared dtypes.  XLA reads ``XLA_FLAGS`` once, when its backend starts,
so the flag is only set before that.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax._src import xla_bridge

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"
STRICT_ROUNDING = "--xla_allow_excess_precision=false"


def configure_compile_cache() -> str:
    """Point the persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or,
    when unset, at the checkout's ``.jax_cache``.  Returns the directory
    in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)


def set_strict_rounding() -> None:
    """Append :data:`STRICT_ROUNDING` to ``XLA_FLAGS``.  Raises when a JAX
    backend already started without it (the flag would not apply)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if STRICT_ROUNDING in flags.split():
        return
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "set_strict_rounding() must run before the first JAX "
            "computation: XLA reads XLA_FLAGS once, when its backend starts")
    os.environ["XLA_FLAGS"] = f"{flags} {STRICT_ROUNDING}".strip()


def configure_runtime() -> str:
    """Strict rounding plus the compile cache; returns the cache
    directory."""
    set_strict_rounding()
    return configure_compile_cache()
