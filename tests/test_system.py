"""End-to-end behaviour: training converges, checkpoints resume exactly,
serving from a DeepCABAC container matches raw-weight serving, FIM pipeline
(DC-v1) produces valid compression on a trained model."""

import numpy as np
import pytest

from repro.checkpoint.manager import (CheckpointConfig, CheckpointManager,
                                      flatten_tree, unflatten_like)
from repro.configs import get_smoke_config
from repro.core.deepcabac import compress_dc_v1, compress_dc_v2
from repro.core.fim import empirical_fisher_diag
from repro.data.pipeline import make_eval_batches
from repro.launch.mesh import make_local_mesh
from repro.models.transformer import train_loss
from repro.optim.adamw import AdamWConfig
from repro.serve.engine import ServeEngine
from repro.train.loop import LoopConfig, train_loop
from repro.train.steps import init_train_state


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    cfg = get_smoke_config("llama3-8b")
    mesh = make_local_mesh(1, 1)
    d = tmp_path_factory.mktemp("ckpt")
    loop = LoopConfig(total_steps=60, batch=8, seq=64, ckpt_every=30,
                      resume=False)
    res = train_loop(cfg, mesh, loop, opt_cfg=AdamWConfig(lr=2e-3),
                     ckpt_cfg=CheckpointConfig(str(d), params_mode="raw"))
    mgr = CheckpointManager(CheckpointConfig(str(d), params_mode="raw"))
    template = init_train_state(cfg, AdamWConfig(lr=2e-3))
    state, _ = mgr.restore(template)
    return cfg, state, res


def test_training_reduces_loss(trained):
    _, _, res = trained
    first = np.mean(res.losses[:5])
    last = np.mean(res.losses[-5:])
    assert last < first - 0.1, (first, last)


def test_serve_from_compressed_matches_raw(trained):
    cfg, state, _ = trained
    params = state["params"]
    flat = flatten_tree(params)
    res = compress_dc_v2(flat, delta=1e-4, lam=0.0)
    eng_raw = ServeEngine(cfg, params, max_len=96)
    eng_c = ServeEngine.from_compressed(cfg, res.blob, max_len=96)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
    out_raw = eng_raw.generate(prompts, steps=8)
    out_c = eng_c.generate(prompts, steps=8)
    # near-lossless quantization -> identical greedy tokens
    assert np.array_equal(out_raw, out_c)
    assert out_raw.shape == (4, 24)


def test_compression_accuracy_tradeoff(trained):
    """Coarser steps compress more; quality degrades monotonically-ish."""
    cfg, state, _ = trained
    flat = flatten_tree(state["params"])
    evals = make_eval_batches(cfg, 2, batch=8, seq=64)

    def nll(params_flat):
        p = unflatten_like(
            {k: np.asarray(v) for k, v in params_flat.items()},
            state["params"])
        return float(np.mean([train_loss(p, b, cfg) for b in evals]))

    fine = compress_dc_v2(flat, delta=1e-4, lam=0.0)
    coarse = compress_dc_v2(flat, delta=2e-2, lam=1e-4)
    assert len(coarse.blob) < len(fine.blob)
    assert nll(coarse.reconstructed()) >= nll(fine.reconstructed()) - 1e-3


def test_dc_v1_with_empirical_fisher(trained):
    cfg, state, _ = trained
    params = state["params"]
    batches = make_eval_batches(cfg, 2, batch=4, seq=32)
    fim = empirical_fisher_diag(
        lambda p, b: train_loss(p, b, cfg), params, batches)
    flat_p = flatten_tree(params)
    flat_f = flatten_tree(fim)
    sigma = {k: 1.0 / np.sqrt(np.asarray(v) + 1e-8)
             for k, v in flat_f.items()}
    res = compress_dc_v1(flat_p, sigma, s=64.0, lam=1e-4)
    assert res.report["bits_per_param"] < 32
    rec = res.reconstructed()
    assert set(rec) == set(flat_p)


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_compile_cache_location(tmp_path, monkeypatch, env_dir):
    """Entry points keep JAX's compile cache where
    $JAX_COMPILATION_CACHE_DIR says, else at the checkout's fixed
    .jax_cache — never a temp, PID or time-based path."""
    import jax
    from repro.launch import runtime
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(runtime.ENV_VAR, raising=False)
        want = str(runtime.CHECKOUT_CACHE)
    else:
        monkeypatch.setenv(runtime.ENV_VAR, str(tmp_path / env_dir))
        want = str(tmp_path / env_dir)
    try:
        assert runtime.configure_compile_cache() == want
        assert runtime.configure_compile_cache() == want   # stable
        if env_dir is None:
            assert jax.config.jax_compilation_cache_dir == want
            assert (runtime.CHECKOUT_CACHE.parent / "src" / "repro"
                    ).is_dir()
        else:   # JAX reads the variable itself; the code sets nothing
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_strict_rounding_reaches_xla(tmp_path):
    """An entry point's configure_runtime sets strict rounding before
    the backend starts, so every program the process compiles uses it."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    script = (
        "import os\n"
        "from repro.launch.runtime import STRICT_ROUNDING, configure_runtime\n"
        "import jax\n"
        "configure_runtime()\n"
        "assert os.environ['XLA_FLAGS'].split().count(STRICT_ROUNDING) == 1\n"
        "configure_runtime()   # idempotent\n"
        "assert os.environ['XLA_FLAGS'].split().count(STRICT_ROUNDING) == 1\n"
        "print(float(jax.jit(lambda x: x * 2)(1.5)))\n")
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(PYTHONPATH=src, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "3.0"


def test_strict_rounding_refuses_a_started_backend(monkeypatch):
    """XLA reads XLA_FLAGS once: setting the flag after the backend
    started would silently not apply, so it raises instead."""
    import jax
    from repro.launch import runtime
    jax.devices()
    monkeypatch.setenv("XLA_FLAGS", "")
    with pytest.raises(RuntimeError, match="before the first JAX"):
        runtime.set_strict_rounding()
    monkeypatch.setenv("XLA_FLAGS", runtime.STRICT_ROUNDING)
    runtime.set_strict_rounding()             # already set: nothing to do
