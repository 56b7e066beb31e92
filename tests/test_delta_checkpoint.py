"""Temporal delta ("P-frame") checkpoints: keyframe cadence, chain
restore bit-identity vs a direct step-locked encode (both CABAC
engines), elastic mesh restore of a chained step, chain-aware
retention / orphan protection, descriptive chain errors, and the live
weight swap into a running ServeSession."""

import json
import os
import shutil

import jax
import numpy as np
import pytest

from repro import compression
from repro.checkpoint import (CheckpointConfig, CheckpointManager,
                              DeltaBaseMissingError, delta)
from repro.checkpoint.delta import DeltaChainError
from repro.checkpoint import sharded
from repro.checkpoint.sharded import MeshSpec
from repro.configs import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.core.cabac_vec import resolve_backend
from repro.core.codec import DecodeOptions, QuantizedTensor
from repro.models.transformer import init_params
from repro.serve.backends import get_backend
from repro.serve.session import ServeConfig, ServeSession

# both entropy-coding engines must produce/consume identical chains;
# the C lanes kernel is optional per-platform
BACKENDS = ["numpy"] + (["c"] if resolve_backend("auto") == "c" else [])

# The smoke-model integration tests below decode full model containers;
# on the numpy lane engine that is ~100x slower than the C kernel and
# adds nothing (engine-level delta coverage is the backend-parametrized
# tests above, which force the numpy engine explicitly on small tensors).
skip_on_forced_numpy = pytest.mark.skipif(
    os.environ.get("REPRO_CABAC_BACKEND") == "numpy",
    reason="smoke-model decode is impractical on the forced numpy lane "
           "engine; delta coding on the numpy engine is covered by the "
           "backend-parametrized tests")


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"layer/kernel": rng.standard_normal((32, 16)).astype(np.float32),
            "layer/bias": rng.standard_normal(16).astype(np.float32)}


def _drift(flat, seed):
    """Multiplicative drift — the residual model one optimizer step away
    from the base produces (small relative change, zeros stay zero)."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, v in flat.items():
        v = np.asarray(v)
        if v.dtype.kind == "f":
            out[k] = (v * (1 + 1e-4 * rng.standard_normal(v.shape))
                      ).astype(v.dtype)
        else:
            out[k] = v
    return out


def _mgr(tmp_path, name="ckpt", **kw):
    kw.setdefault("codec", "deepcabac-delta")
    return CheckpointManager(CheckpointConfig(
        os.path.join(str(tmp_path), name), **kw))


def _meta(mgr, step):
    with open(os.path.join(mgr.cfg.directory, f"step_{step:08d}",
                           "meta.json")) as f:
        return json.load(f)


def _save_drifting(mgr, steps, seed=0):
    flat = _tree(seed)
    for step in steps:
        mgr.save({"params": dict(flat), "opt": {"count": np.int32(step)}},
                 step)
        flat = _drift(flat, seed + step)
    return flat


# -- keyframe cadence --------------------------------------------------------

def test_keyframe_cadence_and_meta(tmp_path):
    mgr = _mgr(tmp_path, keep=10, delta_every=3)
    _save_drifting(mgr, range(1, 7))
    kinds = [_meta(mgr, s)["kind"] for s in range(1, 7)]
    depths = [_meta(mgr, s)["chain_depth"] for s in range(1, 7)]
    assert kinds == ["keyframe", "delta", "delta",
                     "keyframe", "delta", "delta"]
    assert depths == [0, 1, 2, 0, 1, 2]
    assert [_meta(mgr, s).get("base_step") for s in (2, 3, 5)] == [1, 2, 4]
    # P-frames of a drifting model must be much smaller than I-frames
    kf = _meta(mgr, 1)["params_compressed_bytes"]
    for s in (2, 3, 5, 6):
        assert _meta(mgr, s)["params_compressed_bytes"] < 0.5 * kf


def test_delta_every_zero_keeps_every_save_a_keyframe(tmp_path):
    mgr = _mgr(tmp_path, keep=4, delta_every=0)
    _save_drifting(mgr, (1, 2))
    for s in (1, 2):
        assert delta.base_step_of(
            os.path.join(mgr.cfg.directory, f"step_{s:08d}")) is None


# -- chain restore bit-identity ----------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_chain_restore_bit_identical_to_direct_encode(tmp_path, backend):
    """base + k chained P-frames == one direct step-locked encode of the
    last frame, in integer level space (zero drift across the chain)."""
    mgr = _mgr(tmp_path, keep=10, delta_every=4)
    _save_drifting(mgr, range(1, 4))

    codec = mgr._codec()
    frames = [_tree(0)]
    for step in (1, 2):
        frames.append(_drift(frames[-1], step))
    direct = codec.quantize_entries(frames[0])
    for f in frames[1:]:
        direct = codec.quantize_like(f, direct)

    got = delta.restore_levels(mgr.cfg.directory, 3,
                               opts=DecodeOptions(backend=backend))
    assert sorted(got) == sorted(direct)
    for k in direct:
        a, b = got[k], direct[k]
        if isinstance(b, QuantizedTensor):
            assert isinstance(a, QuantizedTensor), k
            assert a.step == b.step, k
            assert np.array_equal(a.levels, b.levels), k
        else:
            assert np.array_equal(a, np.asarray(b)), k


@pytest.mark.parametrize("backend", BACKENDS)
def test_manager_restore_matches_flat_chain_restore(tmp_path, backend):
    mgr = _mgr(tmp_path, keep=10, delta_every=3)
    _save_drifting(mgr, range(1, 6))
    state = {"params": _tree(0), "opt": {"count": np.int32(0)}}
    restored, meta = mgr.restore(state)
    assert meta["step"] == 5
    flat = delta.restore_flat_delta(mgr.cfg.directory, 5,
                                    opts=DecodeOptions(backend=backend))
    for k, v in flat.items():
        assert np.array_equal(v, np.asarray(restored["params"][k])), k


def test_cold_manager_resumes_chain_without_cache(tmp_path):
    """A restarted manager (empty base cache) must keep writing P-frames
    by rebuilding the base levels from disk — and identically so."""
    mgr = _mgr(tmp_path, keep=10, delta_every=4)
    flat = _save_drifting(mgr, range(1, 3))
    mgr2 = _mgr(tmp_path, keep=10, delta_every=4)
    mgr2.save({"params": flat, "opt": {"count": np.int32(3)}}, 3)
    m = _meta(mgr2, 3)
    assert m["kind"] == "delta"
    assert m["base_step"] == 2 and m["chain_depth"] == 2
    # and the chain still reconstructs
    chain = delta.resolve_chain(mgr2.cfg.directory, 3)
    assert [c["kind"] for c in chain] == ["keyframe", "delta", "delta"]
    delta.restore_levels(mgr2.cfg.directory, 3)


# -- retention / orphan protection -------------------------------------------

def test_retention_never_orphans_a_live_chain(tmp_path):
    mgr = _mgr(tmp_path, keep=2, delta_every=4)
    flat = _save_drifting(mgr, range(1, 5))
    # keep=2 -> {3, 4}, but both are P-frames chained to 1: everything
    # up the chain must survive GC
    assert mgr.steps() == [1, 2, 3, 4]
    delta.restore_flat_delta(mgr.cfg.directory, 4)
    # once the live window re-roots on the step-5 keyframe, the old
    # chain is collectable
    mgr.save({"params": flat, "opt": {"count": np.int32(5)}}, 5)
    flat = _drift(flat, 5)
    mgr.save({"params": flat, "opt": {"count": np.int32(6)}}, 6)
    assert _meta(mgr, 5)["kind"] == "keyframe"
    assert mgr.steps() == [5, 6]


def test_missing_base_raises_descriptive_error(tmp_path):
    mgr = _mgr(tmp_path, keep=10, delta_every=4)
    _save_drifting(mgr, range(1, 4))
    shutil.rmtree(os.path.join(mgr.cfg.directory, "step_00000001"))
    with pytest.raises(DeltaBaseMissingError, match="retention"):
        delta.restore_flat_delta(mgr.cfg.directory, 3)
    # and FileNotFoundError stays the catchable base class
    with pytest.raises(FileNotFoundError):
        delta.resolve_chain(mgr.cfg.directory, 3)


def test_rewritten_base_raises_chain_error(tmp_path):
    mgr = _mgr(tmp_path, keep=10, delta_every=4)
    _save_drifting(mgr, range(1, 3))
    base_payload = os.path.join(mgr.cfg.directory, "step_00000001",
                                "params.dcbc")
    with open(base_payload, "ab") as f:
        f.write(b"\x00")
    with pytest.raises(DeltaChainError, match="rewritten"):
        delta.resolve_chain(mgr.cfg.directory, 2)


def test_sharded_restore_helpers_reject_delta_manifests(tmp_path):
    mgr = _mgr(tmp_path, keep=10, delta_every=4)
    _save_drifting(mgr, range(1, 3))
    d = os.path.join(mgr.cfg.directory, "step_00000002")
    mesh = MeshSpec.from_any({"data": 1})
    for call in (lambda: sharded.restore_flat(d),
                 lambda: sharded.restore_on_mesh(d, mesh),
                 lambda: sharded.restore_local_slices(d, mesh, [0])):
        with pytest.raises(ValueError, match="P-frame"):
            call()


# -- sharded keyframe + mesh restore of a chained step -----------------------

def _model_state(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    return {"params": params, "opt": {"count": np.int32(0)}}


@skip_on_forced_numpy
def test_delta_chain_restores_across_mesh_reshape(tmp_path):
    """Keyframe written sharded over a 2-way mesh, P-frame on top; the
    chain must restore onto a different (1x1) jax mesh bit-identically
    to the host-flat chain restore."""
    cfg = get_smoke_config("llama3-8b")
    state = _model_state(cfg)
    mgr = _mgr(tmp_path, keep=4, delta_every=4, sharded=True,
               shard_workers=2)
    mgr.save(state, 1, mesh=MeshSpec(("data", "model"), (2, 1)))
    flat = dict(compression.flatten_tree(jax.device_get(state["params"])))
    pert = _drift(flat, 1)
    state2 = {"params": compression.unflatten_like(pert, state["params"]),
              "opt": {"count": np.int32(1)}}
    mgr.save(state2, 2)
    assert _meta(mgr, 2)["kind"] == "delta"

    ref = delta.restore_flat_delta(mgr.cfg.directory, 2)
    mesh = make_local_mesh(1, 1)
    on_mesh = delta.restore_on_mesh_delta(mgr.cfg.directory, 2, mesh)
    assert sorted(on_mesh) == sorted(ref)
    for k, arr in on_mesh.items():
        assert isinstance(arr, jax.Array), k
        np.testing.assert_array_equal(np.asarray(arr), ref[k], err_msg=k)

    # the manager's own restore resolves the chain too
    restored, meta = mgr.restore(state)
    rflat = dict(compression.flatten_tree(jax.device_get(
        restored["params"])))
    for k, v in ref.items():
        assert np.array_equal(v, np.asarray(rflat[k])), k


# -- live weight swap into serving -------------------------------------------

@skip_on_forced_numpy
def test_swap_weights_bitwise_equals_cold_start_with_inflight(tmp_path):
    cfg = get_smoke_config("llama3-8b")
    state = _model_state(cfg)
    mgr = _mgr(tmp_path, keep=4, delta_every=4)
    mgr.save(state, 1)
    flat = dict(compression.flatten_tree(jax.device_get(state["params"])))
    pert = _drift(flat, 7)
    mgr.save({"params": compression.unflatten_like(pert, state["params"]),
              "opt": {"count": np.int32(1)}}, 2)
    kf_dir = os.path.join(mgr.cfg.directory, "step_00000001")
    delta_dir = os.path.join(mgr.cfg.directory, "step_00000002")
    with open(os.path.join(kf_dir, "params.dcbc"), "rb") as f:
        kf_blob = f.read()

    backend = get_backend("container", track_levels=True)
    session = ServeSession(cfg, kf_blob, backend=backend,
                           serve_cfg=ServeConfig(slots=2, max_len=32))
    h = session.submit(np.arange(5, dtype=np.int32), max_new_tokens=8)
    session.step()
    session.step()
    pre_swap = list(h.tokens)
    n = session.swap_weights(delta_dir)
    assert n > 0
    session.run()
    assert h.done
    assert list(h.tokens)[:len(pre_swap)] == pre_swap

    # swapped-in weights must be bitwise what a cold start from the
    # direct step-locked encode of the new frame would load
    codec = mgr._codec()
    base_entries = codec.compress(flat).quantized
    ref_blob = codec.compress_entries(
        codec.quantize_like(pert, base_entries)).blob
    cold = ServeSession(cfg, ref_blob, backend="container",
                        serve_cfg=ServeConfig(slots=2, max_len=32))
    fa = compression.flatten_tree(session.params)
    fb = compression.flatten_tree(cold.params)
    assert sorted(fa) == sorted(fb)
    for k in fa:
        a, b = np.asarray(fa[k]), np.asarray(fb[k])
        assert a.dtype == b.dtype and np.array_equal(a, b), k


@skip_on_forced_numpy
def test_swap_weights_error_paths(tmp_path):
    cfg = get_smoke_config("llama3-8b")
    state = _model_state(cfg)
    mgr = _mgr(tmp_path, keep=4, delta_every=4)
    mgr.save(state, 1)
    flat = dict(compression.flatten_tree(jax.device_get(state["params"])))
    mgr.save({"params": compression.unflatten_like(_drift(flat, 3),
                                                   state["params"]),
              "opt": {"count": np.int32(1)}}, 2)
    kf_dir = os.path.join(mgr.cfg.directory, "step_00000001")
    delta_dir = os.path.join(mgr.cfg.directory, "step_00000002")

    # a backend that never tracked levels cannot patch in residuals
    with pytest.raises(RuntimeError, match="track_levels"):
        get_backend("container").apply_delta(cfg, delta_dir)
    # a keyframe step is not a delta
    backend = get_backend("container", track_levels=True)
    with open(os.path.join(kf_dir, "params.dcbc"), "rb") as f:
        backend.load(cfg, f.read())
    with pytest.raises(ValueError, match="not a delta"):
        backend.apply_delta(cfg, kf_dir)
