"""Checkpoint manager: round-trip fidelity, compression, retention,
atomicity, elastic (resharded) restore."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import (CheckpointConfig, CheckpointManager,
                                      flatten_tree, unflatten_like)
from repro.configs import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.distributed.sharding import build_param_specs, named_shardings
from repro.models.transformer import init_params
from repro.optim.adamw import AdamWConfig
from repro.train.steps import init_train_state


def _state(seed=0, quant=False):
    cfg = get_smoke_config("llama3-8b")
    return cfg, init_train_state(cfg, AdamWConfig(quantized_moments=quant),
                                 seed=seed)


def test_roundtrip_raw(tmp_path):
    cfg, state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             params_mode="raw"))
    mgr.save(state, 7)
    restored, meta = mgr.restore(state)
    assert meta["step"] == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_roundtrip_cabac_bounded_error(tmp_path):
    cfg, state = _state()
    delta_rel = 1e-3
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             params_mode="cabac",
                                             delta_rel=delta_rel))
    mgr.save(state, 1)
    restored, meta = mgr.restore(state)
    assert meta["params_compressed_bytes"] < meta["params_raw_bytes"]
    for (pa, a), (pb, b) in zip(
            flatten_tree(state["params"]).items(),
            flatten_tree(restored["params"]).items()):
        if a.ndim >= 2:
            step = delta_rel * a.astype(np.float64).std()
            # step/2 from rounding + f32 dequantization rounding slack
            assert np.max(np.abs(a.astype(np.float64)
                                 - b.astype(np.float64))) <= \
                step / 2 * (1 + 1e-3) + 1e-7
        else:
            np.testing.assert_array_equal(a, b)
    # optimizer state is exact
    np.testing.assert_array_equal(
        np.asarray(state["step"]), np.asarray(restored["step"]))


def test_roundtrip_v3_codec_batched_restore(tmp_path):
    """codec="deepcabac-v3" saves a version-3 container and restore's
    batched lane decode must agree bit-for-bit with decoding the same blob
    through the serial scalar path."""
    from repro.compression.codec import DecodeOptions, decompress
    from repro.core.container import VERSION_V3, ContainerReader

    cfg, state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             codec="deepcabac-v3",
                                             delta_rel=1e-3))
    mgr.save(state, 3)
    with open(os.path.join(str(tmp_path), "step_00000003",
                           "params.dcbc"), "rb") as f:
        blob = f.read()
    assert ContainerReader(blob).version == VERSION_V3
    restored, meta = mgr.restore(state)
    assert meta["codec"] == "deepcabac-v3"
    serial = decompress(blob, like=state["params"],
                        opts=DecodeOptions(backend="scalar"))
    for a, b in zip(jax.tree.leaves(serial),
                    jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retention_and_latest(tmp_path):
    cfg, state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path), keep=2,
                                             params_mode="raw"))
    for s in [1, 2, 3, 4]:
        mgr.save(state, s)
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_no_tmp_dirs_left(tmp_path):
    cfg, state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             params_mode="raw"))
    mgr.save(state, 5)
    assert not [d for d in os.listdir(tmp_path) if ".tmp" in d]


def test_async_save(tmp_path):
    cfg, state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path), params_mode="raw",
                                             async_save=True))
    mgr.save(state, 9, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 9


def test_elastic_resharded_restore(tmp_path):
    """Save unsharded, restore onto an explicit 2-device mesh sharding."""
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    cfg, state = _state()
    mgr = CheckpointManager(CheckpointConfig(str(tmp_path),
                                             params_mode="raw"))
    mgr.save(state, 3)
    mesh = make_local_mesh(1, 1)
    shardings = {
        "params": named_shardings(
            build_param_specs(state["params"], mesh), mesh),
        "opt": {"count": jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()),
                "moments": named_shardings(build_param_specs(
                    state["opt"]["moments"], mesh), mesh)},
        "ef": None,
        "step": jax.sharding.NamedSharding(mesh,
                                           jax.sharding.PartitionSpec()),
    }
    restored, _ = mgr.restore(state, shardings=shardings)
    chex_leaf = jax.tree.leaves(restored["params"])[0]
    assert chex_leaf.sharding.mesh.shape == {"data": 1, "model": 1}


def test_flatten_unflatten_identity():
    cfg, state = _state()
    flat = flatten_tree(state["params"])
    back = unflatten_like(flat, state["params"])
    for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
