"""Sharded checkpoints: shard-grid math, manifest integrity, byte-range
record reads, elastic N->M restore (bit-identical to the monolithic
path), sub-mesh decode accounting, and backend cold-start from a
manifest."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.checkpoint import sharded
from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
from repro.checkpoint.sharded import MeshSpec
from repro.compression.tree import flatten_tree
from repro.configs import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.steps import init_train_state

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _state(seed=0):
    cfg = get_smoke_config("llama3-8b")
    return cfg, init_train_state(cfg, AdamWConfig(), seed=seed)


def _save_both(tmp_path, state, codec="deepcabac-v3", save_shards=4):
    mono = CheckpointManager(CheckpointConfig(
        os.path.join(str(tmp_path), "mono"), codec=codec, delta_rel=1e-3))
    mono.save(state, 1)
    shard = CheckpointManager(CheckpointConfig(
        os.path.join(str(tmp_path), "shard"), codec=codec, delta_rel=1e-3,
        sharded=True, shard_workers=2))
    shard.save(state, 1, mesh=MeshSpec(("data", "model"), (save_shards, 1)))
    return mono, shard


def _step_dir(mgr, step=1):
    return os.path.join(mgr.cfg.directory, f"step_{step:08d}")


# -- shard-grid math ---------------------------------------------------------

def test_mesh_spec_from_any():
    ms = MeshSpec.from_any({"data": 4, "model": 2})
    assert ms.axis_names == ("data", "model")
    assert ms.size == 8
    assert MeshSpec.from_any(ms) is ms
    assert MeshSpec.from_any(None).size == 1


def test_shard_grid_and_boxes():
    mesh = MeshSpec(("data", "model"), (4, 2))
    axes = [("data",), ()]
    assert sharded.shard_grid(axes, mesh) == (4, 1)
    starts, stops = sharded.shard_box((8, 6), (4, 1), (2, 0))
    assert starts == (4, 0) and stops == (6, 6)
    # tuple-axis dim: 8-way shard over (data, model), data major
    axes = [("data", "model"), ()]
    assert sharded.shard_grid(axes, mesh) == (8, 1)
    starts, stops = sharded.shard_box((16, 4), (8, 1), (5, 0))
    assert starts == (10, 0) and stops == (12, 4)


def test_owner_device_dedupes_replicas():
    mesh = MeshSpec(("data", "model"), (2, 2))
    axes = [("data",), ()]          # replicated over model
    owners = {sharded._owner_device(axes, mesh, (i, 0)) for i in range(2)}
    # owners are the model=0 replicas: flat ids 0 and 2
    assert owners == {0, 2}


def test_device_box_covers_mesh():
    mesh = MeshSpec(("data", "model"), (2, 2))
    axes = [("data",), ("model",)]
    seen = set()
    for dev in range(mesh.size):
        starts, stops = sharded.device_box((8, 8), axes, mesh, dev)
        seen.add((starts, stops))
    assert len(seen) == 4           # 2x2 distinct boxes
    assert sum((b[0] - a[0]) * (b[1] - a[1])
               for (a, b) in seen) == 64


# -- save/restore round trips ------------------------------------------------

def test_sharded_restore_bit_identical_to_monolithic(tmp_path):
    cfg, state = _state()
    mono, shard = _save_both(tmp_path, state)
    r_mono, _ = mono.restore(state)
    r_shard, meta = shard.restore(state)
    assert meta["sharded"] is True
    assert meta["shard_files"] >= 2
    for a, b in zip(jax.tree.leaves(r_mono["params"]),
                    jax.tree.leaves(r_shard["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # non-param state is exact
    np.testing.assert_array_equal(np.asarray(state["step"]),
                                  np.asarray(r_shard["step"]))


def test_restore_on_mesh_in_process(tmp_path):
    """mesh= restore returns mesh-sharded jax Arrays, bit-identical."""
    cfg, state = _state()
    mono, shard = _save_both(tmp_path, state, save_shards=2)
    mesh = make_local_mesh(1, 1)
    r_mesh, _ = shard.restore(state, mesh=mesh)
    r_mono, _ = mono.restore(state)
    leaves = jax.tree.leaves(r_mesh["params"])
    assert all(isinstance(x, jax.Array) for x in leaves)
    assert leaves[0].sharding.mesh.shape == {"data": 1, "model": 1}
    for a, b in zip(jax.tree.leaves(r_mono["params"]), leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_manifest_schema_and_byte_ranges(tmp_path):
    from repro.core.container import read_record_at
    cfg, state = _state()
    _, shard = _save_both(tmp_path, state)
    d = _step_dir(shard)
    manifest = sharded.load_manifest(d)
    assert manifest["format"] == "dcbc-manifest"
    assert manifest["mesh"] == {"axes": ["data", "model"], "shape": [4, 1]}
    sharded.verify_files(d, manifest)      # content hashes hold
    n_cabac = 0
    for name, tinfo in manifest["tensors"].items():
        covered = 0
        for sh in tinfo["shards"]:
            # every manifest byte-range must parse standalone
            with open(os.path.join(d, sh["file"]), "rb") as f:
                f.seek(sh["offset"])
                buf = f.read(sh["length"])
            hdr, payload = read_record_at(buf)
            assert hdr.name == sh["record"]
            assert tuple(hdr.shape) == tuple(
                b - a for a, b in zip(sh["start"], sh["stop"]))
            covered += int(np.prod(hdr.shape)) if hdr.shape else 1
            if tinfo["encoding"] == "cabac_v3":
                assert sh["chunk_counts"] == list(hdr.chunk_counts)
                n_cabac += 1
        assert covered == int(np.prod(tinfo["shape"]))
    assert n_cabac > 4                      # tensors actually sharded


def test_submesh_restore_decodes_strictly_fewer_values(tmp_path):
    cfg, state = _state()
    _, shard = _save_both(tmp_path, state)
    d = _step_dir(shard)
    manifest = sharded.load_manifest(d)
    total = sharded.manifest_total_values(manifest)
    stats = sharded.RestoreStats()
    out = sharded.restore_local_slices(
        d, MeshSpec(("data", "model"), (2, 1)), [0], stats=stats)
    assert stats.decoded_values < total
    # ... and the decoded slices are the right slices
    flat = flatten_tree(jax.device_get(state["params"]))
    full = sharded.restore_flat(d)
    for name, by_dev in out.items():
        (arr,) = by_dev.values()
        ref = full[name]
        box = tuple(slice(0, s) for s in arr.shape)
        np.testing.assert_array_equal(arr, ref[box])
        assert name in flat


def test_truncated_shard_file_errors(tmp_path):
    cfg, state = _state()
    _, shard = _save_both(tmp_path, state)
    d = _step_dir(shard)
    fname = sorted(f for f in os.listdir(d) if f.endswith(".dcbc"))[0]
    path = os.path.join(d, fname)
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[:len(data) // 2])
    with pytest.raises(ValueError, match="truncated"):
        sharded.restore_flat(d)
    # hash verification also catches it
    with pytest.raises(ValueError, match="hash mismatch"):
        sharded.verify_files(d, sharded.load_manifest(d))


def test_restore_mesh_on_monolithic_checkpoint_errors(tmp_path):
    """mesh= must not be a silent no-op against a monolithic save."""
    cfg, state = _state()
    mono = CheckpointManager(CheckpointConfig(
        str(tmp_path), codec="deepcabac-v3", delta_rel=1e-3))
    mono.save(state, 1)
    mesh = make_local_mesh(1, 1)
    with pytest.raises(ValueError, match="sharded checkpoint"):
        mono.restore(state, mesh=mesh)


def test_manifest_version_gate(tmp_path):
    cfg, state = _state()
    _, shard = _save_both(tmp_path, state)
    d = _step_dir(shard)
    mpath = os.path.join(d, sharded.MANIFEST_NAME)
    manifest = json.load(open(mpath))
    manifest["manifest_version"] = sharded.MANIFEST_MAX_VERSION + 1
    json.dump(manifest, open(mpath, "w"))
    with pytest.raises(ValueError, match="manifest version"):
        sharded.load_manifest(d)


# -- serve backend cold start from a manifest --------------------------------

@pytest.mark.parametrize("backend", ["bf16", "container", "q8"])
def test_backend_cold_start_from_manifest(tmp_path, backend):
    from repro import compression
    from repro.serve.backends import get_backend

    cfg = get_smoke_config("llama3-8b")
    from repro.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))
    codec = compression.get("deepcabac-v3", delta_rel=1e-3)
    blob = codec.compress(params).blob
    payloads, manifest = sharded.write_sharded(
        codec.quantize_entries(flatten_tree(params)),
        MeshSpec(("data", "model"), (2, 1)), codec_name=codec.name)
    d = str(tmp_path)
    for fname, data in payloads.items():
        with open(os.path.join(d, fname), "wb") as f:
            f.write(data)
    with open(os.path.join(d, sharded.MANIFEST_NAME), "w") as f:
        json.dump(manifest, f)

    from_blob = get_backend(backend).load(cfg, blob)
    from_manifest = get_backend(backend).load(cfg, d)
    la, lb = jax.tree.leaves(from_blob), jax.tree.leaves(from_manifest)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_backend_manifest_on_mesh(tmp_path):
    from repro import compression
    from repro.serve.backends import Bf16Backend

    cfg = get_smoke_config("llama3-8b")
    from repro.models.transformer import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))
    codec = compression.get("deepcabac-v3", delta_rel=1e-3)
    payloads, manifest = sharded.write_sharded(
        codec.quantize_entries(flatten_tree(params)),
        MeshSpec(("data", "model"), (2, 1)), codec_name=codec.name)
    d = str(tmp_path)
    for fname, data in payloads.items():
        with open(os.path.join(d, fname), "wb") as f:
            f.write(data)
    with open(os.path.join(d, sharded.MANIFEST_NAME), "w") as f:
        json.dump(manifest, f)

    mesh = make_local_mesh(1, 1)
    tree = Bf16Backend(mesh=mesh).load(cfg, d)
    leaves = jax.tree.leaves(tree)
    assert all(isinstance(x, jax.Array) for x in leaves)
    assert leaves[0].sharding.mesh.shape == {"data": 1, "model": 1}
    ref = Bf16Backend().load(cfg, codec.compress(params).blob)
    for a, b in zip(jax.tree.leaves(ref), leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- N -> M elastic resharding (real multi-device meshes, subprocess) --------

ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, tempfile
import jax
import numpy as np

from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
from repro.checkpoint.sharded import MeshSpec
from repro.configs import get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.steps import init_train_state

cfg = get_smoke_config("llama3-8b")
state = init_train_state(cfg, AdamWConfig(), seed=0)
with tempfile.TemporaryDirectory() as td:
    mono = CheckpointManager(CheckpointConfig(td + "/mono",
                                              codec="deepcabac-v3"))
    mono.save(state, 1)
    ref, _ = mono.restore(state)
    mgr = CheckpointManager(CheckpointConfig(td + "/shard",
                                             codec="deepcabac-v3",
                                             sharded=True, shard_workers=2))
    # save on a simulated 4-device mesh ...
    mgr.save(state, 1, mesh=MeshSpec(("data", "model"), (4, 1)))
    # ... restore on 1-, 2- and 8-device meshes
    for shape in [(1, 1), (2, 1), (4, 2)]:
        mesh = make_local_mesh(*shape)
        restored, _ = mgr.restore(state, mesh=mesh)
        leaves = jax.tree.leaves(restored["params"])
        assert all(isinstance(x, jax.Array) for x in leaves)
        assert leaves[0].sharding.mesh.shape == dict(
            zip(("data", "model"), shape)), leaves[0].sharding
        for a, b in zip(jax.tree.leaves(ref["params"]), leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print(json.dumps({"ok": True}))
"""


def test_elastic_nm_resharding_roundtrip():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", ELASTIC_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"] is True
