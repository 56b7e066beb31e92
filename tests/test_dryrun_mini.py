"""Mini dry-run in a subprocess: 8 fake host devices, reduced configs,
(2,2,2) pod mesh — exercises the real lower_cell/analyze path including the
cross-pod axis and the compressed cross-pod collective."""

import json
import os
import subprocess
import sys

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.dryrun import analyze, collective_bytes
from repro.launch.mesh import make_local_mesh
from repro.distributed.compress import cross_pod_psum_compressed
from repro.distributed.sharding import DEFAULT_RULES
from repro.optim.adamw import AdamWConfig
from repro.distributed.compress import CompressionConfig
from repro.train.steps import (batch_specs, init_train_state,
                               make_train_step, state_specs)
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = make_local_mesh(data=2, model=2, pod=2)
cfg = get_smoke_config("llama3-8b").replace(
    num_heads=4, num_kv_heads=2, d_model=128, d_ff=256)
ocfg, ccfg = AdamWConfig(), CompressionConfig(enabled=True)
state_shape = jax.eval_shape(lambda: init_train_state(cfg, ocfg, ccfg))
step_fn, _ = make_train_step(cfg, mesh, ocfg, ccfg)
batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
st_specs = state_specs(state_shape, mesh, DEFAULT_RULES)
b_specs = batch_specs(batch, mesh, DEFAULT_RULES)
sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
lowered = jax.jit(step_fn, in_shardings=(sh(st_specs), sh(b_specs)),
                  donate_argnums=(0,)).lower(state_shape, batch)
compiled = lowered.compile()
res = analyze(lowered, compiled, 8)
assert res["flops_per_device"] > 0

# compressed cross-pod collective: numerical check on real devices
x = jnp.stack([jnp.full((4, 128), float(i + 1)) for i in range(2)])
x = jax.device_put(x, NamedSharding(mesh, P("pod")))
out = cross_pod_psum_compressed(x, mesh)
np.testing.assert_allclose(np.asarray(out)[0], 3.0, rtol=1e-2)
np.testing.assert_allclose(np.asarray(out)[1], 3.0, rtol=1e-2)
print(json.dumps({"ok": True,
                  "coll": res["collectives"]["total_per_device_bytes"]}))
"""


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["ok"]
    assert payload["coll"] > 0, "train step must contain collectives"
