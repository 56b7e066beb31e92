"""GPipe pipeline over a mesh axis: numerical equivalence with sequential
stage application (subprocess with 8 fake devices)."""

import os
import subprocess
import sys

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from repro.distributed.pipeline_stage import gpipe_apply, split_stages

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("pod", "data"))
S, L, M, MB, D = 4, 8, 6, 4, 32
rng = np.random.default_rng(0)
layers = {"w": jnp.asarray(rng.standard_normal((L, D, D)) * (D ** -0.5)),
          "b": jnp.asarray(rng.standard_normal((L, D)) * 0.01)}
x = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)

def block(p, h):
    def body(hh, lp):
        return jnp.tanh(hh @ lp["w"] + lp["b"]), None
    out, _ = jax.lax.scan(body, h, p)
    return out

stages = split_stages(layers, S)
got = gpipe_apply(block, stages, x, mesh, axis="pod")

# sequential reference: all L layers over each microbatch
ref = jax.vmap(lambda xb: block(layers, xb))(x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print("PIPELINE_OK")
"""


@pytest.mark.slow
def test_gpipe_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PIPELINE_OK" in proc.stdout
