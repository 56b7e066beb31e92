"""The plain reference against the program's serving path, at the smoke
presets of both configurations on the CPU: the logits a ``ServeSession``
in paged mode sampled from (the admission prefill, then paged decode
steps) against the reference's logits at the same positions."""

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import spec
import weights
from repro.serve.session import ServeConfig, ServeSession

# the smoke presets compute in f32; the session's matmuls run the
# dequant_matmul kernel in interpret mode and attention in f32 on the
# CPU, the reference at "highest": the two differ by f32 summation order
# over two layers, far below the logits' unit scale
ATOL = 2e-3


@pytest.mark.parametrize("config", ["qwen1.5-4b-q8", "mistral-nemo-12b-q8"])
def test_reference_matches_prefill_then_paged_decode(config):
    cell = spec.load(f"{config}.chat")
    cfg, sizes = harness.model(cell, rehearse=True)
    cfg = cfg.replace(param_dtype="float32", compute_dtype="float32")
    tree = weights.make_weights(cell.family, sizes, 3)
    session = ServeSession(cfg, tree, backend="q8", serve_cfg=ServeConfig(
        slots=2, max_len=64, kv_page_size=16, kv_prefix_sharing=False,
        prefill_buckets=(16, 32)))
    seen = []
    sample = session._sample

    def record(row, req):
        seen.append((req.id, len(req.tokens), np.asarray(row)))
        return sample(row, req)
    session._sample = record
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, sizes["vocab_size"], n) for n in (13, 32)]
    handles = [session.submit(p, 9) for p in prompts]
    session.run()

    v = sizes["vocab_size"]
    tokens = np.zeros((2, 64), np.int32)
    rows, want = [], []
    for b, (p, h) in enumerate(zip(prompts, handles)):
        seq = np.concatenate([p, h.tokens[:-1]])
        tokens[b, :seq.size] = seq
        for rid, j, logits in seen:
            if rid == h.id:
                rows.append(b * 64 + p.size - 1 + j)
                want.append(logits)
    hidden = cell.family.hidden_rows(tree, sizes, jnp.asarray(tokens),
                                     jnp.asarray(rows, jnp.int32))
    targets = np.broadcast_to(np.arange(v), (len(rows), v))
    _, _, got = cell.family.head(tree, hidden, jnp.asarray(targets))
    got, want = np.asarray(got), np.stack(want)
    assert len(rows) == 18
    assert np.abs(want).max() > 1.0       # logits of unit scale
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
