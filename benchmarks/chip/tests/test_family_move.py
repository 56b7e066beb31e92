"""The dense family serves both configurations to the bit as the harness
did before model families were files: the seeded weights tree, the
reference and its fp8 control, and every work count, against values
pinned in ``data/dense_pins.json``.  The pins were computed on the CPU
from the harness as it stood before ``families/`` existed, with these
same inputs; a leaf or an output is pinned by the SHA-256 of its bytes.
"""

import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import readers
import reference
import spec
import weights
from loop import Step

PINS = json.loads((Path(__file__).resolve().parent / "data"
                   / "dense_pins.json").read_text())
CONFIGS = ["qwen1.5-4b-q8", "mistral-nemo-12b-q8"]
STEPS = [Step(0, 1, 0, [], [33]), Step(1, 2, 0, [], [100, 1279]),
         Step(2, 6, 0, [], [1, 17, 384, 700, 1024, 1280]),
         Step(3, 8, 512, [300], [5, 9, 600]), Step(4, 0, 1024, [1000], []),
         Step(5, 4, 1152, [128, 1000], [64, 64, 900, 1100]),
         Step(6, 0, 384, [32, 129], [])]


def sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


def by_path(tree, fn) -> dict:
    return {jax.tree_util.keystr(p): fn(x)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def smoke(config):
    cell = spec.load(f"{config}.chat")
    _, sizes = harness.model(cell, rehearse=True)
    return cell, sizes


@pytest.mark.parametrize("config", CONFIGS)
def test_weights_tree_is_bit_identical(config):
    cell, sizes = smoke(config)
    pins = PINS[config]
    assert sizes == pins["smoke_sizes"]
    seed = cell.config["deployment"]["weight_seed"]
    tree = weights.make_weights(cell.family, sizes, seed)
    assert by_path(tree, sha) == pins["weights_smoke"]
    full = jax.eval_shape(lambda k: cell.family.make_tree(cell.sizes, k),
                          weights.seed_key(seed))
    assert by_path(full, lambda x: [list(x.shape), str(x.dtype)]) == pins[
        "weights_full"]


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_and_control_are_bit_identical(config):
    cell, sizes = smoke(config)
    fam = cell.family
    tree = weights.make_weights(fam, sizes,
                                cell.config["deployment"]["weight_seed"])
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, sizes["vocab_size"], (2, 256)),
                         jnp.int32)
    rows = jnp.asarray([3, 100, 255, 256 + 7, 256 + 200, 511], jnp.int32)
    targets = jnp.asarray(rng.integers(0, sizes["vocab_size"], (6, 3)),
                          jnp.int32)
    got = {}
    for quant in (None, "fp8"):
        hidden = fam.hidden_rows(tree, sizes, tokens, rows, quant=quant)
        best, arg, at = fam.head(tree, hidden, targets, quant=quant)
        got[str(quant)] = {"hidden": sha(hidden), "best": sha(best),
                           "arg": sha(arg), "at": sha(at)}
    g = reference.gaps(fam, tree, sizes, tokens, rows, targets[:, 0],
                       control=True)
    got["gaps"] = {k: sha(v) for k, v in g.items()}
    assert got == PINS[config]["reference"]


@pytest.mark.parametrize("config", CONFIGS)
def test_work_counts_are_unchanged(config):
    cell = spec.load(f"{config}.chat")
    fam, s = cell.family, cell.sizes
    run = readers.Traced(fam, s, spec.peaks("TPU v5 lite"),
                         cell.mix["prefill_buckets"], [], None, 0, 1)
    got = {
        "decode_row_flops": {str(c): fam.decode_row_flops(s, c)
                             for c in (1, 17, 128, 384, 1024, 1280)},
        "prefill_flops": {str(n): fam.prefill_flops(s, n)
                          for n in (1, 32, 127, 384, 1000, 1024)},
        "decode_step_bytes": [fam.decode_step_bytes(s, st.ctxs)
                              for st in STEPS],
        "resident_weight_bytes": fam.resident_weight_bytes(s),
        "useful_flops": [run.useful_flops(st) for st in STEPS],
        "dequant_matmul": [[w.flops, w.bytes] for w in (
            run.kernel_work("dequant_matmul", st) for st in STEPS)],
        "flash_attention": [[w.flops, w.bytes] for w in (
            run.kernel_work("flash_attention", st) for st in STEPS)],
    }
    assert got == PINS[config]["work"]
    assert [run.decode_bytes(st) for st in STEPS] == got["decode_step_bytes"]
