"""A model family is files only: a family module and a configuration that
names it, written outside ``benchmarks/chip/``, serve every call that
set-up, the weights, the reference and the readers make, and no file of
the benchmark changes.  The module is a copy of ``families/dense.py``
that counts its calls and runs one kernel of its own."""

import dataclasses
import gzip
import hashlib
import json
import re
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import readers
import reference
import spec
import weights
from loop import Step
from trace import STEP, Span, Trace

HERE = Path(__file__).resolve().parent
CELL = "qwen1.5-4b-q8.chat"
HOOKS = ("sizes", "check_registry", "smoke_sizes",
         "make_tree", "hidden_rows", "head", "decode_row_flops",
         "prefill_flops", "decode_step_bytes", "resident_weight_bytes",
         "kernel_work")
COUNTING = '''

import collections

CALLS = collections.Counter()


def _counted(fn):
    def call(*args, **kwargs):
        CALLS[fn.__name__] += 1
        return fn(*args, **kwargs)
    return call


for _name in HOOKS:
    globals()[_name] = _counted(globals()[_name])

KERNELS = {"grouped_demo": re.compile(r"^%?_grouped_demo(\\.\\d+)?(\\s|$)")}
_dense_kernel_work = kernel_work


def kernel_work(s, kernel, st, bucket):
    if kernel == "grouped_demo":
        return work.Work(flops=0.0, bytes=819e9 * 0.25e-3 * st.rows)
    return _dense_kernel_work(s, kernel, st, bucket)
'''


def files_of(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture
def counting(tmp_path, monkeypatch):
    """(cell, family): the qwen cell with its configuration naming the
    family ``counting``, both written to ``tmp_path``; the dense module
    raises on any call meanwhile."""
    dense = spec.family("dense")
    (tmp_path / "counting.py").write_text(
        (spec.FAMILIES / "dense.py").read_text() + "\nimport re\n"
        + f"HOOKS = {HOOKS!r}\n" + COUNTING)
    base = spec.load(CELL)
    config = json.loads(json.dumps(base.config))
    config["architecture"]["family"] = "counting"
    path = tmp_path / "qwen1.5-4b-q8-counting.json"
    path.write_text(json.dumps(config))
    config = json.loads(path.read_text())
    fam = spec.family(config["architecture"]["family"], tmp_path)

    def refuse(*_, **__):
        raise AssertionError("the dense family was called")
    for name in HOOKS:
        monkeypatch.setattr(dense, name, refuse)
    cell = dataclasses.replace(base, config=config, family=fam,
                               sizes=fam.sizes(config))
    return cell, fam


def recorded_qwen_run(cell):
    from jax.profiler import ProfileData
    data = HERE / "data"
    with gzip.open(data / "qwen_trace.textpb.gz", "rt") as f:
        tr = Trace.from_profile(ProfileData.from_text_proto(f.read()))
    kept = json.loads((data / "qwen_trace.steps.json").read_text())
    spans = tr.steps()
    steps = [(Step(**st), spans[st["index"]]) for st in kept["steps"]]
    start, end = kept["window_ns"]
    return readers.Traced(cell.family, cell.sizes, spec.peaks("TPU v5 lite"),
                          cell.mix["prefill_buckets"], steps, tr, start, end)


def test_a_family_added_as_a_file_serves_every_call(counting):
    before = files_of(spec.HERE)
    cell, fam = counting
    assert fam.CALLS["sizes"] == 1
    harness.model(cell, rehearse=False)
    _, sizes = harness.model(cell, rehearse=True)
    tree = weights.make_weights(fam, sizes, 0)
    pins = json.loads((HERE / "data" / "dense_pins.json").read_text())
    # the copy makes the dense tree, to the bit
    assert sizes == pins["qwen1.5-4b-q8"]["smoke_sizes"]
    assert hashlib.sha256(np.asarray(tree["head"]["q8"]).tobytes(
    )).hexdigest() == pins["qwen1.5-4b-q8"]["weights_smoke"][
        "['head']['q8']"]
    tokens = jnp.zeros((2, 128), jnp.int32)
    rows = jnp.asarray([3, 130], jnp.int32)
    g = reference.gaps(fam, tree, sizes, tokens, rows,
                       jnp.asarray([1, 2], jnp.int32), control=True)
    assert set(g) == {"program", "control"}

    read = readers.load_readers(["mfu.decode", "hbm_share.decode",
                                 "dequant_matmul_roofline.decode"])
    run = recorded_qwen_run(cell)
    got = {name: fn(run) for name, fn in read.items()}
    # the values test_trace.py pins for the dense family
    assert got == pytest.approx({
        "mfu.decode": 0.1697944352466542,
        "hbm_share.decode": 12.327387594916475,
        "dequant_matmul_roofline.decode": 59.073118668951935}, rel=1e-9)
    # the recorded steps only decode: count one prefill too
    assert run.useful_flops(Step(0, 0, 128, [100], [])) > 0
    assert {name for name in HOOKS if not fam.CALLS[name]} == set()
    assert files_of(spec.HERE) == before


def test_an_unknown_family_exits_naming_its_file(tmp_path):
    with pytest.raises(SystemExit, match=re.escape(
            str(tmp_path / "nosuch.py"))):
        spec.family("nosuch", tmp_path)
    with pytest.raises(SystemExit, match=re.escape(
            str(spec.FAMILIES / "nosuch.py"))):
        spec.family("nosuch")


def test_a_family_kernel_pattern_reaches_kernel_share(counting):
    cell, fam = counting
    ms = 1_000_000
    tr = Trace([("_grouped_demo.7", 1 * ms, 3 * ms),
                ("_dequant_matmul_jit.2", 3 * ms, 4 * ms)],
               [Span(STEP, 0, 5 * ms, 0)])
    steps = [(Step(0, 4, 0, [], [10, 20, 30, 40]), tr.steps()[0])]
    run = readers.Traced(fam, cell.sizes, spec.peaks("TPU v5 lite"), [128],
                         steps, tr, 0, 5 * ms)
    # 4 rows x 0.25 ms of least time at the HBM bandwidth over 2 ms of
    # the kernel's device time
    assert run.kernel_share("grouped_demo", steps) == pytest.approx(50.0)
    assert run.kernel_share("dequant_matmul", steps) > 0
    dense = dataclasses.replace(run, family=spec.family("dense"))
    with pytest.raises(KeyError):
        dense.kernel_share("grouped_demo", steps)
