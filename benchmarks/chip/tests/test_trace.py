"""The trace reduction and the per-layer readers, on a hand-made trace and
on eight decode steps recorded on a TPU v5e (``tests/data/``)."""

import gzip
import json
from pathlib import Path

import pytest

import readers
import spec
from loop import Step
from trace import STEP, WAIT, Span, Trace, excerpt, find_xplane

MS = 1_000_000


def toy():
    ops = [("fusion.1", 0, 4 * MS), ("_dequant_matmul_jit.2", 3 * MS, 6 * MS),
           ("_flash.1", 12 * MS, 15 * MS), ("copy.3", 14 * MS, 16 * MS)]
    spans = [Span(STEP, 0, 8 * MS, 0), Span(WAIT, 8 * MS, 11 * MS),
             Span(STEP, 11 * MS, 17 * MS, 1)]
    return Trace(ops, spans)


def test_busy_union_and_kernel_time():
    tr = toy()
    assert tr.busy == [[0, 6 * MS], [12 * MS, 16 * MS]]
    assert tr.busy_ns(0, 17 * MS) == 10 * MS
    assert tr.busy_ns(5 * MS, 13 * MS) == 2 * MS
    pat = readers.KERNELS["dequant_matmul"]
    assert tr.kernel_ns(pat, 0, 8 * MS) == 3 * MS
    assert tr.kernel_ns(pat, 11 * MS, 17 * MS) == 0
    assert readers.KERNELS["flash_attention"].search("_flash.1")
    assert not readers.KERNELS["flash_attention"].search("fusion.12")


def test_idle_gaps_are_named_by_host_span():
    tr = toy()
    gaps = tr.idle_gaps(0, 17 * MS)
    assert gaps == [["generator.wait", 0.006], ["session.step", 0.001]]
    assert tr.top_ops(0, 17 * MS, 2) == [["fusion.1", 0.004],
                                          ["_dequant_matmul_jit.2", 0.003]]


def test_readers_on_a_toy_trace():
    tr = toy()
    cell = spec.load("qwen1.5-4b-q8.chat")
    steps = [(Step(0, 2, 0, [], [100, 200]), tr.steps()[0]),
             (Step(1, 1, 128, [100], [300]), tr.steps()[1])]
    run = readers.Traced(cell.family, cell.sizes, spec.peaks("TPU v5 lite"),
                         [128], steps, tr, 0, 17 * MS)
    got = {n: f(run) for n, f in readers.load_readers(
        [m["name"] for m in spec.load("qwen1.5-4b-q8.chat").benchmark[
            "per_layer"]]).items()}
    assert got["host_ms_per_step.decode"] == pytest.approx(2.0)
    assert got["device_idle_share"] == pytest.approx(100 * 7 / 17)
    assert got["dequant_matmul_roofline.prefill"] is None   # no kernel event
    assert got["flash_attention_roofline.prefill"] is not None
    assert all(v is None or v > 0 for v in got.values())


def test_readers_on_a_recorded_v5e_trace():
    """Eight decode-only steps of ``qwen1.5-4b-q8.chat`` at two rows, cut
    by ``trace.py`` from a ``run.py --trace 1 --keep-trace`` run on one
    v5e: the readers' numbers on it are pinned."""
    from jax.profiler import ProfileData
    data = Path(__file__).resolve().parent / "data"
    with gzip.open(data / "qwen_trace.textpb.gz", "rt") as f:
        tr = Trace.from_profile(ProfileData.from_text_proto(f.read()))
    kept = json.loads((data / "qwen_trace.steps.json").read_text())
    spans = tr.steps()
    steps = [(Step(**st), spans[st["index"]]) for st in kept["steps"]]
    assert len(steps) == 8 and all(st.decode_only for st, _ in steps)
    cell = spec.load(kept["workload"])
    start, end = kept["window_ns"]
    run = readers.Traced(cell.family, cell.sizes, spec.peaks("TPU v5 lite"),
                         cell.mix["prefill_buckets"], steps, tr, start, end)
    got = {n: f(run) for n, f in readers.load_readers(
        [m["name"] for m in cell.benchmark["per_layer"]]).items()}
    want = {"host_ms_per_step.decode": 3.67171575,
            "mfu.decode": 0.1697944352466542,
            "hbm_share.decode": 12.327387594916475,
            "dequant_matmul_roofline.decode": 59.073118668951935,
            "device_idle_share": 8.435048461634963}
    for name, v in want.items():
        assert got[name] == pytest.approx(v, rel=1e-9), name
    # no step prefills, so the prefill metrics have nothing to read
    assert got["mfu.prefill"] is None
    assert got["dequant_matmul_roofline.prefill"] is None
    assert got["flash_attention_roofline.prefill"] is None
    # eight kernel calls per layer and step: q/k/v/o, gate/up/down, head
    pat = readers.KERNELS["dequant_matmul"]
    assert sum(1 for name, _, _ in tr.ops if pat.search(name)) == 8 * (
        7 * cell.sizes["num_layers"] + 1)
    top = tr.top_ops(start, end, 3)
    assert top[0][0].startswith("%select_convert_fusion.3 = ")
    assert not any(name.startswith("%while") for name, _ in top)
    assert tr.idle_gaps(start, end, 1)[0][0] == "session.step"


def test_excerpt_reads_back_as_the_same_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation(STEP, i=i):
            jnp.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation(WAIT):
            pass
    jax.profiler.stop_trace()
    path = find_xplane(str(tmp_path))
    full = Trace.from_xplane(path)
    lo, hi = full.window()
    back = Trace.from_profile(ProfileData.from_text_proto(
        excerpt(path, lo, hi)))
    assert len(full.spans) == 6
    assert back.spans == full.spans and back.ops == full.ops
