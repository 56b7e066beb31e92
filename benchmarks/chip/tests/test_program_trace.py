"""The readers of the program's own spans and module events
(``program_trace.py`` and its four metrics): on a hand-made trace, on a
CPU trace of a smoke ``ServeSession``, and on steps of
``mistral-nemo-12b-q8.chat`` recorded on a TPU v5e (``tests/data/``)."""

import gzip
import json
import tempfile
from pathlib import Path

import pytest

import program_trace
import readers
import spec
from loop import Step
from program_trace import ProgramSpan, ProgramTrace
from trace import STEP, WAIT, Span, Trace, find_xplane

MS = 1_000_000
DATA = Path(__file__).resolve().parent / "data"
NEW = ("fetch_ms_per_step.decode", "sample_ms_per_step.decode",
       "dispatch_ms_per_step.decode", "decode_program_ms_per_step.decode")


def _ms(name, a, b, **stats):
    return ProgramSpan(name, round(a * MS), round(b * MS), stats)


def toy(skew=0.0):
    """A decode-only step (0-10 ms) with a try at admission that finds no
    pages, a wait, and a step (12-30 ms) that admits request 4 and then
    decodes; the device's events sit ``skew`` ms off the host's clock."""
    ops = [("fusion.1", 2.2, 7.0), ("_flash.1", 12.8, 21.0),
           ("fusion.2", 22.8, 27.5)]
    tr = Trace([(n, round((a + skew) * MS), round((b + skew) * MS))
                for n, a, b in ops],
               [Span(STEP, 0, 10 * MS, 0), Span(WAIT, 10 * MS, 12 * MS),
                Span(STEP, 12 * MS, 30 * MS, 1)])
    spans = [_ms("serve.step", 0.1, 9.5, rows=2),
             _ms("serve.admit", 0.2, 0.4, req=4, wait_us=100_000),
             _ms("serve.pages", 0.5, 1.0), _ms("serve.pack", 1.0, 2.0, rows=2),
             _ms("serve.dispatch", 2.0, 2.5), _ms("serve.fetch", 2.5, 8.0),
             _ms("serve.sample", 8.0, 9.0),
             _ms("serve.step", 12.2, 29.8, rows=2),
             _ms("serve.admit", 12.5, 22.0, req=4, wait_us=300_000),
             _ms("serve.prefill", 12.5, 13.0, tokens=128),
             _ms("serve.fetch", 13.0, 21.5), _ms("serve.sample", 21.5, 22.0),
             _ms("serve.pages", 22.0, 22.2), _ms("serve.pack", 22.2, 22.6),
             _ms("serve.dispatch", 22.6, 23.0), _ms("serve.fetch", 23.0, 28.0),
             _ms("serve.sample", 28.0, 29.0)]
    programs = [("jit_serve_decode(7)", 2.2, 7.0),
                ("jit_serve_prefill(3)", 12.8, 20.0),
                ("jit_serve_scatter(5)", 20.0, 21.0),
                ("jit_serve_decode(7)", 22.8, 27.5)]
    pt = ProgramTrace(spans, [(n, round((a + skew) * MS),
                               round((b + skew) * MS))
                              for n, a, b in programs])
    steps = [(Step(0, 2, 0, [], [100, 200]), tr.steps()[0]),
             (Step(1, 2, 128, [100], [300]), tr.steps()[1])]
    return tr, pt, steps


def _read(tr, steps, start, end, cell="mistral-nemo-12b-q8.chat"):
    c = spec.load(cell)
    run = readers.Traced(c.family, c.sizes, spec.peaks("TPU v5 lite"),
                         c.mix["prefill_buckets"], steps, tr, start, end)
    return {n: f(run) for n, f in readers.load_readers(
        [m["name"] for m in c.per_layer]).items()}


@pytest.mark.parametrize("skew", [0.0, -0.6, 0.4])
def test_new_readers_on_a_toy_trace(skew):
    """The device clock sitting off the host's (the decode program
    "starting" before its dispatch at -0.6) moves none of them."""
    tr, pt, steps = toy(skew)
    tr.program = pt
    got = _read(tr, steps, 0, 30 * MS)
    want = {# dispatch returns at 2.5, the logits are in at 8.0, the
            # program runs 4.8
            "fetch_ms_per_step.decode": 0.7,
            "sample_ms_per_step.decode": 1.0,     # 8.0-9.0
            # the stalled try 0.2, pages 0.5, pack 1.0, dispatch 0.5
            "dispatch_ms_per_step.decode": 2.2,
            "decode_program_ms_per_step.decode": 4.8,
            "host_ms_per_step.decode": 5.2}
    for name, v in want.items():
        assert got[name] == pytest.approx(v), name
    assert (got["fetch_ms_per_step.decode"] + got["sample_ms_per_step.decode"]
            + got["dispatch_ms_per_step.decode"]
            <= got["host_ms_per_step.decode"])


def test_a_stall_in_one_step_moves_host_and_not_fetch():
    """Three decode-only steps of 10 ms, the second one waiting 100 ms
    more in ``serve.fetch`` with the chip idle: the mean host gap takes
    the stall, the median fetch does not."""
    ops, spans, programs, steps = [], [], [], []
    t = 0.0
    for i, stall in enumerate((0.0, 100.0, 0.0)):
        ops.append(("fusion.1", t + 2.2, t + 7.0))
        programs.append(("jit_serve_decode(7)", t + 2.2, t + 7.0))
        spans += [_ms("serve.pack", t + 1.0, t + 2.0, rows=2),
                  _ms("serve.dispatch", t + 2.0, t + 2.5),
                  _ms("serve.fetch", t + 2.5, t + 8.0 + stall),
                  _ms("serve.sample", t + 8.0 + stall, t + 9.0 + stall)]
        steps.append(Span(STEP, round(t * MS), round((t + 10 + stall) * MS),
                          i))
        t += 10 + stall
    tr = Trace([(n, round(a * MS), round(b * MS)) for n, a, b in ops], steps)
    tr.program = ProgramTrace(spans, [(n, round(a * MS), round(b * MS))
                                      for n, a, b in programs])
    got = _read(tr, [(Step(i, 2, 0, [], [100, 200]), sp)
                     for i, sp in enumerate(steps)], 0, round(t * MS))
    assert got["fetch_ms_per_step.decode"] == pytest.approx(0.7)
    assert got["host_ms_per_step.decode"] == pytest.approx(5.2 + 100 / 3)
    assert got["sample_ms_per_step.decode"] == pytest.approx(1.0)


def test_old_readers_ignore_program_spans_and_new_ones_need_them():
    tr, pt, steps = toy()
    tr.program = pt
    with_spans = _read(tr, steps, 0, 30 * MS)
    tr, _, steps = toy()
    tr.program = ProgramTrace([], [])
    without = _read(tr, steps, 0, 30 * MS)
    assert len(with_spans) == 12 and all(without[n] is None for n in NEW)
    for name, v in without.items():
        if name not in NEW:
            assert with_spans[name] == v, name


def test_of_reads_the_newest_run_trace(tmp_path, monkeypatch):
    """A smoke ``ServeSession`` traced on the CPU into a directory named
    as ``run.py`` names its own: ``of`` finds it, and the program spans
    read back through ``excerpt`` unchanged."""
    import jax
    import numpy as np
    from jax.profiler import ProfileData
    from repro.configs import get_smoke_config
    from repro.models.transformer import init_params
    from repro.serve.session import ServeConfig, ServeSession
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    cfg = get_smoke_config("llama3-8b")
    session = ServeSession(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                           serve_cfg=ServeConfig(slots=2, max_len=32,
                                                 kv_page_size=8))
    for n in (5, 9):
        session.submit(np.arange(n), 4)
    session.step()                            # compile outside the trace
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
    jax.profiler.start_trace(trace_dir)
    for i in range(3):
        with jax.profiler.TraceAnnotation(STEP, i=i):
            session.step()
    jax.profiler.stop_trace()
    tr = Trace.from_xplane(find_xplane(trace_dir))
    steps = [(Step(i, 2, 0, [], [6 + i, 10 + i]), sp)
             for i, sp in sorted(tr.steps().items())]
    start, end = tr.window()
    got = _read(tr, steps, start, end)
    # the CPU trace has no TPU plane: the device is idle throughout, and
    # no module event names a program, so the two readers that need the
    # decode program's device time read nothing
    assert got["decode_program_ms_per_step.decode"] is None
    assert got["fetch_ms_per_step.decode"] is None
    for name in ("sample_ms_per_step.decode", "dispatch_ms_per_step.decode"):
        assert got[name] > 0, name
    assert (got["sample_ms_per_step.decode"]
            + got["dispatch_ms_per_step.decode"]
            < got["host_ms_per_step.decode"])
    pt = tr.program
    assert {sp.name for sp in pt.spans} == {
        "serve.step", "serve.pages", "serve.pack", "serve.dispatch",
        "serve.fetch", "serve.sample"}
    path = find_xplane(trace_dir)
    lo, hi = tr.window()
    back = ProgramTrace.from_profile(ProfileData.from_text_proto(
        program_trace.excerpt(path, lo, hi)))
    assert back.spans == pt.spans

    # a newer trace of another run is not read for this one
    jax.profiler.start_trace(tempfile.mkdtemp(prefix="chipbench_trace_"))
    with jax.profiler.TraceAnnotation(STEP, i=0):
        session.step()
    jax.profiler.stop_trace()
    tr = Trace.from_xplane(path)
    again = _read(tr, [(st, tr.steps()[st.index]) for st, _ in steps],
                  start, end)
    assert tr.program.spans == [] and all(again[n] is None for n in NEW)


def test_new_readers_on_a_recorded_v5e_trace():
    """One admission step and three decode-only steps of
    ``mistral-nemo-12b-q8.chat``, cut by ``program_trace.py`` from a
    ``run.py --trace 1 --keep-trace`` run on one v5e: the new readers'
    numbers on it are pinned, and the old ones read as on a trace
    without program spans.  On it the decode program's module event
    starts 0.6 ms before its ``serve.dispatch`` span, so the device clock
    sits that far off the host's; shifting the module events a millisecond
    either way moves none of the new readers."""
    from jax.profiler import ProfileData
    with gzip.open(DATA / "mistral_trace.textpb.gz", "rt") as f:
        pd = ProfileData.from_text_proto(f.read())
    kept = json.loads((DATA / "mistral_trace.steps.json").read_text())
    tr = Trace.from_profile(pd)
    tr.program = ProgramTrace.from_profile(pd)
    spans = tr.steps()
    steps = [(Step(**st), spans[st["index"]]) for st in kept["steps"]]
    assert [st.decode_only for st, _ in steps] == [False, True, True, True]
    start, end = kept["window_ns"]
    got = _read(tr, steps, start, end, kept["workload"])
    want = {"fetch_ms_per_step.decode": 1.537626,    # of 1.75, 1.53, 1.54
            "sample_ms_per_step.decode": 0.27827,
            "dispatch_ms_per_step.decode": 1.87872,
            "decode_program_ms_per_step.decode": 42.243220666666666,
            "host_ms_per_step.decode": 3.8351763333333335}
    for name, v in want.items():
        assert got[name] == pytest.approx(v, rel=1e-9), name
    assert (got["fetch_ms_per_step.decode"] + got["sample_ms_per_step.decode"]
            + got["dispatch_ms_per_step.decode"]
            <= got["host_ms_per_step.decode"])
    for st, sp in steps[1:]:
        dispatch, = tr.program.inside(("serve.dispatch",), sp.start, sp.end)
        assert (dispatch.start - 700_000 < min(
            p[1] for p in tr.program.programs if p[1] > sp.start)
            < dispatch.start - 400_000)
    for shift in (-MS, MS):
        moved = Trace.from_profile(pd)
        moved.program = ProgramTrace(tr.program.spans, [
            (n, s + shift, e + shift) for n, s, e in tr.program.programs])
        again = _read(moved, [(st, moved.steps()[st.index])
                              for st, _ in steps], start, end,
                      kept["workload"])
        for name in NEW:
            assert again[name] == pytest.approx(got[name], rel=1e-9), name
    admit, = tr.program.inside(("serve.admit",), start, end)
    assert admit.stats == {"req": 35, "wait_us": 26}
    assert [p[0].split("(")[0] for p in tr.program.programs] == [
        "jit_serve_prefill_padded", "jit_convert_element_type",
        "jit_serve_scatter"] + ["jit_serve_decode"] * 4
    bare = Trace.from_profile(pd)
    bare.program = ProgramTrace([], [])
    old = _read(bare, [(st, bare.steps()[st.index]) for st, _ in steps],
                start, end, kept["workload"])
    for name, v in old.items():
        assert (v is None) if name in NEW else got[name] == v, name
