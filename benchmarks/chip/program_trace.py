"""The program's own spans and named programs in a profiler trace.

``ServeSession`` (``src/repro/serve/session.py``) writes ``serve.*`` spans
inside every ``step()`` and jits its programs under fixed names, so that
the chip's ``XLA Modules`` line names each run of a program
(``jit_serve_decode(<id>)``).  ``trace.Trace`` reads neither.  This module
reads both from the same ``.xplane.pb``:

* program spans: the host events named ``serve.*``, with their stats
  (``serve.admit`` carries ``req`` and ``wait_us``);
* programs: the events of the ``/device:TPU:0`` plane's ``XLA Modules``
  line, as (name, start, end).

The device's events there can sit about a millisecond off the host's
clock, differently in each run, so the readers here compare durations
across the two clocks, never instants.

A metric reader is handed a ``readers.Traced``, which holds the reduced
``Trace`` and not its file, so ``of(run)`` reads the newest trace that
``run.py`` recorded under the temporary directory, once per ``Trace``, and
only if its benchmark spans are the run's own.  A program that writes no
``serve.*`` span and names no ``jit_serve_decode`` gives every reader here
None.

    python3 benchmarks/chip/program_trace.py TRACE_DIR OUT --decode-steps 3

cuts a kept trace (``run.py --trace 1 --keep-trace TRACE_DIR``) to its
first traced step that admits a request and the decode-only steps that
follow it: ``OUT.textpb.gz`` holds their device operations, module events
and host spans, and ``OUT.steps.json`` those steps as the window recorded
them.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import tempfile
from dataclasses import dataclass

from trace import DEVICE, OPS_LINE, STEP, WAIT, Trace, find_xplane

MODULES_LINE = "XLA Modules"
PREFIX = "serve."
DECODE = re.compile(r"^jit_serve_decode(\(|$)")
RUN_TRACE_DIRS = "chipbench_trace_*"    # run.py's mkdtemp prefix


@dataclass
class ProgramSpan:
    name: str
    start: int
    end: int
    stats: dict


class ProgramTrace:
    def __init__(self, spans: list[ProgramSpan],
                 programs: list[tuple[str, int, int]]):
        self.spans = sorted(spans, key=lambda s: s.start)
        self.programs = sorted(programs, key=lambda p: p[1])
        self._program_starts = [p[1] for p in self.programs]

    @classmethod
    def from_profile(cls, pd) -> "ProgramTrace":
        spans, programs = [], []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE):
                for line in plane.lines:
                    if line.name != MODULES_LINE:
                        continue
                    for ev in line.events:
                        s = int(ev.start_ns)
                        programs.append((ev.name, s,
                                         s + int(ev.duration_ns)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(PREFIX):
                            s = int(ev.start_ns)
                            spans.append(ProgramSpan(
                                ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
        return cls(spans, programs)

    def inside(self, names, start: int, end: int) -> list[ProgramSpan]:
        """Spans named one of ``names`` that lie inside [start, end]."""
        return [sp for sp in self.spans if sp.name in names
                and start <= sp.start and sp.end <= end]

    def decode_ns(self, start: int, end: int) -> list[int]:
        """Device time of each ``jit_serve_decode`` module event whose
        midpoint lies inside [start, end].  The trace's device clock sits
        up to about a millisecond off the host's, so an event's start can
        fall before the host span of the step that dispatched it; its
        midpoint, half a decode program later, does not."""
        out = []
        # a step waits for its decode program to end, so no such event
        # is longer than its step and none starts a step's length early
        i = bisect.bisect_left(self._program_starts, 2 * start - end)
        j = bisect.bisect_right(self._program_starts, end)
        for name, s, e in self.programs[i:j]:
            if DECODE.search(name) and start <= (s + e) // 2 <= end:
                out.append(e - s)
        return out


def of(run) -> ProgramTrace:
    """The program trace of ``run.trace``: the one set on it as
    ``.program`` (tests do), else that of the newest trace ``run.py``
    recorded if it holds the same benchmark spans (an empty one if not),
    kept on the ``Trace`` for the next reader."""
    pt = getattr(run.trace, "program", None)
    if pt is None:
        paths = glob.glob(os.path.join(tempfile.gettempdir(), RUN_TRACE_DIRS,
                                       "plugins", "profile", "*",
                                       "*.xplane.pb"))
        pt = ProgramTrace([], [])
        if paths:
            from jax.profiler import ProfileData
            pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
            # a trace's clock starts with the trace, so only the spans
            # tell this run's file from one another run left behind
            if Trace.from_profile(pd).spans == run.trace.spans:
                pt = ProgramTrace.from_profile(pd)
        run.trace.program = pt
    return pt


def wall_ms_per_decode_step(run, names) -> float | None:
    """Host-clock milliseconds of the spans named ``names`` inside the
    decode-only steps, per decode-only step; None where those steps hold
    no such span."""
    steps = run.of_kind(decode_only=True)
    pt = of(run)
    spans = [p for _, sp in steps for p in pt.inside(names, sp.start, sp.end)]
    if not spans:
        return None
    return sum(p.end - p.start for p in spans) / len(steps) / 1e6


def decode_programs(run) -> tuple[list, int]:
    """(``serve.dispatch``, ``serve.fetch``, device ns of the decode
    program) of each decode-only step that holds one of each and one
    ``jit_serve_decode`` event, and the number of decode-only steps."""
    steps = run.of_kind(decode_only=True)
    pt = of(run)
    out = []
    for _, sp in steps:
        dispatch = pt.inside(("serve.dispatch",), sp.start, sp.end)
        fetch = pt.inside(("serve.fetch",), sp.start, sp.end)
        ns = pt.decode_ns(sp.start, sp.end)
        if len(dispatch) == len(fetch) == len(ns) == 1:
            out.append((dispatch[0], fetch[0], ns[0]))
    return out, len(steps)


# -- excerpt ----------------------------------------------------------------

def _keep(plane_name: str, line_name: str, ev_name: str) -> bool:
    if plane_name.startswith(DEVICE):
        return line_name in (OPS_LINE, MODULES_LINE)
    return plane_name.startswith("/host:") and (
        ev_name in (STEP, WAIT) or ev_name.startswith(PREFIX))


def _stat(v) -> str:
    if isinstance(v, int):
        return f"int64_value: {v}"
    if isinstance(v, float):
        return f"double_value: {v!r}"
    return f"str_value: {json.dumps(str(v))}"


def excerpt(path: str, start_ns: int, end_ns: int) -> str:
    """As ``trace.excerpt``, with the module events and the program spans
    besides: the events of the trace at ``path`` that start inside
    [start_ns, end_ns], as a text-format XSpace that
    ``ProfileData.from_text_proto`` reads back."""
    from jax.profiler import ProfileData
    out = []
    for pid, plane in enumerate(ProfileData.from_file(path).planes, 1):
        names, stat_ids, lines = {}, {}, []
        for lid, line in enumerate(plane.lines, 1):
            evs = [ev for ev in line.events
                   if _keep(plane.name, line.name, ev.name)
                   and start_ns <= ev.start_ns <= end_ns]
            if not evs:
                continue
            t0 = int(min(ev.start_ns for ev in evs))
            body = []
            for ev in evs:
                mid = names.setdefault(ev.name, len(names) + 1)
                stats = "".join(
                    f" stats {{ metadata_id: "
                    f"{stat_ids.setdefault(k, len(stat_ids) + 1)} "
                    f"{_stat(v)} }}" for k, v in ev.stats)
                body.append(f"    events {{ metadata_id: {mid} offset_ps: "
                            f"{round((ev.start_ns - t0) * 1000)} "
                            f"duration_ps: {round(ev.duration_ns * 1000)}"
                            f"{stats} }}")
            lines.append(f"  lines {{ id: {lid} name: {json.dumps(line.name)}"
                         f" timestamp_ns: {t0}\n" + "\n".join(body) + " }")
        if not lines:
            continue
        meta = [f"  event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{json.dumps(n)} }} }}" for n, i in names.items()]
        meta += [f"  stat_metadata {{ key: {i} value {{ id: {i} name: "
                 f"{json.dumps(n)} }} }}" for n, i in stat_ids.items()]
        out.append(f"planes {{ id: {pid} name: {json.dumps(plane.name)}\n"
                   + "\n".join(lines + meta) + " }")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    import argparse
    import gzip
    ap = argparse.ArgumentParser(
        description="Cut a kept trace to one admission step and the "
        "decode-only steps after it, program spans and modules included.")
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--decode-steps", type=int, default=3)
    args = ap.parse_args(argv)
    path = find_xplane(args.trace_dir)
    spans = Trace.from_xplane(path).steps()
    with open(os.path.join(args.trace_dir, "steps.json")) as f:
        kept = json.load(f)
    traced = [st for st in kept["steps"] if st["index"] in spans]
    n = args.decode_steps
    first = next(k for k, st in enumerate(traced[:len(traced) - n])
                 if st["prefill_tokens"] and not any(
                     nxt["prefill_tokens"] for nxt in traced[k + 1:k + 1 + n]))
    chosen = traced[first:first + 1 + n]
    start = spans[chosen[0]["index"]].start
    end = spans[chosen[-1]["index"]].end
    with gzip.open(args.out + ".textpb.gz", "wt") as f:
        f.write(excerpt(path, start, end))
    kept["steps"] = chosen
    kept["window_ns"] = [start, end]
    with open(args.out + ".steps.json", "w") as f:
        json.dump(kept, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
