"""Reduce a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
From it this module takes, on the trace's own clock (nanoseconds):

* device operations: the events of the ``/device:TPU:0`` plane's
  ``XLA Ops`` line (HLO instructions; a Pallas kernel appears under the
  name of the jitted wrapper that holds its ``pallas_call``, such as
  ``_dequant_matmul_jit.3`` or ``_flash.1``);
* host spans: the benchmark's ``session.step`` (with its step index ``i``)
  and ``generator.wait`` annotations.

``Trace`` then answers the questions the readers ask: the union of device
busy time over an interval, the device time of a kernel inside an
interval, and the longest idle gaps named by the host span they fall in.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE, OPS_LINE = "/device:TPU:0", "XLA Ops"   # one-chip cells
STEP, WAIT = "session.step", "generator.wait"


@dataclass
class Span:
    name: str
    start: int
    end: int
    index: int | None = None


def short(name: str, width: int = 120) -> str:
    """An operation's HLO text cut to its name and the start of its
    right-hand side (``%copy.15 = bf16[40,481,16,20,128]... copy(``)."""
    return name if len(name) <= width else name[:width] + "..."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    def __init__(self, ops: list[tuple[str, int, int]], spans: list[Span]):
        """``ops``: (name, start, end) operations of the chip;
        ``spans``: host spans."""
        self.ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        self.spans = sorted(spans, key=lambda s: s.start)
        self.busy = _merge((s, e) for _, s, e in self.ops)
        self._busy_starts = [b[0] for b in self.busy]
        self._op_starts = [o[1] for o in self.ops]

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops, spans = [], []
        for plane in pd.planes:
            if plane.name.startswith(DEVICE):
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        s = int(ev.start_ns)
                        ops.append((ev.name, s, s + int(ev.duration_ns)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name not in (STEP, WAIT):
                            continue
                        stats = dict(ev.stats)
                        idx = stats.get("i")
                        s = int(ev.start_ns)
                        spans.append(Span(ev.name, s,
                                          s + int(ev.duration_ns),
                                          None if idx is None else int(idx)))
        return cls(ops, spans)

    # -- questions ----------------------------------------------------------

    def steps(self) -> dict[int, Span]:
        return {s.index: s for s in self.spans
                if s.name == STEP and s.index is not None}

    def window(self) -> tuple[int, int]:
        """From the first to the last host span or device operation."""
        starts = [s.start for s in self.spans] + [o[1] for o in self.ops]
        ends = [s.end for s in self.spans] + [o[2] for o in self.ops]
        return min(starts), max(ends)

    def busy_ns(self, start: int, end: int) -> int:
        """Union of device operation time inside [start, end]."""
        i = max(0, bisect.bisect_right(self._busy_starts, start) - 1)
        total = 0
        for s, e in self.busy[i:]:
            if s >= end:
                break
            total += max(0, min(e, end) - max(s, start))
        return total

    def kernel_ns(self, pattern: re.Pattern, start: int, end: int) -> int:
        """Device time of operations whose name matches ``pattern`` and
        that start inside [start, end]."""
        i = bisect.bisect_left(self._op_starts, start)
        j = bisect.bisect_right(self._op_starts, end)
        return sum(e - s for name, s, e in self.ops[i:j]
                   if pattern.search(name))

    def kernel_count(self, pattern: re.Pattern) -> int:
        return sum(1 for name, _, _ in self.ops if pattern.search(name))

    def top_ops(self, start: int, end: int, n: int = 10):
        """[name, seconds] of the device operations with the most time,
        leaving out an operation that holds others (a ``while`` around
        its body), whose time its inner operations already count."""
        tot = defaultdict(int)
        i = bisect.bisect_left(self._op_starts, start)
        j = bisect.bisect_right(self._op_starts, end)
        for k in range(i, j):
            name, s, e = self.ops[k]
            if k + 1 < len(self.ops) and self.ops[k + 1][2] <= e:
                continue
            tot[short(name)] += e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, start: int, end: int, n: int = 10):
        """[host span, seconds] of the longest gaps with no device
        operation inside [start, end], named by the host span that holds
        the gap's midpoint (``between steps`` where none does)."""
        gaps, prev = [], start
        for s, e in self.busy:
            if e <= start:
                continue
            if s >= end:
                break
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if end > prev:
            gaps.append((prev, end))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            name = next((sp.name for sp in self.spans
                         if sp.start <= mid < sp.end), "between steps")
            out.append([name, (e - s) / 1e9])
        return out


def excerpt(path: str, start_ns: int, end_ns: int) -> str:
    """The device operations and the benchmark's host spans of the trace
    at ``path`` that start inside [start_ns, end_ns], as a text-format
    XSpace that ``ProfileData.from_text_proto`` reads back: a small real
    trace for tests."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for pid, plane in enumerate(pd.planes, 1):
        if plane.name.startswith(DEVICE):
            keep = lambda line, ev: line.name == OPS_LINE   # noqa: E731
        elif plane.name.startswith("/host:"):
            keep = lambda line, ev: ev.name in (STEP, WAIT)  # noqa: E731
        else:
            continue
        names, stat_ids, lines = {}, {}, []
        for lid, line in enumerate(plane.lines, 1):
            evs = [ev for ev in line.events if keep(line, ev)
                   and start_ns <= ev.start_ns <= end_ns]
            if not evs:
                continue
            t0 = int(min(ev.start_ns for ev in evs))
            body = []
            for ev in evs:
                mid = names.setdefault(ev.name, len(names) + 1)
                stats = ""
                for k, v in ev.stats:
                    sid = stat_ids.setdefault(k, len(stat_ids) + 1)
                    val = (f"int64_value: {v}" if isinstance(v, int)
                           else f"double_value: {v!r}"
                           if isinstance(v, float) else
                           f"str_value: {json.dumps(str(v))}")
                    stats += f" stats {{ metadata_id: {sid} {val} }}"
                off = round((ev.start_ns - t0) * 1000)
                dur = round(ev.duration_ns * 1000)
                body.append(f"    events {{ metadata_id: {mid} offset_ps: "
                            f"{off} duration_ps: {dur}{stats} }}")
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
    """Cut a kept trace (``run.py --keep-trace DIR``) to its first
    ``--steps`` traced steps: ``<out>.textpb.gz`` (``excerpt``) and
    ``<out>.steps.json`` (those steps as the window recorded them)."""
    import argparse
    import gzip
    ap = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args(argv)
    path = find_xplane(args.trace_dir)
    spans = sorted(Trace.from_xplane(path).steps().values(),
                   key=lambda sp: sp.start)[:args.steps]
    start, end = spans[0].start, spans[-1].end
    with gzip.open(args.out + ".textpb.gz", "wt") as f:
        f.write(excerpt(path, start, end))
    with open(os.path.join(args.trace_dir, "steps.json")) as f:
        kept = json.load(f)
    idx = {sp.index for sp in spans}
    kept["steps"] = [st for st in kept["steps"] if st["index"] in idx]
    kept["window_ns"] = [start, end]
    with open(args.out + ".steps.json", "w") as f:
        json.dump(kept, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
