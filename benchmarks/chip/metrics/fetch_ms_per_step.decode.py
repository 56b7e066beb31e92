"""Scheduler (``serve/session.py``): milliseconds from the return of the
decode program's dispatch (end of ``serve.dispatch``, host clock) to the
logits in host memory (end of ``serve.fetch``), less the program's own
device time (its ``jit_serve_decode`` module event): the wait past the
program's end and the copy of its (rows, vocab) f32 logits.  Only
durations are read across the two clocks, so an offset between them
moves nothing.  The median over the decode-only steps that hold one of
each: a rare stall of the device and the host together (0.06-1.3 s on
a v5e) lands inside ``serve.fetch`` and would set a mean;
``host_ms_per_step.decode`` still counts it."""

import statistics

import program_trace


def read(run):
    got, _ = program_trace.decode_programs(run)
    if not got:
        return None
    return statistics.median(f.end - d.end - ns for d, f, ns in got) / 1e6
