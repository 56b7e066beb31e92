"""Model step (``decode_step``): device milliseconds of the
``jit_serve_decode`` module event of each decode-only step (matched by
its midpoint), per step that holds one: the decode program on the chip,
without the host's gap around it.  How many steps held none goes to
stderr."""

import sys

import program_trace


def read(run):
    got, n = program_trace.decode_programs(run)
    if not got:
        return None
    print(f"decode_program_ms_per_step.decode: {n - len(got)} of {n} "
          f"decode-only steps matched no single decode program",
          file=sys.stderr, flush=True)
    return sum(ns for _, _, ns in got) / len(got) / 1e6
