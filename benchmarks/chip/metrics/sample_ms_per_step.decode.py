"""Scheduler (``serve/session.py``): host-clock milliseconds of the
program's ``serve.sample`` spans of the decode-only steps, per step: host
sampling of each row's next token, and retiring finished requests.  The
device has no work queued then."""

import program_trace


def read(run):
    return program_trace.wall_ms_per_decode_step(run, ("serve.sample",))
