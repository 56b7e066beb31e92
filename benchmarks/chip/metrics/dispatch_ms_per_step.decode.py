"""Scheduler (``serve/session.py``): host-clock milliseconds of the
program's ``serve.admit``, ``serve.pages``, ``serve.pack`` and
``serve.dispatch`` spans of the decode-only steps, per step: the host's
work before the decode program is handed to the device (a try at
admission that finds no pages, page growth, packing the batch and copying
it to the device, the jit dispatch)."""

import program_trace


def read(run):
    return program_trace.wall_ms_per_decode_step(
        run, ("serve.admit", "serve.pages", "serve.pack", "serve.dispatch"))
