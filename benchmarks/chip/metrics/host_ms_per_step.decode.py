"""Scheduler (``serve/session.py``): device-idle milliseconds inside the
benchmark's ``session.step`` span, per decode-only step.  What the host
adds to every decode step: building the batch, copying the logits back,
sampling, bookkeeping."""


def read(run):
    steps = run.of_kind(decode_only=True)
    if not steps:
        return None
    idle = sum((sp.end - sp.start) - run.trace.busy_ns(sp.start, sp.end)
               for _, sp in steps)
    return idle / len(steps) / 1e6
