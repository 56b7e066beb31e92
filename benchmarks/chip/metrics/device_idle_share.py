"""Device: the share of the traced window in which no operation ran on
the chip (one minus the union of device operation intervals over the
window), in percent."""


def read(run):
    span = run.end - run.start
    if span <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns(run.start, run.end) / span)
