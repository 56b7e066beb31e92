"""Model step (``models/transformer.py`` ``decode_step``): model FLOPs of
the useful decode rows (two per matmul parameter, plus attention over
each row's real context) over the wall time of the decode-only step
spans times the chip's bf16 peak, in percent."""


def read(run):
    steps = run.of_kind(decode_only=True)
    if not steps:
        return None
    flops = sum(run.useful_flops(st) for st, _ in steps)
    secs = run.seconds(sp for _, sp in steps)
    return 100.0 * flops / (secs * run.peaks["bf16_flops_per_s"])
