"""Model step (``prefill``): model FLOPs of the admitted prompts' real
tokens plus the decode rows of the same steps, over the wall time of the
steps that prefill times the chip's bf16 peak, in percent."""


def read(run):
    steps = run.of_kind(decode_only=False)
    if not steps:
        return None
    flops = sum(run.useful_flops(st) for st, _ in steps)
    secs = run.seconds(sp for _, sp in steps)
    return 100.0 * flops / (secs * run.peaks["bf16_flops_per_s"])
