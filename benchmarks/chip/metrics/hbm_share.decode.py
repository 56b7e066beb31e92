"""Model step (``decode_step``): the least bytes a decode step must move
(the q8-resident weights once, each live row's real KV, one new KV
token per row) over the wall time of the decode-only step spans times
the chip's HBM bandwidth, in percent."""


def read(run):
    steps = run.of_kind(decode_only=True)
    if not steps:
        return None
    need = sum(run.decode_bytes(st) for st, _ in steps)
    secs = run.seconds(sp for _, sp in steps)
    return 100.0 * need / (secs * run.peaks["hbm_bytes_per_s"])
