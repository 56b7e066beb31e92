"""Kernels (``kernels/dequant_matmul``): the least time of every
``dequant_matmul`` call of the decode-only steps (the larger of its
FLOPs over the bf16 peak and its least bytes over the HBM bandwidth, at
the padded rows dispatched) over the kernel's device time in those
steps, in percent."""


def read(run):
    return run.kernel_share("dequant_matmul", run.of_kind(decode_only=True))
