"""Kernels (``kernels/dequant_matmul``): as ``.decode``, for the calls of
the steps that prefill (each admitted prompt at its bucket, plus the
decode rows of the same step)."""


def read(run):
    return run.kernel_share("dequant_matmul", run.of_kind(decode_only=False))
