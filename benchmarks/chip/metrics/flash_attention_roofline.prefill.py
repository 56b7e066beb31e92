"""Kernels (``kernels/flash_attention``): the least time of the Pallas
flash kernel's calls (one per layer of each admission prefill, at the
prompt's bucket, causal) over the kernel's device time in the steps that
prefill, in percent.  Decode attention does not use this kernel."""


def read(run):
    return run.kernel_share("flash_attention",
                            run.of_kind(decode_only=False))
