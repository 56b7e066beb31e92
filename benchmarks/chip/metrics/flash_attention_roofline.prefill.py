"""Kernels (``kernels/flash_attention``): the least time of the Pallas
flash kernel's calls (one per layer of each admission prefill, at the
prompt's bucket, causal) over the kernel's device time in the steps that
prefill, in percent.  Decode attention does not use this kernel."""

import work


def read(run):
    def least(st):
        w = work.ZERO
        for n in st.prefills:
            w = w + work.flash_prefill_calls(run.sizes, run.bucket(n))
        return w
    return run.kernel_share("flash_attention",
                            run.of_kind(decode_only=False), least)
