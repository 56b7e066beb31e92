"""What a per-layer metric reader is handed: the traced steps with their
host spans, the device trace, the sizes and the chip's peaks.

Each ``metrics/<name>.py`` defines ``read(run: Traced) -> float | None``
and returns None when its window holds nothing to read (no step of its
kind, no event of its kernel); the harness then leaves the metric out.
"""

from __future__ import annotations

import importlib.util
import re
from dataclasses import dataclass
from pathlib import Path

import work

# how a kernel's device operations are named in the trace: the jitted
# wrapper around each pallas_call names the HLO custom call, and an
# operation's name is its HLO text (``%_dequant_matmul_jit.71 = ...``), so
# the pattern holds to the instruction's own name and not to an operand
KERNELS = {"dequant_matmul": re.compile(r"^%?_dequant_matmul_jit(\.\d+)?(\s|$)"),
           "flash_attention": re.compile(r"^%?_flash(\.\d+)?(\s|$)")}


@dataclass
class Traced:
    sizes: dict
    peaks: dict
    buckets: list            # the mix's prefill buckets
    steps: list              # (loop.Step, trace.Span) inside the trace
    trace: object            # trace.Trace
    start: int               # traced window on the trace clock, ns
    end: int

    def of_kind(self, decode_only: bool):
        return [(st, sp) for st, sp in self.steps
                if st.decode_only == decode_only]

    def bucket(self, n: int) -> int:
        fits = [b for b in self.buckets if b >= n]
        return min(fits) if fits else n

    @staticmethod
    def seconds(spans) -> float:
        return sum(sp.end - sp.start for sp in spans) / 1e9

    # -- work of one step ---------------------------------------------------

    def useful_flops(self, st) -> float:
        s = self.sizes
        return (sum(work.decode_row_flops(s, c) for c in st.ctxs)
                + sum(work.prefill_flops(s, n) for n in st.prefills))

    def dequant_work(self, st) -> work.Work:
        s = self.sizes
        w = work.ZERO
        if st.rows:
            w = w + work.dequant_matmul_calls(s, st.rows, st.rows)
        for n in st.prefills:
            w = w + work.dequant_matmul_calls(s, self.bucket(n), 1)
        return w

    def kernel_share(self, kernel: str, steps, least) -> float | None:
        """Sum of the least time of each step's calls over the kernel's
        device time in those steps, in percent."""
        pat = KERNELS[kernel]
        dev = sum(self.trace.kernel_ns(pat, sp.start, sp.end)
                  for _, sp in steps)
        if not steps or dev == 0:
            return None
        need = sum(least(st).least_seconds(self.peaks) for st, _ in steps)
        return 100.0 * need / (dev / 1e9)


def load_readers(names: list[str]) -> dict:
    """name -> read function, from ``metrics/<name>.py``."""
    here = Path(__file__).resolve().parent / "metrics"
    out = {}
    for name in names:
        spec = importlib.util.spec_from_file_location(
            f"metric_{name.replace('.', '_')}", here / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod.read
    return out
