"""What a per-layer metric reader is handed: the traced steps with their
host spans, the device trace, the model's family and sizes, and the
chip's peaks.  A step's work is the family's count (``families/``).

Each ``metrics/<name>.py`` defines ``read(run: Traced) -> float | None``
and returns None when its window holds nothing to read (no step of its
kind, no event of its kernel); the harness then leaves the metric out.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

import spec
import work

# how a kernel's device operations are named in the trace: the jitted
# wrapper around each pallas_call names the HLO custom call, and an
# operation's name is its HLO text (``%_dequant_matmul_jit.71 = ...``), so
# the pattern holds to the instruction's own name and not to an operand.
# A kernel that only one family runs has its pattern in that family's
# ``KERNELS``.
KERNELS = {"dequant_matmul": re.compile(r"^%?_dequant_matmul_jit(\.\d+)?(\s|$)"),
           "flash_attention": re.compile(r"^%?_flash(\.\d+)?(\s|$)")}


@dataclass
class Traced:
    family: ModuleType       # families/<family>.py
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
        f, s = self.family, self.sizes
        return (sum(f.decode_row_flops(s, c) for c in st.ctxs)
                + sum(f.prefill_flops(s, n) for n in st.prefills))

    def decode_bytes(self, st) -> float:
        """Least bytes of decode step ``st``."""
        return self.family.decode_step_bytes(self.sizes, st.ctxs)

    def kernel_work(self, kernel: str, st) -> work.Work:
        """Least work of ``kernel``'s calls in step ``st``."""
        return self.family.kernel_work(self.sizes, kernel, st, self.bucket)

    def kernel_share(self, kernel: str, steps) -> float | None:
        """Sum of the least time of each step's calls of ``kernel`` over
        the kernel's device time in those steps, in percent."""
        pat = (KERNELS.get(kernel)
               or getattr(self.family, "KERNELS", {})[kernel])
        dev = sum(self.trace.kernel_ns(pat, sp.start, sp.end)
                  for _, sp in steps)
        if not steps or dev == 0:
            return None
        need = sum(self.kernel_work(kernel, st).least_seconds(self.peaks)
                   for st, _ in steps)
        return 100.0 * need / (dev / 1e9)


def load_readers(names: list[str]) -> dict:
    """name -> read function, from ``metrics/<name>.py``."""
    here = Path(__file__).resolve().parent / "metrics"
    return {name: spec.module(here / f"{name}.py",
                              f"metric_{name.replace('.', '_')}").read
            for name in names}
