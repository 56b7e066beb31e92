"""Find an open-loop cell's knee: the highest offered rate the session
sustains.  One process, one warmed session, one window per rate:

    python benchmarks/chip/sweep.py --workload qwen1.5-4b-q8.chat \
        --rates 0.5,1,1.5,2 --seconds 30 --seed 7

For each rate it prints the end-to-end numbers and the backlog at the
window's close (requests due but not yet answered with a first token)
and writes them to ``chiprun_out/sweep_<workload>.json``.  A rate is
sustained when the backlog stays near zero and TTFT does not grow with
the window.  The cell's ``rate_per_s`` is set by hand from this, once.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import loop
    import spec
    from harness import Bench, end_to_end, log
    bench = Bench(args.workload, rehearse=args.rehearse, t_start=T_START)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        w = bench.window(args.seed, args.seconds, rate=rate)
        e2e = end_to_end(w, args.seconds)
        backlog = sum(1 for s in w.sent
                      if not s.times or s.times[0] > args.seconds)
        unsent = sum(1 for s in w.sent if s.req.due > args.seconds)
        half = [s.times[0] - s.req.due for s in w.sent
                if s.times and s.req.due >= args.seconds / 2]
        row = dict(rate=rate, sent=len(w.sent), backlog=backlog,
                   late_half_ttft_p50_ms=(1e3 * sorted(half)[len(half) // 2]
                                          if half else None),
                   unsent=unsent, **e2e)
        rows.append(row)
        log(f"sweep {json.dumps(row)}")
        loop.drain(bench.session)
    out = spec.ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"sweep_{args.workload}.json").write_text(json.dumps(rows,
                                                                indent=1))
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
