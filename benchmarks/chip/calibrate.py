"""Readings for the output check's limit: the program's widest logit gap
over a dozen seeds or more, and the fp8 control's, in one process.

    python benchmarks/chip/calibrate.py --workload qwen1.5-4b-q8.chat \
        --seeds 101,102,...,112 --seconds 20 [--control-seeds 101,102,103]

Each seed drives the warmed session for one short window at the cell's
own load (long enough to finish the mix's longest requests), then the
same seeded sample that a run compares goes through the reference; for
the control seeds the fp8 control is read on the same prompts and served
tokens.  Lines go to stderr and ``chiprun_out/calibrate_<workload>.json``.
The lower reading is the largest program gap, the upper the smallest
control gap; the cell's ``logit_gap_limit`` is set between them by hand.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop rate instead of the cell's")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import loop
    import spec
    from harness import Bench, log
    bench = Bench(args.workload, rehearse=args.rehearse, t_start=T_START)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        w = bench.window(seed, args.seconds, rate=args.rate)
        loop.drain(bench.session)
        t0 = time.perf_counter()
        got = bench.check(w, seed, control=seed in control)
        got.update(seed=seed, sent=len(w.sent),
                   reference_s=time.perf_counter() - t0)
        rows.append(got)
        log(f"calibrate {json.dumps(got)}")
    prog = [r["program"] for r in rows if "program" in r]
    ctl = [r["control"] for r in rows if "control" in r]
    summary = {"workload": args.workload, "rows": rows,
               "lower": max(prog) if prog else None,
               "upper": min(ctl) if ctl else None}
    out = spec.ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"calibrate_{args.workload}.json").write_text(
        json.dumps(summary, indent=1))
    print(json.dumps({k: summary[k] for k in ("lower", "upper")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
