"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload qwen1.5-4b-q8.chat \
        --seed 1234 --seconds 51 --trace 0

Set-up (``setup_s``: process start to window start) builds the cell's
model from its configuration file, makes the q8 weights on the device
from the configuration's weight seed, opens a ``ServeSession`` and warms
up every program the cell's traffic can meet.  The window then drives
the session with the cell's traffic from ``--seed`` for ``--seconds``.
With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the profiler records a part of the window and the result
holds the per-layer metrics, ``busy_s``/``window_s`` and ``breakdown``.
After the window the session is freed and the served tokens of a seeded
sample of finished requests are compared with the plain reference
(``check.py``); each number compared and its limit end stderr and the
result line (``checks``).

Without the TPUs the cell asks for it exits non-zero and prints no
result.  ``--rehearse`` instead runs the cell end to end on the CPU at
the registry's smoke preset (Pallas kernels in interpret mode, lengths
divided by 8) and reports no device metric.  The last stdout line is the
result's JSON object.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

TRACE_AT, TRACE_SECONDS = 0.4, 8.0   # traced part: from 40% of the window


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, smoke preset; no device metric")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the recorded trace, and the window's "
                    "steps as steps.json, here")
    return ap.parse_args(argv)


def traced_metrics(bench, w, trace_dir):
    """Per-layer metrics, device busy/window and breakdown of the traced
    part of window ``w``."""
    import readers
    import spec
    from trace import Trace, find_xplane
    tr = Trace.from_xplane(find_xplane(trace_dir))
    spans = tr.steps()
    steps = [(st, spans[st.index]) for st in w.steps if st.index in spans]
    start, end = ((min(sp.start for sp in tr.spans),
                   max(sp.end for sp in tr.spans)) if tr.spans
                  else tr.window())
    run = readers.Traced(bench.cell.family, bench.sizes,
                         spec.peaks(bench.devices[0].device_kind),
                         bench.mix["prefill_buckets"], steps, tr, start, end)
    metrics = {}
    read = readers.load_readers([m["name"] for m in bench.cell.per_layer])
    units = {m["name"]: m["unit"] for m in bench.cell.per_layer}
    for name, fn in read.items():
        v = fn(run)
        if v is not None:
            metrics[name] = {"value": v, "unit": units[name]}
    device = {"busy_s": tr.busy_ns(start, end) / 1e9,
              "window_s": (end - start) / 1e9}
    breakdown = {"device_ops": tr.top_ops(start, end),
                 "idle_gaps": tr.idle_gaps(start, end)}
    from harness import log
    log(f"trace: {len(tr.ops)} device ops, {len(steps)} steps traced; "
        f"dequant_matmul events {tr.kernel_count(readers.KERNELS['dequant_matmul'])}, "
        f"flash events {tr.kernel_count(readers.KERNELS['flash_attention'])}")
    return metrics, device, breakdown


def main(argv=None) -> int:
    args = parse(argv)
    from harness import Bench, describe, end_to_end, log
    bench = Bench(args.workload, rehearse=args.rehearse, t_start=T_START)
    cell = bench.cell
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if args.trace \
        else None
    span = ((TRACE_AT * args.seconds, min(TRACE_SECONDS, args.seconds / 2))
            if args.trace else None)
    c0 = bench.clock.snapshot()
    w = bench.window(args.seed, args.seconds, trace_span=span,
                     trace_dir=trace_dir)
    c1 = bench.clock.snapshot()
    log(f"compiles inside the window: {c1[1] - c0[1]} programs, "
        f"{c1[0] - c0[0]:.3f}s")
    describe(w, dict(bench.session.stats))
    peak = bench.memory_peak()
    log(f"peak device memory: {peak} bytes ({peak / 2**30:.3f} GiB)")
    bench.check_plan()
    e2e = end_to_end(w, args.seconds)
    log(f"end to end: {e2e}")

    dev = bench.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(bench.devices), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if args.trace:
        if args.keep_trace:
            shutil.copytree(trace_dir, args.keep_trace, dirs_exist_ok=True)
            steps = [dataclasses.asdict(st) for st in w.steps]
            Path(args.keep_trace, "steps.json").write_text(json.dumps(
                {"workload": args.workload, "steps": steps}))
        if not args.rehearse:
            metrics, extra, breakdown = traced_metrics(bench, w, trace_dir)
            device.update(extra)
        shutil.rmtree(trace_dir, ignore_errors=True)
    elif not args.rehearse:
        e2e["setup_s"] = bench.setup_s
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    bench.free_session()
    t0 = time.perf_counter()
    got = bench.check(w, args.seed)
    limit = cell.cell["logit_gap_limit"]
    checks = {"bad_requests": {"value": got["bad_requests"], "limit": 0}}
    if "program" in got:
        checks["logit_gap"] = {"value": got["program"], "limit": limit}
    log(f"reference over {got.get('tokens', 0)} served tokens in "
        f"{time.perf_counter() - t0:.2f}s")
    correct = ("logit_gap" in checks and got["bad_requests"] == 0
               and got["program"] <= limit)
    result = {"correct": correct, "attempted": len(w.sent),
              "failed": got["bad_requests"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
