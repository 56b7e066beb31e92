"""One cell, set up once: runtime, device check, model, weights, session,
warm-up; then windows of traffic, their metrics and the output check.

``run.py`` drives one window per process; ``calibrate.py`` and
``sweep.py`` drive several windows through the same session.
"""

from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

import spec
import traffic

REHEARSE_SCALE = 8      # rehearsal lengths: the mix's divided by this


class CompileClock:
    """Sums XLA backend compile time (persistent-cache reads included)
    and counts cache hits, through JAX's monitoring hooks."""

    def __init__(self):
        import jax.monitoring as mon
        self.secs, self.programs, self.hits = 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.secs, self.programs, self.hits


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def start_runtime(rehearse: bool, chips: int):
    """Compile cache and strict rounding before JAX starts, then the
    device check: without the TPUs the cell asks for, exit non-zero
    before any result."""
    sys.path.insert(0, str(spec.ROOT / "src"))
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from repro.launch.runtime import configure_runtime
    try:
        cache = configure_runtime()
    except RuntimeError:
        if not rehearse:
            raise
        # a rehearsal inside a process whose JAX already started (a test)
        cache = "(left as the process set it)"
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu"
                         or len(devices) < chips):
        log(f"needs {chips} TPU device(s); JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s)")
        raise SystemExit(2)
    log(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={cache}")
    return devices


def _policy(dep: dict, rehearse: bool):
    from repro import kernels
    k = dep["kernels"]
    pol = kernels.KernelPolicy(strict=k["strict"],
                               use_tuning_cache=k["use_tuning_cache"])
    for op, impl in k["pin"].items():
        pol = pol.override(op, "interpret" if rehearse and impl == "pallas"
                           else impl)
    return pol


def model(cell: spec.Cell, rehearse: bool):
    """(ModelConfig, sizes): the registry entry with the published
    rope_theta and norm epsilon, its widths checked against the file by
    the cell's family.  A rehearsal takes the registry's smoke preset at
    the full model's dtypes."""
    from repro import configs
    conf, fam, sizes = cell.config, cell.family, cell.sizes
    cfg = configs.get(conf["model"])
    if rehearse:
        cfg = configs.get(conf["model"], smoke=True).replace(
            param_dtype=cfg.param_dtype, compute_dtype=cfg.compute_dtype)
        sizes = dict(sizes, **fam.smoke_sizes(cfg))
    else:
        wrong = fam.check_registry(cfg, sizes)
        if wrong:
            raise SystemExit(f"{conf['model']}: registry differs from the "
                             f"configuration file: {wrong}")
    cfg = cfg.replace(kernels=_policy(conf["deployment"], rehearse),
                      rope_theta=float(sizes["rope_theta"]),
                      norm_eps=float(sizes["norm_eps"]))
    return cfg, sizes


class Bench:
    """A built cell: weights, a warmed session, and its traffic mix."""

    def __init__(self, workload: str, *, rehearse: bool = False,
                 t_start: float | None = None):
        self.t_start = t_start if t_start is not None else time.perf_counter()
        self.cell = spec.load(workload)
        self.devices = start_runtime(rehearse, self.cell.chips)
        import jax
        from repro import kernels
        from repro.serve.session import ServeConfig, ServeSession
        import weights

        self.clock = CompileClock()
        self.cfg, self.sizes = model(self.cell, rehearse)
        mix = self.cell.mix
        if rehearse:
            mix = traffic.scaled(mix, REHEARSE_SCALE)
        self.mix = mix
        dep = self.cell.config["deployment"]
        self.slots = spec.slots(self.cell.config, self.cell.mix)
        t0 = time.perf_counter()
        self.tree = jax.block_until_ready(
            weights.make_weights(self.cell.family, self.sizes,
                                 dep["weight_seed"]))
        n_bytes = sum(x.nbytes for x in jax.tree.leaves(self.tree))
        log(f"weights: {n_bytes / 2**30:.3f} GiB in "
            f"{time.perf_counter() - t0:.2f}s")
        self.serve_cfg = ServeConfig(
            slots=self.slots, max_len=mix["max_len"],
            prefill_buckets=tuple(mix["prefill_buckets"]),
            kv_page_size=dep["kv_page_size"],
            kv_prefix_sharing=dep["kv_prefix_sharing"])
        kernels.clear_dispatch_report()
        self.session = ServeSession(self.cfg, self.tree,
                                    backend=dep["residency"],
                                    serve_cfg=self.serve_cfg)
        self.warm_up()
        self.check_plan()
        secs, n, hits = self.clock.snapshot()
        self.setup_s = time.perf_counter() - self.t_start
        log(f"setup: {self.setup_s:.3f}s; compile {secs:.3f}s ({n} programs,"
            f" {hits} from the persistent cache); slots={self.slots} "
            f"max_len={mix['max_len']} buckets={mix['prefill_buckets']} "
            f"kv={self.session.kv_report()['device_bytes'] / 2**30:.3f}GiB")

    # -- set-up ---------------------------------------------------------------

    def warm_up(self) -> None:
        """Every program the window can meet and no other: the exact and
        the padded admission prefill of each bucket (with its page
        scatter), then each decode batch size from ``slots`` down to 1."""
        from loop import drain
        rng = np.random.default_rng(0)
        vocab = self.sizes["vocab_size"]
        buckets = self.mix["prefill_buckets"]
        prev = 0
        for b in buckets:
            for n in ([b, b - 1] if b - 1 > prev else [b]):
                self.session.submit(rng.integers(0, vocab, n), 1)
            prev = b
        drain(self.session)
        for i in range(self.slots):
            self.session.submit(rng.integers(0, vocab, buckets[0]), 2 + i)
        drain(self.session)

    def check_plan(self) -> None:
        """Print the kernel plan; a fallback fails the run."""
        from repro import kernels
        report = kernels.dispatch_report()
        for rec in report:
            log(f"dispatch {rec['kind']}: {rec['op']} "
                f"{rec['requested'] or 'default'} -> {rec['impl']} "
                f"({rec['reason']})")
        bad = [r for r in report if r["kind"] == "fallback"]
        if bad:
            raise SystemExit(f"{len(bad)} kernel fallback(s) in the plan")

    # -- windows --------------------------------------------------------------

    def window(self, seed: int, seconds: float, *, rate: float | None = None,
               trace_span=None, trace_dir=None):
        import loop
        mix, vocab = self.mix, self.sizes["vocab_size"]
        if mix["loop"] == "open":
            rate = rate if rate is not None else self.cell.cell["rate_per_s"]
            sched = traffic.open_loop(mix, rate, seconds, seed, vocab)
            return loop.run(self.session, seconds=seconds, schedule=sched,
                            trace_span=trace_span, trace_dir=trace_dir)
        return loop.run(self.session, seconds=seconds,
                        closed=traffic.ClosedLoop(mix, seed, vocab),
                        clients=self.slots, trace_span=trace_span,
                        trace_dir=trace_dir)

    def memory_peak(self) -> int:
        stats = self.devices[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def free_session(self) -> None:
        self.session.close()
        self.session = None
        gc.collect()

    def check(self, w, seed: int, control: bool = False) -> dict:
        import check
        k = self.mix["check_requests"]
        chosen = check.sample(w, k, seed)
        out = {"bad_requests": check.bad_requests(w, self.sizes["vocab_size"])}
        if chosen:
            out.update(check.logit_gaps(self.cell.family, self.tree,
                                        self.sizes, chosen, self.mix, k,
                                        control=control))
        return out


def end_to_end(w, seconds: float) -> dict:
    """The client-side numbers of one window (milliseconds, tokens/s)."""
    # every request due in the window, its first token awaited past the
    # close if need be (``loop.run``)
    ttft = [s.times[0] - s.req.due for s in w.sent if s.times]
    gaps = [b - a for s in w.sent for a, b in zip(s.times, s.times[1:])
            if b <= seconds]
    toks = sum(1 for s in w.sent for t in s.times if t <= seconds)
    out = {"output_tok_s": toks / seconds, "first_tokens": len(ttft),
           "first_after_close": sum(1 for s in w.sent
                                    if s.times and s.times[0] > seconds),
           "gaps": len(gaps)}
    if ttft:
        out["ttft_p90_ms"] = 1e3 * float(np.percentile(ttft, 90))
    if gaps:
        out["itl_p50_ms"] = 1e3 * float(np.percentile(gaps, 50))
        out["itl_p95_ms"] = 1e3 * float(np.percentile(gaps, 95))
    return out


def describe(w, session_stats: dict) -> None:
    """The window's counts on stderr: requests, steps, lateness."""
    late = np.asarray(w.lateness) if w.lateness else np.zeros(1)
    dec = [s for s in w.steps if s.decode_only]
    log(f"window: {len(w.sent)} requests sent, "
        f"{sum(s.handle.done for s in w.sent)} finished; {len(w.steps)} "
        f"steps ({len(dec)} decode-only), mean decode rows "
        f"{np.mean([s.rows for s in dec]) if dec else 0:.2f}; "
        f"{len(w.waits)} waits")
    log(f"generator lateness: p50 {1e3 * np.percentile(late, 50):.3f}ms "
        f"max {1e3 * late.max():.3f}ms")
    log(f"session.stats: {session_stats}")
