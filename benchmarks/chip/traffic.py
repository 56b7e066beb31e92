"""One general generator for every traffic mix.

A mix is a JSON file under ``traffic/`` (see ``spec.load``):

``loop``            ``"open"`` (arrivals on a schedule) or ``"closed"``
                    (each client resubmits when its answer completes)
``prompt_len``,     ``{"lognormal": {"median": m, "sigma": s},
``output_len``        "min": lo, "max": hi}`` in tokens
``max_len``,        the session's per-slot capacity and prompt buckets
``prefill_buckets``
``check_requests``  how many finished requests the output check compares

The load level (an open loop's ``rate_per_s``) lives in the cell's file,
since one mix runs at each model's own rate.

Every seed gets the same multiset of lengths and of inter-arrival gaps,
in one order: lengths are the distribution's quantiles at the stratified
points ``(i + 0.5) / n``, and gaps the exponential's quantiles (a Poisson
process with the count fixed at ``round(rate * seconds)``), each permuted
by :data:`ORDER_SEED`.  So every seed runs one schedule and draws only the
prompt tokens: when lengths and gaps were permuted by the seed, some
orders filled every slot and queued, and a cell's TTFT tail swung
fortyfold from seed to seed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

BLOCK = 64            # closed-loop requests are stratified per block
ORDER_SEED = 0        # the one order of lengths and gaps


@dataclass
class Request:
    prompt: np.ndarray           # (P,) int32
    max_new_tokens: int
    due: float = 0.0             # seconds after the window opens


def quantiles(dist: dict, n: int) -> np.ndarray:
    """Lengths at the stratified points of a clipped lognormal."""
    ln = dist["lognormal"]
    u = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    vals = np.round(ln["median"] * np.exp(ln["sigma"] * z))
    return np.clip(vals, dist["min"], dist["max"]).astype(np.int64)


def _requests(mix: dict, n: int, order, rng, vocab: int) -> list[Request]:
    prompts = order.permutation(quantiles(mix["prompt_len"], n))
    outputs = order.permutation(quantiles(mix["output_len"], n))
    return [Request(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]


def open_loop(mix: dict, rate: float, seconds: float, seed: int,
              vocab: int) -> list[Request]:
    """``round(rate * seconds)`` requests due over ``[0, seconds)``."""
    rng, order = (np.random.default_rng(seed),
                  np.random.default_rng(ORDER_SEED))
    n = max(1, round(rate * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u))
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    reqs = _requests(mix, n, order, rng, vocab)
    for r, t in zip(reqs, due):
        r.due = float(t)
    return reqs


class ClosedLoop:
    """An endless, seeded stream of requests for closed-loop clients,
    stratified per block of :data:`BLOCK`."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.vocab = mix, vocab
        self.rng = np.random.default_rng(seed)
        self.order = np.random.default_rng(ORDER_SEED)
        self._buf: list[Request] = []

    def next(self, now: float) -> Request:
        if not self._buf:
            self._buf = _requests(self.mix, BLOCK, self.order, self.rng,
                                  self.vocab)
        r = self._buf.pop(0)
        r.due = now
        return r


def scaled(mix: dict, factor: int) -> dict:
    """The mix with every length divided by ``factor`` (rehearsals at the
    smoke preset's sizes)."""
    out = dict(mix)

    def div(x):
        return max(1, math.ceil(x / factor))
    for key in ("prompt_len", "output_len"):
        d = dict(mix[key])
        d["lognormal"] = dict(d["lognormal"],
                              median=div(d["lognormal"]["median"]))
        d["min"], d["max"] = div(d["min"]), div(d["max"])
        out[key] = d
    out["max_len"] = div(mix["max_len"])
    out["prefill_buckets"] = [div(b) for b in mix["prefill_buckets"]]
    return out
