"""The output check fails what it must: a whole rehearsed run (the
harness's look for a chip skipped, everything else as on the chip) reads
``correct`` true on the program as it is, and false with the timed path
broken underneath it; the fp8 control reads above the limit that the
program reads below."""

import json

import numpy as np
import pytest

import calibrate
import run
import spec
from repro.serve import session as session_mod

CELL = "qwen1.5-4b-q8.chat"
ARGS = ["--workload", CELL, "--seconds", "4", "--rehearse"]


def result(capsys, seed):
    assert run.main(ARGS + ["--seed", str(seed)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_program_is_correct(capsys):
    r = result(capsys, 2**31 + 11)
    assert r["correct"], r["checks"]
    assert r["device"]["platform"] == "cpu" and r["metrics"] == {}


def test_altered_token_is_caught(capsys, monkeypatch):
    sample = session_mod.ServeSession._sample

    def altered(self, row, req):
        tok = sample(self, row, req)
        # the fourth token of every request, altered where it is produced
        return (tok + 1) % row.shape[-1] if len(req.tokens) == 3 else tok
    monkeypatch.setattr(session_mod.ServeSession, "_sample", altered)
    r = result(capsys, 12)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"][
        "limit"]


def test_step_returning_its_state_unchanged_is_caught(capsys, monkeypatch):
    decode = session_mod.decode_step

    def stale(params, cfg, caches, pos, **kw):
        logits, _ = decode(params, cfg, caches, pos, **kw)
        return logits, caches            # the KV write of the step dropped
    monkeypatch.setattr(session_mod, "decode_step", stale)
    r = result(capsys, 13)
    assert not r["correct"]


def test_control_reads_above_the_limit(capsys):
    assert calibrate.main(["--workload", CELL, "--seeds", "21,22",
                           "--control-seeds", "21", "--seconds", "4",
                           "--rehearse"]) == 0
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limit = spec.load(CELL).cell["logit_gap_limit"]
    assert np.isfinite(got["upper"]) and got["upper"] >= 3 * got["lower"]
    assert got["lower"] <= limit < got["upper"]


@pytest.fixture(autouse=True)
def _fresh_reports():
    from repro import kernels
    kernels.clear_dispatch_report()
    yield
