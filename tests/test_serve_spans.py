"""The scheduler's profiler spans and program names, in slot and paged
mode: every ``serve.*`` span lies inside a ``serve.step``, each admitted
request has one ``serve.admit`` with its id and wait, each decode step one
``serve.fetch`` and one ``serve.sample``, tracing leaves the greedy tokens
as they were, and the decode program is ``jit_serve_decode``."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.models.transformer import init_params
from repro.serve.session import ServeConfig, ServeSession

MODES = {"slots": ServeConfig(slots=2, max_len=64),
         "paged": ServeConfig(slots=2, max_len=64, kv_page_size=8)}
PROMPTS = ((5, 4), (9, 3), (7, 5))       # (prompt length, new tokens)


@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("llama3-8b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _serve(cfg, params, serve_cfg):
    """Three requests through two slots, so the third waits in the queue;
    returns the session, the number of steps and the greedy tokens."""
    session = ServeSession(cfg, params, serve_cfg=serve_cfg)
    rng = np.random.default_rng(0)
    handles = [session.submit(rng.integers(0, cfg.vocab_size, n), k)
               for n, k in PROMPTS]
    steps = 0
    while session.pending:
        session.step()
        steps += 1
    return session, steps, [list(h.result()) for h in handles]


def _serve_spans(trace_dir) -> list:
    """(name, start, end, stats) of the ``serve.*`` host events."""
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda sp: sp[1])


def _inside(spans, outer, name):
    return [sp for sp in spans if sp[0] == name
            and outer[1] <= sp[1] and sp[2] <= outer[2]]


@pytest.mark.parametrize("mode", sorted(MODES))
def test_spans_nest_count_and_keep_tokens(smoke, mode, tmp_path):
    cfg, params = smoke
    _, _, untraced = _serve(cfg, params, MODES[mode])
    with jax.profiler.trace(str(tmp_path)):
        session, n_steps, traced = _serve(cfg, params, MODES[mode])
    assert traced == untraced

    spans = _serve_spans(str(tmp_path))
    steps = [sp for sp in spans if sp[0] == "serve.step"]
    assert len(steps) == n_steps
    assert sum(sp[3]["rows"] for sp in steps) == session.stats["decode_rows"]
    for sp in spans:
        assert any(st[1] <= sp[1] and sp[2] <= st[2] for st in steps), sp

    admits = [sp for sp in spans if sp[0] == "serve.admit"]
    assert sorted(sp[3]["req"] for sp in admits) == list(range(len(PROMPTS)))
    assert all(sp[3]["wait_us"] >= 0 for sp in admits)
    # the third request waited for a slot: its wait spans whole steps
    assert admits[-1][3]["req"] == 2
    assert admits[-1][3]["wait_us"] > max(sp[3]["wait_us"]
                                         for sp in admits[:-1])
    for sp in admits:
        prefill, = _inside(spans, sp, "serve.prefill")
        assert prefill[3]["tokens"] == PROMPTS[sp[3]["req"]][0]
        assert len(_inside(spans, sp, "serve.fetch")) == 1
        assert len(_inside(spans, sp, "serve.sample")) == 1

    decode_only = 0
    for st in steps:
        n_admit = len(_inside(spans, st, "serve.admit"))
        decoded = int(st[3]["rows"] > 0)
        assert len(_inside(spans, st, "serve.dispatch")) == decoded
        assert len(_inside(spans, st, "serve.pack")) == decoded
        assert len(_inside(spans, st, "serve.fetch")) == n_admit + decoded
        assert len(_inside(spans, st, "serve.sample")) == n_admit + decoded
        decode_only += decoded and not n_admit
    assert decode_only >= 3


@pytest.mark.parametrize("mode", sorted(MODES))
def test_decode_program_is_named(smoke, mode):
    cfg, params = smoke
    session = ServeSession(cfg, params, serve_cfg=MODES[mode])
    rows = session.serve_cfg.slots
    tok = pos = jnp.zeros(rows, jnp.int32)
    if mode == "paged":
        pages = jnp.zeros((rows, session._kv.n_max), jnp.int32)
        lowered = session._decode_paged.lower(session.params,
                                              session._kv.pools, pages,
                                              tok, pos)
    else:
        lowered = session._decode.lower(session.params, session._caches,
                                        tok, pos)
    assert lowered.as_text().startswith("module @jit_serve_decode ")
