"""Paged decode updates the KV page pool in place.

The layer scan carries the stacked pools (L, P, page, ...) whole, each
layer writes its token and gathers its rows' views at its own layer
index (``models.attention._paged_update_load``), and the serving decode
and scatter programs donate the pool.  These tests pin that the results
are bit-identical to the per-layer formulation (each layer's pool sliced
out of the stack as the scan's ``xs`` and re-stacked as its ``ys``), that
padding rows write only the scratch page, that the compiled programs
alias the pool instead of copying it, and that a session never touches a
donated pool.
"""

import contextlib
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import get_smoke_config
from repro.models import attention, transformer
from repro.models.transformer import decode_step, init_cache, init_params
from repro.serve.session import ServeConfig, ServeSession

PAGE, POOL_PAGES = 4, 16

# (rows' page-table rows, rows' positions): three live rows at different
# offsets (one at a page start, one mid-page, one on its third page) and
# padding rows aimed at the scratch page 0, as the session packs them
TABLE = np.array([[1, 2, 0, 0], [3, 0, 0, 0], [4, 5, 6, 0],
                  [0, 0, 0, 0], [0, 0, 0, 0]], np.int32)
POS = np.array([5, 0, 11, 0, 0], np.int32)
LIVE = 3

CASES = {
    "gqa-bias": ("qwen1.5-4b", {}),
    "gqa-bias-bf16": ("qwen1.5-4b", {"compute_dtype": "bfloat16",
                                     "param_dtype": "bfloat16"}),
    "gqa-4to1": ("llama3-8b", {"num_heads": 8, "num_kv_heads": 2}),
    "int8-kv": ("llama3-8b", {"q8_cache": True}),
    "mla-moe-first-dense": ("deepseek-v3-671b", {}),
    "moe-first-dense": ("deepseek-moe-16b", {}),
}


def _model(case):
    arch, over = CASES[case]
    cfg = get_smoke_config(arch).replace(**over)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _filled_pools(cfg, seed=1):
    """A pool whose every page holds distinct values, so a write or a
    read at the wrong place shows."""
    pools = init_cache(cfg, POOL_PAGES, PAGE)
    leaves, treedef = jax.tree.flatten(pools)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for k, leaf in zip(keys, leaves):
        if leaf.dtype == jnp.int8:
            out.append(jax.random.randint(k, leaf.shape, -100, 100,
                                          jnp.int32).astype(jnp.int8))
        else:
            out.append(jax.random.normal(k, leaf.shape,
                                         jnp.float32).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


def _ref_paged_update_load(pool, new_vals, cache_pos, cache_pages, layer,
                           delta, dtype):
    """The per-layer formulation: ``pool`` is one layer's (P, page, ...)
    slice; one scatter of each row's token, one gather of the views."""
    del layer
    b = cache_pages.shape[0]
    page_len = pool.shape[1]
    cp = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (b,))
    pid = jnp.take_along_axis(cache_pages, (cp // page_len)[:, None],
                              axis=1)[:, 0]
    pool = pool.at[pid, cp % page_len].set(
        attention._cache_store(new_vals, pool, delta)[:, 0])
    view = jnp.take(pool, cache_pages, axis=0)
    view = view.reshape(b, view.shape[1] * page_len, *view.shape[3:])
    return pool, attention._cache_load(view, dtype, delta)


def _ref_scan_stack(x, stacked, block, cfg, positions, pos3d, caches,
                    cache_pos, cache_pages=None):
    """The layer scan with each layer's pool as ``xs`` and the updated
    pools re-stacked as ``ys``."""
    dt = jnp.dtype(cfg.compute_dtype)
    body = functools.partial(block, cfg=cfg, positions=positions,
                             pos3d=pos3d, cache_pos=cache_pos,
                             cache_pages=cache_pages)

    def f(carry, xs):
        h, aux = carry
        lp, cache_l = xs
        h2, newc, a = body(h, transformer._fused_layer_params(lp, dt),
                           cache=cache_l)
        return (h2, aux + a), newc
    (x, aux), new_caches = lax.scan(
        f, (x, jnp.zeros((), jnp.float32)), (stacked, caches))
    return x, new_caches, aux


def _decode(cfg, params, pools, rows):
    fn = jax.jit(lambda p, pl, pg, tok, pos: decode_step(
        p, cfg, pl, pos, tokens=tok, cache_pages=pg))
    tok = jnp.arange(7, 7 + rows, dtype=jnp.int32)
    return fn(params, pools, jnp.asarray(TABLE[:rows]), tok,
              jnp.asarray(POS[:rows]))


@pytest.mark.parametrize("case", sorted(CASES))
def test_paged_decode_bit_identical_to_per_layer_formulation(case,
                                                             monkeypatch):
    cfg, params = _model(case)
    pools = _filled_pools(cfg)
    logits, new = _decode(cfg, params, pools, len(TABLE))
    monkeypatch.setattr(attention, "_paged_update_load",
                        _ref_paged_update_load)
    monkeypatch.setattr(transformer, "_scan_stack", _ref_scan_stack)
    ref_logits, ref_new = _decode(cfg, params, pools, len(TABLE))
    np.testing.assert_array_equal(np.asarray(logits[:LIVE]),
                                  np.asarray(ref_logits[:LIVE]))
    # every page but the scratch page, where padding rows collide
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(ref_new)):
        np.testing.assert_array_equal(np.asarray(a[:, 1:]),
                                      np.asarray(b[:, 1:]))


@pytest.mark.parametrize("case", ["gqa-4to1", "int8-kv",
                                  "mla-moe-first-dense"])
def test_padding_rows_write_only_the_scratch_page(case):
    cfg, params = _model(case)
    pools = _filled_pools(cfg)
    _, new = _decode(cfg, params, pools, len(TABLE))
    written = np.zeros((POOL_PAGES, PAGE), bool)
    for row in range(LIVE):
        written[TABLE[row, POS[row] // PAGE], POS[row] % PAGE] = True
    for old, upd in zip(jax.tree.leaves(pools), jax.tree.leaves(new)):
        old, upd = np.asarray(old), np.asarray(upd)
        changed = (old != upd).reshape(*old.shape[:3], -1).any(-1)
        # a live row's token lands in every layer; nothing else outside
        # the scratch page moves, in any layer
        assert changed[:, 1:].sum() == written[1:].sum() * old.shape[0]
        assert not (changed[:, 1:] & ~written[None, 1:]).any()


def _paged_session(cfg, params, **kw):
    kw = {"slots": 4, "max_len": 32, "kv_page_size": PAGE, **kw}
    return ServeSession(cfg, params, serve_cfg=ServeConfig(**kw))


@contextlib.contextmanager
def _donation_must_be_usable():
    """XLA warns, and copies, when it cannot reuse a donated buffer."""
    with warnings.catch_warnings():
        warnings.filterwarnings("error",
                                message=".*donated buffers were not usable")
        yield


@pytest.mark.parametrize("case", ["gqa-bias", "mla-moe-first-dense"])
def test_decode_and_scatter_programs_alias_the_pool(case):
    cfg, params = _model(case)
    s = _paged_session(cfg, params)
    pool_bytes = sum(l.nbytes for l in jax.tree.leaves(s._kv.pools))
    rows = 4
    with _donation_must_be_usable():
        decode = s._decode_paged.lower(
            s.params, s._kv.pools, jnp.zeros((rows, s._kv.n_max), jnp.int32),
            jnp.zeros(rows, jnp.int32), jnp.zeros(rows, jnp.int32)).compile()
        caches = jax.eval_shape(lambda: init_cache(cfg, 1, 2 * PAGE))
        scatter = s._scatter_paged.lower(
            s._kv.pools, caches, jnp.arange(1, 3, dtype=jnp.int32)).compile()
    assert decode.memory_analysis().alias_size_in_bytes >= pool_bytes
    assert scatter.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_session_rebinds_the_donated_pool():
    cfg, params = _model("gqa-bias")
    s = _paged_session(cfg, params)
    h = s.submit([1, 2, 3, 4, 5], max_new_tokens=3)
    with _donation_must_be_usable():
        before = s._kv.pools
        s.step()                   # admission scatter + first decode step
        assert all(l.is_deleted() for l in jax.tree.leaves(before))
        before = s._kv.pools
        s.step()
        assert all(l.is_deleted() for l in jax.tree.leaves(before))
        s.run(max_steps=20)
    assert h.done and len(h.result()) == 3
    assert not any(l.is_deleted() for l in jax.tree.leaves(s._kv.pools))
    assert s.kv_report()["device_bytes"] > 0


@pytest.mark.parametrize("restore_workers", [0, 1])
def test_cold_store_and_prefix_sharing_never_touch_a_donated_pool(
        restore_workers):
    """Pool pressure parks and resumes slots through the cold store, and
    a manual park/resume and shared prompt prefixes run between donated
    decode steps; the outputs stay those of a paged session that never
    parks (int8 pages round-trip the cold store bit-exactly)."""
    cfg = get_smoke_config("llama3-8b").replace(q8_cache=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    system = rng.integers(0, cfg.vocab_size, 8)
    prompts = [np.concatenate([system, rng.integers(0, cfg.vocab_size, n)])
               for n in (3, 5, 2, 6)]

    roomy = _paged_session(cfg, params)
    want = [roomy.submit(p, max_new_tokens=10) for p in prompts]
    roomy.run(max_steps=200)
    assert roomy.stats["parks"] == 0

    s = _paged_session(cfg, params, kv_pool_pages=16,
                       kv_restore_workers=restore_workers)
    got = [s.submit(p, max_new_tokens=10) for p in prompts]
    try:
        with _donation_must_be_usable():
            for _ in range(3):
                s.step()
            s.park(got[0])
            s.step()
            s.resume(got[0])
            s.run(max_steps=200)
            report = s.kv_report()
    finally:
        s.close()
        roomy.close()
    assert [list(h.result()) for h in got] == \
        [list(h.result()) for h in want]
    st = report["stats"]
    assert report["scheduler"]["parks"] > 1     # pressure, not only manual
    assert st["pages_restored"] > 0 and st["prefix_hits"] > 0
