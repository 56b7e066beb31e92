"""Request-level serving: ``ServeSession`` with continuous batching.

Clients ``submit(prompt, max_new_tokens, temperature)`` and receive
:class:`RequestHandle`\\ s; the scheduler packs active requests into a
KV cache (admission on free slot, eviction on EOS/length) and runs one
batched decode step per :meth:`ServeSession.step`, surfacing per-request
token streams via ``handle.new_tokens()``.

Two cache layouts:

* **Slot mode** (default): the session preallocates
  ``init_cache(cfg, slots, max_len)`` once.  A request is admitted by
  prefilling its prompt at batch=1 and scattering the resulting caches
  into its slot (axis 1 is the slot axis on every cache leaf).  Decode
  advances the *active* slots with per-slot ragged positions
  (``cache_pos`` as an (S,) int32 vector — see ``models.transformer``);
  free slots still occupy decode rows (their rows compute at position 0
  and are dead by construction), counted in ``stats["free_slot_rows"]``,
  and an all-free tick skips the decode call entirely.
* **Paged mode** (``ServeConfig.kv_page_size``): the cache is a page
  pool + per-slot page table (:mod:`repro.serve.kv`).  Decode batches
  are *compacted* — only active slots are gathered (padded to a
  power-of-two batch over the scratch page), so free slots never burn
  decode FLOPs.  Cold pages are entropy-coded (``kv-q8-cabac``) and
  evicted to a host cold store under pool pressure; parked requests
  restore through the lane-parallel batched decoder on re-admission, and
  page-aligned shared prompt prefixes prefill once
  (copy-on-write prefix sharing).

Weights come from a pluggable :mod:`backend <.backends>` (``bf16`` /
``q8`` / ``container``).  ``ServeEngine`` is a thin compatibility wrapper
over this class.

Every step writes ``serve.*`` spans (``jax.profiler.TraceAnnotation``)
and every jitted program carries a fixed name (``jit_serve_decode``, ...),
so a profiler trace splits a step's host time and names each program's
device time; see docs/serving_api.md "Tracing".
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..distributed.sharding import SERVE_RULES, activation_sharding
from ..models.config import ModelConfig
from ..models.transformer import decode_step, forward, init_cache, prefill
from .backends import _insert, resolve_backend
from .kv import PagedKV, kv_cache_bytes


@dataclass(frozen=True)
class ServeConfig:
    """Session knobs (model shape/quantization stays on ModelConfig)."""

    slots: int = 4                 # concurrent requests in the KV cache
    max_len: int = 512             # per-slot KV capacity (prompt + new)
    eos_token: int | None = None   # evict a request when it emits this id
    kv_cache_delta: float | None = None   # override the int8 KV grid step
    # (see serve.quantized.calibrate_kv_cache_delta); None keeps the
    # model config's value
    seed: int = 0                  # base seed for temperature sampling
    prefill_buckets: tuple = ()    # sorted prompt-length buckets: pad each
    # admission prefill up to the next bucket so XLA compiles once per
    # bucket instead of once per distinct prompt length.  Dense-family
    # only: padded tail tokens are causally invisible to the prompt and
    # their stale KV is masked/overwritten, but an SSM state or MoE
    # capacity routing would see them.

    # -- paged KV cache (docs/serving_api.md "Paged KV cache") ------------
    kv_page_size: int | None = None   # tokens per page; None = slot mode
    kv_pool_pages: int | None = None  # hot pool size; None sizes it for
    # every slot at max_len (no eviction pressure)
    kv_cold_store: str = "host"       # KVColdStore registry name/instance
    kv_evict_codec: str = "kv-q8-cabac"   # compression codec for cold pages
    kv_prefix_sharing: bool = True    # share page-aligned prompt prefixes
    kv_restore_workers: int = 0       # >0: entropy-decode restores on a
    # worker pool so decode latency hides behind the admission path


@dataclass
class RequestHandle:
    """Client-side view of one submitted request."""

    id: int
    prompt: np.ndarray             # (S,) int32
    max_new_tokens: int
    temperature: float = 0.0
    seed: object = None            # per-request sampling seed (int/tuple);
    # None derives from the session seed + request id
    tokens: list = field(default_factory=list)   # generated ids (incl. EOS)
    done: bool = False
    finish_reason: str | None = None     # "eos" | "length"
    submitted_s: float = 0.0       # time.perf_counter() at submit
    _stream_cursor: int = 0

    def new_tokens(self) -> list:
        """Drain this request's token stream (ids since the last call)."""
        out = self.tokens[self._stream_cursor:]
        self._stream_cursor = len(self.tokens)
        return out

    def result(self) -> np.ndarray:
        assert self.done, "request still in flight; run session.step()"
        return np.asarray(self.tokens, dtype=np.int32)


class _Slot:
    __slots__ = ("req", "pos", "next_token")

    def __init__(self):
        self.req: RequestHandle | None = None
        self.pos = 0               # where next_token's KV will be written
        self.next_token = 0        # token to feed on the next decode step

    def clear(self):
        self.req, self.pos, self.next_token = None, 0, 0


class ServeSession:
    """Continuous-batching serving session over a slot or paged KV cache."""

    def __init__(self, cfg: ModelConfig, weights, *, backend="bf16",
                 serve_cfg: ServeConfig | None = None,
                 preloaded: bool = False):
        serve_cfg = serve_cfg or ServeConfig()
        if serve_cfg.slots < 1 or serve_cfg.max_len < 1:
            raise ValueError(
                f"ServeConfig needs slots >= 1 and max_len >= 1; got "
                f"slots={serve_cfg.slots}, max_len={serve_cfg.max_len}")
        if serve_cfg.kv_cache_delta is not None:
            cfg = cfg.replace(kv_cache_delta=serve_cfg.kv_cache_delta)
        if serve_cfg.prefill_buckets and cfg.family != "dense":
            raise ValueError(
                "prefill_buckets pads prompts, which only dense-family "
                "models ignore (SSM state / MoE routing see pad tokens); "
                f"got family {cfg.family!r}")
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.backend = resolve_backend(backend)
        # preloaded: ``weights`` is already this backend's serving tree
        # (a ModelZoo admission that decoded or warm-forked it) — loading
        # again would double the cold-start cost and clobber the
        # backend's tracked delta levels
        self.params = weights if preloaded else self.backend.load(cfg,
                                                                  weights)

        self._slots = [_Slot() for _ in range(serve_cfg.slots)]
        self._queue: deque[RequestHandle] = deque()
        self._ids = itertools.count()
        self._rngs: dict[int, np.random.Generator] = {}
        self.stats = {
            "decode_steps": 0, "decode_rows": 0, "free_slot_rows": 0,
            "padded_rows": 0, "skipped_all_free_steps": 0,
            "prefill_tokens": 0, "prefix_reused_tokens": 0,
            "parks": 0, "resumes": 0, "admit_stalls": 0,
        }

        max_len = serve_cfg.max_len
        if any(b > max_len for b in serve_cfg.prefill_buckets):
            raise ValueError(f"prefill bucket exceeds max_len {max_len}")

        self._paged = serve_cfg.kv_page_size is not None
        if self._paged:
            self._caches = None        # no monolithic slot cache allocated
            self._kv = PagedKV(
                cfg, slots=serve_cfg.slots, max_len=max_len,
                page_size=serve_cfg.kv_page_size,
                pool_pages=serve_cfg.kv_pool_pages,
                cold_store=serve_cfg.kv_cold_store,
                codec=serve_cfg.kv_evict_codec,
                prefix_sharing=serve_cfg.kv_prefix_sharing,
                restore_workers=serve_cfg.kv_restore_workers)
            self._resume_q: deque = deque()     # (req, parked, pos, next)
            self._parked: dict = {}             # manual parks, by req id
            # the decode and scatter programs donate the pool: each writes
            # its pages in place, and the caller rebinds ``_kv.pools``
            self._decode_paged = self._jit(
                lambda p, pools, pages, tok, pos: decode_step(
                    p, cfg, pools, pos, tokens=tok, cache_pages=pages),
                "serve_decode", donate_argnums=1)
            self._prefill_fns: dict = {}        # cache_len -> jit
            self._prefill_pad_fns: dict = {}
            self._partial_fns: dict = {}        # n_ctx -> jit
            self._scatter_paged = jax.jit(
                _named(self._scatter_paged_impl, "serve_scatter"),
                donate_argnums=0)
        else:
            self._kv = None
            self._caches = init_cache(cfg, serve_cfg.slots, max_len)
            self._prefill = self._jit(
                lambda p, toks: prefill(p, cfg, tokens=toks,
                                        max_len=max_len), "serve_prefill")

            def prefill_padded(p, toks, last_idx):
                # padded admission: gather the last *real* prompt position
                # per row before the head projection (pad tail is causally
                # invisible, and the head only ever sees one position)
                caches = init_cache(cfg, toks.shape[0], max_len)
                logits, new_caches, _ = forward(p, cfg, tokens=toks,
                                                caches=caches,
                                                last_index=last_idx)
                return logits[:, 0, :], new_caches
            self._prefill_padded = self._jit(prefill_padded,
                                             "serve_prefill_padded")
            self._decode = self._jit(
                lambda p, caches, tok, pos: decode_step(p, cfg, caches, pos,
                                                        tokens=tok),
                "serve_decode")
            self._scatter = jax.jit(_named(self._scatter_impl,
                                           "serve_scatter"))

    def _jit(self, fn, name: str, donate_argnums=()):
        """``jax.jit`` of a model step as the program ``jit_<name>`` (its
        HLO module and its device-trace events carry that name), donating
        ``donate_argnums``.  When the backend placed the weights on a
        serving mesh, tracing runs under that mesh's serve sharding rules,
        so activation constraints and mesh-aware attention resolve against
        the devices the weights live on."""
        mesh = self.backend.mesh
        if mesh is None:
            return jax.jit(_named(fn, name), donate_argnums=donate_argnums)

        def on_mesh(*args):
            with activation_sharding(mesh, SERVE_RULES):
                return fn(*args)
        return jax.jit(_named(on_mesh, name), donate_argnums=donate_argnums)

    @classmethod
    def from_container(cls, cfg: ModelConfig, blob: bytes, *,
                       backend="container",
                       serve_cfg: ServeConfig | None = None
                       ) -> "ServeSession":
        """Build a session straight from a DCBC deployment artifact."""
        return cls(cfg, blob, backend=backend, serve_cfg=serve_cfg)

    @classmethod
    def from_loaded(cls, cfg: ModelConfig, params, *, backend,
                    serve_cfg: ServeConfig | None = None) -> "ServeSession":
        """Wrap an already-built serving tree.  ``backend`` must be the
        instance that produced ``params`` (its tracked levels, if any,
        describe exactly this tree), so delta swaps keep working."""
        return cls(cfg, params, backend=backend, serve_cfg=serve_cfg,
                   preloaded=True)

    # -- client API ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               temperature: float = 0.0, seed=None) -> RequestHandle:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must contain at least one token")
        if prompt.size + max_new_tokens > self.serve_cfg.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds slot capacity "
                f"{self.serve_cfg.max_len}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        req = RequestHandle(id=next(self._ids), prompt=prompt,
                            max_new_tokens=max_new_tokens,
                            temperature=temperature, seed=seed,
                            submitted_s=time.perf_counter())
        self._queue.append(req)
        return req

    @property
    def num_queued(self) -> int:
        return len(self._queue)

    @property
    def num_active(self) -> int:
        return sum(s.req is not None for s in self._slots)

    @property
    def num_parked(self) -> int:
        """Requests evicted to the compressed cold store (paged mode):
        auto-parked ones waiting to resume, plus manual :meth:`park`\\ s."""
        if not self._paged:
            return 0
        return len(self._resume_q) + len(self._parked)

    @property
    def pending(self) -> bool:
        active = bool(self._queue) or self.num_active > 0
        if self._paged:
            # manual parks (self._parked) wait for an explicit resume();
            # auto-parked requests re-admit themselves, so they count
            return active or bool(self._resume_q)
        return active

    def park(self, handle: RequestHandle) -> None:
        """Evict ``handle``'s slot to the compressed cold store.  The
        request keeps its sampling state and resumes **token-identically**
        (int8 caches round-trip bit-exactly) after :meth:`resume`."""
        if not self._paged:
            raise ValueError("park() needs the paged KV cache "
                             "(ServeConfig.kv_page_size)")
        idx = self._slot_of(handle)
        slot = self._slots[idx]
        parked = self._kv.park(idx)
        self._parked[handle.id] = (handle, parked, slot.pos,
                                   slot.next_token)
        slot.clear()
        self.stats["parks"] += 1

    def resume(self, handle: RequestHandle) -> None:
        """Queue a manually parked request for re-admission; its pages
        restore through the lane-parallel decoder on the next steps."""
        rec = self._parked.pop(handle.id, None)
        if rec is None:
            raise ValueError(f"request {handle.id} is not parked")
        self._kv.prefetch(rec[1])
        self._resume_q.append(rec)

    def cancel(self, handle: RequestHandle) -> bool:
        """Abort a request wherever it lives — queued, active, manually
        parked, or waiting to resume — releasing its slot/pages and, for
        parked requests, dropping the cold-store blob (a dir-backed
        store would otherwise keep the file until ``close()``).  Already
        finished requests are left alone (returns False)."""
        if handle.done:
            return False
        try:
            self._queue.remove(handle)
            return self._finish_cancelled(handle)
        except ValueError:
            pass
        if self._paged:
            rec = self._parked.pop(handle.id, None)
            if rec is not None:
                self._kv.discard(rec[1])
                return self._finish_cancelled(handle)
            for i, rec in enumerate(self._resume_q):
                if rec[0] is handle:
                    del self._resume_q[i]
                    self._kv.discard(rec[1])
                    return self._finish_cancelled(handle)
        for i, s in enumerate(self._slots):
            if s.req is handle:
                if self._paged:
                    self._kv.release(i)
                s.clear()
                return self._finish_cancelled(handle)
        raise ValueError(f"request {handle.id} is not known to this session")

    def _finish_cancelled(self, handle: RequestHandle) -> bool:
        handle.done = True
        handle.finish_reason = "cancelled"
        self._rngs.pop(handle.id, None)
        return True

    def _slot_of(self, handle: RequestHandle) -> int:
        for i, s in enumerate(self._slots):
            if s.req is handle:
                return i
        raise ValueError(f"request {handle.id} holds no slot")

    def swap_weights(self, source) -> int:
        """Swap in a delta ("P-frame") checkpoint step at a batch
        boundary: the backend decodes the step's residual records against
        its tracked base levels (``WeightBackend.apply_delta``) and the
        updated leaves replace their counterparts in ``self.params``.

        In-flight requests keep their slots and KV caches — the next
        :meth:`step` simply decodes with the new weights.  Leaf shapes,
        dtypes and the tree structure are unchanged by construction (a
        delta step is coded on the base frame's grid), so the jitted
        prefill/decode functions don't recompile.  The backend must have
        been built with ``track_levels=True`` and loaded from the chain's
        base frame.  Returns the number of updated tensors."""
        updates = self.backend.apply_delta(self.cfg, source)
        for name, leaf in updates.items():
            _insert(self.params, name, leaf)
        return len(updates)

    def run(self, max_steps: int | None = None) -> None:
        """Step until every submitted request finished (or max_steps)."""
        steps = 0
        while self.pending:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break

    def close(self) -> None:
        """Release the paged cache's cold store (no-op in slot mode)."""
        if self._paged:
            self._kv.close()

    # -- capacity accounting (one source of truth for bench + admission) ----

    def kv_bytes_per_slot(self) -> int:
        """Device KV bytes one request at full ``max_len`` context costs —
        derived from the real cache shapes via ``jax.eval_shape``, never
        recomputed by hand (``serve.kv.kv_cache_bytes``)."""
        return kv_cache_bytes(self.cfg, 1, self.serve_cfg.max_len)

    def kv_report(self) -> dict:
        """Total-KV accounting: device-resident bytes plus compressed
        host bytes, the per-slot cost, and the scheduler counters."""
        if self._paged:
            r = self._kv.report()
        else:
            r = {"mode": "slots",
                 "device_bytes": int(sum(
                     l.nbytes for l in jax.tree.leaves(self._caches))),
                 "host_compressed_bytes": 0}
        r["slots"] = len(self._slots)
        r["max_len"] = self.serve_cfg.max_len
        r["bytes_per_slot"] = self.kv_bytes_per_slot()
        r["scheduler"] = dict(self.stats)
        return r

    # -- scheduler -----------------------------------------------------------

    def step(self) -> None:
        """One scheduler tick: admit onto free slots, then one batched
        decode step, then evict finished requests.  In slot mode the
        decode batch spans every slot; in paged mode it is compacted to
        the active ones."""
        with TraceAnnotation("serve.step") as span:
            rows = self.stats["decode_rows"]
            if self._paged:
                self._step_paged()
            else:
                self._step_slots()
            span.set_metadata(rows=self.stats["decode_rows"] - rows)

    def _step_slots(self) -> None:
        self._admit()
        if self.num_active == 0:
            self.stats["skipped_all_free_steps"] += 1
            return
        active = [i for i, s in enumerate(self._slots) if s.req is not None]
        with TraceAnnotation("serve.pack", rows=len(self._slots)):
            tok = np.zeros(len(self._slots), np.int32)
            pos = np.zeros(len(self._slots), np.int32)
            for i in active:
                tok[i] = self._slots[i].next_token
                pos[i] = self._slots[i].pos
            tok, pos = jnp.asarray(tok), jnp.asarray(pos)
        self.stats["decode_steps"] += 1
        self.stats["decode_rows"] += len(self._slots)
        self.stats["free_slot_rows"] += len(self._slots) - len(active)
        with TraceAnnotation("serve.dispatch"):
            logits, self._caches = self._decode(self.params, self._caches,
                                                tok, pos)
        self._advance(logits, [(i, i) for i in active])

    def _advance(self, logits, rows: list) -> None:
        """Copy a decode step's logits to the host and sample each live
        row's next token into its slot; ``rows`` holds (logits row, slot
        index) pairs."""
        with TraceAnnotation("serve.fetch"):
            logits = np.asarray(logits)
        with TraceAnnotation("serve.sample"):
            for j, i in rows:
                slot = self._slots[i]
                slot.pos += 1
                nxt = self._sample(logits[j], slot.req)
                slot.req.tokens.append(nxt)
                slot.next_token = nxt
                self._maybe_evict(slot, i)

    def _admit(self) -> None:
        """Admit queued requests onto free slots.  The FIFO prefix sharing
        one (bucketed) prefill length is admitted as a single batched
        prefill — so a same-length burst (the ServeEngine wrapper's whole
        batch) costs one forward pass, not one per request."""
        while self._queue:
            free = [i for i, s in enumerate(self._slots) if s.req is None]
            if not free:
                return
            with _admit_span(self._queue[0]):
                self._admit_group(free)

    def _admit_group(self, free: list) -> None:
        length = self._bucket_len(self._queue[0].prompt.size)
        group = []
        for req in itertools.islice(self._queue, len(free)):
            if self._bucket_len(req.prompt.size) != length:
                break
            group.append(req)
        for _ in group:
            self._queue.popleft()
        slots_idx = free[:len(group)]

        with TraceAnnotation("serve.prefill", tokens=len(group) * length):
            toks = np.zeros((len(group), length), np.int32)
            for j, req in enumerate(group):
                toks[j, :req.prompt.size] = req.prompt
            if any(req.prompt.size < length for req in group):
                logits, caches_g = self._prefill_padded(
                    self.params, jnp.asarray(toks),
                    jnp.asarray([r.prompt.size - 1 for r in group],
                                jnp.int32))
            else:
                logits, caches_g = self._prefill(self.params,
                                                 jnp.asarray(toks))
            self._place(caches_g, slots_idx)
        with TraceAnnotation("serve.fetch"):
            logits = np.asarray(logits)
        with TraceAnnotation("serve.sample"):
            for j, req in enumerate(group):
                i = slots_idx[j]
                slot = self._slots[i]
                first = self._sample(logits[j], req)
                req.tokens.append(first)
                slot.req = req
                slot.pos = req.prompt.size
                slot.next_token = first
                self.stats["prefill_tokens"] += length
                self._maybe_evict(slot, i)

    def _place(self, caches_g, slots_idx: list) -> None:
        """Scatter a batch-k prefill's caches into slots ``slots_idx``:
        one contiguous write when the slots are adjacent (the common case
        on an idle session), per-row writes otherwise."""
        if slots_idx == list(range(slots_idx[0],
                                   slots_idx[0] + len(slots_idx))):
            self._caches = self._scatter(
                self._caches, caches_g,
                jnp.asarray(slots_idx[0], jnp.int32))
            return
        for j, slot_i in enumerate(slots_idx):
            row = jax.tree.map(lambda a: a[:, j:j + 1], caches_g)
            self._caches = self._scatter(self._caches, row,
                                         jnp.asarray(slot_i, jnp.int32))

    def _maybe_evict(self, slot: _Slot, idx: int) -> None:
        req = slot.req
        eos = self.serve_cfg.eos_token
        if eos is not None and req.tokens[-1] == eos:
            req.finish_reason = "eos"
        elif len(req.tokens) >= req.max_new_tokens:
            req.finish_reason = "length"
        elif slot.pos >= self.serve_cfg.max_len:
            req.finish_reason = "length"
        else:
            return
        req.done = True
        self._rngs.pop(req.id, None)
        if self._paged:
            self._kv.release(idx)
        slot.clear()

    # -- paged scheduler -----------------------------------------------------

    def _step_paged(self) -> None:
        self._admit_paged()
        active = [i for i, s in enumerate(self._slots) if s.req is not None]
        if not active:
            self.stats["skipped_all_free_steps"] += 1
            return
        # page-boundary allocation; a slot the pool can't grow parks
        # itself (compressed to host) and re-admits when pressure clears
        with TraceAnnotation("serve.pages"):
            still = []
            for i in active:
                if self._kv.ensure_writable(i, self._slots[i].pos):
                    still.append(i)
                else:
                    self._auto_park(i)
        active = still
        if not active:
            return
        bs = min(1 << (len(active) - 1).bit_length(), len(self._slots))
        with TraceAnnotation("serve.pack", rows=bs):
            tok = np.zeros(bs, np.int32)
            pos = np.zeros(bs, np.int32)
            pages = np.zeros((bs, self._kv.n_max), np.int32)  # pads: scratch
            for j, i in enumerate(active):
                tok[j] = self._slots[i].next_token
                pos[j] = self._slots[i].pos
                pages[j] = self._kv.page_row(i)
            pages, tok, pos = (jnp.asarray(pages), jnp.asarray(tok),
                               jnp.asarray(pos))
        self.stats["decode_steps"] += 1
        self.stats["decode_rows"] += bs
        self.stats["padded_rows"] += bs - len(active)
        with TraceAnnotation("serve.dispatch"):
            logits, self._kv.pools = self._decode_paged(
                self.params, self._kv.pools, pages, tok, pos)
        self._advance(logits, list(enumerate(active)))

    def _admit_paged(self) -> None:
        """Resumes first (FIFO), then fresh admissions — one batch=1
        prefill each, since page tables are per-request."""
        while self._resume_q:
            free = [i for i, s in enumerate(self._slots) if s.req is None]
            if not free:
                return
            req, parked, pos, next_token = self._resume_q[0]
            if not self._kv.resume(free[0], parked):
                self.stats["admit_stalls"] += 1
                break                      # pool pressure; retry next step
            self._resume_q.popleft()
            slot = self._slots[free[0]]
            slot.req, slot.pos, slot.next_token = req, pos, next_token
            self.stats["resumes"] += 1
        while self._queue:
            free = [i for i, s in enumerate(self._slots) if s.req is None]
            if not free:
                return
            req = self._queue[0]
            with _admit_span(req):
                # fresh admissions may park a victim slot to make room,
                # but never while resumes are waiting (no priority
                # inversion)
                make_room = self._park_victim if not self._resume_q else None
                min_len = self._bucket_len(req.prompt.size)
                ctx_len = self._kv.admit(free[0], req.prompt,
                                         min_len=min_len,
                                         make_room=make_room)
                if ctx_len is None:
                    self.stats["admit_stalls"] += 1
                    return
                self._queue.popleft()
                logits = self._prefill_paged(free[0], req, ctx_len)
                with TraceAnnotation("serve.fetch"):
                    logits_row = np.asarray(logits)[0]
                self._kv.publish(free[0])
                with TraceAnnotation("serve.sample"):
                    slot = self._slots[free[0]]
                    first = self._sample(logits_row, req)
                    req.tokens.append(first)
                    slot.req = req
                    slot.pos = req.prompt.size
                    slot.next_token = first
                    self._maybe_evict(slot, free[0])

    def _prefill_paged(self, idx: int, req: RequestHandle, ctx_len: int):
        """Prefill into the slot's freshly built page table; returns the
        (1, vocab) logits of the last prompt position, on the device.
        With a shared-prefix hit only the suffix runs (partial prefill
        over the gathered context pages); otherwise the whole (bucketed)
        prompt prefills into a contiguous cache that is scattered to the
        pages."""
        prompt = req.prompt
        page = self._kv.page
        ids = self._kv.slot_ids(idx)
        if ctx_len > 0:
            n_ctx = ctx_len // page
            fn = self._partial_prefill_fn(n_ctx)
            with TraceAnnotation("serve.prefill",
                                 tokens=prompt.size - ctx_len):
                logits, self._kv.pools = fn(
                    self.params, self._kv.pools, jnp.asarray(ids, jnp.int32),
                    jnp.asarray(prompt[None, ctx_len:]))
            self.stats["prefix_reused_tokens"] += ctx_len
            self.stats["prefill_tokens"] += prompt.size - ctx_len
            return logits
        length = self._bucket_len(prompt.size)
        cache_len = len(ids) * page
        with TraceAnnotation("serve.prefill", tokens=length):
            toks = np.zeros((1, length), np.int32)
            toks[0, :prompt.size] = prompt
            if prompt.size < length:
                logits, caches = self._prefill_pad_fn(cache_len)(
                    self.params, jnp.asarray(toks),
                    jnp.asarray([prompt.size - 1], jnp.int32))
            else:
                logits, caches = self._prefill_fn(cache_len)(
                    self.params, jnp.asarray(toks))
            self._kv.pools = self._scatter_paged(
                self._kv.pools, caches, jnp.asarray(ids, jnp.int32))
        self.stats["prefill_tokens"] += length
        return logits

    def _auto_park(self, idx: int) -> None:
        slot = self._slots[idx]
        parked = self._kv.park(idx)
        rec = (slot.req, parked, slot.pos, slot.next_token)
        self._kv.prefetch(parked)
        self._resume_q.append(rec)
        slot.clear()
        self.stats["parks"] += 1

    def _park_victim(self) -> bool:
        """Pool-pressure callback: auto-park the active slot holding the
        most pages (ties to the youngest request, keeping older requests
        running).  False when no slot can be parked."""
        cands = [(len(self._kv.slot_ids(i)), self._slots[i].req.id, i)
                 for i, s in enumerate(self._slots) if s.req is not None]
        if not cands:
            return False
        _, _, idx = max(cands)
        self._auto_park(idx)
        return True

    # -- jit caches (paged mode compiles per cache length / ctx pages) ------

    def _prefill_fn(self, cache_len: int):
        fn = self._prefill_fns.get(cache_len)
        if fn is None:
            cfg = self.cfg
            fn = self._jit(lambda p, toks: prefill(p, cfg, tokens=toks,
                                                   max_len=cache_len),
                           "serve_prefill")
            self._prefill_fns[cache_len] = fn
        return fn

    def _prefill_pad_fn(self, cache_len: int):
        fn = self._prefill_pad_fns.get(cache_len)
        if fn is None:
            cfg = self.cfg

            def pad_fn(p, toks, last_idx):
                caches = init_cache(cfg, toks.shape[0], cache_len)
                logits, new_caches, _ = forward(p, cfg, tokens=toks,
                                                caches=caches,
                                                last_index=last_idx)
                return logits[:, 0, :], new_caches
            fn = self._jit(pad_fn, "serve_prefill_padded")
            self._prefill_pad_fns[cache_len] = fn
        return fn

    def _partial_prefill_fn(self, n_ctx: int):
        """Suffix prefill over a shared prefix: gather the slot's pages to
        a contiguous view, run the suffix at ``cache_pos = n_ctx * page``
        (scalar — the S>1 cache write / causal-mask path), scatter back
        only the suffix pages.  The shared context pages are read-only."""
        fn = self._partial_fns.get(n_ctx)
        if fn is None:
            cfg, page = self.cfg, self._kv.page

            def partial_fn(p, pools, ids, toks):
                def gather(pool):
                    g = jnp.take(pool, ids, axis=1)
                    return g.reshape(g.shape[0], 1, g.shape[1] * page,
                                     *g.shape[3:])
                contig = jax.tree.map(gather, pools)
                logits, newc, _ = forward(p, cfg, tokens=toks,
                                          caches=contig,
                                          cache_pos=n_ctx * page,
                                          last_only=True)

                def put(pool, c):
                    c = c.reshape(c.shape[0], ids.shape[0], page,
                                  *c.shape[3:])
                    return pool.at[:, ids[n_ctx:]].set(
                        c[:, n_ctx:].astype(pool.dtype))
                return logits[:, 0], jax.tree.map(put, pools, newc)
            fn = self._jit(partial_fn, "serve_prefill_partial")
            self._partial_fns[n_ctx] = fn
        return fn

    @staticmethod
    def _scatter_paged_impl(pools, caches, ids):
        """Scatter a batch-1 prefill's contiguous caches (L, 1, n*page,
        ...) into pool pages ``ids``."""
        def put(pool, c):
            page = pool.shape[2]
            c = c.reshape(c.shape[0], ids.shape[0], page, *c.shape[3:])
            return pool.at[:, ids].set(c.astype(pool.dtype))
        return jax.tree.map(put, pools, caches)

    # -- helpers -------------------------------------------------------------

    def _bucket_len(self, n: int) -> int:
        """Smallest configured prefill bucket >= n (n itself if none)."""
        fits = [b for b in self.serve_cfg.prefill_buckets if b >= n]
        return min(fits) if fits else n

    @staticmethod
    def _scatter_impl(caches, caches1, slot_idx):
        """Write a batch=1 prefill's caches into slot ``slot_idx`` (every
        cache leaf carries the slot axis at position 1)."""
        return jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot_idx, axis=1),
            caches, caches1)

    def _sample(self, logits_row: np.ndarray, req: RequestHandle) -> int:
        if req.temperature <= 0.0:
            return int(np.argmax(logits_row))
        rng = self._rngs.get(req.id)
        if rng is None:
            # per-request seed (reproducible across sessions) or a
            # session-seed + request-id derivation
            key = (req.seed if req.seed is not None
                   else (self.serve_cfg.seed, req.id))
            rng = np.random.default_rng(key)
            self._rngs[req.id] = rng
        z = logits_row.astype(np.float64) / req.temperature
        return int(np.argmax(z + rng.gumbel(size=z.shape)))


def _named(fn, name: str):
    """``fn`` under ``name``: ``jax.jit`` names its program ``jit_<name>``."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


def _admit_span(req: RequestHandle) -> TraceAnnotation:
    """The ``serve.admit`` span of an admission that starts now: the
    request's id and how long it waited since ``submit``, in µs."""
    wait_us = int((time.perf_counter() - req.submitted_s) * 1e6)
    return TraceAnnotation("serve.admit", req=req.id, wait_us=wait_us)
