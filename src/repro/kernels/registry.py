"""Kernel-op registry: one dispatch point for every compute kernel.

Every op (``rd_quant``, ``dequant_matmul``, ``flash_attention``,
``embed_lookup_q8``) registers an :class:`OpSpec` via :func:`register_op`:
named implementations (``pallas`` / ``interpret`` / ``ref`` / ...), a
tile-parameter search space, shape constraints, and a pure-jnp oracle.
Call sites then do::

    from repro import kernels
    out = kernels.get("dequant_matmul")(x, w_q, scale, policy=cfg.kernels)

and dispatch picks the implementation by platform (TPU -> pallas,
CPU -> ref/scan; the Pallas ``interpret`` impls are CPU-only, so no
fallback chain on a TPU reaches interpret mode), honors a single
:class:`KernelPolicy`, consults the
persistent tuning cache (:mod:`repro.kernels.tune`) for tile parameters at
trace time, and surfaces every constraint-driven fallback through
:func:`dispatch_report` instead of downgrading silently.  Requesting an
impl explicitly (a policy override) that cannot run raises under
``KernelPolicy(strict=True)``.

Dispatch happens at Python call time — inside a ``jax.jit`` that is trace
time, so impl/tile choices are compile-time constants and repeated calls
with cached shapes pay no dispatch overhead.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import jax


class KernelDispatchError(RuntimeError):
    """An explicitly requested impl cannot run under the given policy."""


# ---------------------------------------------------------------------------
# Policy
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class KernelPolicy:
    """Session-wide kernel selection policy (hashable; lives on ModelConfig).

    platform        "auto" (jax.default_backend()) or a pin ("tpu"/"cpu").
    strict          a constraint-driven fallback on an *explicitly
                    requested* impl raises instead of downgrading.
    use_tuning_cache  consult the persistent tuning cache for tile params.
    overrides       ((op, impl), ...) per-op impl pins.
    tile_overrides  ((op, ((param, value), ...)), ...) per-op tile pins
                    (win over both defaults and the tuning cache).
    """

    platform: str = "auto"
    strict: bool = False
    use_tuning_cache: bool = True
    overrides: tuple = ()
    tile_overrides: tuple = ()

    def impl_for(self, op: str) -> str | None:
        for name, impl in self.overrides:
            if name == op:
                return impl
        return None

    def tiles_for(self, op: str) -> dict:
        for name, tiles in self.tile_overrides:
            if name == op:
                return dict(tiles)
        return {}

    def override(self, op: str, impl: str) -> "KernelPolicy":
        """Return a policy with ``op`` pinned to ``impl`` (replaces any
        existing pin for the same op — idempotent)."""
        kept = tuple((n, i) for n, i in self.overrides if n != op)
        return dataclasses.replace(self, overrides=kept + ((op, impl),))

    def with_tiles(self, op: str, **tiles) -> "KernelPolicy":
        kept = tuple((n, t) for n, t in self.tile_overrides if n != op)
        pin = (op, tuple(sorted(tiles.items())))
        return dataclasses.replace(self, tile_overrides=kept + (pin,))


DEFAULT_POLICY = KernelPolicy()


# ---------------------------------------------------------------------------
# Op specification
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Impl:
    """One named implementation of an op.

    fn          callable with the op's public signature, plus the op's tile
                parameters as keyword arguments when ``uses_tiles``.
    platforms   backends the impl can execute on.
    constraint  shapes-dict -> None (ok) or a human-readable reason string.
    """

    name: str
    fn: Callable
    platforms: tuple = ("cpu", "gpu", "tpu")
    constraint: Callable | None = None
    uses_tiles: bool = True


@dataclass
class OpSpec:
    """Registered kernel op: impls, platform defaults, tile search space.

    defaults     platform -> impl name; "*" is the required catch-all.
    route        optional shape-based routing hook consulted before
                 ``defaults`` when no impl is pinned: (shapes, platform)
                 -> impl name or None.  Use it for *designed* shape
                 routing (e.g. decode -> scan) so the choice is not
                 reported as a constraint fallback.
    fallbacks    ordered impl names to try when the primary choice fails
                 its constraint or platform check.
    tile_space   tile param -> candidate values (the autotune sweep).
    default_tiles  shapes-dict -> tile dict (shape-adaptive defaults).
    tile_ok      (shapes, tiles) -> bool filter over the search space.
    shape_info   (*args, **kwargs) -> shapes dict fed to constraints,
                 default_tiles and bucket.
    bucket       shapes-dict -> tuning-cache key segment.
    example_inputs  shape tuple -> (args, kwargs) for autotune/benchmarks.
    oracle       pure-jnp reference callable (differential tests).
    tune_impls   platform -> impl name the autotuner times ("*" catch-all).
    """

    name: str
    impls: dict
    defaults: dict
    route: Callable | None = None
    fallbacks: tuple = ()
    tile_space: dict = field(default_factory=dict)
    default_tiles: Callable | None = None
    tile_ok: Callable | None = None
    shape_info: Callable = lambda *a, **k: {}
    bucket: Callable | None = None
    example_inputs: Callable | None = None
    oracle: Callable | None = None
    tune_impls: dict = field(default_factory=dict)


_OPS: dict[str, OpSpec] = {}
_REPORT: deque = deque(maxlen=512)


def register_op(build: Callable[[], OpSpec]) -> Callable[[], OpSpec]:
    """Decorator: ``build`` returns an OpSpec, registered at import time."""
    op = build()
    _OPS[op.name] = op
    return build


def available_ops() -> list[str]:
    return sorted(_OPS)


def spec(name: str) -> OpSpec:
    if name not in _OPS:
        raise KeyError(
            f"unknown kernel op {name!r}; available: {available_ops()}")
    return _OPS[name]


def dispatch_report() -> list[dict]:
    """Constraint-driven fallbacks observed so far (most recent last).

    Each record: {op, platform, requested, impl, reason}.  ``requested`` is
    the impl the policy asked for (None when the platform default fell
    back), ``impl`` what actually ran."""
    return list(_REPORT)


def clear_dispatch_report() -> None:
    _REPORT.clear()


def record_event(*, op: str, platform: str, impl: str, reason: str,
                 requested: str | None = None, kind: str = "event") -> None:
    """Append a non-dispatch event to the report stream.

    Dispatch itself records constraint-driven fallbacks automatically;
    this hook is for adjacent decisions that must be just as loud — a
    tile clamp at kernel dispatch (``kind="tile_clamp"``), a loop-body
    dequantize of a tensor the fused q8 path can't take
    (``kind="loop_dequant"``).  Records share the fallback schema
    ({op, platform, requested, impl, reason}) plus ``kind``, so existing
    ``dispatch_report()`` consumers keep working and new ones can filter
    by kind.  Call sites fire at trace time (inside ``jax.jit`` tracing),
    so a recorded event costs nothing per executed step."""
    _REPORT.append({"op": op, "platform": platform, "requested": requested,
                    "impl": impl, "reason": reason, "kind": kind})


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DispatchPlan:
    """What :class:`BoundOp` decided for one call, without executing it."""

    op: str
    platform: str
    requested: str | None        # explicit policy pin, if any
    impl: str                    # impl that will run
    tiles: tuple                 # ((param, value), ...) sorted
    fallback_reason: str | None  # why the primary choice was downgraded
    cache_hit: bool              # tiles came from the tuning cache


class BoundOp:
    """Callable handle returned by :func:`get`; dispatches on call."""

    def __init__(self, op_spec: OpSpec):
        self.spec = op_spec

    def __repr__(self):
        return f"BoundOp({self.spec.name!r}, impls={sorted(self.spec.impls)})"

    def plan(self, *args, policy: KernelPolicy | None = None,
             **kwargs) -> DispatchPlan:
        """Resolve platform, impl and tiles for these arguments."""
        s = self.spec
        policy = policy or DEFAULT_POLICY
        platform = (policy.platform if policy.platform != "auto"
                    else jax.default_backend())
        shapes = s.shape_info(*args, **kwargs)
        requested = policy.impl_for(s.name)
        if requested is not None and requested not in s.impls:
            raise KeyError(
                f"{s.name}: unknown impl {requested!r}; "
                f"available: {sorted(s.impls)}")
        primary = requested
        if primary is None and s.route is not None:
            primary = s.route(shapes, platform)
        if primary is None:
            primary = s.defaults.get(platform, s.defaults["*"])

        reason = None
        chosen = None
        for cand in [primary] + [f for f in s.fallbacks if f != primary]:
            impl = s.impls.get(cand)
            if impl is None:
                continue
            if platform not in impl.platforms:
                why = f"impl {cand!r} unavailable on platform {platform!r}"
            else:
                why = impl.constraint(shapes) if impl.constraint else None
            if why is None:
                chosen = cand
                break
            if cand == primary:
                reason = why
        if chosen is None:
            raise KernelDispatchError(
                f"{s.name}: no feasible impl on {platform!r} "
                f"(primary {primary!r}: {reason})")

        tiles: dict = {}
        cache_hit = False
        impl = s.impls[chosen]
        if impl.uses_tiles and s.tile_space:
            if s.default_tiles is not None:
                tiles.update(s.default_tiles(shapes))
            if policy.use_tuning_cache and s.bucket is not None:
                from . import tune
                hit = tune.lookup(s.name, platform, s.bucket(shapes))
                if hit:
                    tiles.update(hit)
                    cache_hit = True
            tiles.update(policy.tiles_for(s.name))
        return DispatchPlan(
            op=s.name, platform=platform, requested=requested, impl=chosen,
            tiles=tuple(sorted(tiles.items())),
            fallback_reason=reason if chosen != primary else None,
            cache_hit=cache_hit)

    def __call__(self, *args, policy: KernelPolicy | None = None, **kwargs):
        plan = self.plan(*args, policy=policy, **kwargs)
        if plan.fallback_reason is not None:
            _REPORT.append({
                "op": plan.op, "platform": plan.platform,
                "requested": plan.requested, "impl": plan.impl,
                "reason": plan.fallback_reason, "kind": "fallback",
            })
            if (policy is not None and policy.strict
                    and plan.requested is not None):
                raise KernelDispatchError(
                    f"{plan.op}: requested impl {plan.requested!r} cannot "
                    f"run ({plan.fallback_reason}) and policy is strict")
        impl = self.spec.impls[plan.impl]
        tiles = dict(plan.tiles) if impl.uses_tiles else {}
        return impl.fn(*args, **kwargs, **tiles)


def get(name: str) -> BoundOp:
    """Look up a registered op; the returned handle dispatches per call."""
    return BoundOp(spec(name))
