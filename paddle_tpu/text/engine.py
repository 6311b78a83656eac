"""The step-compilation Engine: ONE declarative subsystem that builds,
caches, donates, and instruments every jitted decode/serving executable.

Round 15 unifies the step-function zoo: ``serving.py`` grew 20+
hand-written jitted step getters (prefill buckets, chunked admission,
paged twins, blocks, async selects, spec verify, the whole adapter
family, constrained masks) and ``generate.py`` a parallel
``_jit_by_cfg`` family — every capability since PR 3 meant another N
getters and another hand-threaded jit-key fragment, and the
compositions the roadmap wanted next (spec x ``mesh=`` TP, adapter
pools under TP) were "rejected at construction" precisely because
nobody wanted getter-family number ten.  The reference framework hit
the same wall and converged on a registry (fluid's ``OperatorRegistry``
resolving ops by declarative ``OpDesc``, with ``Executor::Prepare``
caching the prepared contexts); vLLM/SGLang's unified model-runner
layer is the modern serving shape.  This module is that layer for
paddle_tpu:

* :class:`StepSpec` — the declarative description of one step
  executable: model config (whose ``cfg_key`` embeds
  ``flags.decode_jit_key()`` — KV dtype/layout/block geometry, spec-K,
  prefill budget, kernel routing), cache layout tag, placement
  (``_ShardCtx`` mesh fingerprint or device pin), prompt bucket /
  chunk width, block length, adapter-pool geometry.
* the step *registry* — ``@register("kind", key=..., name=...)``
  builder functions, each keyed ONLY by the spec fields it actually
  reads.  Adding a cache layout or parallelism mode touches one
  registry entry, not nine getters.
* :class:`Engine` — owns the two bounded executable caches (the old
  ``serving._STEP_CACHE`` / ``generate._GEN_CACHE``, kept as two
  domains because their env-sized bounds and test surfaces are
  distinct), funnels every build through the PR 4 recompile watch
  (``telemetry.instrument_compile``), and carries warmup / purge as
  methods — ``DecodeServer.close`` no longer hand-enumerates cfg
  families (the old silent ``_GEN_CACHE`` leak), it calls
  :meth:`Engine.purge` which sweeps BOTH caches in one pass.

``serving._get_*_fn`` and ``generate._get_generate_fn`` survive as
thin shims over ``ENGINE.get(kind, spec)`` so call sites and tests
keep their names; the keys, watch names, jit bodies, and donation are
byte-compatible — a migrated server produces the exact same executable
count and cache-key set as the getter zoo did (pinned by
``tests/test_engine.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import os as _os
import threading
import time as _time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags as _flags
from .. import telemetry as _telemetry

__all__ = ["StepSpec", "Engine", "ENGINE", "register", "cfg_key",
           "donate_cache"]


class _LRU:
    """Bounded executable cache (round-5 verdict Weak #7: the jit caches
    grow per config VALUE and hold compiled executables + implicit param
    references — fine for tests, a leak for a long-lived server cycling
    models).  dict-compatible get/[] with least-recently-used eviction;
    evicting an entry drops the last reference to its executable.

    Thread-safe: the fleet router ticks replicas concurrently, and every
    replica's step builds share these Engine-level caches — an unlocked
    OrderedDict corrupts under concurrent move_to_end/popitem."""

    def __init__(self, maxsize: int):
        import collections

        self._d = collections.OrderedDict()
        self._mu = threading.Lock()
        self.maxsize = maxsize

    def get(self, k, default=None):
        with self._mu:
            if k in self._d:
                self._d.move_to_end(k)
                return self._d[k]
            return default

    _MISS = object()

    def __getitem__(self, k):
        v = self.get(k, _LRU._MISS)
        if v is _LRU._MISS:
            raise KeyError(k)
        return v

    def __contains__(self, k):
        with self._mu:
            return k in self._d

    def __setitem__(self, k, v):
        with self._mu:
            self._d[k] = v
            self._d.move_to_end(k)
            while len(self._d) > self.maxsize:
                self._d.popitem(last=False)

    def __len__(self):
        with self._mu:
            return len(self._d)

    def keys(self):
        with self._mu:
            return list(self._d.keys())

    def pop(self, k, default=None):
        with self._mu:
            return self._d.pop(k, default)

    def clear(self):
        """Drop every cached executable (tests that flip trace-time env
        flags — e.g. PADDLE_TPU_W4_KERNEL — must force a retrace)."""
        with self._mu:
            self._d.clear()


def donate_cache():
    """``donate_argnums`` for the decode-path jits, whose cache is arg 1.

    Donation lets XLA alias the [L, B, T, Hkv, hd] K/V buffers in place
    instead of allocating + copying the whole cache every token — the
    hot-path optimization this serving stack's throughput stands on.
    Callers of a donated step MUST treat the passed cache as consumed
    (reassign from the return value; every call site in this repo does).
    ``PADDLE_TPU_DONATE_DECODE=0`` turns it off (flags.donate_decode);
    the flag is part of cfg_key so flipping it retraces."""
    return (1,) if _flags.donate_decode() else ()


def _watch_jit(name: str, key, fn):
    """Telemetry recompile watch around a jit-cache MISS: every build the
    Engine performs funnels its freshly built executable through this,
    so each compile records (fn name, cfg/flags key, wall time) and a
    mid-process flip of ``flags.decode_jit_key`` — whose tuple every
    ``cfg_key`` embeds — raises the rate-limited recompile warning with
    the key diff.  With telemetry off the raw jit function is returned
    untouched."""
    return _telemetry.instrument_compile(name, key,
                                         _flags.decode_jit_key(), fn)


_building = threading.local()    # .scope: the step being built, see _scoped


def _scoped(fn):
    """``fn`` with its body under ``jax.named_scope(<instrument name>)`` of
    the step kind :meth:`Engine.get` is building, so that every device
    op's ``op_name`` path starts with the step it belongs to
    (``jit(<lambda>)/serving.async_step/attn/...``).  ``@`` becomes ``_``
    (XLA cuts an op_name at ``@``: ``serving.block@8`` is
    ``serving.block_8``).  The function keeps its name, and with it the
    XLA module's (``jit__lambda``): HLO metadata is all that changes."""
    scope = _building.scope.replace("@", "_")

    @functools.wraps(fn)
    def scoped(*a, **k):
        with jax.named_scope(scope):
            return fn(*a, **k)

    if isinstance(fn, functools.partial):   # no __name__ for wraps to copy
        scoped.__name__ = fn.func.__name__
    return scoped


def cfg_key(cfg):
    """Value-based cache key (GPTConfig is an unhashable dataclass; keying
    by id() would recompile per object and leak executables)."""
    moe = cfg.moe
    # every routing-relevant field: two MoE configs differing in top_k or
    # capacity must never share a jitted executable
    moe_key = ((moe.num_experts, moe.top_k, moe.capacity_factor,
                moe.router_noise, moe.aux_loss_weight)
               if moe is not None else None)
    return (cfg.vocab_size, cfg.hidden_size, cfg.num_layers, cfg.num_heads,
            cfg.num_kv_heads,
            cfg.max_seq_len, cfg.ffn_ratio, str(cfg.dtype), cfg.use_flash,
            cfg.pos_embed, cfg.norm, cfg.activation,
            moe_key,
            # explicit widths, the untied head, bias-free projections, the
            # rope base, the forward multipliers and the parallel mixer
            (cfg.head_dim, cfg.intermediate_size, cfg.tie_embeddings,
             cfg.bias, cfg.rope_theta, cfg.embedding_multiplier,
             cfg.lm_head_multiplier, cfg.attention_in_multiplier,
             cfg.attention_out_multiplier, cfg.key_multiplier,
             tuple(cfg.mlp_multipliers),
             cfg.ssm.key() if cfg.ssm is not None else None,
             cfg.mla.key() if cfg.mla is not None else None,
             cfg.experts.key() if cfg.experts is not None else None,
             # the layer pattern and its two forward scalars
             cfg.layer_types, cfg.attention_multiplier,
             cfg.residual_multiplier),
            # trace-time env routing flags (flags.decode_jit_key): an
            # executable BAKES these in — W4 kernel gate (woq.mm), fused
            # LN (gpt._ln), cache donation (aliased vs copied buffers),
            # flash-decode kernel routing, the KV-cache storage dtype,
            # paged layout + block geometry, spec-K, and the prefill
            # budget.  Flipping any of them mid-process must retrace,
            # not silently reuse the other routing's executable.
            _flags.decode_jit_key())


class _ShardCtx:
    """Tensor-parallel serving context (round 9): one mesh + the
    sharding trees the Engine threads into ``jax.jit`` so the batched
    tick runs Megatron-sharded INSIDE the server.

    Params take ``generate._decode_param_specs`` (the
    ``build_sharded_decode`` rules — ``distributed/sharding_rules``-style
    regex specs resolved per leaf); the cache takes
    ``generate.sharded_cache_specs`` — the Hkv axis shards over ``mp``
    for BOTH layouts (slab head axis / pool Hkv axis), the paged
    ``tables`` leaf replicates.  An attached :class:`AdapterPool`
    contributes stacked-leaf shardings (``adapters.stacked_pool_specs``
    — base leaf's Megatron spec with the leading stack axis replicated,
    round 15's pool x TP unlock).  Donation composes unchanged (in and
    out cache shardings match, so aliasing is exact per shard); ``key``
    folds into every step-cache key so a sharded server's compiles stay
    visible to the recompile watch instead of colliding with the
    single-chip executables."""

    def __init__(self, mesh, cfg, params, cache, mp: str = "mp",
                 pool=None, ep: str | None = None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from . import generate

        if mp not in mesh.shape:
            raise ValueError(f"mesh has no {mp!r} axis (axes: "
                             f"{tuple(mesh.shape)})")
        if ep is not None:
            if cfg.moe is None:
                raise ValueError("ep axis given but cfg.moe is None — "
                                 "expert parallelism needs experts")
            if ep not in mesh.shape:
                raise ValueError(f"mesh has no {ep!r} axis (axes: "
                                 f"{tuple(mesh.shape)})")
            if cfg.moe.num_experts % mesh.shape[ep] != 0:
                raise ValueError(
                    f"num_experts={cfg.moe.num_experts} not divisible by "
                    f"ep axis size {mesh.shape[ep]}")
        self.mesh = mesh
        self.mp = mp
        self.ep = ep
        ns = lambda s: NamedSharding(mesh, s)  # noqa: E731
        if cfg.moe is not None:
            # MoE params carry blocks/moe/* leaves the legacy resolver
            # has no placements for — the round-19 regex table covers
            # them (dense leaves pinned equal by test); ep=None serves
            # the experts replicated (pure TP over an MoE model)
            from . import moe_serving as _moe_serving

            pspecs = _moe_serving.moe_decode_param_specs(
                params, cfg, mp=mp, ep=ep)
        else:
            pspecs = generate._decode_param_specs(params, cfg, mp)
        self.params = jax.tree_util.tree_map(
            ns, pspecs, is_leaf=lambda s: isinstance(s, P))
        self.cache = {
            name: ns(spec) for name, spec in
            generate.sharded_cache_specs(cfg, cache, mesh, mp).items()}
        self.repl = ns(P())
        if pool is not None:
            from . import adapters as _adapters

            self.adapters = {
                name: ns(spec) for name, spec in
                _adapters.stacked_pool_specs(pool, mp=mp).items()}
        else:
            self.adapters = None
        self.key = (mp, tuple(mesh.shape.items()),
                    tuple(int(d.id) for d in mesh.devices.flat))
        if ep is not None:
            # the ep placement changes the compiled program (all_to_all
            # vs replicated experts) — two contexts differing only in ep
            # must never share an executable
            self.key = self.key + (("ep", ep),)


def _shard_kw(shard, n_extra: int, outs: str,
              with_params: bool = True, adapters: bool = False) -> dict:
    """jit kwargs for one step builder under a shard context (empty dict
    single-chip — the builders stay byte-identical to the unsharded
    build).  Inputs are (params, cache[, adapter stacks], ``n_extra``
    replicated host args); ``outs`` spells the output structure ('r'
    replicated leaf, 'c' the cache tree — a one-char string for
    cache-only returns).  ``adapters=True`` slots the pool's stacked
    leaves right after the cache (the adapter step calling convention)
    with their Megatron-derived shardings, replicated when the shard
    context carries no pool."""
    if not isinstance(shard, _ShardCtx):
        # None, or a device-pinned server's placement tuple: no explicit
        # shardings, the key alone keeps executables per-placement
        return {}
    lead = ((shard.params, shard.cache) if with_params
            else (shard.cache,))
    if adapters:
        lead = lead + (shard.adapters if shard.adapters is not None
                       else shard.repl,)
    out = tuple(shard.cache if o == "c" else shard.repl for o in outs)
    return {"in_shardings": lead + (shard.repl,) * n_extra,
            "out_shardings": out if len(outs) > 1 else out[0]}


def _traced_per_shard(shard, fn):
    """A tensor-parallel step's Pallas kernels run per shard of the
    server's mesh (GSPMD cannot partition a Mosaic call): the partition
    context is entered around every call and every ``lower``, so it is
    active whenever the jit traces.  Single-chip steps come back as
    they are."""
    if not isinstance(shard, _ShardCtx):
        return fn
    from ..ops import _pallas

    per_shard = functools.partial(_pallas.partitioned, shard.mesh,
                                  heads=shard.mp)

    @functools.wraps(fn)
    def call(*a, **k):
        with per_shard():
            return fn(*a, **k)

    def lower(*a, **k):
        with per_shard():
            return fn.lower(*a, **k)

    call.lower = lower
    call._cache_size = fn._cache_size
    return call


def _shard_key(shard):
    """Step-cache key fragment for a server's placement: the mesh
    fingerprint under TP, the device id tuple for a pinned single-chip
    replica (two replicas pinned to different chips must NOT share one
    watch-instrumented wrapper — the second chip's compile would be
    invisible to the recompile watch and its wall charged to
    steady-state telemetry), None for the default placement."""
    if shard is None:
        return None
    return shard.key if isinstance(shard, _ShardCtx) else shard


@dataclasses.dataclass(frozen=True)
class StepSpec:
    """Declarative description of ONE step executable.

    The spec is the Engine's entire input: a registry entry's ``key``
    function reads only the fields that change its compiled program,
    and everything trace-relevant that lives in env flags (KV dtype,
    layout, block geometry, spec-K, prefill budget, kernel routing,
    donation) rides inside ``cfg_key(spec.cfg)`` via
    ``flags.decode_jit_key()`` — so ``spec.key(kind)`` IS the single
    cache-key authority the recompile watch sees.

    Fields (each ``None``/default when the kind doesn't read it):

    * ``cfg`` — the model's GPTConfig (value-keyed via :func:`cfg_key`).
    * ``paged`` — KV-layout tag: ``True`` keys the paged (block-table)
      cache's executables apart from the contiguous slab's.
    * ``shard`` — placement: ``None`` (default devices), a
      :class:`_ShardCtx` (``mesh=`` TP: in/out shardings threaded into
      the jit), or a ``("device", id)`` pin tuple.
    * ``bucket`` — prompt bucket / chunk width for prefill kinds (a
      compiled shape).
    * ``width`` — explicit chunk width for the budgeted
      ``prefill_chunk`` family (``None`` keeps the legacy
      one-name-per-cfg key).
    * ``k`` — block length (``block@k``) or speculative K
      (``spec_verify@K``) — a compiled shape.
    * ``pkey`` — ``AdapterPool.pool_key()``: the pool GEOMETRY
      (capacity/rank/targets); two servers sharing a pool share
      executables.
    * ``extra`` — kind-specific scalar knobs (e.g. generate's
      ``(max_new_tokens, top_k, top_p)``) folded into the key verbatim.
    * ``payload`` — call-time objects the builder needs but that must
      NEVER be keyed (e.g. ``jit_by_cfg``'s step fn, whose identity is
      already pinned by the ``extra`` tag).
    """

    cfg: Any
    paged: bool = False
    shard: Any = None
    bucket: int | None = None
    width: int | None = None
    k: int | None = None
    pkey: Any = None
    extra: tuple = ()
    payload: Any = dataclasses.field(default=None, compare=False)

    def key(self, kind: str) -> tuple:
        """The jit-cache key this spec resolves to for ``kind`` — the
        registry entry's key function, which embeds ``cfg_key`` (and
        with it ``flags.decode_jit_key()``) plus exactly the spec
        fields the kind's program depends on."""
        return _REGISTRY[kind].key(self)

    def name(self, kind: str) -> str:
        """The telemetry instrument name for ``kind`` at this spec."""
        return _REGISTRY[kind].name(self)


class _Kind:
    """One registry entry: how to key, name, and build a step kind."""

    __slots__ = ("kind", "key", "name", "build", "domain", "cached")

    def __init__(self, kind: str, key: Callable, name: Callable,
                 build: Callable, domain: str, cached: bool):
        self.kind = kind
        self.key = key
        self.name = name
        self.build = build
        self.domain = domain
        self.cached = cached


_REGISTRY: dict[str, _Kind] = {}


def register(kind: str, *, key: Callable, name, domain: str = "step",
             cached: bool = True):
    """Register a step builder: ``key(spec)`` -> cache key (must read
    only the fields the compiled program depends on), ``name(spec)`` ->
    recompile-watch instrument name, ``domain`` -> which Engine cache
    holds it ('step' = the serving step cache, 'gen' = the offline
    generate cache), ``cached=False`` for kinds whose wrapper is
    rebuilt per call by contract (``sharded_decode`` returns a fresh
    instrumented wrapper per build — its executables still dedupe in
    jax's own trace cache).  The decorated builder takes the
    :class:`StepSpec` and returns a BARE ``jax.jit`` callable; the
    Engine is the single place that instruments it."""
    if isinstance(name, str):
        name_fn = lambda spec, _n=name: _n  # noqa: E731
    else:
        name_fn = name

    def deco(build: Callable) -> Callable:
        _REGISTRY[kind] = _Kind(kind, key, name_fn, build, domain, cached)
        return build

    return deco


def kinds() -> tuple:
    """Every registered step kind (sorted) — the purge/lint surface."""
    return tuple(sorted(_REGISTRY))


# --------------------------------------------------------------------------
# registry: serving step kinds.  Keys, instrument names, jit bodies, and
# donation are byte-compatible with the retired serving._get_*_fn getter
# zoo — tests pin key-set equality across the migration.  Builders import
# siblings lazily (they run at Engine.get time, when the package is fully
# imported); the module top imports only flags/telemetry, which breaks the
# serving -> generate -> engine import cycle.
# --------------------------------------------------------------------------


@register("prefill",
          key=lambda s: ("prefill", cfg_key(s.cfg), int(s.bucket),
                         _shard_key(s.shard)),
          name=lambda s: f"serving.prefill@{s.bucket}")
def _build_prefill(spec: StepSpec):
    """One wrapper per (cfg, prompt bucket): the jit would retrace per
    bucket shape anyway, and a per-bucket wrapper keeps the device
    feed's captured FLOPs joined to walls of the SAME bucket — one
    shared wrapper would divide bucket-8 FLOPs by bucket-512 walls."""
    from . import generate

    return jax.jit(
        _scoped(lambda p, c, t, ln, sl, _cfg=spec.cfg:
                generate.prefill_slot(p, c, t, ln, sl, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 3, "rc"))


@register("prefill_chunk",
          key=lambda s: ("prefill_chunk", cfg_key(s.cfg),
                         _shard_key(s.shard),
                         None if s.width is None else int(s.width)),
          name=lambda s: ("serving.prefill_chunk" if s.width is None
                          else f"serving.prefill_chunk@{int(s.width)}"))
def _build_prefill_chunk(spec: StepSpec):
    """Contiguous fixed-chunk admission step.  ``width=None`` keeps the
    legacy key (the server's configured ``prefill_chunk`` width — the
    jit retraces per chunk shape under that one name); an explicit
    ``width`` (budgeted admission: the per-round prefill budget) keys
    and names the wrapper per width, so the recompile watch joins each
    budget's compiles to walls of the SAME width."""
    from . import generate

    return jax.jit(
        _scoped(lambda p, c, t, p0, ln, sl, _cfg=spec.cfg:
                generate.prefill_slot_chunk(p, c, t, p0, ln, sl, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rc"))


@register("paged_prefill",
          key=lambda s: ("paged_prefill", cfg_key(s.cfg), int(s.bucket),
                         _shard_key(s.shard)),
          name=lambda s: f"serving.paged_prefill@{s.bucket}")
def _build_paged_prefill(spec: StepSpec):
    """Paged admission step: one ``kv_pool.paged_prefill_chunk``
    executable per (cfg, chunk width) — ONE program serves any prompt
    offset (the chunk attends rows [0, pos0) through the block table),
    so bucketed-suffix and fixed-chunk admission share this kind."""
    from . import kv_pool

    return jax.jit(
        _scoped(lambda p, c, t, p0, ln, sl, _cfg=spec.cfg:
                kv_pool.paged_prefill_chunk(p, c, t, p0, ln, sl, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rc"))


@register("kv_copy",
          key=lambda s: ("kv_copy", cfg_key(s.cfg), int(s.k),
                         _shard_key(s.shard)),
          name=lambda s: f"serving.kv_copy@{s.k}")
def _build_kv_copy(spec: StepSpec):
    """Copy-on-write device half: gather/scatter ``k`` pool block pairs
    in one donated call (``kv_pool.copy_blocks``)."""
    from . import kv_pool

    return jax.jit(
        _scoped(lambda c, s, d: kv_pool.copy_blocks(c, s, d)),
        donate_argnums=donate_cache() and (0,),
        **_shard_kw(spec.shard, 2, "c", with_params=False))


@register("inject",
          key=lambda s: ("inject", cfg_key(s.cfg), int(s.bucket), s.paged,
                         _shard_key(s.shard)),
          name=lambda s: f"serving.inject@{s.bucket}")
def _build_inject(spec: StepSpec):
    """Prefill-handoff injector (round 9, the fleet's decode half): one
    donated executable per (cfg, rows bucket) writing an externally
    prefilled row block — leaves [L, 1, bucket, Hkv(, hd)], valid
    through ``length`` — into one slot's cache rows [start, length)
    (``start`` skips rows an adopted prefix already holds).
    Contiguous: the ``generate._merge_slot_rows`` masked write; paged:
    ``kv_pool.inject_rows`` scatters through the slot's block table."""
    from . import generate

    if spec.paged:
        from . import kv_pool

        body = lambda c, r, st, ln, sl: kv_pool.inject_rows(  # noqa: E731
            c, r, st, ln, sl)
    else:
        body = lambda c, r, st, ln, sl, _b=int(spec.bucket): \
            generate._merge_slot_rows(
                c, r, sl, jnp.asarray(0),
                ((jnp.arange(_b) >= st)
                 & (jnp.arange(_b) < ln))[None, :])  # noqa: E731
    return jax.jit(
        _scoped(body), donate_argnums=donate_cache() and (0,),
        **_shard_kw(spec.shard, 4, "c", with_params=False))


@register("block",
          key=lambda s: ("block", cfg_key(s.cfg), s.k, s.paged,
                         _shard_key(s.shard)),
          name=lambda s: f"serving.block@{s.k}")
def _build_block(spec: StepSpec):
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, t, s, _cfg=spec.cfg, _k=spec.k:
                serving.decode_block_batched(p, c, t, s, _k, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 2, "rcrr"))


@register("sample",
          key=lambda s: ("sample", cfg_key(s.cfg), s.paged,
                         _shard_key(s.shard)),
          name="serving.sample_step")
def _build_sample(spec: StepSpec):
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, t, s, ky, te, tk, tp, _cfg=spec.cfg:
                serving.sample_step_batched(p, c, t, s, ky, te, tk, tp, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 6, "rc"))


@register("sample_block",
          key=lambda s: ("sample_block", cfg_key(s.cfg), s.k, s.paged,
                         _shard_key(s.shard)),
          name=lambda s: f"serving.sample_block@{s.k}")
def _build_sample_block(spec: StepSpec):
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, t, s, ky, off, te, tk, tp, _cfg=spec.cfg,
                _k=spec.k:
                serving.sample_block_batched(p, c, t, s, ky, off, te, tk, tp,
                                             _k, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 7, "rc"))


@register("step",
          key=lambda s: ("step", cfg_key(s.cfg), s.paged,
                         _shard_key(s.shard)),
          name="serving.step")
def _build_step(spec: StepSpec):
    """One jitted batched step per config VALUE.  Every step fn here
    DONATES its cache (arg 1, :func:`donate_cache`): the caller must
    reassign the cache from the return value — DecodeServer always
    does.  ``paged`` tags the cache key (not the math:
    decode_step_batched branches on the cache structure itself), so a
    paged server's compiles stay visible to the recompile watch instead
    of hiding behind a same-key retrace."""
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, t, s, _cfg=spec.cfg:
                serving.decode_step_batched(p, c, t, s, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 2, "rc"))


@register("async",
          key=lambda s: ("async", cfg_key(s.cfg), s.paged,
                         _shard_key(s.shard)),
          name="serving.async_step")
def _build_async(spec: StepSpec):
    """The async-dispatch tick step: like the ``sample`` kind but the
    feed token is selected ON DEVICE between the host-built token and
    the previous (still in flight, unfetched) step's output — ``pm``
    [B] bool picks ``pv`` (previous device tokens) over ``ht`` (host
    tokens).  Greedy slots pass temp 0 and take the raw argmax, so one
    executable serves greedy and sampled async ticks bit-identically to
    the sync paths."""
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, ht, pm, pv, s, ky, te, tk, tp, _cfg=spec.cfg:
                serving.sample_step_batched(p, c, jnp.where(pm, pv, ht), s,
                                            ky, te, tk, tp, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 8, "rc"))


@register("async_block",
          key=lambda s: ("async_block", cfg_key(s.cfg), s.k, s.paged,
                         _shard_key(s.shard)),
          name=lambda s: f"serving.async_block@{s.k}")
def _build_async_block(spec: StepSpec):
    """Async greedy block: decode_block_batched with the device-side
    feed select (see the ``async`` kind)."""
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, ht, pm, pv, s, _cfg=spec.cfg, _k=spec.k:
                serving.decode_block_batched(p, c, jnp.where(pm, pv, ht), s,
                                             _k, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rcrr"))


@register("async_sample_block",
          key=lambda s: ("async_sample_block", cfg_key(s.cfg), s.k,
                         s.paged, _shard_key(s.shard)),
          name=lambda s: f"serving.async_sample_block@{s.k}")
def _build_async_sample_block(spec: StepSpec):
    """Async sampled block: sample_block_batched with the device-side
    feed select (see the ``async`` kind)."""
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, ht, pm, pv, s, ky, off, te, tk, tp, _cfg=spec.cfg,
                _k=spec.k:
                serving.sample_block_batched(p, c, jnp.where(pm, pv, ht), s,
                                             ky, off, te, tk, tp, _k, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 9, "rc"))


@register("spec_verify",
          key=lambda s: ("spec_verify", cfg_key(s.cfg), int(s.k), s.paged,
                         _shard_key(s.shard)),
          name=lambda s: f"serving.spec_verify@{s.k}")
def _build_spec_verify(spec: StepSpec):
    """The speculative serving verify step: one executable per
    (cfg, K, layout, placement) — K is baked into the token/logit
    shapes, and ``decode_jit_key`` carries PADDLE_TPU_SPEC_K so the
    recompile watch sees every spec compile.  Under a ``mesh=`` shard
    context this composes with TP exactly like the plain ``step`` kind
    (the round-15 unlock: verify@K built with ``_ShardCtx`` specs)."""
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, t, s, _cfg=spec.cfg:
                serving.spec_verify_batched(p, c, t, s, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 2, "rc"))


@register("spec_tree_verify",
          key=lambda s: ("spec_tree_verify", cfg_key(s.cfg), int(s.k),
                         s.paged, _shard_key(s.shard)),
          name=lambda s: f"serving.spec_tree_verify@{s.k}")
def _build_spec_tree_verify(spec: StepSpec):
    """Tree-speculation verify: ONE pass over an N-node token tree per
    slot (tokens [B, N], node 0 = feed token) under a tree-attention
    mask.  The tree's TOPOLOGY — ancestor-or-self mask [B, N, N] +
    per-node depths [B, N] — rides as RUNTIME arguments built host-side
    from the propose step's parent lists, so per-round topology changes
    never retrace; only the node count N is a compiled shape (it rides
    ``spec.k``, and ``decode_jit_key`` carries PADDLE_TPU_SPEC_TREE so
    the recompile watch sees every tree compile).  Einsum-only on both
    layouts — the flash kernels assume causal masks (on-device tree
    kernel: ROADMAP follow-up)."""
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, t, m, d, s, _cfg=spec.cfg:
                serving.spec_tree_verify_batched(p, c, t, m, d, s, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rc"))


@register("spec_tree_commit",
          key=lambda s: ("spec_tree_commit", cfg_key(s.cfg), int(s.k),
                         s.paged, _shard_key(s.shard)),
          name=lambda s: f"serving.spec_tree_commit@{s.k}")
def _build_spec_tree_commit(spec: StepSpec):
    """Post-acceptance KV permute for tree rounds: per slot, gather the
    accepted path's rows (``src`` [B, N-1] node indices, identity for
    slots that accepted a trunk prefix) and write them back contiguously
    at [pos+1, pos+N).  Cache-only like ``kv_copy`` — same donation
    idiom (gather-then-scatter inside, so aliasing under donation is
    safe), no params, no logits; the host skips this dispatch entirely
    on all-trunk rounds."""
    from . import serving

    return jax.jit(
        _scoped(lambda c, src, s: serving.spec_tree_commit_batched(c, src, s)),
        donate_argnums=donate_cache() and (0,),
        **_shard_kw(spec.shard, 2, "c", with_params=False))


@register("masked_step",
          key=lambda s: ("masked_step", cfg_key(s.cfg), s.paged,
                         _shard_key(s.shard)),
          name="serving.masked_step")
def _build_masked_step(spec: StepSpec):
    """Constrained step for servers WITHOUT an adapter pool: the plain
    sampled step plus the [B, V] constraint mask input.  Greedy slots
    (temp 0) take the argmax of the masked logits."""
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, t, s, ky, te, tk, tp, m, _cfg=spec.cfg:
                serving.sample_step_batched(p, c, t, s, ky, te, tk, tp, _cfg,
                                            mask=m)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 7, "rc"))


# -- MoE serving kinds (round 19: text/moe_serving.py) ---------------------
#
# The expert-parallel StepSpec family: joint-routing step bodies that
# thread the device-side drop accumulator (moe_serving.moe_stats_init)
# through the jit like the cache and take the occupied-slot mask ``act``
# as a runtime input.  Keys stay on the standard fragments — cfg_key
# already embeds (E, top_k, capacity_factor, ...) via moe_key and the
# shard key carries ("ep", axis) when expert parallelism is on, so the
# "(E, C, ep)" keying the subsystem promises falls out of the existing
# authorities.  The prefill kinds are THIN wrappers of the dense prefill
# bodies: chunked admission routes with valid= + the dropless capacity
# override (moe_ffn capacity=N), which is already MoE-exact — they exist
# as distinct kinds so an MoE server's admission compiles are named and
# keyed apart from a dense server's.


@register("moe_step",
          key=lambda s: ("moe_step", cfg_key(s.cfg), s.paged,
                         _shard_key(s.shard)),
          name="serving.moe_step")
def _build_moe_step(spec: StepSpec):
    """Greedy joint-routing batched step: (p, cache, tok [B], pos [B],
    act [B], stats) -> (logits [B, V], cache, stats')."""
    from . import moe_serving

    return jax.jit(
        _scoped(lambda p, c, t, s, a, st, _cfg=spec.cfg:
                moe_serving.moe_decode_step_batched(p, c, t, s, a, st, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rcr"))


@register("moe_sample",
          key=lambda s: ("moe_sample", cfg_key(s.cfg), s.paged,
                         _shard_key(s.shard)),
          name="serving.moe_sample_step")
def _build_moe_sample(spec: StepSpec):
    """Sampled joint-routing step: the moe_step body + the shared
    per-slot sampler (same key schedule as the dense ``sample`` kind)."""
    from . import moe_serving

    return jax.jit(
        _scoped(lambda p, c, t, s, ky, te, tk, tp, a, st, _cfg=spec.cfg:
                moe_serving.moe_sample_step_batched(p, c, t, s, ky, te, tk, tp,
                                                    a, st, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 8, "rcr"))


@register("moe_block",
          key=lambda s: ("moe_block", cfg_key(s.cfg), s.k, s.paged,
                         _shard_key(s.shard)),
          name=lambda s: f"serving.moe_block@{s.k}")
def _build_moe_block(spec: StepSpec):
    """Greedy joint-routing block: k steps on device, one host fetch —
    (p, cache, tok, pos, act, stats) -> (toks [B, k], cache, tok, pos,
    stats').  ``act`` is dispatch-time occupancy for the whole block."""
    from . import moe_serving

    return jax.jit(
        _scoped(lambda p, c, t, s, a, st, _cfg=spec.cfg, _k=spec.k:
                moe_serving.moe_decode_block_batched(p, c, t, s, a, st, _k,
                                                     _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rcrrr"))


@register("moe_async",
          key=lambda s: ("moe_async", cfg_key(s.cfg), s.paged,
                         _shard_key(s.shard)),
          name="serving.moe_async_step")
def _build_moe_async(spec: StepSpec):
    """Async-dispatch joint-routing tick: the device-side feed select
    (``pm`` picks the in-flight step's tokens over the host feed — see
    the dense ``async`` kind) in front of the sampled moe step."""
    from . import moe_serving

    return jax.jit(
        _scoped(lambda p, c, ht, pm, pv, s, ky, te, tk, tp, a, st,
                _cfg=spec.cfg:
                moe_serving.moe_sample_step_batched(
                    p, c, jnp.where(pm, pv, ht), s, ky, te, tk, tp, a, st,
                    _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 10, "rcr"))


@register("moe_prefill",
          key=lambda s: ("moe_prefill", cfg_key(s.cfg), int(s.bucket),
                         _shard_key(s.shard)),
          name=lambda s: f"serving.moe_prefill@{s.bucket}")
def _build_moe_prefill(spec: StepSpec):
    """Bucketed MoE admission: generate.prefill_slot already routes the
    padded bucket with valid= masking + the dropless capacity override,
    which is exact for MoE — this kind only names/keys those compiles
    apart from dense servers'."""
    from . import generate

    return jax.jit(
        _scoped(lambda p, c, t, ln, sl, _cfg=spec.cfg:
                generate.prefill_slot(p, c, t, ln, sl, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 3, "rc"))


@register("moe_prefill_chunk",
          key=lambda s: ("moe_prefill_chunk", cfg_key(s.cfg),
                         _shard_key(s.shard),
                         None if s.width is None else int(s.width)),
          name=lambda s: ("serving.moe_prefill_chunk" if s.width is None
                          else f"serving.moe_prefill_chunk@{int(s.width)}"))
def _build_moe_prefill_chunk(spec: StepSpec):
    """Chunked/budgeted MoE admission (dropless — see moe_prefill)."""
    from . import generate

    return jax.jit(
        _scoped(lambda p, c, t, p0, ln, sl, _cfg=spec.cfg:
                generate.prefill_slot_chunk(p, c, t, p0, ln, sl, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rc"))


@register("moe_paged_prefill",
          key=lambda s: ("moe_paged_prefill", cfg_key(s.cfg),
                         int(s.bucket), _shard_key(s.shard)),
          name=lambda s: f"serving.moe_paged_prefill@{s.bucket}")
def _build_moe_paged_prefill(spec: StepSpec):
    """Paged MoE admission (dropless — see moe_prefill)."""
    from . import kv_pool

    return jax.jit(
        _scoped(lambda p, c, t, p0, ln, sl, _cfg=spec.cfg:
                kv_pool.paged_prefill_chunk(p, c, t, p0, ln, sl, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rc"))


@register("moe_verify",
          key=lambda s: ("moe_verify", cfg_key(s.cfg), int(s.k), s.paged,
                         _shard_key(s.shard)),
          name=lambda s: f"serving.moe_verify@{s.k}")
def _build_moe_verify(spec: StepSpec):
    """Speculative verify over an MoE target: the chunked verify body
    routes the [B, K+1] window per slot with the dropless capacity
    override, so acceptance is exact vs the solo target.  Registered and
    unit-tested; DecodeServer still REJECTS spec x MoE at construction —
    batched verify's joint-routing twin (capacity semantics across
    slots' windows) is the ROADMAP follow-up this kind is staged for."""
    from . import serving

    return jax.jit(
        _scoped(lambda p, c, t, s, _cfg=spec.cfg:
                serving.spec_verify_batched(p, c, t, s, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 2, "rc"))


# -- adapter kinds (multi-tenant serving: text/adapters.py) ----------------
#
# Every kind below keys on ``pkey`` (AdapterPool.pool_key() — the pool
# GEOMETRY: capacity/rank/targets) next to the usual cfg/layout/placement
# fragments, so two servers sharing one pool share executables while a
# differently-shaped pool compiles its own.  The stacked lora leaves ride
# as an extra input right after the cache (NEVER donated — the pool keeps
# the live copy; only the cache at arg 1 aliases); under a ``mesh=`` shard
# context they take their Megatron-derived stacked specs
# (``adapters.stacked_pool_specs`` via ``_ShardCtx(pool=...)``), and
# registering an adapter is a row write into fixed [A, ...] shapes — zero
# mid-serving retraces.


@register("adapter_step",
          key=lambda s: ("adapter_step", cfg_key(s.cfg), s.pkey, s.paged,
                         _shard_key(s.shard)),
          name="serving.adapter_step")
def _build_adapter_step(spec: StepSpec):
    """Greedy adapter-gathered batched step: (p, cache, stacks, ids [B],
    tok [B], pos [B]) -> (logits [B, V], cache)."""
    from . import adapters as _adapters

    return jax.jit(
        _scoped(lambda p, c, ad, ids, t, s, _cfg=spec.cfg:
                _adapters.adapter_decode_step_batched(p, c, ad, ids, t, s,
                                                      _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 3, "rc", adapters=True))


@register("adapter_sample",
          key=lambda s: ("adapter_sample", cfg_key(s.cfg), s.pkey,
                         s.paged, _shard_key(s.shard)),
          name="serving.adapter_sample_step")
def _build_adapter_sample(spec: StepSpec):
    """Adapter-gathered sampled/masked step: the constraint mask [B, V]
    is a plain array input (all-zero = unconstrained), so per-request
    automaton state never retraces anything."""
    from . import adapters as _adapters

    return jax.jit(
        _scoped(lambda p, c, ad, ids, t, s, ky, te, tk, tp, m, _cfg=spec.cfg:
                _adapters.adapter_sample_step_batched(
                    p, c, ad, ids, t, s, ky, te, tk, tp, m, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 8, "rc", adapters=True))


@register("adapter_block",
          key=lambda s: ("adapter_block", cfg_key(s.cfg), s.k, s.pkey,
                         s.paged, _shard_key(s.shard)),
          name=lambda s: f"serving.adapter_block@{s.k}")
def _build_adapter_block(spec: StepSpec):
    """Adapter-gathered greedy block (tick_block's gathered twin)."""
    from . import adapters as _adapters

    return jax.jit(
        _scoped(lambda p, c, ad, ids, t, s, _cfg=spec.cfg, _k=spec.k:
                _adapters.adapter_decode_block_batched(p, c, ad, ids, t, s, _k,
                                                       _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 3, "rcrr", adapters=True))


@register("adapter_async",
          key=lambda s: ("adapter_async", cfg_key(s.cfg), s.pkey, s.paged,
                         _shard_key(s.shard)),
          name="serving.adapter_async_step")
def _build_adapter_async(spec: StepSpec):
    """Adapter-gathered async step: the device-side feed select of the
    ``async`` kind plus the per-slot gather.  No mask input —
    constrained slots force the sync path (the mask must be built from
    the PREVIOUS token, which an async pipeline hasn't fetched yet)."""
    from . import adapters as _adapters

    return jax.jit(
        _scoped(lambda p, c, ad, ids, ht, pm, pv, s, ky, te, tk, tp,
                _cfg=spec.cfg:
                _adapters.adapter_sample_step_batched(
                    p, c, ad, ids, jnp.where(pm, pv, ht), s, ky, te, tk,
                    tp, None, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 9, "rc", adapters=True))


@register("adapter_spec_verify",
          key=lambda s: ("adapter_spec_verify", cfg_key(s.cfg), int(s.k),
                         s.pkey, s.paged, _shard_key(s.shard)),
          name=lambda s: f"serving.adapter_spec_verify@{s.k}")
def _build_adapter_spec_verify(spec: StepSpec):
    """Adapter-gathered speculative verify: the verify pass gathers the
    SAME per-slot adapter the decode step uses, so accepted tokens are
    exactly the adapter-aware target's tokens (the base-model draft
    only affects the acceptance RATE, never the output)."""
    from . import adapters as _adapters

    return jax.jit(
        _scoped(lambda p, c, ad, ids, t, s, _cfg=spec.cfg:
                _adapters.adapter_spec_verify_batched(p, c, ad, ids, t, s,
                                                      _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 3, "rc", adapters=True))


@register("adapter_prefill",
          key=lambda s: ("adapter_prefill", cfg_key(s.cfg), int(s.bucket),
                         s.pkey, _shard_key(s.shard)),
          name=lambda s: f"serving.adapter_prefill@{s.bucket}")
def _build_adapter_prefill(spec: StepSpec):
    """Whole-prompt admission under one slot's adapter (scalar aid):
    the prompt's cache rows must reflect the ADAPTED weights, or decode
    would attend base-model rows."""
    from . import adapters as _adapters

    return jax.jit(
        _scoped(lambda p, c, ad, aid, t, ln, sl, _cfg=spec.cfg:
                _adapters.adapter_prefill_slot(p, c, ad, aid, t, ln, sl,
                                               _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 4, "rc", adapters=True))


@register("adapter_prefill_chunk",
          key=lambda s: ("adapter_prefill_chunk", cfg_key(s.cfg), s.pkey,
                         _shard_key(s.shard),
                         None if s.width is None else int(s.width)),
          name=lambda s: ("serving.adapter_prefill_chunk"
                          if s.width is None else
                          f"serving.adapter_prefill_chunk@{int(s.width)}"))
def _build_adapter_prefill_chunk(spec: StepSpec):
    """Fixed-chunk / budgeted admission under one slot's adapter (the
    adapter twin of the ``prefill_chunk`` kind, same width keying)."""
    from . import adapters as _adapters

    return jax.jit(
        _scoped(lambda p, c, ad, aid, t, p0, ln, sl, _cfg=spec.cfg:
                _adapters.adapter_prefill_slot_chunk(p, c, ad, aid, t, p0,
                                                     ln, sl, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 5, "rc", adapters=True))


@register("adapter_paged_prefill",
          key=lambda s: ("adapter_paged_prefill", cfg_key(s.cfg),
                         int(s.bucket), s.pkey, _shard_key(s.shard)),
          name=lambda s: f"serving.adapter_paged_prefill@{s.bucket}")
def _build_adapter_paged_prefill(spec: StepSpec):
    """Paged admission chunk under one slot's adapter."""
    from . import adapters as _adapters

    return jax.jit(
        _scoped(lambda p, c, ad, aid, t, p0, ln, sl, _cfg=spec.cfg:
                _adapters.adapter_paged_prefill_chunk(
                    p, c, ad, aid, t, p0, ln, sl, _cfg)),
        donate_argnums=donate_cache(),
        **_shard_kw(spec.shard, 5, "rc", adapters=True))


# -- offline generate kinds (text/generate.py's _GEN_CACHE domain) ---------


@register("generate", domain="gen",
          key=lambda s: (cfg_key(s.cfg),) + tuple(s.extra),
          name="generate.generate")
def _build_generate(spec: StepSpec):
    """jit per (config VALUE, gen params) — GPTConfig is closed over
    (dataclass isn't hashable for static_argnames)."""
    from . import generate as _g

    max_new_tokens, top_k, top_p = spec.extra
    return jax.jit(_scoped(functools.partial(
                _g._generate_impl, cfg=spec.cfg, max_new_tokens=max_new_tokens,
                top_k=top_k, top_p=float(top_p))))


@register("beam", domain="gen",
          key=lambda s: ("beam", cfg_key(s.cfg)) + tuple(s.extra),
          name="generate.beam_search")
def _build_beam(spec: StepSpec):
    from . import generate as _g

    max_new_tokens, num_beams, length_penalty, eos_id = spec.extra
    return jax.jit(_scoped(functools.partial(
                _g._beam_impl, cfg=spec.cfg, max_new_tokens=max_new_tokens,
                num_beams=num_beams, length_penalty=length_penalty,
                eos_id=eos_id)))


@register("jit_by_cfg", domain="gen",
          key=lambda s: (s.extra[0], cfg_key(s.cfg)),
          name=lambda s: f"generate.{s.extra[0]}")
def _build_jit_by_cfg(spec: StepSpec):
    """Value-keyed decode-path jit (the old generate._jit_by_cfg): the
    tag in ``extra[0]`` pins the step fn's identity (decode / verify /
    ...), so the fn itself rides in ``payload`` un-keyed."""
    fn = spec.payload
    return jax.jit(
        _scoped(lambda p, c, t, s, _cfg=spec.cfg: fn(p, c, t, s, _cfg)),
        donate_argnums=donate_cache())


@register("sharded_decode", domain="gen", cached=False,
          key=lambda s: (cfg_key(s.cfg),) + tuple(s.extra),
          name="generate.sharded_decode")
def _build_sharded_decode(spec: StepSpec):
    """``build_sharded_decode``'s jitted step: the builder computes the
    mesh/pspec trees (call-time objects) and passes the step fn + jit
    kwargs via ``payload``; ``extra`` carries (layout, block_size) —
    the key fragments.  Uncached by contract: each build call returns a
    fresh instrumented wrapper (jax's trace cache still dedupes the
    underlying executable), matching the pre-Engine behavior."""
    fn, jit_kwargs = spec.payload
    return jax.jit(_scoped(fn), **jit_kwargs)


class Engine:
    """THE step-compilation authority: build via the registry, cache in
    two bounded LRU domains, donate per :func:`donate_cache`, and
    instrument every build through the PR 4 recompile watch.

    ``_steps`` is the old ``serving._STEP_CACHE`` and ``_gen`` the old
    ``generate._GEN_CACHE`` — both modules now alias these same
    objects, so every legacy test surface (clear/keys/maxsize) and the
    eviction bounds keep working unchanged."""

    def __init__(self):
        self._steps = _LRU(
            int(_os.environ.get("PADDLE_TPU_STEP_CACHE_SIZE", "64")))
        # generous defaults: eviction only matters for servers cycling
        # many model configs; one served model stays far under the
        # bound
        self._gen = _LRU(
            int(_os.environ.get("PADDLE_TPU_GEN_CACHE_SIZE", "64")))

    def _domain(self, entry: _Kind) -> _LRU:
        return self._gen if entry.domain == "gen" else self._steps

    def get(self, kind: str, spec: StepSpec):
        """The single cache-get choke point: resolve ``kind`` in the
        registry, key it by ``spec``, and on a miss build + instrument
        the executable.  Every jitted step in text/ funnels through
        here (or :meth:`jit`) — ``tools/check_instrumented.py``'s
        ENGINE lint fails any ``jax.jit`` outside this module."""
        entry = _REGISTRY[kind]
        key = entry.key(spec)

        def build():
            name = _building.scope = entry.name(spec)
            return _watch_jit(name, key, _traced_per_shard(
                spec.shard, entry.build(spec)))

        if not entry.cached:
            return build()
        cache = self._domain(entry)
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = build()
        return fn

    def jit(self, name: str, key, fn, *, cache: bool = True,
            **jit_kwargs):
        """Generic instrumented jit for the one-off compiles that don't
        warrant a registry kind (evaluate's NLL passes, gpt_hybrid's
        init/step builds, lora's train step): same watch, same ``_gen``
        cache when ``cache=True``, a fresh instrumented wrapper per
        call when not (builders whose out_shardings differ per mesh
        must not share by key)."""
        if not cache:
            return _watch_jit(name, key, jax.jit(fn, **jit_kwargs))
        hit = self._gen.get(key)
        if hit is None:
            hit = _watch_jit(name, key, jax.jit(fn, **jit_kwargs))
            self._gen[key] = hit
        return hit

    def purge(self, *cfgs) -> int:
        """Drop every cached executable keyed to any of ``cfgs`` — BOTH
        domains (step + generate), every registered family (plain,
        adapter, spec, draft twins) in one pass over the Engine's own
        caches.  This is the round-15 close()-leak fix: the old
        ``DecodeServer.close`` hand-enumerated ``_STEP_CACHE`` families
        and silently leaked the ``_GEN_CACHE`` entries (offline
        generate/eval compiles against a served config), and every new
        family meant another line to forget.  ``None`` entries are
        skipped so ``purge(cfg, draft_cfg)`` works draftless."""
        cks = [cfg_key(c) for c in cfgs if c is not None]
        if not cks:
            return 0
        dropped = 0
        for cache in (self._steps, self._gen):
            for k in cache.keys():
                if any(k == ck or (isinstance(k, tuple) and ck in k)
                       for ck in cks):
                    if cache.pop(k, None) is not None:
                        dropped += 1
        if dropped:
            _telemetry.count("engine.purged_executables", dropped)
        return dropped

    def warmup(self, srv, prompt_lens=None, blocks=(),
               sample: bool = False, constrained: bool = False):
        """Pre-compile the executables ``srv`` (a DecodeServer) will
        serve, so the first request pays device time only (and
        re-launches hit the persistent compilation cache —
        framework.platform.init_compile_cache, called here).  Owned by
        the Engine since round 15: warmup is a pure walk of the step
        registry over the server's declared spec space, so it lives
        next to the registry — ``DecodeServer.warmup`` delegates here.

        With an ``adapter_pool`` attached, every warm site compiles the
        ADAPTER twin instead (gathered steps/blocks/verify/prefill, ids
        all-zero — the executables are shape-keyed, so base-only warmup
        covers every adapter id), and ``sample=True`` warms the
        masked+sampled adapter step (the one executable constrained OR
        sampled pool traffic runs).  ``constrained=True`` warms the
        pool-less masked step for servers expecting ``constraint=``
        requests without a pool.

        This also warms the flash-decode kernel variants: tracing the
        step executables compiles the split-KV Pallas kernel
        (ops/decode_attention) for this
        server's exact (cache length, head, KV-dtype) configuration —
        under ``PADDLE_TPU_FLASH_DECODE``/``PADDLE_TPU_KV_DTYPE`` the
        first tick pays device time only, like every other executable
        here.

        ``prompt_lens``: prompt lengths to warm admission for — their
        power-of-two buckets dedupe to one compile each (default: every
        bucket up to the serving window; chunked-prefill servers have a
        single executable regardless).  ``blocks``: tick_block sizes to
        warm.  ``sample``: also warm the sampled-step twins.

        Warm steps run on the LIVE cache (donation chains it through),
        writing garbage rows at pos 0 for every slot — hidden by the
        same stale-row invariant as slot reuse: admission prefill
        overwrites rows [0, n), n >= 1, before any mask exposes them.
        That invariant only holds for requests admitted AFTER warmup,
        so warming an idle server is enforced: an active slot's
        already-prefilled rows would be silently corrupted.  The PRNG
        step counter is NOT advanced, so a warmed server produces
        bit-identical tokens to a cold one.

        Returns {executable: seconds} compile+first-run timings."""
        from ..framework import platform as _platform

        if (srv._inflight is not None and not srv._slots
                and not srv._queue):
            # a drained async server's final overrun dispatch: every slot
            # it fed has retired, so its tokens are disposable by design
            srv._inflight = None
        if srv._slots or srv._queue or srv._inflight is not None:
            raise RuntimeError(
                "DecodeServer.warmup() requires an idle server: warm "
                "steps write garbage rows at pos 0 of every slot, which "
                "only un-admitted requests are guaranteed to overwrite")
        _platform.init_compile_cache()
        timings = {}
        B = srv.max_batch
        zi = np.zeros((B,), np.int32)
        zb = np.zeros((B,), bool)
        zf = np.zeros((B,), np.float32)
        of = np.ones((B,), np.float32)
        # any key works (warmup compiles; values are discarded) — a high
        # sentinel keeps clear of the per-step fold_in counters
        key = jax.random.fold_in(srv._base_key, (1 << 31) + 1)
        # target-model and draft-twin specs: the draft twin places by the
        # DRAFT shard context (its own sharded_cache_specs under mesh=)
        tspec = lambda **kw: StepSpec(  # noqa: E731
            cfg=srv.cfg, shard=srv._shard, **kw)
        dspec = lambda **kw: StepSpec(  # noqa: E731
            cfg=srv.draft_cfg, shard=srv._draft_shard, **kw)

        def warm(name, thunk):
            t0 = _time.perf_counter()
            out = thunk()
            jax.block_until_ready(out[0])
            srv.cache = out[1]
            timings[name] = round(_time.perf_counter() - t0, 3)

        def warm_draft(name, thunk):
            # the draft twin: reassigns the DRAFT cache (donation
            # chains it through exactly like the target's)
            t0 = _time.perf_counter()
            out = thunk()
            jax.block_until_ready(out[0])
            srv._draft_cache = out[1]
            timings[name] = round(_time.perf_counter() - t0, 3)

        tok, pos = jnp.asarray(zi), jnp.asarray(zi)
        # the async kinds' "previous device tokens" argument, built the way
        # the server builds a fresh one: under a mesh its type carries the
        # mesh, and warming with a plain array would leave the first
        # steady tick to compile again
        pv0 = srv._prev_feed(None)
        moe = srv.cfg.moe is not None
        if moe:
            # the joint-routing kinds' extra runtime inputs: an all-False
            # occupancy mask (an idle server's act — zero valid tokens,
            # so the warm routes claim nothing and the stats delta is
            # exactly zero: a warmed MoE server's counters match a cold
            # one's) and the live accumulator
            mact = jnp.asarray(zb)
            mst = srv._moe_stats
        pool = srv._adapters
        if pool is not None:
            pk = pool.pool_key()
            ad = pool.stacks()
            ids0 = jnp.asarray(zi)          # all-base gather
            aid0 = jnp.asarray(0)
            zm = jnp.zeros((B, srv.cfg.vocab_size), jnp.float32)
        if pool is not None:
            # adapter twins: these ARE the executables a pool-attached
            # server dispatches (see _tick_impl) — the plain ones would
            # be dead compiles
            if srv._async:
                fn = self.get("adapter_async",
                              tspec(paged=srv._paged, pkey=pk))
                warm("adapter_async_step", lambda: fn(
                    srv.params, srv.cache, ad, ids0, tok,
                    jnp.asarray(zb), pv0, pos, key, jnp.asarray(zf),
                    jnp.asarray(zi), jnp.asarray(of)))
            # the sync greedy step also serves async servers' stepwise
            # constraint fallback, so warm it unconditionally
            fn = self.get("adapter_step",
                          tspec(paged=srv._paged, pkey=pk))
            warm("adapter_step", lambda: fn(
                srv.params, srv.cache, ad, ids0, tok, pos))
            if sample or constrained:
                fn = self.get("adapter_sample",
                              tspec(paged=srv._paged, pkey=pk))
                warm("adapter_sample_step", lambda: fn(
                    srv.params, srv.cache, ad, ids0, tok, pos, key,
                    jnp.asarray(zf), jnp.asarray(zi), jnp.asarray(of),
                    zm))
        elif srv._async and moe:
            fn = self.get("moe_async", tspec(paged=srv._paged))
            warm("moe_async_step", lambda: fn(
                srv.params, srv.cache, tok, jnp.asarray(zb), pv0, pos,
                key, jnp.asarray(zf), jnp.asarray(zi), jnp.asarray(of),
                mact, mst))
            # constrained x MoE is rejected at submit — nothing to warm
        elif srv._async:
            fn = self.get("async", tspec(paged=srv._paged))
            warm("async_step", lambda: fn(
                srv.params, srv.cache, tok, jnp.asarray(zb), pv0, pos,
                key, jnp.asarray(zf), jnp.asarray(zi), jnp.asarray(of)))
            if constrained:
                # async constrained traffic drains to the SYNC masked
                # step (_tick_impl's fallback) — warm that path too
                fn = self.get("masked_step", tspec(paged=srv._paged))
                zm = jnp.zeros((B, srv.cfg.vocab_size), jnp.float32)
                warm("masked_step", lambda: fn(
                    srv.params, srv.cache, tok, pos, key,
                    jnp.asarray(zf), jnp.asarray(zi), jnp.asarray(of),
                    zm))
        else:
            # srv._step is the moe-wrapped joint step under MoE (the
            # wrapper appends act+stats and peels the stats output), so
            # this one call warms moe_step and step alike
            warm("step", lambda: srv._step(srv.params, srv.cache, tok,
                                           pos))
            if sample and moe:
                fn = self.get("moe_sample", tspec(paged=srv._paged))
                warm("moe_sample_step", lambda: fn(
                    srv.params, srv.cache, tok, pos, key,
                    jnp.asarray(zf), jnp.asarray(zi), jnp.asarray(of),
                    mact, mst))
            elif sample:
                fn = self.get("sample", tspec(paged=srv._paged))
                warm("sample_step", lambda: fn(
                    srv.params, srv.cache, tok, pos, key,
                    jnp.asarray(zf), jnp.asarray(zi), jnp.asarray(of)))
            if constrained and not moe:
                fn = self.get("masked_step", tspec(paged=srv._paged))
                zm = jnp.zeros((B, srv.cfg.vocab_size), jnp.float32)
                warm("masked_step", lambda: fn(
                    srv.params, srv.cache, tok, pos, key,
                    jnp.asarray(zf), jnp.asarray(zi), jnp.asarray(of),
                    zm))
        for k in blocks:
            k = int(k)
            if pool is not None:
                if srv._async:
                    # async adapter tick_block falls back to stepwise
                    # async ticks (adapter_async_step, warmed above) —
                    # no block executable to compile
                    continue
                fn = self.get("adapter_block",
                              tspec(paged=srv._paged, pkey=pk, k=k))
                warm(f"adapter_block{k}", lambda fn=fn: fn(
                    srv.params, srv.cache, ad, ids0, tok, pos)[:2])
                # sampled pool traffic steps through adapter_sample_step
                # (tick_block's stepwise fallback) — no sampled block
            elif srv._async and moe:
                # async MoE tick_block drains to stepwise async ticks
                # (moe_async_step, warmed above) — no block executable
                continue
            elif moe:
                fn = self.get("moe_block", tspec(paged=srv._paged, k=k))
                warm(f"moe_block{k}", lambda fn=fn: fn(
                    srv.params, srv.cache, tok, pos, mact, mst)[:2])
                # sampled MoE traffic steps through moe_sample_step
                # (tick_block's stepwise fallback) — no sampled block
            elif srv._async:
                fn = self.get("async_block",
                              tspec(paged=srv._paged, k=k))
                warm(f"async_block{k}", lambda fn=fn: fn(
                    srv.params, srv.cache, tok, jnp.asarray(zb), pv0,
                    pos)[:2])
                if sample:
                    fn = self.get("async_sample_block",
                                  tspec(paged=srv._paged, k=k))
                    warm(f"async_sample_block{k}", lambda fn=fn: fn(
                        srv.params, srv.cache, tok, jnp.asarray(zb),
                        pv0, pos, srv._base_key, jnp.asarray(0),
                        jnp.asarray(zf), jnp.asarray(zi),
                        jnp.asarray(of)))
            else:
                fn = self.get("block", tspec(paged=srv._paged, k=k))
                warm(f"block{k}", lambda fn=fn: fn(
                    srv.params, srv.cache, tok, pos)[:2])
                if sample:
                    fn = self.get("sample_block",
                                  tspec(paged=srv._paged, k=k))
                    warm(f"sample_block{k}", lambda fn=fn: fn(
                        srv.params, srv.cache, tok, pos,
                        srv._base_key, jnp.asarray(0), jnp.asarray(zf),
                        jnp.asarray(zi), jnp.asarray(of)))
        if srv._spec_on:
            # the speculative round's executables: the batched verify
            # (K garbage rows per slot at pos 0 — the same stale-row
            # cover as the plain warm steps) and, in draft mode, the
            # draft's own decode step
            if getattr(srv, "_spec_tree_n", 0):
                # tree mode: the tree-masked verify (topology runtime
                # args: a self-only mask + zero depths compile the same
                # executable any real tree reuses) plus the acceptance
                # permute (identity src — rewrites the garbage rows)
                N = srv._spec_tree_n
                tokN = jnp.zeros((B, N), jnp.int32)
                am = jnp.zeros((B, N, N), bool)
                am = am.at[:, jnp.arange(N), jnp.arange(N)].set(True)
                dep = jnp.zeros((B, N), jnp.int32)
                sfn = self.get("spec_tree_verify",
                               tspec(paged=srv._paged, k=N))
                warm(f"spec_tree_verify@{N}", lambda: sfn(
                    srv.params, srv.cache, tokN, am, dep, pos))
                cfn = self.get("spec_tree_commit",
                               tspec(paged=srv._paged, k=N))
                src = jnp.tile(jnp.arange(1, N, dtype=jnp.int32)[None],
                               (B, 1))
                t0c = _time.perf_counter()
                out = cfn(srv.cache, src, pos)
                jax.block_until_ready(out["k"])
                srv.cache = out
                timings[f"spec_tree_commit@{N}"] = round(
                    _time.perf_counter() - t0c, 3)
            else:
                K = srv._spec_k
                tokK = jnp.zeros((B, K), jnp.int32)
                if pool is not None:
                    sfn = self.get("adapter_spec_verify",
                                   tspec(paged=srv._paged, pkey=pk, k=K))
                    warm(f"adapter_spec_verify@{K}", lambda: sfn(
                        srv.params, srv.cache, ad, ids0, tokK, pos))
                else:
                    sfn = self.get("spec_verify",
                                   tspec(paged=srv._paged, k=K))
                    warm(f"spec_verify@{K}", lambda: sfn(
                        srv.params, srv.cache, tokK, pos))
            if srv._draft_cache is not None:
                dfn = self.get("step", dspec(paged=srv._paged))
                warm_draft("draft_step", lambda: dfn(
                    srv._draft_params, srv._draft_cache, tok, pos))
        window = min(srv.max_len, srv.cfg.max_seq_len)
        if srv._paged and srv._prefill_on:
            # paged admission executables: one offset-aware chunk
            # program per width (fixed chunk, or the suffix buckets).
            # Widths floor at the block size (admission's rule), and the
            # block-size width itself is always warmed: a prefix-hit
            # admission prefills a sub-block suffix through it, which
            # must not compile mid-serving on a warmed server
            if srv._chunk:
                widths = [min(srv._chunk, window)]
            else:
                # admission buckets the suffix to
                # min(max(pow2(n - shared), bs), window): a PARTIAL
                # prefix hit lands on ANY power of two in (bs, pow2(n)]
                # (not bs*2^k — bs need not be a power of two), plus the
                # bs floor itself.  Warm exactly that reachable set —
                # log-many executables, no mid-serving compile
                def _ladder(top):
                    if srv._recurrent:
                        # no prefix is ever adopted beside a recurrent
                        # state: an admission prefills its whole prompt,
                        # at that prompt's own bucket and no other
                        return {min(max(top, srv._pool.bs), window)}
                    ws, p = {min(srv._pool.bs, window)}, 1
                    while p < top:
                        p *= 2
                        if p > srv._pool.bs:
                            ws.add(min(p, window))
                    return ws

                if prompt_lens is None:
                    widths = _ladder(window)
                else:
                    widths = set()
                    for n in prompt_lens:
                        widths |= _ladder(
                            1 << max(0, int(n) - 1).bit_length())
            if srv._budget:
                # budgeted admission walks the budget-width chunk
                # executable for every claimed (multi-chunk) prompt —
                # and, with admission control on, EVERY degradation-
                # ladder rung (admission.ladder_widths): the SLO
                # controller's budget moves must pick among compiled
                # programs, never retrace mid-serving
                rungs = (srv._adm.budget_rungs if srv._adm is not None
                         else (srv._budget,))
                widths = set(widths) | {min(w, window)
                                        for w in rungs or (srv._budget,)}
            for C in sorted(set(widths)):
                padded = jnp.zeros((1, C), jnp.int32)
                if pool is not None:
                    afn = self.get("adapter_paged_prefill",
                                   tspec(bucket=C, pkey=pk))
                    warm(f"adapter_paged_prefill{C}",
                         lambda afn=afn, padded=padded: afn(
                             srv.params, srv.cache, ad, aid0, padded,
                             jnp.asarray(0), jnp.asarray(1),
                             jnp.asarray(0)))
                else:
                    fn = self.get(
                        "moe_paged_prefill" if moe else "paged_prefill",
                        tspec(bucket=C))
                    warm(f"paged_prefill{C}",
                         lambda fn=fn, padded=padded: fn(
                             srv.params, srv.cache, padded,
                             jnp.asarray(0), jnp.asarray(1),
                             jnp.asarray(0)))
                if srv._draft_cache is not None:
                    dfn = self.get("paged_prefill", dspec(bucket=C))
                    warm_draft(f"draft_paged_prefill{C}",
                               lambda dfn=dfn, padded=padded: dfn(
                                   srv._draft_params,
                                   srv._draft_cache, padded,
                                   jnp.asarray(0), jnp.asarray(1),
                                   jnp.asarray(0)))
        elif srv._prefill_chunk is not None:
            C = srv._chunk
            padded = jnp.zeros((1, C), jnp.int32)
            if pool is not None:
                afn = self.get("adapter_prefill_chunk", tspec(pkey=pk))
                warm(f"adapter_prefill_chunk{C}", lambda: afn(
                    srv.params, srv.cache, ad, aid0, padded,
                    jnp.asarray(0), jnp.asarray(1), jnp.asarray(0)))
            else:
                warm(f"prefill_chunk{C}", lambda: srv._prefill_chunk(
                    srv.params, srv.cache, padded, jnp.asarray(0),
                    jnp.asarray(1), jnp.asarray(0)))
            if srv._draft_cache is not None:
                dfn = self.get("prefill_chunk", dspec())
                warm_draft(f"draft_prefill_chunk{C}",
                           lambda: dfn(srv._draft_params,
                                       srv._draft_cache, padded,
                                       jnp.asarray(0), jnp.asarray(1),
                                       jnp.asarray(0)))
        elif srv._prefill is not None:
            if prompt_lens is None:
                buckets, b = [], 1
                while b < window:
                    buckets.append(b)
                    b *= 2
                buckets.append(window)
            else:
                buckets = [min(1 << max(0, int(n) - 1).bit_length(),
                               window) for n in prompt_lens]
            for b in sorted(set(buckets)):
                padded = jnp.zeros((1, b), jnp.int32)
                if pool is not None:
                    afn = self.get("adapter_prefill",
                                   tspec(bucket=b, pkey=pk))
                    warm(f"adapter_prefill{b}",
                         lambda afn=afn, padded=padded: afn(
                             srv.params, srv.cache, ad, aid0, padded,
                             jnp.asarray(1), jnp.asarray(0)))
                else:
                    fn = srv._prefill(b)
                    warm(f"prefill{b}", lambda fn=fn, padded=padded: fn(
                        srv.params, srv.cache, padded, jnp.asarray(1),
                        jnp.asarray(0)))
                if srv._draft_cache is not None:
                    dfn = self.get("prefill", dspec(bucket=b))
                    warm_draft(f"draft_prefill{b}",
                               lambda dfn=dfn, padded=padded: dfn(
                                   srv._draft_params,
                                   srv._draft_cache, padded,
                                   jnp.asarray(1), jnp.asarray(0)))
        if srv._budget and not srv._paged:
            # budgeted admission's offset-aware chunk executables: the
            # base width, plus — with admission control on — every
            # degradation-ladder rung (admission.ladder_widths), so the
            # SLO controller's budget moves (including round 15's
            # ADAPTIVE shrink-on-TPOT-breach) pick among compiled
            # programs and never retrace mid-serving
            rungs = (srv._adm.budget_rungs if srv._adm is not None
                     else ()) or (srv._budget,)
            for Wb in sorted({min(w, window) for w in rungs},
                             reverse=True):
                pad_b = jnp.zeros((1, Wb), jnp.int32)
                if pool is not None:
                    abfn = self.get("adapter_prefill_chunk",
                                    tspec(pkey=pk, width=Wb))
                    warm(f"adapter_prefill_chunk@{Wb}",
                         lambda abfn=abfn, pad_b=pad_b: abfn(
                             srv.params, srv.cache, ad, aid0, pad_b,
                             jnp.asarray(0), jnp.asarray(1),
                             jnp.asarray(0)))
                else:
                    bfn = self.get(
                        "moe_prefill_chunk" if moe else "prefill_chunk",
                        tspec(width=Wb))
                    warm(f"prefill_chunk@{Wb}",
                         lambda bfn=bfn, pad_b=pad_b: bfn(
                             srv.params, srv.cache, pad_b,
                             jnp.asarray(0),
                             jnp.asarray(1), jnp.asarray(0)))
                if srv._draft_cache is not None:
                    dbfn = self.get("prefill_chunk", dspec(width=Wb))
                    warm_draft(f"draft_prefill_chunk@{Wb}",
                               lambda dbfn=dbfn, pad_b=pad_b: dbfn(
                                   srv._draft_params,
                                   srv._draft_cache, pad_b,
                                   jnp.asarray(0), jnp.asarray(1),
                                   jnp.asarray(0)))
        return timings


# the process-wide Engine: serving._STEP_CACHE and generate._GEN_CACHE
# alias its two domains, so legacy clear()/keys()/maxsize surfaces (and
# the tests that pin them) operate on the same objects
ENGINE = Engine()
