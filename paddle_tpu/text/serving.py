"""Continuous-batching decode server (slot scheduler over the KV cache).

Beyond-reference serving: the reference serves with one AnalysisPredictor
per thread (inference/api/analysis_predictor.cc — fixed batch, no shared
state); modern LLM serving instead keeps ONE resident batched KV cache and
lets requests join and leave mid-flight (continuous batching).  TPU-first
shape: the whole tick is one jitted ``decode_step`` vmapped over slots
with PER-SLOT positions — fixed shapes (XLA compiles once per
(max_batch, max_len)), no re-running prefixes, no cache re-allocation; a
freed slot is reused without clearing (the causal mask ``t <= pos`` hides
stale rows until they are overwritten).

    srv = DecodeServer(params, cfg, max_batch=8, max_len=256, eos_id=2)
    rid = srv.submit([5, 3, 9], max_new_tokens=32)
    while srv.pending():
        srv.tick()
    tokens = srv.result(rid)

Weight-only quantized params (text/woq.py) work unchanged — the vmapped
step routes through the same woq accessors.
"""
from __future__ import annotations

import contextlib
import os as _os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import admission as _admission
from . import engine as _engine
from . import generate, gpt
from .engine import StepSpec as _Spec
from .moe import SHARE_COUNTS as _SHARE_COUNTS
from .. import faults as _faults
from .. import flags as _flags
from .. import resilience as _resilience
from .. import telemetry as _telemetry

__all__ = ["decode_step_batched", "DecodeServer", "validate_request"]


def decode_step_batched(params, cache, token, pos, cfg: gpt.GPTConfig):
    """decode_step with PER-SLOT positions: token [B] int32, pos [B] int32.

    Implemented as vmap of the scalar-pos ``decode_step`` over the batch
    axis (params broadcast, every cache leaf's batch axis 1 — int8 scale
    planes included) — identical math, batched cache scatter.

    A pooled cache (text/kv_pool — a ``tables`` leaf marks the paged
    layout) routes to the block-table twin instead; the branch is on
    pytree STRUCTURE at trace time, so every step getter (sample/block/
    async) serves both layouts without new plumbing."""
    if "tables" in cache:
        from . import kv_pool

        return kv_pool.paged_decode_step_batched(params, cache, token,
                                                 pos, cfg)

    def one(tok, csl, p):
        sl = {name: v[:, None] for name, v in csl.items()}
        logits, new = generate.decode_step(params, sl, tok[None], p, cfg)
        return logits[0], {name: v[:, 0] for name, v in new.items()}

    logits, new = jax.vmap(one, in_axes=(0, 1, 0), out_axes=(0, 1))(
        token, cache, pos)
    return logits, new


def _sample_batched(logits, key, temp, topk, topp, mask=None):
    """Per-slot sampling over batched logits [B, V]: temperature scale,
    then top-k, then nucleus — the same pipeline (and order) as
    ``generate``'s sampler, vectorized with PER-SLOT parameters so one
    compiled step serves a batch mixing greedy and sampled requests.
    temp/topp are float32 [B], topk int32 [B] (0 = off); slots with
    temp == 0 take the argmax of the raw logits (bit-identical to the
    greedy path).  The filter math lives in generate._filter_logits —
    the single shared implementation.

    The step does what its batch asks for, chosen on the device from
    these same arrays (``lax.cond`` on a scalar of the step's inputs:
    one executable, the same under ``lax.scan`` and under a mesh): an
    all-greedy batch takes the argmax and nothing else; a batch whose
    sampled slots ask for no filter scales and draws; only a sampled
    slot with top-k or top-p on sorts the vocabulary, once.  A row's
    token is the same whichever branch its batch-mates select
    (``_asked`` is the choice; ``_count_sample_steps`` counts it).

    ``mask``: optional additive constraint mask [B, V] float32
    (0 = allowed, ``adapters.NEG_INF`` = banned — see
    text/adapters.mask_logits), applied BEFORE both branches so greedy
    (temp == 0) slots take the argmax of the MASKED logits: one
    executable serves constrained-greedy and constrained-sampled.  An
    all-zero row is exactly the unconstrained math."""
    with jax.named_scope("sample"):
        if mask is not None:
            logits = logits + mask
        on, draws, filters = _asked(temp, topk, topp)

        def greedy():
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def draw(scaled):
            sampled = jax.random.categorical(key, scaled,
                                             axis=-1).astype(jnp.int32)
            return jnp.where(on, sampled, greedy())

        def sampled():
            return jax.lax.cond(
                filters,
                lambda: draw(generate._filter_logits(logits, temp, topk,
                                                     topp)),
                lambda: draw(generate._scale_logits(logits, temp)))

        return jax.lax.cond(draws, sampled, greedy)


def _asked(temp, topk, topp):
    """What a batch's sampling arrays ask of the sampler: (the slots that
    sample, whether any does, whether any of those has top-k or top-p
    on).  One expression for the device's arrays, where the two scalars
    select ``_sample_batched``'s branch, and for the host's numpy copy
    of them, where ``_count_sample_steps`` counts it."""
    on = temp > 0.0
    return on, on.any(), (on & ((topk > 0) | (topp < 1.0))).any()


def _count_sample_steps(temp, tk, tp, steps: int = 1):
    """``serving.sample_steps_greedy`` / ``_sampled`` (drawn, no sort) /
    ``_filtered`` (sorted): which of ``_sample_batched``'s outcomes the
    dispatched steps take, from the host's arrays (no device read)."""
    _, draws, filters = _asked(temp, tk, tp)
    kind = "filtered" if filters else "sampled" if draws else "greedy"
    _telemetry.count(f"serving.sample_steps_{kind}", steps)


def sample_step_batched(params, cache, tok, pos, key, temp, topk, topp,
                        cfg: gpt.GPTConfig, mask=None):
    """One batched decode step that returns sampled TOKENS [B] (greedy
    where temp == 0) instead of logits — the sampling-serving twin of
    decode_step_batched.  ``mask`` (optional [B, V] additive constraint
    mask, see _sample_batched) rides through to the sampler."""
    logits, cache = decode_step_batched(params, cache, tok, pos, cfg)
    return _sample_batched(logits, key, temp, topk, topp, mask=mask), cache


def sample_block_batched(params, cache, tok, pos, base_key, off, temp, topk,
                         topp, k: int, cfg: gpt.GPTConfig):
    """``k`` sampled decode steps on device, one host fetch — the
    sampling twin of decode_block_batched.  Step j draws with
    fold_in(base_key, off + j): the SAME key schedule the per-tick path
    uses, so tick and tick_block produce identical tokens for identical
    step counters (tests rely on this parity)."""
    def body(carry, j):
        cache, tok, pos = carry
        logits, cache = decode_step_batched(params, cache, tok, pos, cfg)
        nxt = _sample_batched(logits, jax.random.fold_in(base_key, off + j),
                              temp, topk, topp)
        return (cache, nxt, pos + 1), nxt

    (cache, tok, pos), toks = jax.lax.scan(body, (cache, tok, pos),
                                           jnp.arange(k))
    return toks.T, cache


def decode_block_batched(params, cache, tok, pos, k: int, cfg: gpt.GPTConfig):
    """``k`` greedy decode steps entirely ON DEVICE (round-4 verdict Weak
    #3: fetching the argmax to numpy every tick makes decode
    latency host-round-trip-bound).  Each step's argmax feeds the next
    step inside one jitted ``lax.scan`` — the host sees one [B, k] token
    block per call instead of k scalar fetches.

    tok/pos [B] int32 are the NEXT token to feed / its position per slot.
    Returns (tokens [B, k], cache, next_tok [B], next_pos [B]).  Slots
    whose request finishes mid-block keep decoding (their surplus tokens
    are discarded by the caller) — the standard chunked-serving overrun
    tradeoff; their cache rows stay hidden by the slot-reuse invariant."""
    def body(carry, _):
        cache, tok, pos = carry
        logits, cache = decode_step_batched(params, cache, tok, pos, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt, pos + 1), nxt

    (cache, tok, pos), toks = jax.lax.scan(body, (cache, tok, pos), None,
                                           length=k)
    return toks.T, cache, tok, pos


# a tick that dispatched no step is named by what it did instead
_KIND_WITHOUT_STEP = {"admit": "admit_only", "wait": "fetch_only"}


def _hits_stop(st: dict) -> bool:
    gen = st["generated"]
    return any(len(gen) >= len(seq) and gen[-len(seq):] == seq
               for seq in st.get("stop", []))


# round 15: the Engine (text/engine.py) owns the step cache, the shard
# context, and every builder — the names below stay as aliases (tests and
# the fleet address them here), and each retired getter survives as a
# one-line shim over ``ENGINE.get(kind, StepSpec(...))`` with
# byte-compatible keys, watch names, jit bodies, and donation.
_STEP_CACHE = _engine.ENGINE._steps
_ShardCtx = _engine._ShardCtx
_shard_kw = _engine._shard_kw
_shard_key = _engine._shard_key

# cold prefix-cache entries evicted per OOM-chain engagement (LRU-first
# batches — repeated engagements drain more; never the whole index)
_EVICT_BATCH = 4


def _get_prefill_fn(cfg: gpt.GPTConfig, bucket: int, shard=None):
    """Engine shim: whole-prompt admission at one power-of-two bucket.
    MoE configs route to the ``moe_prefill`` kind (same dropless body,
    named/keyed apart) — call sites never branch."""
    kind = "moe_prefill" if cfg.moe is not None else "prefill"
    return _engine.ENGINE.get(kind, _Spec(
        cfg=cfg, bucket=int(bucket), shard=shard))


def _get_prefill_chunk_fn(cfg: gpt.GPTConfig, shard=None,
                          width: int | None = None):
    """Engine shim: contiguous fixed-chunk / budgeted admission step
    (``moe_prefill_chunk`` for MoE configs)."""
    kind = "moe_prefill_chunk" if cfg.moe is not None else "prefill_chunk"
    return _engine.ENGINE.get(kind, _Spec(
        cfg=cfg, shard=shard, width=width))


def _get_paged_prefill_fn(cfg: gpt.GPTConfig, bucket: int, shard=None):
    """Engine shim: paged offset-aware admission chunk
    (``moe_paged_prefill`` for MoE configs)."""
    kind = "moe_paged_prefill" if cfg.moe is not None else "paged_prefill"
    return _engine.ENGINE.get(kind, _Spec(
        cfg=cfg, bucket=int(bucket), shard=shard))


def _get_copy_fn(cfg: gpt.GPTConfig, n_pairs: int, shard=None):
    """Engine shim: copy-on-write block gather/scatter (n_pairs wide)."""
    return _engine.ENGINE.get("kv_copy", _Spec(
        cfg=cfg, k=int(n_pairs), shard=shard))


def _get_inject_fn(cfg: gpt.GPTConfig, bucket: int, paged: bool,
                   shard=None):
    """Engine shim: prefill-handoff row injector (the fleet's decode
    half)."""
    return _engine.ENGINE.get("inject", _Spec(
        cfg=cfg, bucket=int(bucket), paged=paged, shard=shard))


def _get_block_fn(cfg: gpt.GPTConfig, k: int, paged: bool = False,
                  shard=None):
    """Engine shim: k greedy decode steps on device per host fetch."""
    return _engine.ENGINE.get("block", _Spec(
        cfg=cfg, k=k, paged=paged, shard=shard))


def _get_sample_step_fn(cfg: gpt.GPTConfig, paged: bool = False,
                        shard=None):
    """Engine shim: the batched sampled tick step."""
    return _engine.ENGINE.get("sample", _Spec(
        cfg=cfg, paged=paged, shard=shard))


def _get_sample_block_fn(cfg: gpt.GPTConfig, k: int, paged: bool = False,
                         shard=None):
    """Engine shim: k sampled decode steps on device per host fetch."""
    return _engine.ENGINE.get("sample_block", _Spec(
        cfg=cfg, k=k, paged=paged, shard=shard))


def _get_step_fn(cfg: gpt.GPTConfig, paged: bool = False, shard=None):
    """Engine shim: THE batched greedy tick step (cache donated — the
    caller reassigns from the return; DecodeServer always does)."""
    return _engine.ENGINE.get("step", _Spec(
        cfg=cfg, paged=paged, shard=shard))


def _get_async_step_fn(cfg: gpt.GPTConfig, paged: bool = False,
                       shard=None):
    """Engine shim: the async-dispatch tick step (device-side feed
    select between host token and the in-flight step's output)."""
    return _engine.ENGINE.get("async", _Spec(
        cfg=cfg, paged=paged, shard=shard))


def _get_async_block_fn(cfg: gpt.GPTConfig, k: int, paged: bool = False,
                        shard=None):
    """Engine shim: async greedy block."""
    return _engine.ENGINE.get("async_block", _Spec(
        cfg=cfg, k=k, paged=paged, shard=shard))


def _get_async_sample_block_fn(cfg: gpt.GPTConfig, k: int,
                               paged: bool = False, shard=None):
    """Engine shim: async sampled block."""
    return _engine.ENGINE.get("async_sample_block", _Spec(
        cfg=cfg, k=k, paged=paged, shard=shard))


def _get_moe_step_fn(cfg: gpt.GPTConfig, paged: bool = False, shard=None):
    """Engine shim: the joint-routing greedy MoE tick step (round 19) —
    (p, cache, tok, pos, act, stats) -> (logits, cache, stats')."""
    return _engine.ENGINE.get("moe_step", _Spec(
        cfg=cfg, paged=paged, shard=shard))


def _get_moe_sample_step_fn(cfg: gpt.GPTConfig, paged: bool = False,
                            shard=None):
    """Engine shim: the sampled joint-routing MoE tick step."""
    return _engine.ENGINE.get("moe_sample", _Spec(
        cfg=cfg, paged=paged, shard=shard))


def _get_moe_block_fn(cfg: gpt.GPTConfig, k: int, paged: bool = False,
                      shard=None):
    """Engine shim: k greedy joint-routing MoE steps per host fetch."""
    return _engine.ENGINE.get("moe_block", _Spec(
        cfg=cfg, k=k, paged=paged, shard=shard))


def _get_moe_async_step_fn(cfg: gpt.GPTConfig, paged: bool = False,
                           shard=None):
    """Engine shim: the async-dispatch joint-routing MoE tick step."""
    return _engine.ENGINE.get("moe_async", _Spec(
        cfg=cfg, paged=paged, shard=shard))


def spec_verify_batched(params, cache, tokens, pos, cfg: gpt.GPTConfig):
    """Batched draft-then-verify scoring: tokens [B, K] int32 fed at
    PER-SLOT positions [pos_b, pos_b + K) -> (logits [B, K, V] fp32,
    cache).  Column 0 is each slot's normal feed token, columns 1..K-1
    its draft proposals; row j scores position pos_b + j, so row 0
    equals the plain decode step's logits (greedy parity) and rows
    1.. are the target's verdicts on the proposals.

    Contiguous: vmap of ``generate.verify_chunk`` per slot — the
    offline speculative path's exact math at decode_step_batched's
    batching shapes — or, when the flash-decode flag + shape gate allow
    it, ``generate.verify_chunk_batched`` (layer loop at top level, one
    Tq=K kernel launch per block — the ROADMAP "flash-verify" item).
    Paged (a ``tables`` leaf): the block-table twin
    ``kv_pool.paged_verify_chunk_batched`` (which routes to its own
    kernel form under the same gate).  Either way the chunk's K
    cache rows are written unconditionally: rejected rows sit at/past
    the slot's position pointer where the causal mask hides them and
    the next round overwrites them (the stale-row invariant the whole
    server rests on), so no masked write is needed."""
    if "tables" in cache:
        from . import kv_pool

        return kv_pool.paged_verify_chunk_batched(params, cache, tokens,
                                                  pos, cfg)
    B, K = tokens.shape
    if generate._use_decode_kernel(
            cfg, (B, K, cfg.num_heads, cfg.head_dim),
            cache["k"].shape[1:]):
        return generate.verify_chunk_batched(params, cache, tokens, pos,
                                             cfg)

    def one(tok, csl, p):
        sl = {name: v[:, None] for name, v in csl.items()}
        logits, new = generate.verify_chunk(params, sl, tok[None], p, cfg)
        return logits[0], {name: v[:, 0] for name, v in new.items()}

    logits, new = jax.vmap(one, in_axes=(0, 1, 0), out_axes=(0, 1))(
        tokens, cache, pos)
    return logits, new


def _get_spec_verify_fn(cfg: gpt.GPTConfig, k: int, paged: bool = False,
                        shard=None):
    """Engine shim: the speculative serving verify step — one
    executable per (cfg, K, layout, placement); under a ``mesh=`` shard
    context it composes with TP exactly like the plain step (round 15's
    registry unlock)."""
    return _engine.ENGINE.get("spec_verify", _Spec(
        cfg=cfg, k=int(k), paged=paged, shard=shard))


def spec_tree_verify_batched(params, cache, tokens, amask, depth, pos,
                             cfg: gpt.GPTConfig):
    """Batched TREE verify: tokens [B, N] int32 (column 0 = each slot's
    feed token = the tree root, columns 1.. its proposed tree nodes in
    topological order), ancestor-or-self mask ``amask`` [B, N, N] bool
    and ``depth`` [B, N] int32 describing each slot's topology as
    RUNTIME arguments, per-slot positions ``pos`` [B] ->
    (logits [B, N, V] fp32, cache).  Node j's row scores the
    continuation of j's root path — row 0 still equals the plain decode
    step's logits (a chain tree reduces to ``spec_verify_batched``'s
    fallback bit-for-bit), which is what greedy tree parity rests on.

    Contiguous routes to ``generate.tree_verify_chunk_batched``, paged
    (a ``tables`` leaf) to ``kv_pool.paged_tree_verify_chunk_batched``
    — both share ``generate._tree_attend_block`` so the layouts cannot
    drift.  No kernel route: the flash kernels assume causal masks, so
    tree verify is einsum-only everywhere (ROADMAP follow-up).  All N
    rows are written unconditionally; rejected/unused nodes sit at or
    past the slot's pointer as stale rows (the PR 11 invariant)."""
    if "tables" in cache:
        from . import kv_pool

        return kv_pool.paged_tree_verify_chunk_batched(
            params, cache, tokens, amask, depth, pos, cfg)
    return generate.tree_verify_chunk_batched(params, cache, tokens,
                                              amask, depth, pos, cfg)


def spec_tree_commit_batched(cache, src, pos):
    """Post-acceptance KV permute: move each slot's accepted-path rows
    (``src`` [B, N-1] node indices, identity where nothing moved) to
    the contiguous rows [pos+1, pos+N) their committed positions
    require.  Layout-routed like the verify; cache-only (the Engine
    donates it like ``kv_copy``)."""
    if "tables" in cache:
        from . import kv_pool

        return kv_pool.paged_tree_commit(cache, src, pos)
    return generate.tree_commit_rows(cache, src, pos)


def _get_spec_tree_verify_fn(cfg: gpt.GPTConfig, nodes: int,
                             paged: bool = False, shard=None):
    """Engine shim: the tree-speculation verify — one executable per
    (cfg, node count, layout, placement); topology never keys."""
    return _engine.ENGINE.get("spec_tree_verify", _Spec(
        cfg=cfg, k=int(nodes), paged=paged, shard=shard))


def _get_spec_tree_commit_fn(cfg: gpt.GPTConfig, nodes: int,
                             paged: bool = False, shard=None):
    """Engine shim: the tree acceptance KV permute (cache-only)."""
    return _engine.ENGINE.get("spec_tree_commit", _Spec(
        cfg=cfg, k=int(nodes), paged=paged, shard=shard))


# -- adapter-aware shims (multi-tenant serving: text/adapters.py) ----------
#
# Every kind keys on ``pkey`` (AdapterPool.pool_key() — the pool GEOMETRY:
# capacity/rank/targets) next to the usual cfg/layout/placement fragments,
# so two servers sharing one pool share executables while a differently-
# shaped pool compiles its own; see the registry entries in engine.py for
# the stacked-leaf sharding and donation story.


def _get_adapter_step_fn(cfg: gpt.GPTConfig, pkey, paged: bool = False,
                         shard=None):
    """Engine shim: greedy adapter-gathered batched step."""
    return _engine.ENGINE.get("adapter_step", _Spec(
        cfg=cfg, pkey=pkey, paged=paged, shard=shard))


def _get_adapter_sample_step_fn(cfg: gpt.GPTConfig, pkey,
                                paged: bool = False, shard=None):
    """Engine shim: adapter-gathered sampled/masked step."""
    return _engine.ENGINE.get("adapter_sample", _Spec(
        cfg=cfg, pkey=pkey, paged=paged, shard=shard))


def _get_adapter_block_fn(cfg: gpt.GPTConfig, k: int, pkey,
                          paged: bool = False, shard=None):
    """Engine shim: adapter-gathered greedy block."""
    return _engine.ENGINE.get("adapter_block", _Spec(
        cfg=cfg, k=k, pkey=pkey, paged=paged, shard=shard))


def _get_adapter_async_step_fn(cfg: gpt.GPTConfig, pkey,
                               paged: bool = False, shard=None):
    """Engine shim: adapter-gathered async step."""
    return _engine.ENGINE.get("adapter_async", _Spec(
        cfg=cfg, pkey=pkey, paged=paged, shard=shard))


def _get_adapter_spec_verify_fn(cfg: gpt.GPTConfig, k: int, pkey,
                                paged: bool = False, shard=None):
    """Engine shim: adapter-gathered speculative verify."""
    return _engine.ENGINE.get("adapter_spec_verify", _Spec(
        cfg=cfg, k=int(k), pkey=pkey, paged=paged, shard=shard))


def _get_adapter_prefill_fn(cfg: gpt.GPTConfig, bucket: int, pkey,
                            shard=None):
    """Engine shim: whole-prompt admission under one slot's adapter."""
    return _engine.ENGINE.get("adapter_prefill", _Spec(
        cfg=cfg, bucket=int(bucket), pkey=pkey, shard=shard))


def _get_adapter_prefill_chunk_fn(cfg: gpt.GPTConfig, pkey, shard=None,
                                  width: int | None = None):
    """Engine shim: fixed-chunk / budgeted admission under one slot's
    adapter."""
    return _engine.ENGINE.get("adapter_prefill_chunk", _Spec(
        cfg=cfg, pkey=pkey, shard=shard, width=width))


def _get_adapter_paged_prefill_fn(cfg: gpt.GPTConfig, bucket: int, pkey,
                                  shard=None):
    """Engine shim: paged admission chunk under one slot's adapter."""
    return _engine.ENGINE.get("adapter_paged_prefill", _Spec(
        cfg=cfg, bucket=int(bucket), pkey=pkey, shard=shard))


def _get_masked_step_fn(cfg: gpt.GPTConfig, paged: bool = False,
                        shard=None):
    """Engine shim: constrained (masked) step for pool-less servers."""
    return _engine.ENGINE.get("masked_step", _Spec(
        cfg=cfg, paged=paged, shard=shard))


def _pow2_bucket(n: int, *bounds) -> int:
    """Smallest power of two >= ``n``, clamped to the given upper
    bounds — THE prompt-bucket rule.  The bucket is a jit-cache key, so
    every admission surface (local prefill, the paged suffix walk,
    prefill workers, row injection) must compute it HERE or executables
    silently split between surfaces."""
    b = 1
    while b < n:
        b *= 2
    return min(b, *bounds) if bounds else b


def validate_request(prompt, max_new_tokens, stop, temperature, top_k,
                     top_p, ttl_s, *, window, vocab_size, default_ttl):
    """THE request-validation rules, shared by ``DecodeServer`` and the
    fleet ``Router`` (one level up, with the fleet-wide window) so the
    two admission surfaces can never drift.  Returns the normalized
    ``(prompt, stop, ttl, top_k)``."""
    prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
    if not prompt:
        raise ValueError("empty prompt")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, "
                         f"got {max_new_tokens}")
    total = len(prompt) + max_new_tokens
    if total > window:
        raise ValueError(
            f"prompt+max_new_tokens {total} exceeds serving window "
            f"{window}")
    stop = [[int(t) for t in seq] for seq in (stop or [])]
    if any(not seq for seq in stop):
        raise ValueError("empty stop sequence")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    ttl = default_ttl if ttl_s is None else float(ttl_s)
    if ttl is not None and ttl <= 0:
        raise ValueError(f"ttl_s must be > 0, got {ttl}")
    return prompt, stop, ttl, min(int(top_k), vocab_size)


def _speculation_asked(draft_cfg, spec_k, spec_tree) -> bool:
    """Would a server built with these arguments speculate (a draft, a
    linear or tree budget, or the environment's where neither is
    stated)?  What a configuration that cannot speculate refuses on."""
    return (draft_cfg is not None or (spec_k or 0) > 0
            or (spec_tree or 0) > 0
            or (spec_k is None and spec_tree is None
                and bool(_flags.spec_k() or _flags.spec_tree())))


class DecodeServer:
    """Host-side slot scheduler around one jitted batched decode step.

    Greedy by default; per-request ``temperature``/``top_k``/``top_p``
    (round-5) sample on device with per-slot parameters, so one batch
    mixes greedy and sampled requests in the same compiled step.  With
    the default ``prefill=True``, submit/_admit
    runs the whole (bucket-padded) prompt through ONE jitted
    ``generate.prefill_slot`` step — device work at admission, one XLA
    compile per power-of-two bucket — and ticks only generate; with
    ``prefill=False`` prompts are consumed token-by-token through the
    tick step (each prompt token's logits discarded until the prompt
    ends)."""

    def __init__(self, params, cfg: gpt.GPTConfig, max_batch: int,
                 max_len: int, eos_id: int | None = None,
                 prefill: bool = True, seed: int = 0,
                 prefill_chunk: int | None = None,
                 async_dispatch: bool = False,
                 metrics_port: int | None = None,
                 layout: str | None = None,
                 block_size: int | None = None,
                 num_blocks: int | None = None,
                 mesh=None, mp_axis: str = "mp",
                 ep_axis: str | None = None,
                 device=None,
                 draft_cfg: gpt.GPTConfig | None = None,
                 draft_params=None, spec_k: int | None = None,
                 spec_tree: int | None = None,
                 prefill_budget: int | None = None,
                 adapter_pool=None):
        self.params = params
        # telemetry (request tracing + latency histograms + gauges):
        # decided once at construction — per-tick records are lock-cheap
        # host counters off the already-fetched host values, and with
        # PADDLE_TPU_TELEMETRY=0 every sample site is one bool check.
        # ``metrics_port`` opts into the /metrics HTTP endpoint
        # (telemetry.serve_metrics; port 0 = ephemeral, see
        # ``self.metrics_server.port``).
        self._tel = _telemetry.enabled()
        self.metrics_server = (_telemetry.serve_metrics(metrics_port)
                               if metrics_port is not None else None)
        # fleet observability plane (round 20): completed trace spans
        # for requests carrying a router-minted trace context, plus
        # per-SERVER histogram twins — loopback fleets co-host many
        # replicas in one process, so the fleet metrics merge needs
        # per-server distributions the process-global registry can't
        # give.  Both empty and untouched when no trace/telemetry.
        self._span_ring = _telemetry.SpanRing()
        # program spans: the open ``serving.tick`` span (None between
        # ticks), the open admit phase, and the tokens that reached the
        # host since the last ``serving.emit`` record as (rid, n, stamp)
        self._tick_sp = None
        self._admit_sp = None
        self._cpu_turn = 0          # ticks since a cpu_ms-stamped one (0: this)
        self._emit: list = []
        self._hist_local: dict = {}
        self._counts_local: dict = {}
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        # KV-cache layout (round 8): 'contiguous' (the default slab —
        # every slot provisioned for max_len rows) or 'paged'
        # (text/kv_pool: a shared block pool addressed through per-slot
        # block tables, blocks allocated as ``pos`` crosses block
        # boundaries, refcounted prefix reuse + copy-on-write).
        # ``PADDLE_TPU_KV_LAYOUT`` flips the default; ``num_blocks``
        # defaults to full provisioning (slab-equivalent capacity) and
        # is the knob operators shrink to actual-traffic budgets.
        lay = layout if layout is not None else _flags.kv_layout()
        if lay not in ("contiguous", "paged"):
            raise ValueError(
                f"layout {lay!r}: expected 'contiguous' or 'paged'")
        self._paged = lay == "paged"
        # a recurrent mixer beside attention (cfg.ssm): per-slot state
        # leaves ride the paged cache pytree (kv_pool.STATE_LEAVES) and a
        # ``live`` leaf tells each decode step which slots it advances.
        # A KV row written by a slot that is not really decoding is
        # overwritten later; a recurrent state cannot be, so whatever
        # relies on rewriting or rolling back rows is refused here, by
        # name, before anything is built.
        self._recurrent = cfg.ssm is not None
        # one chip's share of a routed expert layer (cfg.experts): the
        # ``live`` leaf tells the step which slots select experts, and
        # the expert layer's counts ride the cache (kv_pool.COUNTS,
        # drained by load_stats and close).  Its refusals name the absent
        # chips, so they come first
        self._experts = cfg.experts is not None
        if self._experts:
            self._refuse_share(mesh, draft_cfg, spec_k, spec_tree,
                               adapter_pool)
        if self._recurrent:
            self._refuse_recurrent(
                lay, mesh, draft_cfg, spec_k, spec_tree, adapter_pool,
                prefill_chunk, prefill_budget, max_len)
        # latent attention (cfg.mla): the pool holds one leaf of latent
        # rows in place of K and V
        self._latent = cfg.mla is not None
        self._share_counts = np.zeros((len(_SHARE_COUNTS),), np.int64)
        # the device-side counts are int32 and a step adds at most
        # max_batch * top_k * layers to one: tick() drains them long
        # before one can wrap (every 13 minutes of steps at the cell's
        # size: one fetch that waits for the step in flight)
        self._share_ticks = 0
        self._share_drain_every = (
            (1 << 28) // (max_batch * cfg.experts.top_k * cfg.num_layers)
            if self._experts else 0)
        # the rows x held experts a decode step computes (every slot's row
        # through every held expert of every expert layer): a number the
        # host knows, counted at the drain for the steps since the last
        self._share_step_rows = (
            max_batch * cfg.experts.n_held
            * (cfg.num_layers - cfg.first_k_dense) if self._experts else 0)
        self._share_step0 = self._share_rows = 0
        if self._latent:
            self._refuse_latent(lay)
        if self._paged:
            from . import kv_pool as _kv

            # init_cache -> kv_pool.init_paged_cache is the ONE
            # validator of block_size/num_blocks (and the default pool
            # sizing); the allocator mirrors the built cache's geometry
            self.cache = generate.init_cache(
                cfg, max_batch, max_len, layout="paged",
                block_size=block_size, num_blocks=num_blocks)
            self._pool = _kv.PagedAllocator(*_kv._geometry(self.cache),
                                            max_batch)
            # whether a decode step advances the recurrent state where
            # it is stored, the decoding slots alone
            self._state_in_place = _kv.state_in_place(self.cache, cfg)
            if self._recurrent and self._tel:
                _telemetry.gauge("kv_pool.state_bytes").set(sum(
                    self.cache[n].nbytes for n in _kv.STATE_LEAVES))
                # how deep each kind of leaf is (a layer pattern's differ)
                _telemetry.gauge("kv_pool.kv_layers").set(
                    self.cache["k"].shape[0])
                _telemetry.gauge("kv_pool.state_layers").set(
                    self.cache[_kv.STATE_LEAVES[0]].shape[0])
            if self._latent and self._tel:
                leaf = self.cache[_kv.LATENT]
                _telemetry.gauge("kv_pool.latent_row_bytes").set(
                    leaf.shape[0] * leaf.shape[3] * leaf.dtype.itemsize)
        else:
            self._pool = None
            self.cache = generate.init_cache(cfg, max_batch, max_len)
        self._rss_tick = 0          # host-RSS watchdog cadence counter
        # speculative decoding (draft-then-verify in the serving tick):
        # spec_k > 0 turns speculation on — with (draft_cfg,
        # draft_params) a small draft model proposes K-1 tokens per
        # round (its KV state rides a twin cache pytree; under the
        # paged layout the draft pool shares THE SAME allocator/table,
        # so eviction/rollback frees both coherently), without a draft
        # the server self-drafts via host n-gram lookup
        # (generate.ngram_propose — zero extra model FLOPs).  Greedy
        # output stays bit-identical to the non-speculative server;
        # per-request rolling acceptance below PADDLE_TPU_SPEC_MIN_ACCEPT
        # falls the slot back to plain decode.
        # tree speculation (Medusa/SpecInfer shape, round 17): a token
        # TREE of `spec_tree` node slots per round — n-gram trie or the
        # draft's top-b fanout — verified in ONE tree-masked pass with
        # host-side best-path acceptance.  Mutually exclusive with the
        # linear spec_k round shape; constrained slots SPECULATE in
        # tree mode (branches the grammar forbids are pruned before the
        # verify) instead of falling back to plain stepping.
        if spec_tree is not None:
            n_tree = int(spec_tree)
            if n_tree < 0 or n_tree == 1:
                raise ValueError(
                    f"spec_tree must be 0 (off) or >= 2 node slots "
                    f"(node 0 carries the feed token), got {n_tree}")
        else:
            n_tree = _flags.spec_tree()
        self._spec_tree_n = n_tree
        self._spec_branch = _flags.spec_branch()
        if spec_k is not None:
            k_spec = int(spec_k)
            if n_tree and k_spec:
                raise ValueError(
                    f"spec_k={k_spec} and spec_tree={n_tree} are "
                    f"mutually exclusive — a round is either a linear "
                    f"verify or a tree verify")
        else:
            # an explicit/env tree budget overrides the env spec_k (one
            # env flip turns tree mode on without unsetting the other)
            k_spec = 0 if n_tree else _flags.spec_k()
            if k_spec == 0 and draft_cfg is not None and not n_tree:
                k_spec = 4          # passing a draft model IS opting in
        if k_spec < 0:
            raise ValueError(f"spec_k must be >= 0, got {k_spec}")
        if k_spec == 0 and draft_cfg is not None and not n_tree:
            raise ValueError("draft_cfg given but spec_k=0 disables "
                             "speculation — drop one or the other")
        self._spec_k = k_spec
        self._spec_on = k_spec > 0 or n_tree > 0
        self.draft_cfg = draft_cfg
        self._draft_params = draft_params
        self._draft_cache = None
        self._self_draft = self._spec_on and draft_cfg is None
        self._min_accept = _flags.spec_min_accept()
        # server-level speculation accounting (load_stats / the
        # acceptance-rate gauge / the tests' target passes per token)
        self._spec_prop = 0         # proposals scored by the target
        self._spec_acc = 0          # ... of those, accepted
        self._spec_rounds = 0       # batched verify dispatches
        self._spec_plain_steps = 0  # plain target steps while spec on
        self._tree_path_sum = 0     # accepted path tokens (tree rounds)
        self._tree_path_cnt = 0     # ... over this many slot-rounds
        if self._spec_on:
            window = min(max_len, cfg.max_seq_len)
            if cfg.moe is not None or (draft_cfg is not None
                                       and draft_cfg.moe is not None):
                # speculative_generate's rule, enforced at BUILD (not
                # first tick): chunked verify routes a chunk's tokens
                # jointly through MoE capacity, stepwise decode routes
                # them one at a time — the two are not bit-equal
                raise NotImplementedError(
                    "speculative serving requires dense models (MoE "
                    "capacity routing differs between chunked verify "
                    "and stepwise decode — speculative_generate's "
                    "rule)")
            if n_tree:
                if not 2 <= n_tree < window:
                    raise ValueError(
                        f"spec_tree {n_tree} must be in [2, {window}) — "
                        f"the tree chunk must fit the serving window")
                if adapter_pool is not None:
                    raise NotImplementedError(
                        "spec_tree with an adapter_pool is not "
                        "supported yet (the tree verify kind has no "
                        "adapter-gathered twin); linear spec_k composes "
                        "with pools")
            elif not 1 <= k_spec < window:
                raise ValueError(
                    f"spec_k {k_spec} must be in [1, {window}) — the "
                    f"verify chunk must fit the serving window")
            if draft_cfg is not None:
                if draft_params is None:
                    raise ValueError("draft_cfg requires draft_params")
                if draft_cfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {draft_cfg.vocab_size} != target "
                        f"vocab {cfg.vocab_size}")
                if draft_cfg.max_seq_len < window:
                    raise ValueError(
                        f"draft max_seq_len {draft_cfg.max_seq_len} < "
                        f"serving window {window}")
        # tensor-parallel decode INSIDE the server (round 9): with a
        # ``mesh``, params take the Megatron specs and every cache leaf
        # shards its Hkv axis over ``mp_axis`` (paged pool included, the
        # slab rule) — the batched tick then runs pjit'd with XLA's
        # collectives over ICI, donation/jit-key/recompile-watch
        # composing unchanged (_ShardCtx).  ``device`` instead pins a
        # single-chip server to one device (the fleet's per-replica
        # placement knob); the two are mutually exclusive.
        self._device = None
        self._shard = None
        if ep_axis is not None and mesh is None:
            raise ValueError("ep_axis requires mesh= (expert parallelism "
                             "is a mesh placement)")
        if mesh is not None:
            if device is not None:
                raise ValueError("mesh= and device= are mutually "
                                 "exclusive (TP server vs pinned "
                                 "single-chip replica)")
            # round 19: MoE configs shard through the regex rule table
            # (moe_serving.moe_decode_param_specs) — _ShardCtx routes
            # there itself, placing experts over ``ep_axis`` when given
            # (replicated experts under pure TP otherwise)
            self._shard = _ShardCtx(mesh, cfg, params, self.cache,
                                    mp_axis, pool=adapter_pool,
                                    ep=ep_axis)
            self.params = jax.tree_util.tree_map(
                jax.device_put, params, self._shard.params)
            self.cache = {n: jax.device_put(a, self._shard.cache[n])
                          for n, a in self.cache.items()}
        elif device is not None:
            self._device = device
            self.params = jax.device_put(params, device)
            self.cache = jax.device_put(self.cache, device)
            # placement joins every step-cache key (see _shard_key)
            self._shard = ("device", int(getattr(device, "id", 0)))
        # MoE serving (round 19): the tick runs the JOINT-routing step —
        # all occupied slots' tokens route through expert capacity in
        # one call, with the device-side drop/load accumulator threaded
        # through like the cache.  ``_moe_wrap`` adapts the moe kinds to
        # the dense calling convention (appends act+stats, peels the
        # stats output), so every dispatch site — and warmup — stays
        # shared with the dense server.
        if cfg.moe is not None:
            from . import moe_serving as _moe_serving

            self._moe_stats = _moe_serving.moe_stats_init(
                cfg.moe.num_experts)
            self._moe_counted = 0       # drained high-water mark
            self._step = self._moe_wrap(
                _get_moe_step_fn(cfg, self._paged, self._shard))
        else:
            self._moe_stats = None
            self._step = _get_step_fn(cfg, self._paged, self._shard)
        # the draft model's placement context: identical to the target's
        # for pinned/un-placed servers; under mesh= it gets its OWN
        # _ShardCtx (the draft cfg's Megatron/cache specs differ from the
        # target's), built below once the twin cache exists
        self._draft_shard = self._shard
        if self._spec_on and draft_cfg is not None:
            if self._paged:
                from . import kv_pool as _kv

                # the draft pool mirrors the target pool's geometry
                # (same block size, same block count, same nmax), so the
                # ONE allocator + the one table leaf address both —
                # target and draft positions advance in lockstep, and
                # free_slot/eviction releases both pools' rows together
                self._draft_cache = _kv.init_paged_cache(
                    draft_cfg, max_batch, max_len,
                    block_size=int(self.cache["k"].shape[2]),
                    num_blocks=int(self.cache["k"].shape[1]))
            else:
                self._draft_cache = generate.init_cache(
                    draft_cfg, max_batch, max_len)
            if self._device is not None:
                self._draft_params = jax.device_put(draft_params,
                                                    self._device)
                self._draft_cache = jax.device_put(self._draft_cache,
                                                   self._device)
            elif mesh is not None:
                # spec × TP (the registry unlock): the draft twin shards
                # by the SAME sharded_cache_specs rule as the target —
                # its Hkv axis over mp_axis, params Megatron-style
                self._draft_shard = _ShardCtx(mesh, draft_cfg,
                                              draft_params,
                                              self._draft_cache, mp_axis)
                self._draft_params = jax.tree_util.tree_map(
                    jax.device_put, draft_params,
                    self._draft_shard.params)
                self._draft_cache = {
                    n: jax.device_put(a, self._draft_shard.cache[n])
                    for n, a in self._draft_cache.items()}
        # async_dispatch: keep ONE step/block in flight — tick() first
        # dispatches step N+1 (feeding the previous step's tokens from
        # the DEVICE array, never fetched) and only then blocks on step
        # N's tokens for host bookkeeping, overlapping host scheduling
        # with device compute.  Per-request tokens are identical to the
        # sync path; the one observable schedule shift is that a QUEUED
        # request admits one tick later after a retire (for sampled
        # requests that shifts WHICH global steps the slot occupies —
        # the documented batched-serving dependence above).
        self._async = bool(async_dispatch)
        self._inflight: dict | None = None
        # per-request sampling (round-5): one base key; device step n
        # draws with fold_in(base, n) — the same schedule for tick and
        # tick_block, so the two paths produce identical samples.  A
        # slot's draws depend on its batch-mates only through WHICH
        # global steps it occupies (standard for batched serving).
        self._base_key = jax.random.PRNGKey(seed)
        self._step_no = 0
        # chunked prefill: a whole prompt becomes ONE admission-time step
        # (generate.prefill_slot) instead of len(prompt) ticks; prompts pad
        # to power-of-two buckets so XLA compiles one prefill per bucket.
        # MoE models prefill too (round-5): the pad mask reaches the
        # router, padding claims no expert capacity, and the chunk uses
        # the dropless capacity bound — admission routes exactly like
        # token-by-token feeding.
        # prefill_chunk=N (round-5, vLLM-style): admission instead walks
        # the prompt in FIXED N-token chunks (generate.prefill_slot_chunk,
        # each attending the rows earlier chunks filled) — bounded
        # activation memory and ONE executable for ANY prompt length
        if prefill_chunk is not None:
            if not prefill:
                # the combination would silently degrade to token-by-token
                # feeding — neither the bounded-memory chunks the caller
                # asked for nor whole-prompt prefill
                raise ValueError(
                    "prefill_chunk requires prefill=True (chunked "
                    "admission IS a prefill mode)")
            window = min(max_len, cfg.max_seq_len)
            if not 1 <= int(prefill_chunk) <= window:
                raise ValueError(
                    f"prefill_chunk must be in [1, {window}] "
                    f"(the serving window), got {prefill_chunk}")
        # whole-prompt prefill executables resolve PER BUCKET at
        # admission (_get_prefill_fn(cfg, bucket)); this marker is the
        # factory, kept callable-shaped so `is not None` mode checks read
        # the same as before
        # the paged layout routes ALL prefill admission through the
        # offset-aware kv_pool.paged_prefill_chunk executables (a shared
        # prefix moves the chunk's start past the adopted blocks, which
        # the contiguous bucket/chunk programs cannot express)
        self._prefill_on = bool(prefill)
        self._prefill = ((lambda bucket: _get_prefill_fn(
                             cfg, bucket, self._shard))
                         if prefill and prefill_chunk is None
                         and not self._paged else None)
        self._chunk = (int(prefill_chunk) if prefill_chunk is not None
                       else None)
        self._prefill_chunk = (_get_prefill_chunk_fn(cfg, self._shard)
                               if prefill and self._chunk
                               and not self._paged else None)
        # budgeted admission (stall-free continuous batching,
        # Sarathi-style chunked-prefill co-scheduling): prefill_budget=N
        # (or PADDLE_TPU_PREFILL_BUDGET) caps the prefill tokens any ONE
        # scheduler round may run.  Admission then only CLAIMS a slot
        # (state "admitting") and each round advances the oldest
        # admitting slot by one budget-wide chunk, interleaved with the
        # decode step — a 4k-token prompt no longer freezes every
        # decoding request for its whole prefill.  The budget is the
        # chunk width of the admission executables (contiguous:
        # prefill_slot_chunk at width N; paged: paged_prefill_chunk at
        # width N — the offset-aware resume-at-pos0 machinery), so it
        # rides decode_jit_key.  Greedy tokens are bit-identical to
        # monolithic admission: chunked prefill is exact math (the paged
        # layout ALWAYS admits chunked), only the host schedule changes.
        # When > 0 it supersedes the prefill/prefill_chunk admission
        # modes above; prefilled handoffs (submit_prefilled) stay
        # monolithic — injection is one cheap row-write, not a prefill.
        if prefill_budget is not None:
            b = int(prefill_budget)
            if b < 0:
                raise ValueError(
                    f"prefill_budget must be >= 0, got {b}")
            if b > 0 and not prefill:
                raise ValueError(
                    "prefill_budget requires prefill=True (budgeted "
                    "admission IS a prefill mode)")
        else:
            b = _flags.prefill_budget() if prefill else 0
        self._budget = min(b, min(max_len, cfg.max_seq_len)) if b else 0
        # per-slot host state
        self._free = list(range(max_batch))
        self._slots: dict[int, dict] = {}        # slot -> request state
        self._queue: list[dict] = []             # waiting requests
        self._results: dict[int, list] = {}
        self._dropped: set[int] = set()          # rids abandoned by close()
        self._streams: dict[int, dict] = {}      # rid -> open handoff stream
        self._next_rid = 0
        # resilience layer (PADDLE_TPU_RESILIENCE=0 restores fail-fast):
        # per-request deadlines shed expired queued work, an OOM on a
        # tick engages the degradation chain (drop to sync dispatch ->
        # halve the admitted batch -> evict lowest-priority slots ->
        # re-tick — the reference's retry-on-OOM allocator chain at
        # scheduler granularity), and a wall-budget watchdog recovers a
        # wedged async step with slot state intact.
        self._resil = _resilience.enabled()
        self._default_ttl = _flags.request_ttl_s()
        self._step_budget = _flags.step_budget_s()
        self._admit_cap = max_batch     # halved by the OOM chain
        self._status: dict[int, str] = {}   # rid -> "timeout" | "error"
        #                                   #      | "rejected"
        self._err_reason: dict[int, str] = {}   # rid -> why "error"
        self._wedged = False            # a wedge was detected, not yet
        self._wedge_event = False       # ... recovered by a clean tick
        self._in_tick = False           # guard re-entrancy (block fallback)
        # admission control (text/admission.py): per-tenant token
        # buckets + bounded per-class queues at submit, and the SLO
        # degradation ladder consulted by _admit/_claim_admitting (admit
        # cap, pre-warmed budget rung, spec-off, shed).  Decided once at
        # construction like _tel/_resil: PADDLE_TPU_ADMISSION=0 builds
        # NO controller and every hot-path consult is `is None` —
        # greedy FIFO admission, bit-identical to the pre-admission
        # server.  The budget rungs are ladder_widths(self._budget);
        # warmup() pre-compiles every rung so a ladder move never
        # retraces mid-serving.
        self._adm = (_admission.AdmissionController(
                         scope="serving",
                         budget_rungs=_admission.ladder_widths(
                             self._budget))
                     if _flags.admission_enabled() else None)
        # multi-tenant adapter pool (text/adapters.py): N LoRA products
        # served from ONE base server.  The pool's stacked [A, ...]
        # leaves join every step call as a replicated extra input and the
        # jitted step gathers each slot's (a, b) pair by its int32
        # adapter id — id 0 is the all-zero base row, so a pool-attached
        # server with only base traffic produces the SAME TOKENS as a
        # pool-less one (the delta is + 0.0).  pool=None keeps every
        # code path byte-identical to the pre-adapter server.
        self._adapters = adapter_pool
        if adapter_pool is not None:
            if cfg.moe is not None:
                raise NotImplementedError(
                    "adapter_pool with an MoE config is not supported "
                    "yet — the adapter step kinds have no joint-routing "
                    "twin (the gathered LoRA delta composes with dense "
                    "FFNs only)")
            if (generate._cfg_key(adapter_pool.cfg)
                    != generate._cfg_key(cfg)):
                raise ValueError(
                    "adapter_pool was built for a different GPTConfig "
                    "than this server — pool and server must share the "
                    "base model geometry")
            if any(k.endswith(("_lora_a", "_lora_b"))
                   for k in params["blocks"]):
                raise ValueError(
                    "params already carry lora leaves — merge or strip "
                    "them before attaching an adapter_pool (the pool's "
                    "gathered delta would stack on top of them)")

    def _refuse_recurrent(self, lay, mesh, draft_cfg, spec_k, spec_tree,
                          adapter_pool, prefill_chunk, prefill_budget,
                          max_len):
        """What cannot work with a recurrent state yet raises at
        construction, naming the reason (as MoE speculation does)."""
        cfg = self.cfg

        def no(what, why):
            raise NotImplementedError(
                f"{what} with an ssm mixer (cfg.ssm) is not supported "
                f"yet: {why}")

        if lay != "paged":
            no("layout='contiguous'", "the recurrent state leaves live in "
               "the paged cache pytree only; pass layout='paged'")
        if mesh is not None:
            no("mesh=", "the mixer has no tensor-parallel layout "
               "(gpt.param_shardings)")
        if _speculation_asked(draft_cfg, spec_k, spec_tree):
            no("speculation (spec_k / spec_tree / draft_cfg)",
               "a rejected draft token has advanced the state, and a "
               "state cannot be rolled back as rows are")
        if adapter_pool is not None:
            no("adapter_pool", "the adapter step kinds know no state leaves")
        if _flags.kv_spill_mb():
            no("the host spill tier (PADDLE_TPU_KV_SPILL_MB)",
               "it restores prefix rows by inject_rows, and there is no "
               "state snapshot to restore beside them")
        if "PADDLE_TPU_KV_RADIX" in _os.environ and _flags.kv_radix():
            no("prefix reuse asked for by PADDLE_TPU_KV_RADIX",
               "adopted rows would need the state the shared prefix left, "
               "and no snapshot of it is kept; the index is off for this "
               "configuration (kv_pool.prefix_skipped_recurrent counts)")
        window = min(max_len, cfg.max_seq_len)
        budget = (prefill_budget if prefill_budget is not None
                  else _flags.prefill_budget())
        for name, width in (("prefill_chunk", prefill_chunk),
                            ("prefill_budget", budget)):
            if width and window % int(width):
                # chunks of a state's prefill cannot overlap (the last
                # window of the row walk re-runs rows; a state would
                # advance twice), so they tile the window exactly
                raise ValueError(
                    f"{name}={width} must divide the serving window "
                    f"{window} for a config with an ssm mixer (its "
                    f"prefill chunks cannot overlap)")

    def _refuse_share(self, mesh, draft_cfg, spec_k, spec_tree,
                      adapter_pool):
        """What cannot work with one chip's share of an expert layer yet
        raises at construction, naming the reason."""
        def no(what, why):
            raise NotImplementedError(
                f"{what} with an expert share (cfg.experts) is not "
                f"supported yet: {why}")

        if mesh is not None:
            no("mesh=", "no ep exchange is written: one chip runs its own "
               "share of the experts, and nothing stands in for the "
               "absent chips")
        if _speculation_asked(draft_cfg, spec_k, spec_tree):
            no("speculation (spec_k / spec_tree / draft_cfg)",
               "a verify chunk's tokens would select experts for "
               "rejected drafts, and the verify chunks know neither a "
               "latent row format nor a layer pattern")
        if adapter_pool is not None:
            no("adapter_pool", "the adapter step kinds know no expert "
               "share")

    def _refuse_latent(self, lay):
        """What cannot work with latent rows yet raises at construction,
        naming the reason."""
        def no(what, why):
            raise NotImplementedError(
                f"{what} with latent attention (cfg.mla) is not "
                f"supported yet: {why}")

        if lay != "paged":
            no("layout='contiguous'", "the contiguous slab has no latent "
               "row format; pass layout='paged'")
        if _flags.kv_spill_mb():
            no("the host spill tier (PADDLE_TPU_KV_SPILL_MB)",
               "it restores rows by inject_rows, and a latent row has no "
               "wire form")
        if _flags.kv_cache_dtype() == "int8":
            no("an int8 pool (PADDLE_TPU_KV_DTYPE=int8)",
               "the scale planes are per head, and a latent row has none")

    def _refuse_handoff(self, what: str):
        if self._latent:
            raise NotImplementedError(
                f"{what} with latent attention (cfg.mla) is not supported "
                f"yet: the prefill handoff ships K/V rows (inject_rows), "
                f"and a latent row has no wire form")
        if self._recurrent:
            raise NotImplementedError(
                f"{what} with an ssm mixer (cfg.ssm) is not supported "
                f"yet: the prefill handoff ships KV rows (inject_rows), "
                f"and the recurrent state the prompt leaves has no wire "
                f"form")

    def _chunk_starts(self, shared: int, n: int, width: int,
                      window: int) -> list:
        """Where a prompt's prefill chunks of ``width`` start, after
        ``shared`` adopted rows.  Row walks end on a window that overlaps
        the one before (overlapped rows recompute to identical values)
        rather than overrun the cache; a recurrent state would advance
        twice there, so its chunks tile [0, n) and only the last is
        short (``width`` divides the window: construction checked)."""
        if self._recurrent:
            return list(range(0, n, width))
        if n - shared <= width:
            return [shared if shared + width <= window
                    else max(0, n - width)]
        return list(range(shared, n - width, width)) + [n - width]

    def _adopt_prefix(self, slot: int, req) -> int:
        """Rows of the longest indexed prefix adopted into ``slot``'s
        table (0 where sharing is off: no prefill, an adapter's rows, or
        a recurrent state, which no snapshot could restore beside them)."""
        if self._recurrent:
            if self._tel:
                _telemetry.count("kv_pool.prefix_skipped_recurrent")
            return 0
        if not self._prefill_on or req.get("adapter"):
            return 0
        return self._pool.adopt_prefix(slot, req["prompt"])

    def _push_live(self):
        """Tell the next decode step which slots it advances: the slots
        that decode (or feed their prompt token by token), not the free
        ones and not those mid-admission, whose state the prefill chunks
        own.  A no-op for a cache without the leaf."""
        if not (self._recurrent or self._experts):
            return
        from . import kv_pool as _kv

        # the joint-routing step's occupancy mask is this one too
        leaf = jnp.asarray(self._moe_act())
        if self._device is not None:
            leaf = jax.device_put(leaf, self._device)
        self.cache = dict(self.cache, **{_kv.LIVE: leaf})

    # -- request lifecycle --------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 32,
               stop: list | None = None, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0,
               ttl_s: float | None = None, priority: int = 0,
               tenant: str | None = None,
               adapter: str | None = None, constraint=None) -> int:
        """``stop``: optional list of token SEQUENCES; generation ends
        (sequence included) as soon as the generated tail matches one.

        ``temperature``/``top_k``/``top_p`` (round-5): PER-REQUEST
        sampling — greedy at temperature 0 (the default, bit-identical
        to before); otherwise the same scale→top-k→nucleus pipeline as
        ``generate``, applied per slot so one batch can mix greedy and
        sampled requests.

        ``ttl_s``: per-request deadline (default from
        ``PADDLE_TPU_REQUEST_TTL_S``; None = none) — a request still
        QUEUED past its TTL is shed with the ``timeout`` status
        (``result`` raises ``resilience.DeadlineExceeded``) instead of
        occupying a slot.  ``priority`` (higher = keep longer): the OOM
        degradation chain evicts the lowest-priority slots first, and
        admission control buckets it into three classes (<=0 low, 1
        normal, >=2 high) for queue bounds and shed ordering.

        ``tenant``: admission-control identity — with
        ``PADDLE_TPU_TENANT_RATE`` set, each tenant's admitted tokens
        (prompt + max_new) draw from its own token bucket; an empty
        bucket REJECTS the request at the door (status ``rejected``,
        ``result`` raises ``resilience.Overloaded`` — distinct from the
        TTL ``timeout``: a reject is the back-off signal, the request
        never queued).  ``tenant=None`` shares one default bucket.

        ``adapter``: serve this request under a named LoRA from the
        attached ``adapter_pool`` (None = the tenant's default adapter
        if one was set via ``AdapterPool.set_tenant_default``, else the
        base model).  ``constraint``: constrained decoding — a
        :class:`~paddle_tpu.text.adapters.Constraint` spec (TokenSet /
        Regex / JsonSchema, or a bare iterable of allowed token ids)
        compiled host-side to a per-slot automaton; each step bans
        disallowed tokens with an additive mask inside the jitted
        sampler, so greedy AND sampled slots only ever emit tokens the
        automaton accepts."""
        req = self._build_request(prompt, max_new_tokens, stop,
                                  temperature, top_k, top_p, ttl_s,
                                  priority, tenant=tenant,
                                  adapter=adapter, constraint=constraint)
        if self._tel:
            _telemetry.count("serving.requests_submitted")
        if self._adm is not None:
            self._adm.control_tick()
            ok, _reason = self._adm.admit(
                tenant, priority, len(req["prompt"]) + req["max_new"])
            if not ok:
                self._status[req["rid"]] = "rejected"
                if self._tel:
                    _telemetry.count("serving.requests_rejected")
                self._tel_gauges()
                return req["rid"]
        self._queue.append(req)
        if self._adm is not None:
            self._shed_queue_overflow()
        self._admit()
        self._tel_gauges()
        return req["rid"]

    def _shed_queue_overflow(self) -> None:
        """Enforce the bounded per-class queues: while any class is over
        ``PADDLE_TPU_ADMISSION_QUEUE_CAP``, retire the controller's
        victim (lowest over-cap class, newest entry) with the
        ``rejected`` status.  Runs after every enqueue, so the bound
        holds between submits, not just eventually."""
        while True:
            i = self._adm.overflow_victim(self._queue)
            if i is None:
                return
            req = self._queue.pop(i)
            self._status[req["rid"]] = "rejected"
            self._adm.count_shed(req.get("priority", 0), "queue_full")
            if self._tel:
                _telemetry.count("serving.requests_rejected")

    def _build_request(self, prompt, max_new_tokens, stop, temperature,
                       top_k, top_p, ttl_s, priority,
                       tenant=None, adapter=None, constraint=None) -> dict:
        """Validate one request and mint its queue entry (the shared
        half of :meth:`submit` and :meth:`submit_prefilled`)."""
        prompt, stop, ttl, top_k = validate_request(
            prompt, max_new_tokens, stop, temperature, top_k, top_p,
            ttl_s, window=min(self.max_len, self.cfg.max_seq_len),
            vocab_size=self.cfg.vocab_size,
            default_ttl=self._default_ttl)
        aid = 0
        if adapter is not None and self._adapters is None:
            raise ValueError(
                f"adapter={adapter!r} but no adapter_pool attached to "
                f"this server")
        if self._adapters is not None:
            if adapter is None:
                adapter = self._adapters.default_for(tenant)
            aid = self._adapters.resolve(adapter)
        if constraint is not None:
            if self.cfg.moe is not None:
                # the masked step kinds have no joint-routing twin yet
                # (ROADMAP follow-up) — reject at the door, not ticks
                # later with a silent unconstrained fallback
                raise NotImplementedError(
                    "constrained decoding on an MoE server is not "
                    "supported yet (no joint-routing masked step kind)")
            from . import adapters as _ad

            # compile at the door (and discard): a malformed spec raises
            # HERE in the caller's frame, not ticks later at admission
            _ad.compile_constraint(constraint, self.cfg.vocab_size)
        if self._paged:
            # a request needing more blocks than the whole pool can
            # NEVER be admitted (eviction frees other tenants' blocks,
            # not capacity) — rejecting here prevents it parking at the
            # queue front forever and livelocking the serve loop
            need = -(-(len(prompt) + max_new_tokens) // self._pool.bs)
            if need > self._pool.N:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool has "
                    f"{self._pool.N} (raise num_blocks or shrink the "
                    f"request)")
        rid = self._next_rid
        self._next_rid += 1
        return {"rid": rid, "prompt": prompt,
                "max_new": max_new_tokens, "stop": stop,
                "temperature": float(temperature),
                "top_k": top_k, "top_p": float(top_p),
                "ttl": ttl, "priority": int(priority),
                "tenant": tenant,
                "adapter": aid,
                "adapter_name": adapter if aid else None,
                "constraint": constraint,
                "t_submit": time.perf_counter(),
                "t_enqueue": time.perf_counter()}

    def submit_prefilled(self, prompt, rows, logits,
                         max_new_tokens: int = 32, stop: list | None = None,
                         temperature: float = 0.0, top_k: int = 0,
                         top_p: float = 1.0, ttl_s: float | None = None,
                         priority: int = 0, trace=None) -> int:
        """Admit a request whose prompt a PREFILL WORKER already ran
        (round 9, the fleet's prefill/decode handoff): ``rows`` are the
        worker's finished cache rows — leaves ``[L, 1, n, Hkv(, hd)]``
        in this server's storage dtype (int8 scale planes included) —
        and ``logits`` its admission logits ``[V]``.  Admission writes
        the rows into a slot (one donated injector executable per
        power-of-two bucket; paged: through the slot's block table) and
        seeds the first token from ``logits`` with the exact sampling/
        telemetry/NaN-guard semantics of local prefill — decode then
        proceeds bit-identically to a locally prefilled request."""
        self._refuse_handoff("submit_prefilled")
        req = self._build_request(prompt, max_new_tokens, stop,
                                  temperature, top_k, top_p, ttl_s,
                                  priority)
        n = len(req["prompt"])
        rows = {name: np.asarray(v) for name, v in rows.items()}
        want = {name for name in self.cache if name != "tables"}
        if set(rows) != want:
            raise ValueError(
                f"prefilled rows leaves {sorted(rows)} do not match the "
                f"cache leaves {sorted(want)} (KV dtype mismatch between "
                f"prefill worker and decode server?)")
        for name, v in rows.items():
            have = self.cache[name].dtype
            if v.dtype != have:
                # bf16 worker rows into an fp32 server would otherwise
                # CAST silently in the injector and break the
                # bit-parity-with-local-admission contract
                raise ValueError(
                    f"prefilled rows leaf {name!r} is {v.dtype}, this "
                    f"server stores {have} (PADDLE_TPU_KV_DTYPE drift "
                    f"between prefill worker and decode server?)")
        if rows["k"].shape[2] != n:
            raise ValueError(
                f"prefilled rows cover {rows['k'].shape[2]} positions "
                f"for a {n}-token prompt")
        req["prefilled"] = (rows, np.asarray(logits, np.float32))
        if trace:
            req["trace"] = trace
        self._queue.append(req)
        if self._tel:
            _telemetry.count("serving.requests_submitted")
            _telemetry.count("serving.prefilled_submissions")
        self._admit()
        self._tel_gauges()
        return req["rid"]

    def adopt_request(self, req: dict) -> int:
        """Enqueue a request dict drained from ANOTHER server (the fleet
        router's re-route path): a fresh local rid and queue-entry clock
        (TTL stays a queue-wait bound), with progress carry and any
        prefilled payload preserved.  The dict must come from
        :meth:`drain_queue` / ``_build_request`` — it is trusted, not
        re-validated (but the window is re-checked: replicas may be
        heterogeneous)."""
        if "prefilled" in req or req.get("stream"):
            self._refuse_handoff("adopt_request of a prefilled request")
        total = len(req["prompt"]) + req["max_new"] \
            - len(req.get("carry", ()))
        if total > min(self.max_len, self.cfg.max_seq_len):
            raise ValueError(
                f"adopted request needs a {total}-row window; this "
                f"replica serves {min(self.max_len, self.cfg.max_seq_len)}")
        if self._paged:
            # the submit-side whole-pool check, re-applied per replica
            # (pools may be heterogeneous): a request no eviction can
            # ever fit would park at the queue front and livelock the
            # serve loop
            need = -(-total // self._pool.bs)
            if need > self._pool.N:
                raise ValueError(
                    f"adopted request needs {need} KV blocks; this "
                    f"replica's pool has {self._pool.N}")
        rid = self._next_rid
        self._next_rid += 1
        r = dict(req, rid=rid, t_enqueue=time.perf_counter())
        r.setdefault("t_submit", time.perf_counter())
        self._queue.append(r)
        if self._tel:
            _telemetry.count("serving.requests_adopted")
        self._admit()
        self._tel_gauges()
        return rid

    def _shed_expired(self):
        """Deadline shedding: drop queued requests past their TTL with
        the ``timeout`` status — they never occupy a slot, and
        ``result()`` raises ``resilience.DeadlineExceeded`` for them.
        Host-clock arithmetic only; active slots are never shed (their
        device work is already paid for)."""
        if not self._resil or not self._queue:
            return
        now = time.perf_counter()
        kept = []
        for req in self._queue:
            ttl = req.get("ttl")
            # the deadline bounds QUEUE WAIT (time in this queue entry),
            # not total request age: an OOM-evicted request re-enqueues
            # with a fresh t_enqueue so server-side eviction can never
            # turn its TTL into a total-age limit and discard paid-for
            # progress
            if ttl is not None \
                    and now - req.get("t_enqueue", req["t_submit"]) > ttl:
                rid = req["rid"]
                self._status[rid] = "timeout"
                if self._tel:
                    _telemetry.count("serving.requests_shed")
                    _telemetry.count("resilience.deadline_sheds")
                    _telemetry.event("serving.shed", req["t_submit"], now,
                                     rid=rid, ttl_s=ttl)
            else:
                kept.append(req)
        self._queue[:] = kept

    def _fail_request(self, st, slot, reason: str):
        """Retire one request with the ``error`` status (NaN guard):
        the slot frees for the next tenant, the server lives."""
        rid = st["rid"]
        self._status[rid] = "error"
        self._err_reason[rid] = reason
        if self._paged:
            self._pool.free_slot(slot)
        self._free.append(slot)
        if self._tel:
            _telemetry.count("serving.requests_failed")
            _telemetry.count("resilience.nan_requests")
            _telemetry.event("serving.request_failed",
                             st.get("t_submit", time.perf_counter()),
                             time.perf_counter(), tid=slot, rid=rid,
                             reason=reason)

    def _admit(self):
        """Queued requests take free slots.  With something queued this
        is an ``admit`` phase: queue pop, slot claim, block allocation,
        the prefill dispatch and its first-token fetch."""
        if not (self._tel and self._queue):
            return self._admit_queue()
        with self._phase("admit") as sp:
            self._admit_sp = sp
            try:
                return self._admit_queue()
            finally:
                self._admit_sp = None

    def _admit_queue(self):
        self._shed_expired()
        # the OOM-chain cap binds every class (it is a memory bound);
        # the controller's ladder cap is SHED pressure and binds class-0
        # admissions only — throttling the high-priority traffic the
        # ladder protects would make degradation self-defeating
        cap = self._admit_cap
        adm_cap = None
        if self._adm is not None:
            adm_cap = min(cap,
                          self._adm.effective_admit_cap(self.max_batch))
            if self._adm.engaged and len(self._queue) > 1:
                # a CONFIGURED controller spends free slots on the
                # highest priority class first (stable sort — FIFO
                # within a class); the unconfigured default keeps
                # strict FIFO so plain ADMISSION=1 matches the
                # ADMISSION=0 admit order exactly
                self._queue.sort(key=lambda r: (
                    -_admission.priority_class(r.get("priority", 0)),
                    r.get("t_enqueue", 0.0)))
        while self._queue and self._free \
                and len(self._slots) < cap:
            if (adm_cap is not None and len(self._slots) >= adm_cap
                    and _admission.priority_class(
                        self._queue[0].get("priority", 0)) == 0):
                # queue is class-sorted, so a class-0 head means no
                # higher-priority request is waiting either
                break
            slot = self._free.pop()
            req = self._queue.pop(0)
            t_admit = time.perf_counter()
            if self._admit_sp is not None:
                self._admit_sp.args.setdefault("rids", []).append(req["rid"])
            st = {
                "rid": req["rid"], "prompt": req["prompt"],
                "max_new": req["max_new"], "stop": req.get("stop", []),
                "temperature": req.get("temperature", 0.0),
                "top_k": req.get("top_k", 0),
                "top_p": req.get("top_p", 1.0),
                # an OOM-evicted request re-admits with its progress
                # carried: prompt = original + generated-so-far, and
                # ``carry`` seeds the generated list so result() returns
                # the FULL generation.  ``base`` is the ORIGINAL prompt
                # length — carried tokens appear in BOTH the extended
                # prompt and ``generated``, so the feed index is
                # sequence[i] = prompt[i] while i < len(prompt), else
                # generated[i - base] (i - len(prompt) would skip the
                # carry and re-feed from the wrong offset)
                "generated": list(req.get("carry", ())),
                "base": len(req["prompt"]) - len(req.get("carry", ())),
                "ttl": req.get("ttl"),
                "priority": req.get("priority", 0),
                "tenant": req.get("tenant"),
                # OOM-evict requeue aging (satellite: starvation bound):
                # how many times this request has been evicted and
                # re-queued; past PADDLE_TPU_EVICT_REQUEUE_MAX it fails
                # honestly instead of thrashing forever
                "evictions": req.get("evictions", 0),
                "pos": 0,   # next position == index of the token to feed
                # span timestamps (host clock only; never a device sync)
                "t_submit": req.get("t_submit", t_admit),
                "t_admit": t_admit,
                # fleet trace context (router-minted; absent on direct
                # submits and whenever telemetry is off) — every span
                # this slot records lands under it
                "trace": req.get("trace"),
                # multi-tenant serving: which pool row this slot gathers
                # (0 = base model) and the original spec — the spec (not
                # the live automaton) survives OOM-evict requeues
                "adapter": req.get("adapter", 0),
                "adapter_name": req.get("adapter_name"),
                "constraint_spec": req.get("constraint"),
            }
            if req.get("constraint") is not None:
                from . import adapters as _ad

                cst = _ad.compile_constraint(req["constraint"],
                                             self.cfg.vocab_size)
                # an OOM-evicted request re-admits mid-output: replay
                # the carried tokens so the automaton resumes where the
                # evicted slot's state machine stood
                for tt in req.get("carry", ()):
                    cst.advance(int(tt))
                st["constraint"] = cst
            if self._spec_on and self._adm is not None \
                    and self._adm.spec_forced():
                # ladder rung >= RUNG_SPEC_OFF: this admission decodes
                # plain, via the SAME per-slot flag the acceptance-rate
                # fallback sets — verify passes stop competing with
                # decode while the server is degraded
                st["spec_off"] = True
                if self._tel:
                    _telemetry.count("admission.spec_forced")
            if self._tel:
                self._observe(
                    "serving.queue_wait_ms",
                    (t_admit - st["t_submit"]) * 1e3)
            if req.get("stream"):
                # streamed fleet handoff: claim the slot now with zero
                # rows present — chunks inject as they arrive
                # (stream_prefilled_rows), decode ticks riding the
                # frontier exactly like budgeted admission
                if not self._claim_stream(req, slot, st):
                    break
                continue
            if self._budget and "prefilled" not in req \
                    and len(req["prompt"]) > self._budget:
                # budgeted admission: claim the slot NOW (plan the chunk
                # starts, paged: adopt + allocate) but run ZERO prefill —
                # each scheduler round advances the oldest admitting slot
                # by one budget-width chunk (_advance_admitting),
                # interleaved with decode steps, so a long prompt never
                # stalls the decode loop.  Prompts that fit one chunk
                # take the monolithic path below: one executable call
                # either way, and admission-tick latency stays minimal.
                # Handoff-admitted requests ("prefilled") stay monolithic
                # too — their rows arrive computed; injection is a copy
                if not self._claim_admitting(req, slot, st):
                    break
                continue
            if "prefilled" in req or self._prefill is not None \
                    or self._prefill_chunk is not None \
                    or (self._paged and self._prefill_on):
                n = len(req["prompt"])
                try:
                    if "prefilled" in req:
                        from . import kv_pool as _kv

                        try:
                            prefill_name, logits = \
                                self._inject_prefilled(req, slot)
                        except _kv.PoolExhausted:
                            # same parking rule as local paged
                            # admission below: wait for blocks, never
                            # fail the submit
                            self._pool.free_slot(slot)
                            self._free.append(slot)
                            self._queue.insert(0, req)
                            if self._tel:
                                _telemetry.count("kv_pool.admit_blocked")
                            break
                    elif self._paged:
                        from . import kv_pool as _kv

                        try:
                            prefill_name, logits = \
                                self._paged_prefill_slot(req, slot)
                        except _kv.PoolExhausted:
                            # no free blocks even after evicting the cold
                            # prefix cache: the request WAITS (active
                            # slots will retire and free blocks) instead
                            # of failing the submit — park it back at
                            # the queue front and stop admitting
                            self._pool.free_slot(slot)
                            self._free.append(slot)
                            self._queue.insert(0, req)
                            if self._tel:
                                _telemetry.count("kv_pool.admit_blocked")
                            break
                    elif self._prefill is not None:
                        # the padded chunk must fit both the wpe table
                        # and the cache window; both bounds >= n (submit
                        # checked)
                        bucket = _pow2_bucket(n, self.max_len,
                                              self.cfg.max_seq_len)
                        padded = np.zeros((1, bucket), np.int32)
                        padded[0, :n] = req["prompt"]
                        if self._adapters is not None:
                            # pool attached: ALL admissions run the
                            # adapter prefill (aid 0 merges the zero
                            # row — token-parity with the plain path),
                            # so one executable set serves the mixed
                            # batch and base-only warmup covers it
                            prefill_name = f"adapter_prefill@{bucket}"
                            fn = _get_adapter_prefill_fn(
                                self.cfg, bucket,
                                self._adapters.pool_key(), self._shard)
                            logits, self.cache = fn(
                                self.params, self.cache,
                                self._adapters.stacks(),
                                jnp.asarray(st["adapter"]),
                                jnp.asarray(padded), jnp.asarray(n),
                                jnp.asarray(slot))
                        else:
                            prefill_name = f"prefill@{bucket}"
                            logits, self.cache = self._prefill(bucket)(
                                self.params, self.cache,
                                jnp.asarray(padded),
                                jnp.asarray(n), jnp.asarray(slot))
                    else:
                        # fixed-chunk walk: every chunk reuses ONE
                        # executable.  The LAST window starts at n - C
                        # (overlapping the previous chunk) instead of
                        # overrunning the cache/wpe bounds — overlapped
                        # rows recompute to identical values
                        # (deterministic function of the same tokens +
                        # already-correct prefix), and
                        # dynamic_update_slice would otherwise CLAMP an
                        # overrunning start and silently shift the
                        # written rows (_chunk_attend_block's
                        # precondition)
                        C = self._chunk
                        if n <= C:
                            starts = [0]
                        else:
                            starts = list(range(0, n - C, C)) + [n - C]
                        if self._adapters is not None:
                            prefill_name = "adapter_prefill_chunk"
                            afn = _get_adapter_prefill_chunk_fn(
                                self.cfg, self._adapters.pool_key(),
                                self._shard)
                            _ad_st = self._adapters.stacks()
                            _aid = jnp.asarray(st["adapter"])
                            pf = lambda p, c, t, p0, ln, sl: afn(
                                p, c, _ad_st, _aid, t, p0, ln, sl)
                        else:
                            prefill_name = "prefill_chunk"
                            pf = self._prefill_chunk
                        logits = None
                        for i in starts:
                            chunk = req["prompt"][i:i + C]
                            padded = np.zeros((1, C), np.int32)
                            padded[0, :len(chunk)] = chunk
                            logits, self.cache = pf(
                                self.params, self.cache,
                                jnp.asarray(padded),
                                jnp.asarray(i), jnp.asarray(len(chunk)),
                                jnp.asarray(slot))
                    if self._spec_on and self.draft_cfg is not None:
                        st["spec_dpos"] = self._spec_draft_admit(req,
                                                                 slot, n)
                    # one host fetch of the admission logits; the
                    # timestamp right after it bounds the DEVICE window
                    # (the sampling below is pure host math and must not
                    # be charged to the prefill executable's step wall)
                    logits_np = np.asarray(logits)
                except Exception:
                    # a failed admission prefill (e.g. a real OOM the
                    # guard will degrade around) must neither lose the
                    # request nor leak the slot: both go back where they
                    # came from before the error propagates (paged: the
                    # slot's partially mapped blocks return to the pool)
                    if self._paged:
                        self._pool.free_slot(slot)
                    self._free.append(slot)
                    self._queue.insert(0, req)
                    raise
                if _faults.active():
                    logits_np = _faults.corrupt_nan("logits", logits_np)
                if self._resil and not np.isfinite(logits_np).all():
                    # NaN guard at admission: the logits are ALREADY on
                    # the host, so the finite check costs no extra sync.
                    # A poisoned request fails cleanly (status "error",
                    # slot freed) instead of feeding garbage tokens —
                    # with resilience off the garbage argmax proceeds,
                    # exactly the pre-guard behavior.
                    self._fail_request(st, slot,
                                       "non-finite prefill logits")
                    continue
                cst = st.get("constraint")
                if cst is not None:
                    # first token: the logits are already host-side, so
                    # the constraint masks HERE (same -inf law the jitted
                    # steps apply) — the automaton then advances below
                    from . import adapters as _ad

                    logits_np = _ad.apply_constraint_host(logits_np, cst)
                if st["temperature"] > 0.0:
                    # admission draws host-side from the filtered law,
                    # seeded per rid off the server key — deterministic
                    # regardless of admission order or batch-mates
                    p = generate._filtered_probs(
                        logits_np, st["temperature"],
                        st["top_k"], st["top_p"])
                    rng = np.random.default_rng(generate._key_seed(
                        jax.random.fold_in(self._base_key,
                                           (1 << 20) + st["rid"])))
                    t = int(rng.choice(len(p), p=p))
                else:
                    t = int(logits_np.argmax())
                st["generated"].append(t)
                st["pos"] = n  # cache rows [0, n) are filled
                if self._tel:
                    # the argmax/choice above already fetched the host
                    # value, so "now" IS the first-token time — TTFT and
                    # the prefill span cost zero extra syncs
                    now = self._tel_first_token(st, slot, t_admit,
                                                prefill_name)
                    self._span_ring.record(
                        st.get("trace"), "prefill", t_admit, now,
                        rid=st["rid"], prompt_len=n)
                    _telemetry.count("serving.tokens_generated")
                    self._count_local("serving.tokens_generated")
                # _finished (not the old max_new <= 1 test): a carried
                # (OOM-evicted, re-admitted) request may hit its budget
                # on the admission token
                fin = self._constraint_push(st, t)
                if self._finished(st, t) or fin:
                    self._results[st["rid"]] = st["generated"]
                    if self._paged:
                        self._pool.free_slot(slot)
                    self._free.append(slot)
                    self._tel_retire(st, slot)
                    continue
            if self._spec_on and self.draft_cfg is not None:
                # prefill=False admission: the draft saw nothing yet —
                # the first spec round's catch-up feeds it the sequence
                st.setdefault("spec_dpos", 0)
            self._slots[slot] = st

    # -- budgeted admission: chunked-prefill co-scheduling ------------------

    def _effective_budget(self) -> int:
        """The prefill chunk width NEW budgeted admissions claim at: the
        base budget, or — under SLO degradation — the controller's
        current pre-warmed ladder rung (admission.ladder_widths; every
        rung is compiled by warmup(), so a ladder move is a host-side
        executable pick, never a retrace).  With no controller this is
        exactly ``self._budget``."""
        if self._adm is None:
            return self._budget
        return self._adm.effective_budget(self._budget)

    def _claim_admitting(self, req, slot, st) -> bool:
        """Budgeted admission, part 1 (claim): reserve the slot and plan
        the prompt's chunk starts WITHOUT running any prefill.  The
        starts follow the monolithic walks exactly — contiguous: the
        fixed-chunk rule at width=budget; paged: adopt the longest
        indexed prefix first, then the suffix rule of
        ``_paged_prefill_slot`` — so a budgeted admission writes the
        same rows through the same offset-aware executables, just
        spread over scheduler rounds.  Paged block allocation happens
        here in full (rows [min(starts), n)): the decode steps the slot
        rides during admission write its frontier row, which must
        already be mapped.  A PoolExhausted parks the request back at
        the queue front exactly like monolithic admission.

        Returns False when admission must stop (request parked)."""
        prompt = req["prompt"]
        n = len(prompt)
        window = min(self.max_len, self.cfg.max_seq_len)
        W = min(self._effective_budget(), window)
        if self._paged:
            from . import kv_pool as _kv

            alloc = self._pool
            try:
                # adapter≠0 prompts never share prefix-cache rows: the
                # cached rows were computed under a DIFFERENT weight
                # delta (or the base), so adoption would serve wrong
                # attention state.  Base (adapter 0) traffic shares as
                # before.
                shared = self._adopt_prefix(slot, req)
                self._drain_restores()
                starts = self._chunk_starts(shared, n, W, window)
                while True:
                    try:
                        alloc.ensure_rows(slot, min(starts), n)
                        break
                    except _kv.PoolExhausted:
                        # the OOM chain's first rung at admission (see
                        # _paged_prefill_slot)
                        if self._evict_or_spill(_EVICT_BATCH) == 0:
                            raise
            except _kv.PoolExhausted:
                self._pool.free_slot(slot)
                self._free.append(slot)
                self._queue.insert(0, req)
                if self._tel:
                    _telemetry.count("kv_pool.admit_blocked")
                return False
            self._apply_pool_ops()
        else:
            starts = ([0] if n <= W
                      else list(range(0, n - W, W)) + [n - W])
        st["admitting"] = True
        st["admit_starts"] = starts
        st["admit_i"] = 0
        # the chunk width the starts were planned at: _advance_admitting
        # runs THIS width for the slot's whole admission even if the
        # ladder moves the effective budget mid-flight (the starts and
        # the executable must agree; new claims pick up the new rung)
        st["admit_w"] = W
        # pos doubles as the prefill frontier: rows [starts[0], pos)
        # are written.  While admitting, decode dispatches feed
        # prompt[pos] at pos — the frontier row they write is rewritten
        # (bit-identically, by chunk contiguity) by the next chunk, the
        # same stale-row argument as spec catch-up rides
        st["pos"] = starts[0]
        self._slots[slot] = st
        if self._tel:
            _telemetry.count("serving.admitting_claims")
        return True

    def _advance_admitting(self) -> bool:
        """Budgeted admission, part 2 (advance): run ONE budget-width
        prefill chunk for the OLDEST admitting slot (dict order =
        claim order), then return — at most ``budget`` prefill tokens
        per scheduler round, the decode ticks interleaving in between.
        The last chunk graduates the slot to decoding.

        Host state (admit_i, pos) advances only AFTER the executable
        returned, so a failed call (real or injected OOM) leaves the
        slot exactly as before and the guard's retry re-runs the same
        chunk bit-exactly.  Returns True when a chunk ran."""
        slot = st = None
        for s_, st_ in self._slots.items():
            # stream slots are "admitting" for the ride/skip machinery
            # but have no chunk plan — their rows arrive off-tick
            if st_.get("admitting") and not st_.get("stream"):
                slot, st = s_, st_
                break
        if st is None:
            return False
        with self._phase("admit", rids=[st["rid"]]):
            self._advance_admit_chunk(slot, st)
        return True

    def _advance_admit_chunk(self, slot, st):
        t0 = time.perf_counter()
        prompt = st["prompt"]
        n = len(prompt)
        window = min(self.max_len, self.cfg.max_seq_len)
        # the width the slot's starts were planned at (see
        # _claim_admitting); absent only for pre-upgrade state — then
        # the base budget is what the starts were built from
        W = st.get("admit_w") or min(self._budget, window)
        i = st["admit_i"]
        s = st["admit_starts"][i]
        chunk = prompt[s:s + W]
        padded = np.zeros((1, W), np.int32)
        padded[0, :len(chunk)] = chunk
        if self._adapters is not None:
            pk = self._adapters.pool_key()
            if self._paged:
                kind = f"adapter_paged_prefill@{W}"
                afn = _get_adapter_paged_prefill_fn(self.cfg, W, pk,
                                                    self._shard)
            else:
                kind = f"adapter_prefill_chunk@{W}"
                afn = _get_adapter_prefill_chunk_fn(self.cfg, pk,
                                                    self._shard, width=W)
            _ad_st = self._adapters.stacks()
            _aid = jnp.asarray(st.get("adapter", 0))
            fn = lambda p, c, t, p0, ln, sl: afn(p, c, _ad_st, _aid,
                                                 t, p0, ln, sl)
        elif self._paged:
            kind = f"paged_prefill@{W}"
            fn = _get_paged_prefill_fn(self.cfg, W, self._shard)
        else:
            kind = f"prefill_chunk@{W}"
            fn = _get_prefill_chunk_fn(self.cfg, self._shard, width=W)
        logits, self.cache = fn(
            self.params, self.cache, jnp.asarray(padded),
            jnp.asarray(s), jnp.asarray(len(chunk)), jnp.asarray(slot))
        if self._draft_cache is not None:
            # the draft twin walks the SAME chunk (the budgeted version
            # of _spec_draft_admit / _paged_prefill_slot's draft walk),
            # so graduation can set spec_dpos = n directly
            dfn = (_get_paged_prefill_fn(self.draft_cfg, W,
                                         self._draft_shard)
                   if self._paged else
                   _get_prefill_chunk_fn(self.draft_cfg,
                                         self._draft_shard, width=W))
            _, self._draft_cache = dfn(
                self._draft_params, self._draft_cache,
                jnp.asarray(padded), jnp.asarray(s),
                jnp.asarray(len(chunk)), jnp.asarray(slot))
        st["admit_i"] = i + 1
        st["pos"] = min(s + len(chunk), n)
        if self._tel:
            _telemetry.count("serving.prefill_chunks_interleaved")
            if self._paged:
                _telemetry.count("kv_pool.prefill_rows", len(chunk))
        if st["admit_i"] == len(st["admit_starts"]):
            self._graduate_admitting(slot, st, logits, t0, kind)

    def _graduate_admitting(self, slot, st, logits, t0, kind):
        """The last chunk landed: fetch the admission logits, draw the
        first token (the SAME per-rid host sampling as monolithic
        admission — bit-identical by construction), and flip the slot
        to decoding.  Paged: the completed prompt's blocks index for
        future prefix sharing, exactly where monolithic admission
        registers them."""
        prompt = st["prompt"]
        n = len(prompt)
        logits_np = np.asarray(logits)
        if _faults.active():
            logits_np = _faults.corrupt_nan("logits", logits_np)
        if self._resil and not np.isfinite(logits_np).all():
            # NaN guard at graduation — the budgeted twin of the
            # monolithic admission guard (same fetch, same cost)
            del self._slots[slot]
            self._fail_request(st, slot, "non-finite prefill logits")
            return
        cst = st.get("constraint")
        if cst is not None:
            # same host-side first-token masking as monolithic admission
            from . import adapters as _ad

            logits_np = _ad.apply_constraint_host(logits_np, cst)
        if st["temperature"] > 0.0:
            p = generate._filtered_probs(
                logits_np, st["temperature"], st["top_k"], st["top_p"])
            rng = np.random.default_rng(generate._key_seed(
                jax.random.fold_in(self._base_key,
                                   (1 << 20) + st["rid"])))
            t = int(rng.choice(len(p), p=p))
        else:
            t = int(logits_np.argmax())
        st["generated"].append(t)
        st["pos"] = n
        st.pop("admitting", None)
        st.pop("admit_starts", None)
        st.pop("admit_i", None)
        if self._paged and self._prefill_on and not st.get("adapter") \
                and not self._recurrent:
            # adapter rows never index for sharing (see _claim_admitting)
            self._pool.register_prefix(slot, prompt)
        if self._recurrent and self._tel:
            _telemetry.count("kv_pool.state_resets")
        if self._spec_on and self.draft_cfg is not None:
            # draft chunks advanced in lockstep (see _advance_admitting);
            # without a draft cache the catch-up feeds from 0
            st["spec_dpos"] = n if self._draft_cache is not None else 0
        if self._tel:
            now = self._tel_first_token(st, slot, st.get("t_admit", t0),
                                        kind)
            self._span_ring.record(
                st.get("trace"), "prefill", st.get("t_admit", t0), now,
                rid=st["rid"], prompt_len=n)
            _telemetry.count("serving.tokens_generated")
            self._count_local("serving.tokens_generated")
        fin = self._constraint_push(st, t)
        if self._finished(st, t) or fin:
            # carried (OOM-evicted) requests may hit their budget on
            # the admission token, exactly like monolithic admission
            del self._slots[slot]
            self._results[st["rid"]] = st["generated"]
            if self._paged:
                self._pool.free_slot(slot)
            self._free.append(slot)
            self._tel_retire(st, slot)

    # -- streamed fleet handoff: per-chunk row injection --------------------

    def stream_prefilled_begin(self, prompt, max_new_tokens: int = 32,
                               stop: list | None = None,
                               temperature: float = 0.0, top_k: int = 0,
                               top_p: float = 1.0,
                               ttl_s: float | None = None,
                               priority: int = 0, trace=None) -> int:
        """Open a STREAMED prefill handoff — the chunked twin of
        :meth:`submit_prefilled`.  The caller (the fleet router, as a
        worker's chunks land) follows with one
        :meth:`stream_prefilled_rows` call per finished prefill chunk;
        the final chunk carries the admission logits and graduates the
        request to plain decoding in the same call.  The slot is
        claimed at admission with ZERO rows present and decode ticks
        ride it at the injected frontier exactly like budgeted
        admission (the frontier row a ride writes is rewritten
        bit-identically by the next chunk's injection), so the
        transfer overlaps this server's decode steps instead of
        stalling them.  Chunks that arrive while the request is still
        QUEUED buffer host-side and replay at claim — admission order
        is unchanged.  Decoded output is bit-identical to
        :meth:`submit_prefilled` with the same rows and logits."""
        self._refuse_handoff("stream_prefilled_begin")
        req = self._build_request(prompt, max_new_tokens, stop,
                                  temperature, top_k, top_p, ttl_s,
                                  priority)
        req["stream"] = True
        if trace:
            req["trace"] = trace
        self._streams[req["rid"]] = {
            "req": req, "pending": [], "expect": 0,
            "slot": None, "st": None}
        self._queue.append(req)
        if self._tel:
            _telemetry.count("serving.requests_submitted")
            _telemetry.count("serving.stream_begins")
        self._admit()
        self._tel_gauges()
        return req["rid"]

    def _claim_stream(self, req, slot, st) -> bool:
        """Streamed-handoff admission (claim): reserve the slot and —
        paged — adopt the longest indexed prefix + allocate the FULL
        row range before any chunk lands, mirroring
        :meth:`_inject_prefilled`'s allocation exactly (worker rows
        for adopted blocks are bit-identical to what the index already
        holds, so those blocks are attended, never rewritten).  No
        prefill runs here; rows arrive via
        :meth:`stream_prefilled_rows`.  Returns False when admission
        must stop (request parked on pool pressure, the monolithic
        parking rule)."""
        prompt = req["prompt"]
        n = len(prompt)
        shared = 0
        if self._paged:
            from . import kv_pool as _kv

            try:
                if self._prefill_on:
                    shared = self._pool.adopt_prefix(slot, prompt)
                    self._drain_restores()
                while True:
                    try:
                        self._pool.ensure_rows(slot, shared, n)
                        break
                    except _kv.PoolExhausted:
                        # the OOM chain's first rung at admission (see
                        # _paged_prefill_slot)
                        if self._evict_or_spill(_EVICT_BATCH) == 0:
                            raise
            except _kv.PoolExhausted:
                self._pool.free_slot(slot)
                self._free.append(slot)
                self._queue.insert(0, req)
                if self._tel:
                    _telemetry.count("kv_pool.admit_blocked")
                return False
            self._apply_pool_ops()
        st["admitting"] = True      # decode ticks ride the frontier
        st["stream"] = True
        st["stream_shared"] = shared
        # pos doubles as the injected frontier: rows [0, pos) are
        # valid (adopted prefix now, injected chunks as they land)
        st["pos"] = shared
        self._slots[slot] = st
        sr = self._streams[st["rid"]]
        sr["slot"], sr["st"] = slot, st
        if self._tel:
            _telemetry.count("serving.stream_claims")
        # chunks that arrived while the request was queued replay now
        self._stream_drain(st["rid"])
        return True

    def stream_prefilled_rows(self, rid: int, start: int, stop: int,
                              rows, logits=None) -> None:
        """Fold one streamed chunk — worker cache rows for prompt
        positions ``[start, stop)``, leaves ``[L, 1, stop-start,
        Hkv(, hd)]`` in this server's storage dtype — into the
        request's slot through the pow2 injector bucket.  ``logits``
        ([V], float32) rides the FINAL chunk (``stop == n``):
        graduation happens in the same call, so the slot never sits
        complete awaiting a separate done frame (a window a decode
        ride could corrupt).  Chunks landing before the claim buffer
        host-side.  Raises on leaf/dtype/range mismatch — the
        transport is ordered, so a gap is a protocol bug, not a
        retry."""
        sr = self._streams.get(rid)
        if sr is None:
            raise KeyError(f"no open handoff stream for rid {rid}")
        if self._status.get(rid) is not None:
            # shed or failed while the chunks were in flight: late
            # rows drop, the record closes
            self._streams.pop(rid, None)
            return
        start, stop = int(start), int(stop)
        n = len(sr["req"]["prompt"])
        if start != sr["expect"] or stop <= start or stop > n:
            raise ValueError(
                f"stream chunk [{start}, {stop}) for rid {rid}: "
                f"expected start {sr['expect']} in a {n}-token prompt")
        if logits is None and stop == n:
            raise ValueError(
                f"final stream chunk for rid {rid} carries no "
                f"admission logits")
        if logits is not None and stop != n:
            raise ValueError(
                f"stream chunk [{start}, {stop}) for rid {rid} "
                f"carries logits before the final row {n}")
        rows = {name: np.asarray(v) for name, v in rows.items()}
        want = {name for name in self.cache if name != "tables"}
        if set(rows) != want:
            raise ValueError(
                f"stream chunk leaves {sorted(rows)} do not match the "
                f"cache leaves {sorted(want)}")
        for name, v in rows.items():
            have = self.cache[name].dtype
            if v.dtype != have:
                raise ValueError(
                    f"stream chunk leaf {name!r} is {v.dtype}, this "
                    f"server stores {have} (PADDLE_TPU_KV_DTYPE drift "
                    f"between prefill worker and decode server?)")
            if v.shape[2] != stop - start:
                raise ValueError(
                    f"stream chunk leaf {name!r} covers {v.shape[2]} "
                    f"positions for range [{start}, {stop})")
        sr["expect"] = stop
        sr["pending"].append(
            (start, stop, rows,
             None if logits is None else np.asarray(logits,
                                                    np.float32)))
        if sr["st"] is not None:
            self._stream_drain(rid)

    def _stream_drain(self, rid: int) -> None:
        """Inject every buffered chunk for a CLAIMED stream, in order;
        the chunk carrying logits graduates the slot (and may retire
        the request — single-token budgets finish on the admission
        token, like every admission path)."""
        sr = self._streams.get(rid)
        if sr is None or sr["st"] is None:
            return
        while sr["pending"]:
            start, stop, rows, logits = sr["pending"].pop(0)
            self._stream_inject(sr["slot"], sr["st"], start, stop,
                                rows)
            if logits is not None:
                self._graduate_stream(sr["slot"], sr["st"], logits)
                break

    def _stream_inject(self, slot, st, start, stop, rows) -> None:
        """One chunk through the handoff injector: the rows pad into
        the request's pow2(n) bucket at their ABSOLUTE offsets and the
        range-gated executable writes ``[max(shared, start), stop)`` —
        the SAME ``inject@bucket`` program monolithic handoff
        admission runs, with per-chunk range arguments (zero new
        executable families, so bit-parity with
        :meth:`submit_prefilled` is by construction).  Rows under the
        adopted prefix are attended, never rewritten."""
        n = len(st["prompt"])
        lo = max(st.get("stream_shared", 0), start)
        if stop > lo:
            t_inj = time.perf_counter()
            bucket = _pow2_bucket(n, self.max_len,
                                  self.cfg.max_seq_len)
            padded = {}
            for name, v in rows.items():
                buf = np.zeros(v.shape[:2] + (bucket,) + v.shape[3:],
                               v.dtype)
                buf[:, :, lo:stop] = v[:, :, lo - start:stop - start]
                padded[name] = jnp.asarray(buf)
            fn = _get_inject_fn(self.cfg, bucket, self._paged,
                                self._shard)
            self.cache = fn(self.cache, padded, jnp.asarray(lo),
                            jnp.asarray(stop), jnp.asarray(slot))
            if self._tel:
                _telemetry.count("serving.prefilled_rows", stop - lo)
                self._span_ring.record(
                    st.get("trace"), "inject", t_inj,
                    time.perf_counter(), rid=st["rid"], start=lo,
                    stop=stop)
        # frontier advance: the row a decode ride wrote at the old pos
        # was just rewritten bit-identically by this inject
        st["pos"] = max(st["pos"], stop)

    def _graduate_stream(self, slot, st, logits) -> None:
        """The final chunk landed (logits in the same frame): draw the
        first token with the exact per-rid host sampling of monolithic
        handoff admission and flip the slot to plain decoding."""
        prompt = st["prompt"]
        n = len(prompt)
        rid = st["rid"]
        self._streams.pop(rid, None)
        logits_np = np.asarray(logits, np.float32)
        if _faults.active():
            logits_np = _faults.corrupt_nan("logits", logits_np)
        if self._resil and not np.isfinite(logits_np).all():
            # the admission NaN guard, streamed edition
            del self._slots[slot]
            self._fail_request(st, slot, "non-finite prefill logits")
            return
        if st["temperature"] > 0.0:
            p = generate._filtered_probs(
                logits_np, st["temperature"], st["top_k"], st["top_p"])
            rng = np.random.default_rng(generate._key_seed(
                jax.random.fold_in(self._base_key, (1 << 20) + rid)))
            t = int(rng.choice(len(p), p=p))
        else:
            t = int(logits_np.argmax())
        st["generated"].append(t)
        st["pos"] = n
        st.pop("admitting", None)
        st.pop("stream", None)
        st.pop("stream_shared", None)
        if self._paged and self._prefill_on:
            # streamed rows equal local prefill's bit-for-bit: the
            # prompt's full blocks index for future sharing
            self._pool.register_prefix(slot, prompt)
        if self._spec_on and self.draft_cfg is not None:
            # the draft cache saw none of these rows: the first spec
            # round's catch-up feeds it the sequence from 0
            st["spec_dpos"] = 0
        if self._tel:
            self._tel_first_token(st, slot,
                                  st.get("t_admit", time.perf_counter()))
            _telemetry.count("serving.tokens_generated")
            self._count_local("serving.tokens_generated")
        fin = self._constraint_push(st, t)
        if self._finished(st, t) or fin:
            # single-token budgets finish on the admission token
            del self._slots[slot]
            self._results[rid] = st["generated"]
            if self._paged:
                self._pool.free_slot(slot)
            self._free.append(slot)
            self._tel_retire(st, slot)

    def stream_prefilled_abort(self, rid: int, reason: str) -> None:
        """Tear down a half-streamed handoff (worker death, transport
        loss, TTL, replica removal): the request retires with the
        ``error`` status and — if the stream had claimed a slot — the
        slot and its pool blocks free for the next tenant.  Raises
        ``KeyError`` when no stream is open for ``rid`` (already
        graduated, aborted, or never begun)."""
        sr = self._streams.pop(rid)
        st = sr["st"]
        if st is None:
            self._queue[:] = [r for r in self._queue
                              if r["rid"] != rid]
        else:
            self._slots.pop(sr["slot"], None)
            if self._paged:
                self._pool.free_slot(sr["slot"])
            self._free.append(sr["slot"])
        if self._status.get(rid) is None:
            self._status[rid] = "error"
            self._err_reason[rid] = reason
        if self._tel:
            _telemetry.count("serving.requests_failed")
            _telemetry.count("serving.stream_aborts")

    # -- paged layout: allocator plumbing (text/kv_pool) --------------------

    def _apply_pool_ops(self):
        """Execute the allocator's pending device work: COW block copies
        (one donated gather/scatter) and the host->device table push.
        Called right before any jitted step that depends on them."""
        pairs = self._pool.take_copies()
        if pairs:
            # pad to a power-of-two width by REPEATING the first real
            # pair (duplicate writes of identical rows — scatter-safe):
            # one kv_copy executable per log2 bucket instead of one per
            # distinct pair count, so a COW storm can't compile mid-tick
            # per count or flood the step LRU.  A constant (0, 0) filler
            # would collide when block 0 is itself a COW destination in
            # the same drain (dst=0 twice with DIFFERENT sources — XLA
            # scatter order is undefined), violating copy_blocks'
            # no-dst-in-src precondition
            width = 1
            while width < len(pairs):
                width *= 2
            pad = [pairs[0]] * (width - len(pairs))
            src = jnp.asarray([p[0] for p in pairs + pad], jnp.int32)
            dst = jnp.asarray([p[1] for p in pairs + pad], jnp.int32)
            self.cache = _get_copy_fn(self.cfg, width, self._shard)(
                self.cache, src, dst)
            if self._draft_cache is not None:
                # a COW'd block holds both pools' rows for its logical
                # positions — the draft pool copies the same pairs so
                # the shared table stays valid for both
                self._draft_cache = _get_copy_fn(
                    self.draft_cfg, width, self._draft_shard)(
                    self._draft_cache, src, dst)
        if self._pool.dirty:
            tables = jnp.asarray(self._pool.tables)
            if isinstance(self._shard, _ShardCtx):
                # committed to the replicated tables sharding so the
                # explicit in_shardings see a matching placement
                tables = jax.device_put(tables,
                                        self._shard.cache["tables"])
            elif self._device is not None:
                tables = jax.device_put(tables, self._device)
            self.cache = dict(self.cache, tables=tables)
            if self._draft_cache is not None:
                # the draft pytree gets its OWN device buffer of the
                # same host table: the two caches donate independently,
                # and a shared array would be deleted out from under
                # the draft the first time a target step donates it
                dtables = jnp.asarray(self._pool.tables)
                if isinstance(self._draft_shard, _ShardCtx):
                    dtables = jax.device_put(
                        dtables, self._draft_shard.cache["tables"])
                elif self._device is not None:
                    dtables = jax.device_put(dtables, self._device)
                self._draft_cache = dict(self._draft_cache,
                                         tables=dtables)
            self._pool.dirty = False

    def _evict_or_spill(self, max_entries: int) -> int:
        """The OOM chain's evict-cold rung, spill-aware: with
        ``PADDLE_TPU_KV_SPILL_MB`` set, cold prefix chains demote to
        host RAM (one batched ``device_get`` per round) instead of
        dropping, so the next admission restores them with one batched
        ``device_put`` instead of a recompute walk.  Delegates to
        ``evict_cold`` when spill is off or a draft cache shares the
        allocator (spilled target rows alone would leave the draft
        pool's rows for those blocks stale on restore)."""
        pool = self._pool
        if pool.spill_limit_bytes and self._draft_cache is None:
            return pool.spill_cold(max_entries, fetch=self._spill_fetch)
        return pool.evict_cold(max_entries=max_entries)

    def _spill_fetch(self, blocks):
        """The ONE batched device->host read a spill round pays: gather
        the demoted blocks' rows across every pool leaf."""
        from . import kv_pool as _kv

        idx = jnp.asarray(blocks, jnp.int32)
        return {name: np.asarray(jax.device_get(self.cache[name][:, idx]))
                for name in _kv.POOL_LEAVES if name in self.cache}

    def _drain_restores(self):
        """Promote spilled chains the last ``adopt_prefix`` matched back
        to the device: ONE batched host->device transfer + ONE
        ``inject_rows`` table scatter per slot, through the same
        executable buckets the fleet handoff already warms (zero new
        executable families).  Runs right after adoption — before
        ``ensure_rows`` can park the request — so a restored index entry
        never outlives this call with stale device rows."""
        if not self._paged:
            return
        recs = self._pool.take_restores()
        if not recs:
            return
        # the restored blocks' table entries must be live on device
        # before the scatter resolves through them
        self._apply_pool_ops()
        bs = self._pool.bs
        by_slot: dict = {}
        for slot, start, rows, _b in recs:
            by_slot.setdefault(slot, []).append((start, rows))
        for slot, items in by_slot.items():
            items.sort(key=lambda it: it[0])
            # one adopt walk restores a CONTIGUOUS run of blocks, but
            # inject writes every row in [start, length) — split on gaps
            # so a hole never zero-fills rows it doesn't own
            runs, run = [], [items[0]]
            for it in items[1:]:
                if it[0] == run[-1][0] + bs:
                    run.append(it)
                else:
                    runs.append(run)
                    run = [it]
            runs.append(run)
            for run in runs:
                lo, hi = run[0][0], run[-1][0] + bs
                bucket = _pow2_bucket(hi, self.max_len,
                                      self.cfg.max_seq_len)
                padded = {}
                for name, v0 in run[0][1].items():
                    buf = np.zeros(
                        (v0.shape[0], 1, bucket) + v0.shape[2:],
                        v0.dtype)
                    for s, rows in run:
                        buf[:, 0, s:s + bs] = rows[name]
                    padded[name] = jnp.asarray(buf)
                fn = _get_inject_fn(self.cfg, bucket, True, self._shard)
                self.cache = fn(self.cache, padded, jnp.asarray(lo),
                                jnp.asarray(hi), jnp.asarray(slot))

    def _ensure_decode_blocks(self, steps: int):
        """Incremental allocation: before a dispatch of ``steps`` decode
        steps, map (or copy-on-write) every active slot's blocks
        covering rows [pos, pos+steps) — admission no longer reserves
        ``max_len`` rows up front, THE memory point of the paged layout.
        Rows past the window clamp (block-decode overrun writes drop).
        A PoolExhausted here surfaces inside the guarded tick, where the
        OOM chain's first rung evicts cold prefix-cache entries and
        retries."""
        if not self._paged or not self._slots:
            return
        cap = self._pool.nmax * self._pool.bs
        for slot, st in self._slots.items():
            self._pool.ensure_rows(slot, st["pos"],
                                   min(st["pos"] + steps, cap))
        self._apply_pool_ops()
        self._push_live()

    def _paged_prefill_slot(self, req, slot):
        """Paged admission: adopt the longest indexed prompt prefix into
        the slot's block table (refcounted sharing — those rows are
        never recomputed), allocate/COW the blocks the suffix will
        write, run the suffix through the offset-aware paged prefill
        chunk executable(s), and register this prompt's full blocks for
        future sharing.  Returns (telemetry name, admission logits)."""
        from . import kv_pool as _kv

        prompt = req["prompt"]
        n = len(prompt)
        alloc = self._pool
        # adapter≠0 prompts bypass the prefix cache entirely: adopted
        # rows carry a different (or no) weight delta, and registering
        # adapter rows would poison future base/other-adapter admissions
        shared = self._adopt_prefix(slot, req)
        self._drain_restores()
        window = min(self.max_len, self.cfg.max_seq_len)
        if self._chunk:
            # one chunk covering the suffix starts AT the adopted prefix
            # (recomputing shared rows would COW every adopted block and
            # forfeit the reuse), backing off only when the window bound
            # forces an overlap
            C = min(self._chunk, window)
            starts = self._chunk_starts(shared, n, C, window)
        else:
            # bucketed suffix: one power-of-two chunk per admission,
            # floored at the block size — suffixes after a prefix hit
            # are typically < block_size, and the floor keeps the
            # executable-width set small enough for warmup to cover.
            # pos0 backs off from ``shared`` only when the bucket would
            # overrun the wpe/window bound — overlapped rows recompute
            # to identical values (the contiguous walk's rule) after a
            # COW makes them writable
            C = min(max(_pow2_bucket(n - shared), self._pool.bs), window)
            starts = [shared if shared + C <= window else max(0, n - C)]
        while True:
            try:
                alloc.ensure_rows(slot, min(starts), n)
                break
            except _kv.PoolExhausted:
                # out of blocks: evict cold prefix-cache entries (the
                # OOM chain's first rung, applied at admission) in small
                # LRU batches until the suffix fits — NOT the whole
                # index at once: one pressure blip must not zero the
                # fleet's prefix hit rate.  Cold entries are ref==1, so
                # this request's freshly adopted blocks (ref>=2) are
                # never its own victims
                if self._evict_or_spill(_EVICT_BATCH) == 0:
                    raise
        self._apply_pool_ops()
        if self._adapters is not None:
            name = f"adapter_paged_prefill@{C}"
            afn = _get_adapter_paged_prefill_fn(
                self.cfg, C, self._adapters.pool_key(), self._shard)
            _ad_st = self._adapters.stacks()
            _aid = jnp.asarray(req.get("adapter", 0))
            fn = lambda p, c, t, p0, ln, sl: afn(p, c, _ad_st, _aid,
                                                 t, p0, ln, sl)
        else:
            name = f"paged_prefill@{C}"
            fn = _get_paged_prefill_fn(self.cfg, C, self._shard)
        logits = None
        rows_done = 0
        for s in starts:
            chunk = prompt[s:s + C]
            padded = np.zeros((1, C), np.int32)
            padded[0, :len(chunk)] = chunk
            logits, self.cache = fn(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(s), jnp.asarray(len(chunk)),
                jnp.asarray(slot))
            rows_done += len(chunk)
        if self._draft_cache is not None:
            # the draft cache walks the SAME starts through its own
            # chunk executable: the shared table maps both pools, so an
            # adopted prefix's draft rows are already valid (every
            # admission on this server writes both caches before
            # register_prefix) and the suffix fills here
            dfn = _get_paged_prefill_fn(self.draft_cfg, C,
                                        self._draft_shard)
            for s in starts:
                chunk = prompt[s:s + C]
                padded = np.zeros((1, C), np.int32)
                padded[0, :len(chunk)] = chunk
                _, self._draft_cache = dfn(
                    self._draft_params, self._draft_cache,
                    jnp.asarray(padded), jnp.asarray(s),
                    jnp.asarray(len(chunk)), jnp.asarray(slot))
        if self._tel:
            # rows actually prefilled — the repeated-prefix FLOPs saving
            # is (prompt length - this) per request
            _telemetry.count("kv_pool.prefill_rows", rows_done)
        if not req.get("adapter") and not self._recurrent:
            alloc.register_prefix(slot, prompt)
        if self._recurrent and self._tel:
            # every admission starts from the zero state (the chunk at
            # position 0 reads none)
            _telemetry.count("kv_pool.state_resets")
        return name, logits

    def _inject_prefilled(self, req, slot):
        """Admission half of the prefill/decode handoff: write the
        worker-computed rows into ``slot`` — paged servers first adopt
        the longest indexed prefix (the injected rows for shared blocks
        are bit-identical to what the index already holds, so those
        blocks are attended, never rewritten or duplicated), then
        allocate/COW the remaining write range, evicting cold prefix
        entries under pressure exactly like local admission — and
        return (telemetry name, the worker's admission logits)."""
        rows, logits = req["prefilled"]
        n = len(req["prompt"])
        t_inj = time.perf_counter()
        bucket = _pow2_bucket(n, self.max_len, self.cfg.max_seq_len)
        padded = {}
        for name, v in rows.items():
            buf = np.zeros(v.shape[:2] + (bucket,) + v.shape[3:],
                           v.dtype)
            buf[:, :, :n] = v
            padded[name] = jnp.asarray(buf)
        shared = 0
        if self._paged:
            from . import kv_pool as _kv

            if self._prefill_on:
                # capped at n-1 like local admission: the final row is
                # always written (COW on a fully-shared prompt)
                shared = self._pool.adopt_prefix(slot, req["prompt"])
                self._drain_restores()
            while True:
                try:
                    self._pool.ensure_rows(slot, shared, n)
                    break
                except _kv.PoolExhausted:
                    # the OOM chain's first rung at admission (see
                    # _paged_prefill_slot)
                    if self._evict_or_spill(_EVICT_BATCH) == 0:
                        raise
            self._apply_pool_ops()
        fn = _get_inject_fn(self.cfg, bucket, self._paged, self._shard)
        self.cache = fn(self.cache, padded, jnp.asarray(shared),
                        jnp.asarray(n), jnp.asarray(slot))
        if self._paged and self._prefill_on:
            # the injected rows are exactly what local prefill would
            # have computed, so the prompt's full blocks index for
            # future local admissions to share
            self._pool.register_prefix(slot, req["prompt"])
        if self._tel:
            _telemetry.count("serving.prefilled_rows", n - shared)
            self._span_ring.record(
                req.get("trace"), "inject", t_inj, time.perf_counter(),
                rid=req["rid"], rows=n - shared)
        return f"inject@{bucket}", logits

    def pending(self) -> bool:
        return bool(self._slots or self._queue)

    # -- speculative decoding: batched draft-then-verify rounds -------------

    def _spec_limit(self) -> int:
        """Highest position a spec round may reach: ``pos + K`` must stay
        inside the cache rows, the target's wpe table, and (draft mode)
        the draft's twins — ``dynamic_update_slice``/``dynamic_slice``
        CLAMP out-of-range starts instead of failing, which would
        silently shift the verify chunk's rows.  Near the window the
        server just runs plain ticks (_spec_ready)."""
        if self._paged:
            rows = self._pool.nmax * self._pool.bs
        else:
            rows = int(self.cache["k"].shape[2])
        lim = min(rows, self.cfg.max_seq_len)
        if self._draft_cache is not None:
            drows = (rows if self._paged
                     else int(self._draft_cache["k"].shape[2]))
            lim = min(lim, drows, self.draft_cfg.max_seq_len)
        return lim

    def _spec_chunk(self) -> int:
        """Cache rows one speculative round writes per slot — the tree
        node budget in tree mode, the linear chunk K otherwise (both
        counts include the fed root/feed row)."""
        return self._spec_tree_n or self._spec_k

    def _spec_ready(self) -> bool:
        """Whether THIS tick can run as a speculative round: every slot
        past its prompt (the verify chunk consumes feedback positions
        only), every slot's ``pos + K`` inside :meth:`_spec_limit`, and
        at least one slot still speculating (all fallen back = the
        rounds are pure overhead)."""
        if not self._spec_on or not self._slots:
            return False
        if self._constrained_active() and not self._spec_tree_n:
            # LINEAR mode: constrained slots fall back to plain
            # stepping for the whole batch — draft tokens can't be
            # masked cheaply (each proposal would need the automaton
            # advanced host-side mid-chunk), and an unmasked draft's
            # acceptances could emit banned tokens.  Tree mode lifts
            # this: proposals are walked through a lookahead cursor
            # and grammar-banned branches pruned BEFORE the verify
            # pass (_prune_branches_constrained), so constrained
            # slots speculate and this counter stays at zero.
            if self._tel:
                _telemetry.count("constraint.spec_fallbacks")
            return False
        K = self._spec_chunk()
        lim = self._spec_limit()
        alive = False
        for st in self._slots.values():
            # a mid-admission slot counts as prompt-feeding: its pos is
            # the prefill frontier (possibly n-1), not a feedback
            # position — spec rounds wait for graduation
            if st.get("admitting") or st["pos"] < len(st["prompt"]) - 1:
                return False
            if st["pos"] + K > lim:
                return False
            if st.get("spec_off"):
                # re-earn: a fallen-back slot sits out a cooldown of
                # spec-eligible rounds, then rejoins with a FRESH
                # acceptance window (the old window's verdict was
                # about a different region of the sequence).  The
                # cooldown doubles per trip (16 → 256 cap), so a
                # persistently unpredictable request converges to
                # plain decode while a request that merely passed
                # through a hard patch re-earns its speculation.
                st["spec_cool"] = st.get("spec_cool", 1) - 1
                if st["spec_cool"] <= 0:
                    st["spec_off"] = False
                    st["spec_prop"] = st["spec_acc"] = 0
                    alive = True
                    if self._tel:
                        _telemetry.count("spec.reearns")
            else:
                alive = True
        return alive

    def _spec_rng(self, st):
        """Per-request host RNG for the sampled spec path (proposal
        draws + acceptance tests), seeded per rid off the server key —
        disjoint from the per-step device schedule (fold_in(base, n))
        and the admission draws (1 << 20 namespace)."""
        if "spec_rng" not in st:
            st["spec_rng"] = np.random.default_rng(generate._key_seed(
                jax.random.fold_in(self._base_key,
                                   (1 << 21) + st["rid"])))
        return st["spec_rng"]

    def _spec_draft_admit(self, req, slot, n) -> int:
        """Admission-time draft prefill: fill the draft cache's rows
        [0, n) for this slot so the first spec round drafts from
        position ``n`` directly.  Paged admission already walked the
        draft chunk executable inside ``_paged_prefill_slot`` (same
        starts, same shared table).  Handoff-admitted requests
        ("prefilled") carry TARGET rows only — the draft starts cold
        (returns 0) and the first spec round's catch-up feeds it the
        sequence with batched draft steps, still zero target passes."""
        if "prefilled" in req:
            return 0
        if self._paged:
            return n
        if self._prefill_chunk is not None:
            C = self._chunk
            starts = ([0] if n <= C
                      else list(range(0, n - C, C)) + [n - C])
            dfn = _get_prefill_chunk_fn(self.draft_cfg,
                                        self._draft_shard)
            for i in starts:
                chunk = req["prompt"][i:i + C]
                padded = np.zeros((1, C), np.int32)
                padded[0, :len(chunk)] = chunk
                _, self._draft_cache = dfn(
                    self._draft_params, self._draft_cache,
                    jnp.asarray(padded), jnp.asarray(i),
                    jnp.asarray(len(chunk)), jnp.asarray(slot))
            return n
        bucket = _pow2_bucket(n, self.max_len,
                              self.draft_cfg.max_seq_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = req["prompt"]
        _, self._draft_cache = _get_prefill_fn(
            self.draft_cfg, bucket, self._draft_shard)(
            self._draft_params, self._draft_cache, jnp.asarray(padded),
            jnp.asarray(n), jnp.asarray(slot))
        return n

    def _spec_draft_catchup(self):
        """Advance every lagging slot's draft cache to its target
        position with batched draft steps (``spec_dpos`` = rows the
        draft has consumed).  Lag comes from handoff admission (draft
        starts cold), prefill=False admission (the plain path fed the
        prompt to the target only), and post-rejection rounds capping
        dpos at the last drafted row.  Non-lagging slots ride along
        fed their own feed token at their own pos — that row is
        rewritten identically by the proposal steps, so the overwrite
        is benign (the same argument covers shared paged blocks:
        recomputed rows are a deterministic function of the same
        tokens, hence bit-identical)."""
        step = _get_step_fn(self.draft_cfg, self._paged,
                            self._draft_shard)
        while True:
            lag = [(slot, st) for slot, st in self._slots.items()
                   if not st.get("spec_off")
                   and st.get("spec_dpos", 0) < st["pos"]]
            if not lag:
                return
            tok, pos = self._feed_arrays()
            for slot, st in lag:
                d = st["spec_dpos"]
                np_ = len(st["prompt"])
                base = st.get("base", np_)
                tok[slot] = (st["prompt"][d] if d < np_
                             else st["generated"][d - base])
                pos[slot] = d
            _, self._draft_cache = step(
                self._draft_params, self._draft_cache,
                jnp.asarray(tok), jnp.asarray(pos))
            for slot, st in lag:
                st["spec_dpos"] += 1

    def _spec_propose_draft(self, K):
        """K-1 batched draft steps from each slot's feed position: the
        draft model's proposals for positions pos+1..pos+K-1 (the
        verify chunk's columns 1..K-1) plus — for sampled slots — the
        filtered proposal law q_j the acceptance test divides by.
        Draft logits are fetched per step (host argmax/sampling); the
        draft is the cheap model by construction and K is small.
        Fallen-back slots ride along fed their feed token (their draft
        rows go stale — benign, they never speculate again)."""
        self._spec_draft_catchup()
        step = _get_step_fn(self.draft_cfg, self._paged,
                            self._draft_shard)
        tok, pos = self._feed_arrays()
        temp, tk, tp = self._sampling_arrays()
        eligible = {slot: st for slot, st in self._slots.items()
                    if not st.get("spec_off")}
        props = {slot: ([], [] if temp[slot] > 0 else None)
                 for slot in eligible}
        for _ in range(K - 1):
            logits, self._draft_cache = step(
                self._draft_params, self._draft_cache,
                jnp.asarray(tok), jnp.asarray(pos))
            lnp = np.asarray(logits)
            for slot, st in eligible.items():
                toks, qs = props[slot]
                if qs is None:
                    d = int(lnp[slot].argmax())
                else:
                    q = generate._filtered_probs(
                        lnp[slot], float(temp[slot]), int(tk[slot]),
                        float(tp[slot]))
                    d = int(self._spec_rng(st).choice(len(q), p=q))
                    qs.append(q)
                toks.append(d)
                tok[slot] = d
            pos = pos + 1
        if self._tel and eligible and K > 1:
            _telemetry.count("spec.draft_steps", K - 1)
        return props

    def _spec_propose_ngram(self, K):
        """Model-free self-drafting: propose the continuation that
        followed the most recent earlier occurrence of the sequence's
        current suffix (generate.ngram_propose — longest-match lookup,
        pure host work, zero extra FLOPs).  Misses propose nothing:
        the slot still takes row 0 of the shared verify step, exactly
        one token — plain-decode behavior at plain-decode cost."""
        props = {}
        hits = miss = 0
        for slot, st in self._slots.items():
            if st.get("spec_off"):
                continue
            base = st.get("base", len(st["prompt"]))
            seq = st["prompt"][:base] + st["generated"]
            d = generate.ngram_propose(seq, K - 1) if K > 1 else None
            if d:
                props[slot] = (d, None)
                hits += 1
            else:
                miss += 1
        if self._tel:
            if hits:
                _telemetry.count("spec.ngram_hits", hits)
            if miss:
                _telemetry.count("spec.ngram_misses", miss)
        return props

    def _spec_accept(self, st, rows, prop):
        """Resolve one slot's verify logits [K, V] against its proposal
        -> the token list (1..K) this round appends.  Greedy: accept
        the longest prefix where the target's argmax agrees with the
        draft, append the target's own choice at the first disagreement
        (the correction IS the plain-decode token), and on full
        agreement keep the bonus row — every kept token equals what
        stepwise greedy decode would produce at that position given the
        same prefix, which is the bit-parity argument.  Sampled:
        delegated rejection sampling (_spec_sampled_tokens)."""
        draft, qs = prop if prop is not None else ([], None)
        kk = len(draft)
        if st.get("temperature", 0.0) > 0.0:
            toks, accepted = self._spec_sampled_tokens(st, rows, draft,
                                                       qs)
        else:
            tchoice = rows.argmax(axis=-1)
            toks, accepted = [], 0
            for j in range(kk):
                t = int(tchoice[j])
                toks.append(t)
                if t != draft[j]:
                    break
                accepted += 1
            else:
                toks.append(int(tchoice[kk]))
        if kk:
            self._spec_prop += kk
            self._spec_acc += accepted
            st["spec_prop"] = st.get("spec_prop", 0) + kk
            st["spec_acc"] = st.get("spec_acc", 0) + accepted
            if self._tel:
                _telemetry.count("spec.proposed", kk)
                if accepted:
                    _telemetry.count("spec.accepted", accepted)
        return toks

    def _spec_sampled_tokens(self, st, rows, draft, qs):
        """Leviathan rejection sampling on one slot's verify rows:
        accept draft x_j with prob min(1, p_j(x)/q_j(x)); the first
        rejection resamples the residual (p - q)+ — self-draft's q is
        the point mass at x, so the residual is p with p[x] zeroed —
        and full acceptance draws the bonus row.  Marginals equal
        plain sampled decode (speculative_generate's law;
        test_speculative.py's chi-square, re-checked at batch>1 by the
        serving tests)."""
        t, tk, tp = st["temperature"], st["top_k"], st["top_p"]
        rng = self._spec_rng(st)
        toks, accepted = [], 0
        for j, x in enumerate(draft):
            p = generate._filtered_probs(rows[j], t, tk, tp)
            qx = float(qs[j][x]) if qs is not None else 1.0
            if float(rng.uniform()) < min(
                    1.0, float(p[x]) / max(qx, 1e-300)):
                toks.append(int(x))
                accepted += 1
                continue
            if qs is not None:
                resid = np.maximum(p - qs[j], 0.0)
            else:
                resid = p.copy()
                resid[x] = 0.0
            mass = float(resid.sum())
            if mass > 0.0:
                toks.append(int(rng.choice(len(resid),
                                           p=resid / mass)))
            else:
                toks.append(int(rng.choice(len(p), p=p)))
            break
        else:
            p = generate._filtered_probs(rows[len(draft)], t, tk, tp)
            toks.append(int(rng.choice(len(p), p=p)))
        return toks, accepted

    def _spec_fallback_check(self, st):
        """Acceptance-driven fallback: a slot whose rolling accept rate
        sits below PADDLE_TPU_SPEC_MIN_ACCEPT after a fair trial stops
        speculating (row-0-only rounds — still bit-correct, no longer
        paying proposal work).  The window decays by halving so the
        rate tracks the request's RECENT regime, not its whole
        history.

        The window's unit is the ACCEPTED-PATH LENGTH a round could
        have delivered — K-1 drafted tokens in linear mode, the
        deepest live root-to-leaf path in tree mode — not the raw
        linear K, so tree-mode slots fall back (and later re-earn, see
        _spec_ready) on exactly the same accept-rate contract."""
        if st.get("spec_off") or not st.get("spec_prop"):
            return
        k = max(1, (self._spec_tree_n or self._spec_k) - 1)
        if st["spec_prop"] >= 16 * k:
            st["spec_prop"] //= 2
            st["spec_acc"] //= 2
        if st["spec_prop"] >= 4 * k \
                and st["spec_acc"] / st["spec_prop"] < self._min_accept:
            st["spec_off"] = True
            # next re-earn waits twice as long as the last one did
            st["spec_cool"] = cool = min(256,
                                         2 * st.get("spec_cool0", 8))
            st["spec_cool0"] = cool
            if self._tel:
                _telemetry.count("spec.fallbacks")

    def _tick_spec(self):
        """One speculative round: propose (host n-gram lookup or K-1
        batched draft steps), ONE batched target verify over every
        slot, host-side acceptance, retire.  The verify is the round's
        only target pass — up to K tokens per slot for one pass, the
        multiplier the tests count as target passes.  Rejected verify rows
        land at/past each slot's new position pointer where the
        stale-row invariant already hides them (the same rule as
        warmup garbage and slot reuse), so acceptance needs no masked
        write and no rollback: after a rejection the next round's
        writes start exactly at the first stale row."""
        if self._spec_tree_n:
            return self._tick_spec_tree()
        if self._inflight is not None:
            # async servers run spec rounds synchronously: the pending
            # dispatch's tokens are real work — fetch them first
            self._drain_inflight()
            if not self._slots:
                return
        t0 = time.perf_counter()
        K = self._spec_k
        # rows [pos, pos+K) per slot, BEFORE any state mutates: a
        # PoolExhausted surfaces here and the OOM chain's retry re-runs
        # the round bit-exactly (greedy) / unbiasedly (sampled)
        self._ensure_decode_blocks(K)
        if self._self_draft:
            props = self._spec_propose_ngram(K)
        else:
            props = self._spec_propose_draft(K)
        tok, pos = self._feed_arrays()
        tok = np.repeat(tok[:, None], K, axis=1)
        for slot, (draft, _) in props.items():
            for j, d in enumerate(draft[:K - 1]):
                tok[slot, j + 1] = d
        if self._adapters is not None:
            # the verify pass gathers the SAME per-slot adapter the
            # decode step uses — acceptance compares draft tokens
            # against the ADAPTED target's argmax/law, so accepted
            # tokens are exactly what plain adapted stepping emits.
            # The (base-model) draft only moves the acceptance RATE.
            kind = f"adapter_spec_verify@{K}"
            self._fault_check(kind)
            fn = _get_adapter_spec_verify_fn(
                self.cfg, K, self._adapters.pool_key(), self._paged,
                self._shard)
            logits, self.cache = fn(
                self.params, self.cache, self._adapters.stacks(),
                jnp.asarray(self._gather_adapter_ids()),
                jnp.asarray(tok), jnp.asarray(pos))
        else:
            kind = f"spec_verify@{K}"
            self._fault_check(kind)
            fn = _get_spec_verify_fn(self.cfg, K, self._paged,
                                     self._shard)
            logits, self.cache = fn(self.params, self.cache,
                                    jnp.asarray(tok), jnp.asarray(pos))
        self._step_no += 1   # after the call: see _tick_impl
        self._spec_rounds += 1
        lnp = np.asarray(logits)   # the round's ONE device->host fetch
        failed = []
        if self._resil and (_faults.active()
                            or _os.environ.get(
                                "PADDLE_TPU_NAN_GUARD_SERVING",
                                "") == "1"):  # noqa: E129
            if _faults.active():
                lnp = _faults.corrupt_nan("logits", lnp)
            finite = np.isfinite(lnp).all(axis=(-2, -1))
            failed = [s for s in self._slots if not finite[s]]
        done = []
        appended = []
        for slot, st in self._slots.items():
            if slot in failed:
                continue
            toks = self._spec_accept(st, lnp[slot], props.get(slot))
            old = st["pos"]
            kept = 0
            for t in toks:
                st["generated"].append(t)
                st["pos"] += 1
                kept += 1
                if self._finished(st, t):
                    done.append(slot)
                    break
            appended.append((st, kept))
            if self._draft_cache is not None \
                    and not st.get("spec_off"):
                # draft rows [old, old+K-1) were fed this round; the
                # prefix fed ACCEPTED (real) tokens is valid through
                # the new position, capped at the last drafted row —
                # catch-up re-feeds anything past the cap next round
                st["spec_dpos"] = min(st["pos"], old + K - 1)
            self._spec_fallback_check(st)
        for slot in failed:
            st = self._slots.pop(slot)
            self._fail_request(st, slot, "non-finite spec-verify logits")
        steps = max([kept for _, kept in appended], default=1)
        with self._phase("book") as ph:
            self._book(ph, appended, done, t0, steps=max(steps, 1),
                       kind=kind)
        self._refill()

    # -- draft-tree speculation: one verify pass over a token tree ----------

    def _spec_tree_propose(self):
        """Build each eligible slot's proposal tree.

        Returns {slot: tree}, where a tree is a dict with ``tokens``
        (index 0 is the ROOT — the feed token, already fed, so its
        entry is None), ``parent`` (parent[0] == -1, topological
        order), ``depth``, ``live`` (False == pruned, the node stays
        in the dispatched arrays but no acceptance path may use it),
        ``children`` ({node: [live kids, proposal order]}), and in
        draft mode ``trunk``/``dsteps``/``qs`` (the draft's base law
        per depth, for the sampled acceptance test).

        Self-draft: :func:`generate.ngram_propose_tree` merges up to
        ``branch`` DISTINCT n-gram continuations into one prefix trie.
        Draft mode: :meth:`_spec_tree_propose_draft` lays a trunk and
        fans siblings out at the draft's least-confident positions.
        Constrained slots then get grammar-forbidden subtrees pruned
        BEFORE the verify pass — the tree dispatched for them carries
        only tokens their automaton allows."""
        N = self._spec_tree_n
        b = max(1, min(self._spec_branch, N - 1))
        if self._self_draft:
            props = {}
            hits = miss = 0
            for slot, st in self._slots.items():
                if st.get("spec_off"):
                    continue
                base = st.get("base", len(st["prompt"]))
                seq = st["prompt"][:base] + st["generated"]
                t = generate.ngram_propose_tree(seq, N, branch=b)
                if t is not None:
                    props[slot] = {"tokens": list(t[0]),
                                   "parent": list(t[1])}
                    hits += 1
                else:
                    miss += 1
            if self._tel and hits:
                _telemetry.count("spec.ngram_hits", hits)
            if self._tel and miss:
                _telemetry.count("spec.ngram_misses", miss)
        else:
            props = self._spec_tree_propose_draft(N, b)
        total = 0
        for slot, tp in props.items():
            n = len(tp["tokens"])
            tp["depth"] = generate.tree_depths(tp["parent"])
            tp["live"] = [True] * n
            total += n - 1
            st = self._slots[slot]
            if st.get("constraint") is not None:
                self._prune_branches_constrained(st, tp)
            kids: dict = {}
            for j in range(1, n):
                if tp["live"][j]:
                    kids.setdefault(tp["parent"][j], []).append(j)
            tp["children"] = kids
        if self._tel and total:
            _telemetry.count("spec.tree_nodes_proposed", total)
        return props

    def _spec_tree_propose_draft(self, N, b):
        """Draft-model tree proposals: D = ceil((N-1)/b) batched draft
        steps lay a TRUNK (greedy: the draft's argmax chain; sampled:
        draws from its filtered law q, recorded for the acceptance
        test), then the remaining N-1-D node slots fan out as sibling
        leaves at the trunk positions where the draft was LEAST sure
        (smallest top-1/top-2 margin greedy, smallest chosen-token
        probability sampled) — branching exactly where linear
        speculation actually dies.  Greedy siblings take the draft's
        top-2..b tokens; sampled siblings are drawn from q WITHOUT
        replacement, so child i+1 at a node is distributed as the
        i-times-rejection-renormalized law the SpecInfer acceptance
        chain (_spec_tree_sampled) replays.  Counts spec.draft_steps
        once per batched draft dispatch, like the linear path."""
        self._spec_draft_catchup()
        step = _get_step_fn(self.draft_cfg, self._paged,
                            self._draft_shard)
        tok, pos = self._feed_arrays()
        temp, tk, tp_ = self._sampling_arrays()
        eligible = {slot: st for slot, st in self._slots.items()
                    if not st.get("spec_off")}
        D = max(1, -(-(N - 1) // b))
        rec = {slot: {"trunk": [], "alts": [], "margins": [],
                      "qs": [] if temp[slot] > 0 else None}
               for slot in eligible}
        for _ in range(D):
            logits, self._draft_cache = step(
                self._draft_params, self._draft_cache,
                jnp.asarray(tok), jnp.asarray(pos))
            if self._tel:
                _telemetry.count("spec.draft_steps")
            lnp = np.asarray(logits)
            for slot, st in eligible.items():
                r = rec[slot]
                row = lnp[slot]
                if r["qs"] is None:
                    order = np.argsort(row)[::-1][:max(b, 2)]
                    d = int(order[0])
                    alts = [int(x) for x in order[1:b]]
                    r["margins"].append(
                        float(row[order[0]] - row[order[1]]))
                else:
                    q = generate._filtered_probs(
                        row, float(temp[slot]), int(tk[slot]),
                        float(tp_[slot]))
                    rng = self._spec_rng(st)
                    d = int(rng.choice(len(q), p=q))
                    r["qs"].append(q)
                    alts = []
                    qq = q.copy()
                    last = d
                    for _a in range(b - 1):
                        qq[last] = 0.0
                        m = float(qq.sum())
                        if m <= 0.0:
                            break
                        last = int(rng.choice(len(qq), p=qq / m))
                        alts.append(last)
                    # low chosen-prob == much residual mass elsewhere
                    r["margins"].append(float(q[d]))
                r["trunk"].append(d)
                r["alts"].append(alts)
                tok[slot] = d
            pos = pos + 1
        props = {}
        for slot, r in rec.items():
            toks: list = [None]
            parent = [-1]
            for i, t in enumerate(r["trunk"]):
                toks.append(int(t))
                parent.append(i)      # trunk node i+1 sits at depth i+1
            budget = N - 1 - len(r["trunk"])
            order = np.argsort(np.asarray(r["margins"], np.float64),
                               kind="stable")
            for i in order:
                if budget <= 0:
                    break
                for a in r["alts"][int(i)]:
                    if budget <= 0:
                        break
                    if a == r["trunk"][int(i)]:
                        continue
                    toks.append(int(a))
                    parent.append(int(i))   # sibling of trunk node i+1
                    budget -= 1
            props[slot] = {"tokens": toks, "parent": parent,
                           "trunk": [int(t) for t in r["trunk"]],
                           "dsteps": len(r["trunk"]),
                           "qs": r["qs"]}
        return props

    def _prune_branches_constrained(self, st, tp):
        """Host DFA lookahead over one slot's proposed tree BEFORE the
        verify pass: walk :func:`adapters.constraint_lookahead` cursors
        down the trie from the request's CURRENT automaton state (never
        mutated — acceptance advances the real state through
        _constraint_push like every other path) and mark every node
        whose token the grammar forbids — plus its whole subtree —
        dead.  Pruned nodes still occupy rows in the compiled dispatch
        (shapes are trace keys), but they leave the host-side candidate
        set, so no acceptance path can emit a banned token and
        ``constraint.spec_fallbacks`` stays untouched in tree mode."""
        from . import adapters as _ad

        cst = st.get("constraint")
        tokens, parent, live = tp["tokens"], tp["parent"], tp["live"]
        cursors = {0: _ad.constraint_lookahead(cst)}
        pruned = 0
        for j in range(1, len(tokens)):
            pl = cursors.get(parent[j])
            if pl is None or not pl.allows(tokens[j]):
                live[j] = False       # parent dead, or token banned
                pruned += 1
                continue
            cursors[j] = pl.child(tokens[j])
        if pruned and self._tel:
            _telemetry.count("spec.tree_pruned_constrained", pruned)

    def _spec_tree_accept(self, st, rows, tp):
        """Resolve one slot's tree-verify logits [N, V] into the token
        list this round appends plus the accepted node-index path.

        Greedy: walk from the root; each visited node's target row
        (constraint-masked for constrained slots — np.where over the
        same fp32 values the masked plain step argmaxes, so every
        appended token equals stepwise masked greedy decode on the
        same prefix) yields an argmax; descend into the live child
        carrying that token.  The first miss appends the target's own
        choice — the "correction" IS the plain-decode token — and a
        leaf's choice is the bonus, so the walk always emits at least
        one token (the plain-decode floor).  Sampled: SpecInfer
        sequential multi-child rejection per node
        (:meth:`_spec_tree_sampled`) preserves the target law exactly.

        Constrained automata are NOT advanced here: the lookahead
        cursor only shapes masks; the tick loop pushes every appended
        token through _constraint_push exactly like the plain path.
        The rolling fallback window advances in PATH-LENGTH units —
        proposed = the deepest live root-to-leaf depth this round
        offered, accepted = edges actually taken."""
        from . import adapters as _ad

        if tp is None:
            tokens: list = [None]
            depth = [0]
            children: dict = {}
            live = [True]
            qs = None
        else:
            tokens, depth = tp["tokens"], tp["depth"]
            children, live = tp["children"], tp["live"]
            qs = tp.get("qs")
        cst = st.get("constraint")
        look = (_ad.constraint_lookahead(cst)
                if cst is not None else None)
        sampled = st.get("temperature", 0.0) > 0.0
        cur = 0
        toks: list = []
        sel: list = []
        while True:
            if look is not None and look.exhausted:
                break                 # automaton completed mid-path
            row = rows[cur]
            if look is not None:
                row = _ad.apply_constraint_host(row, look)
            kids = children.get(cur, [])
            if sampled:
                t, child = self._spec_tree_sampled(st, row, kids,
                                                   tokens, depth, qs,
                                                   look)
            else:
                t = int(row.argmax())
                child = next((j for j in kids if tokens[j] == t), None)
            toks.append(t)
            if look is not None:
                look = look.child(t)
            if child is None:
                break
            sel.append(child)
            cur = child
        maxd = max((int(depth[j]) for j in range(len(tokens))
                    if live[j]), default=0)
        if maxd:
            self._spec_prop += maxd
            self._spec_acc += len(sel)
            st["spec_prop"] = st.get("spec_prop", 0) + maxd
            st["spec_acc"] = st.get("spec_acc", 0) + len(sel)
            if self._tel:
                _telemetry.count("spec.proposed", maxd)
                if sel:
                    _telemetry.count("spec.accepted", len(sel))
        if self._tel and sel:
            _telemetry.count("spec.tree_nodes_accepted", len(sel))
        self._tree_path_sum += len(sel)
        self._tree_path_cnt += 1
        return toks, sel

    def _spec_tree_sampled(self, st, row, kids, tokens, depth, qs,
                           look):
        """SpecInfer-style sequential multi-candidate rejection at ONE
        tree node: children x_1..x_m (proposal order) are tested in
        turn against the target law p — accept x_i with probability
        min(1, p(x_i)/q_i(x_i)), where q_1 is the draft's base law at
        this depth and every rejection updates BOTH sides: p becomes
        norm((p - q_i)+) and q_{i+1} becomes norm(q_i with x_i zeroed),
        the very law the proposer drew x_{i+1} from (without-
        replacement draws).  All children rejected -> sample the final
        residual (the correction); no children -> sample p (the
        bonus).  Telescoping the per-child terms shows every emitted
        token is distributed exactly as p — the single-child case
        reduces to the linear path's Leviathan test bit-for-bit.

        Self-draft trees carry no qs: each child is a POINT MASS
        (q_i = 1 at x_i), so accept with probability p(x_i) and zero
        x_i out of the residual — exact for ANY proposal choice, which
        is what the constraint-pruned trie rides on.  Constrained
        draft-model slots condition q on the automaton mask (the
        proposal survived pruning, so its law GIVEN survival is q
        restricted to the allowed set, renormalized) while p is
        already the masked filtered law — the masked target law is
        preserved exactly.  Returns (token, accepted child or None)."""
        rng = self._spec_rng(st)
        p = generate._filtered_probs(row, float(st["temperature"]),
                                     int(st["top_k"]),
                                     float(st["top_p"]))
        p0 = p
        q = None
        if qs is not None and kids:
            q = np.asarray(qs[int(depth[kids[0]]) - 1], np.float64)
            if look is not None:
                q = q * look.allowed_mask()
                m = float(q.sum())
                q = q / m if m > 0.0 else None
        for x_node in kids:
            x = tokens[x_node]
            if qs is not None:
                if q is None:
                    break             # proposer's law exhausted
                qx = float(q[x])
                if qx <= 0.0:
                    continue          # proposer can't have drawn this
                if float(rng.uniform()) < min(1.0, float(p[x]) / qx):
                    return int(x), x_node
                p = np.maximum(p - q, 0.0)
                pm = float(p.sum())
                q = q.copy()
                q[x] = 0.0
                qm = float(q.sum())
                q = q / qm if qm > 0.0 else None
                if pm <= 0.0:
                    p = None
                    break
                p = p / pm
            else:
                if float(rng.uniform()) < float(p[x]):
                    return int(x), x_node
                p = p.copy()
                p[x] = 0.0
                pm = float(p.sum())
                if pm <= 0.0:
                    p = None
                    break
                p = p / pm
        if p is None:
            # numerically empty residual: fall back to the target law
            # itself, as the linear sampled path does
            p = p0
        return int(rng.choice(len(p), p=p)), None

    def _tick_spec_tree(self):
        """One TREE speculative round: propose a token tree per slot,
        prune grammar-forbidden branches, ONE tree-masked target pass
        over all slots, host best-path acceptance, a KV row permute
        for paths that left the trunk, retire.  Same skeleton as
        _tick_spec — one target pass per round is the headline metric
        — but acceptance can follow BRANCHES, so a single pass keeps
        tokens a linear draft of the same row budget loses at its
        first divergence.  The ancestor mask and depths are runtime
        arguments: topology changes round to round, the compiled
        executable keys only on the node COUNT."""
        if self._inflight is not None:
            self._drain_inflight()
            if not self._slots:
                return
        t0 = time.perf_counter()
        N = self._spec_tree_n
        # rows [pos, pos+N) per slot BEFORE any state mutates (the OOM
        # retry rule); the commit permute writes inside [pos+1, pos+N)
        # — covered by the same reservation
        self._ensure_decode_blocks(N)
        props = self._spec_tree_propose()
        tok, pos = self._feed_arrays()
        tokN = np.repeat(tok[:, None], N, axis=1)
        amask = np.zeros((self.max_batch, N, N), bool)
        amask[:, np.arange(N), np.arange(N)] = True  # idle rows: self
        depth = np.zeros((self.max_batch, N), np.int32)
        for slot, tp in props.items():
            n = len(tp["tokens"])
            for j in range(1, n):
                tokN[slot, j] = tp["tokens"][j]
            amask[slot, :n, :n] = generate.tree_ancestor_mask(
                tp["parent"])
            depth[slot, :n] = tp["depth"]
        kind = f"spec_tree_verify@{N}"
        self._fault_check(kind)
        fn = _get_spec_tree_verify_fn(self.cfg, N, self._paged,
                                      self._shard)
        logits, self.cache = fn(self.params, self.cache,
                                jnp.asarray(tokN), jnp.asarray(amask),
                                jnp.asarray(depth), jnp.asarray(pos))
        self._step_no += 1
        self._spec_rounds += 1
        if self._tel:
            _telemetry.count("spec.tree_rounds")
        lnp = np.asarray(logits)  # the round's one device->host fetch
        failed = []
        if self._resil and (_faults.active()
                            or _os.environ.get(
                                "PADDLE_TPU_NAN_GUARD_SERVING",
                                "") == "1"):  # noqa: E129
            if _faults.active():
                lnp = _faults.corrupt_nan("logits", lnp)
            finite = np.isfinite(lnp).all(axis=(-2, -1))
            failed = [s for s in self._slots if not finite[s]]
        done = []
        appended = []
        commit_src = None
        for slot, st in self._slots.items():
            if slot in failed:
                continue
            toks, sel = self._spec_tree_accept(st, lnp[slot],
                                               props.get(slot))
            if sel and any(s != i + 1 for i, s in enumerate(sel)):
                # the accepted path left the trunk: permute its rows
                # into the contiguous committed positions.  Trunk(-
                # prefix) acceptances skip this — the proposer lays the
                # trunk at node indices 1..D, already the committed
                # layout — so pure-chain trees never dispatch a commit
                if commit_src is None:
                    commit_src = np.tile(
                        np.arange(1, N, dtype=np.int32),
                        (self.max_batch, 1))
                commit_src[slot, :len(sel)] = sel
            old = st["pos"]
            kept = 0
            for t in toks:
                st["generated"].append(t)
                st["pos"] += 1
                kept += 1
                fin = self._constraint_push(st, t)
                if self._finished(st, t) or fin:
                    done.append(slot)
                    break
            appended.append((st, kept))
            if self._draft_cache is not None \
                    and not st.get("spec_off"):
                # draft rows [old, old+D) were fed feed+trunk tokens
                # this round; they stay valid through the committed
                # prefix that AGREES with the trunk (a branch
                # acceptance diverges earlier than a linear round's
                # cap) — catch-up re-feeds the rest next round
                tp = props.get(slot, {})
                trunk = tp.get("trunk", [])
                agree = 0
                for a, bt in zip(toks, trunk):
                    if a != bt:
                        break
                    agree += 1
                dsteps = tp.get("dsteps", 1)
                st["spec_dpos"] = min(
                    st["pos"], old + 1 + min(agree, dsteps - 1))
            self._spec_fallback_check(st)
        if commit_src is not None:
            # dispatched with the PRE-ROUND pos array: failed slots
            # keep identity rows, accepted slots permute [pos+1, ...)
            cfn = _get_spec_tree_commit_fn(self.cfg, N, self._paged,
                                           self._shard)
            self.cache = cfn(self.cache, jnp.asarray(commit_src),
                             jnp.asarray(pos))
        for slot in failed:
            st = self._slots.pop(slot)
            self._fail_request(st, slot,
                               "non-finite spec-tree-verify logits")
        steps = max([kept for _, kept in appended], default=1)
        with self._phase("book") as ph:
            self._book(ph, appended, done, t0, steps=max(steps, 1),
                       kind=kind)
        self._refill()

    def close(self):
        """Release this server's compiled executables and KV cache.

        UNFINISHED requests (queued or mid-generation) are ABANDONED:
        their rids are remembered and ``result()`` raises a descriptive
        error for them — call only when the server is drained or the
        pending work is disposable.  The jit caches key by config VALUE,
        so entries may be shared with another live server of the same
        config — that server transparently recompiles on its next tick
        (correctness is unaffected; the cache exists to avoid recompiles,
        not to carry state).  The LRU bound on _STEP_CACHE already caps
        growth; close() is for eagerly dropping a cycled-out model's
        executables (and their implicit param refs).

        Shutdown hardening: the in-flight async dispatch is CANCELLED
        (its device tokens are never fetched — a wedged step cannot hang
        interpreter exit), the metrics HTTP server thread is joined with
        a bound, and a runtime-wedge verdict this server raised is
        cleared so a later server's /healthz starts clean.  Idempotent."""
        if self._wedged:
            self._wedged = False
            _telemetry.clear_runtime_wedge()
        if self._moe_stats is not None or self._experts:
            # publish the final routing totals before the accumulator
            # (and its device buffer) is dropped with the executables
            try:
                if self._experts:
                    self._drain_share_counts()
                else:
                    self._moe_snapshot()
            except Exception:
                pass    # a wedged device must not block shutdown
            self._moe_stats = None
        if self.metrics_server is not None:
            self.metrics_server.close()   # joins the serve thread
            self.metrics_server = None
        # one pass over the Engine's OWN caches: every family (plain,
        # adapter_*, draft twins, generate-side executables) whose key
        # embeds either cfg drops — a new registry kind can't leak
        _engine.ENGINE.purge(self.cfg, self.draft_cfg)
        self.cache = None
        self._draft_cache = None
        self._step = None
        self._prefill = None
        self._prefill_chunk = None
        self._inflight = None
        for st in self._slots.values():
            self._dropped.add(st["rid"])
        for req in self._queue:
            self._dropped.add(req["rid"])
        self._slots.clear()
        self._queue.clear()
        if self._paged and self._pool is not None:
            self._pool.close()

    def shutdown(self):
        """Alias for :meth:`close` (the serving-fleet idiom): cancel
        in-flight work, join the metrics thread, drop executables."""
        self.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def result(self, rid: int):
        """Generated tokens (no prompt) once the request finished.

        A request shed past its deadline raises
        ``resilience.DeadlineExceeded``; one rejected by admission
        control raises ``resilience.Overloaded`` (it never queued —
        back off and resubmit); one failed by the NaN guard or the
        evict-requeue bound raises ``RuntimeError`` — in all cases the
        request retired CLEANLY (slot freed, server alive) and
        :meth:`status` reports the disposition without raising."""
        if rid in self._dropped:
            raise RuntimeError(
                f"request {rid} was abandoned unfinished when the server "
                f"was closed")
        disp = self._status.get(rid)
        if disp == "timeout":
            raise _resilience.DeadlineExceeded(
                f"request {rid} was shed: still queued past its ttl")
        if disp == "rejected":
            raise _resilience.Overloaded(
                f"request {rid} was rejected by admission control "
                f"(rate limit, queue bound, or overload shed) — it "
                f"never queued; back off and resubmit")
        if disp == "error":
            raise RuntimeError(
                f"request {rid} failed: "
                f"{self._err_reason.get(rid, 'non-finite logits')} "
                f"(the request was retired cleanly; the server is "
                f"still serving)")
        return self._results[rid]

    def status(self, rid: int) -> str:
        """One of ``ok`` (result ready), ``timeout`` (deadline shed),
        ``rejected`` (admission control refused it at the door),
        ``error`` (NaN guard / evict-requeue bound), ``dropped``
        (abandoned by close), ``active`` (decoding), ``queued``."""
        if rid in self._results:
            return "ok"
        disp = self._status.get(rid)
        if disp is not None:
            return disp
        if rid in self._dropped:
            return "dropped"
        if any(st["rid"] == rid for st in self._slots.values()) \
                or (self._inflight is not None
                    and any(st["rid"] == rid
                            for _, st, _ in self._inflight["snap"])):
            return "active"
        if any(req["rid"] == rid for req in self._queue):
            return "queued"
        raise KeyError(f"unknown request id {rid}")

    # -- fleet surface: load, health, queue drain (text/fleet.py) -----------

    @property
    def wedged(self) -> bool:
        """The resilience watchdog's live verdict for THIS server (the
        fleet router's per-replica health bit; the process-global
        telemetry wedge state folds every server's verdict)."""
        return self._wedged

    def load_stats(self, include_spans: bool = False) -> dict:
        """The router's load-balancing inputs, read from the scheduler's
        host state — the SAME quantities the telemetry gauges sample
        (queue depth, active slots, slot occupancy, kv utilization),
        returned per server because the registry gauges are
        process-global and a fleet co-hosts many replicas.

        ``include_spans=True`` additionally drains this server's
        completed trace spans (DESTRUCTIVE, piggyback-capped) into
        ``spans``/``span_drops`` — the fleet router's collection ride;
        anything else polling load should leave it off."""
        act = len(self._slots)
        if self._paged:
            kv = self._pool.blocks_in_use / max(1, self._pool.N)
        else:
            rows = (int(self.cache["k"].shape[2])
                    if self.cache is not None else self.max_len)
            kv = sum(min(st["pos"], rows)
                     for st in self._slots.values()) \
                / (self.max_batch * rows)
        eff_cap = self._admit_cap
        if self._adm is not None:
            eff_cap = min(eff_cap,
                          self._adm.effective_admit_cap(self.max_batch))
        ad_active: dict[str, int] = {}
        if self._adapters is not None:
            for st in self._slots.values():
                nm = st.get("adapter_name") or "base"
                ad_active[nm] = ad_active.get(nm, 0) + 1
        return {
            "queue_depth": len(self._queue),
            "active_slots": act,
            "free_slots": min(len(self._free),
                              max(0, eff_cap - act)),
            "slot_occupancy": act / self.max_batch,
            "kv_utilization": kv,
            "admit_cap": self._admit_cap,
            "wedged": self._wedged,
            # budgeted admission: slots mid-prefill (their chunks eat
            # round budget) and the configured budget itself — a router
            # can prefer replicas with admission headroom
            "admitting_slots": sum(
                1 for st in self._slots.values()
                if st.get("admitting")),
            "prefill_budget": self._budget,
            # server-wide rolling acceptance (None until the first
            # proposal is scored) — the router's signal for whether
            # this replica's speculation is paying for itself
            "spec_accept_rate": ((self._spec_acc / self._spec_prop)
                                 if self._spec_prop else None),
            # tree mode: mean accepted root-to-leaf path length per
            # verify round (tokens committed beyond the plain-decode
            # floor ≈ this value) — None off tree mode / before the
            # first round
            "spec_tree_accept_len": (
                (self._tree_path_sum / self._tree_path_cnt)
                if self._tree_path_cnt else None),
            # admission-control verdict: the degradation ladder rung
            # (0 = healthy) — the fleet router folds the worst replica
            # rung into its OWN controller (absorb_fleet_rung) and
            # sheds at the front door instead of stacking queues
            "admission_rung": (0 if self._adm is None
                               else self._adm.rung),
            "slo_ok": self._adm is None or self._adm.rung == 0,
            # multi-tenant serving: slots decoding under a constraint
            # automaton (always present) and, with an adapter pool,
            # per-adapter active-slot counts — the same numbers the
            # adapters.active{adapter=} gauges sample, surfaced per
            # server so the fleet router's docs can point at them
            "constrained_slots": sum(
                1 for st in self._slots.values()
                if st.get("constraint") is not None),
            **({"adapters_active": ad_active}
               if self._adapters is not None else {}),
            # prefix-cache surface (paged only): the hit-rate gauge
            # (fraction of adoptable rows admission did NOT recompute),
            # the compact radix summary prefix-aware routing scores
            # overlap against, and the host spill tier's footprint
            **({"prefix_hit_rate": (
                    self._pool.prefix_hits
                    / max(1, self._pool.prefix_hits
                          + self._pool.prefix_misses)),
                "prefix_summary": self._pool.prefix_summary(),
                "host_spill_bytes": self._pool.host_spill_bytes}
               if self._paged else {}),
            # MoE serving: the device accumulator's honest routing
            # totals — cumulative dropped token→expert assignments and
            # per-expert kept load (the drain also advances the
            # moe.dropped_tokens counter / expert-load gauges).  The
            # fetch blocks on the in-flight step's stats future; the
            # scheduler's own ticks never pay it.
            **(dict(zip(("moe_dropped_tokens", "moe_expert_load"),
                        self._moe_snapshot()))
               if self._moe_stats is not None else {}),
            # an expert share's selections by where they went, since the
            # server was built (the drain publishes the moe.* telemetry)
            **(self._drain_share_counts() if self._experts else {}),
            # fleet tracing: spans ride the stats collection when asked
            **(dict(zip(("spans", "span_drops"), self.drain_spans()))
               if include_spans else {}),
        }

    def drain_spans(self):
        """Destructively take this server's completed trace spans (the
        piggyback cap bounds one take) plus the drop count since the
        last take — what ``load_stats(include_spans=True)`` rides; the
        fleet router calls it directly each collection round."""
        return self._span_ring.drain(_flags.trace_piggyback_cap())

    def local_snapshot(self) -> dict:
        """This SERVER's latency distributions as JSON-safe
        :meth:`telemetry.Histogram.state` dicts keyed by histogram name
        — the fleet metrics plane's merge inputs.  Distinct from the
        process-global ``telemetry.snapshot()``: loopback fleets co-host
        replicas, so per-replica distributions need per-server buckets.
        ``counters`` carries the per-server token/request totals the
        fleet rollups aggregate."""
        return {
            "histograms": {name: h.state()
                           for name, h in sorted(
                               self._hist_local.items())},
            "counters": dict(sorted(self._counts_local.items())),
        }

    def _observe(self, name: str, v: float, n: int = 1) -> None:
        """Observe into the process-global histogram AND this server's
        local twin (see :meth:`local_snapshot`).  Call sites already
        gate on ``self._tel``."""
        _telemetry.observe(name, v, n)
        h = self._hist_local.get(name)
        if h is None:
            h = self._hist_local[name] = _telemetry.Histogram(name)
        h.observe(v, n)

    def _count_local(self, name: str, n: int = 1) -> None:
        """Per-server counter twin of ``telemetry.count`` (same
        loopback-fleet rationale as :meth:`_observe`)."""
        self._counts_local[name] = self._counts_local.get(name, 0) + n

    def drain_queue(self, rids=None) -> list:
        """Remove and return QUEUED request dicts (the fleet router's
        wedge-drain path: a wedged replica's queued work is re-routed
        to healthy replicas via :meth:`adopt_request`; its ACTIVE slots
        keep decoding here — their device work is already paid for and
        the wedge recovery replays it bit-exactly).

        ``rids`` restricts the drain to those request ids: the router
        passes the set it owns, so a request submitted DIRECTLY to this
        server (whose rid only the direct submitter holds) stays queued
        through the drain instead of vanishing."""
        if rids is None:
            out, self._queue[:] = list(self._queue), []
        else:
            out = [r for r in self._queue if r["rid"] in rids]
            self._queue[:] = [r for r in self._queue
                              if r["rid"] not in rids]
        for r in out:
            # a drained stream request leaves with its rid: the open
            # stream record dies here (the drainer fails the request
            # at the fleet level; late chunks would KeyError honestly)
            if r.get("stream"):
                self._streams.pop(r["rid"], None)
        if out and self._tel:
            _telemetry.count("serving.queue_drained", len(out))
        self._tel_gauges()
        return out

    # -- one tick: a single batched device step -----------------------------

    def _feed_arrays(self):
        """The batched (tok, pos) feed for the current slots: the token
        fed at position i is sequence[i] — prompt while i is inside it,
        the generated tail after.

        Donation audit: this (and every host-side helper here) reads
        only the per-slot HOST state (prompt/generated/pos lists) —
        never the device cache, whose buffers the jitted steps donate
        and whose old generations are therefore deleted.  The only
        device arrays the server retains are ``self.cache`` (always the
        newest, reassigned at every step) and the async in-flight token
        array (an output, never donated)."""
        tok = np.zeros((self.max_batch,), np.int32)
        pos = np.zeros((self.max_batch,), np.int32)
        for slot, st in self._slots.items():
            i = st["pos"]
            np_ = len(st["prompt"])
            # base = original prompt length (differs from len(prompt)
            # only for OOM-evicted re-admissions, whose carried tokens
            # live in both the extended prompt and generated)
            base = st.get("base", np_)
            tok[slot] = (st["prompt"][i] if i < np_
                         else st["generated"][i - base])
            pos[slot] = i
        return tok, pos

    def _finished(self, st, t: int) -> bool:
        return (len(st["generated"]) >= st["max_new"]
                or (self.eos_id is not None and t == self.eos_id)
                or _hits_stop(st))

    def _sampling_arrays(self):
        """Per-slot (temperature, top_k, top_p) for the current batch;
        free and prompt-feeding slots sample nothing (temp 0)."""
        temp = np.zeros((self.max_batch,), np.float32)
        tk = np.zeros((self.max_batch,), np.int32)
        tp = np.ones((self.max_batch,), np.float32)
        for slot, st in self._slots.items():
            # admitting slots sample nothing: their frontier may sit at
            # n-1 but the step's output there is never kept
            if st["pos"] >= len(st["prompt"]) - 1 \
                    and not st.get("admitting"):
                temp[slot] = st["temperature"]
                tk[slot] = st["top_k"]
                tp[slot] = st["top_p"]
        return temp, tk, tp

    # -- MoE serving: occupancy mask + stats plumbing (round 19) ------------

    def _moe_act(self):
        """The joint-routing occupancy mask [max_batch] bool: occupied
        slots route (prompt-feeding INCLUDED — their routing writes the
        KV rows deeper layers keep, so they must claim real capacity),
        free slots claim nothing, and ADMITTING slots are excluded —
        their frontier output is discarded and their rows rewritten by
        the next prefill chunk, so letting them contend would charge
        phantom capacity to batch-mates."""
        act = np.zeros((self.max_batch,), bool)
        for slot, st in self._slots.items():
            act[slot] = not st.get("admitting")
        return act

    def _moe_wrap(self, fn):
        """Adapt a joint-routing Engine kind to the dense calling
        convention: append (act, stats) at dispatch, peel the trailing
        stats output back into ``self._moe_stats``, return the rest —
        so every dense dispatch site (and Engine.warmup's ``srv._step``
        call) serves MoE unchanged."""
        def wrapped(*args):
            out = fn(*args, jnp.asarray(self._moe_act()),
                     self._moe_stats)
            self._moe_stats = out[-1]
            return out[:-1]

        return wrapped

    def _moe_snapshot(self):
        """Drain the device accumulator into telemetry (delta-exact:
        ``moe.dropped_tokens`` advances by what the device dropped since
        the last drain) and return (dropped_total, load_list)."""
        from . import moe_serving as _moe_serving

        dropped, load = _moe_serving.drain_drop_stats(
            self._moe_stats, counted=self._moe_counted, tel=self._tel)
        self._moe_counted = dropped
        return dropped, load

    def _drain_share_counts(self) -> dict:
        """Fetch the expert layer's device-side counts
        (``kv_pool.COUNTS``, added up by every decode step over its live
        slots), zero the leaf, and publish what came since the last
        drain: counters ``moe.pairs_held`` / ``pairs_zero`` /
        ``pairs_absent`` (token-expert selections to an expert held
        here, to an identity expert, to another chip's), the gauge
        ``moe.experts_hit`` (distinct held experts hit a layer a step,
        mean over what this server has run), and the counter
        ``moe.expert_rows``: the rows x held experts that the decode
        steps dispatched since the last drain computed (every slot's row
        runs through every held expert), counted from the host's step
        number, not fetched.  The fetch waits for the step in flight
        (the span ``serving.drain_counts``).  Returns the totals."""
        from . import kv_pool as _kv

        self._share_ticks = 0
        steps = max(self._step_no - self._share_step0, 0)
        self._share_step0 = self._step_no
        self._share_rows += steps * self._share_step_rows
        if self._tel and steps:
            _telemetry.count("moe.expert_rows", steps * self._share_step_rows)
        if self.cache is not None:
            with _telemetry.span("serving.drain_counts"):
                new = np.asarray(jax.device_get(self.cache[_kv.COUNTS]),
                                 np.int64)
            zeros = jnp.zeros_like(self.cache[_kv.COUNTS])
            if self._device is not None:
                zeros = jax.device_put(zeros, self._device)
            self.cache = dict(self.cache, **{_kv.COUNTS: zeros})
            self._share_counts = self._share_counts + new
            if self._tel:
                for name, n in zip(_SHARE_COUNTS[:3], new[:3]):
                    if n:
                        _telemetry.count("moe." + name, int(n))
        held, zero, absent, hit, calls = (int(v) for v in
                                          self._share_counts)
        if self._tel and calls:
            _telemetry.set_gauge("moe.experts_hit", hit / calls)
        return {"moe_pairs_held": held, "moe_pairs_zero": zero,
                "moe_pairs_absent": absent,
                "moe_experts_hit": hit / calls if calls else 0.0,
                "moe_expert_rows": self._share_rows}

    # -- multi-tenant serving: adapter gather + constraint masks ------------

    def _constrained_active(self) -> bool:
        """Any ACTIVE slot decoding under a constraint automaton?  The
        gate every incompatible fast path (async pipelining, device
        blocks, speculation) checks before committing: a masked step
        needs the PREVIOUS token fetched to build the next mask, so
        constrained slots always run the stepwise sync path."""
        return any(st.get("constraint") is not None
                   for st in self._slots.values())

    def _gather_adapter_ids(self):
        """Per-slot int32 adapter ids [max_batch] for this dispatch —
        the gather_adapter index array every adapter step consumes
        (free slots read row 0, the all-zero base delta)."""
        ids = np.zeros((self.max_batch,), np.int32)
        for slot, st in self._slots.items():
            ids[slot] = st.get("adapter", 0)
        if self._tel:
            _telemetry.count("adapters.gather_steps")
        return ids

    def _mask_array(self):
        """The [B, V] additive constraint mask for the NEXT step, built
        host-side from each constrained slot's automaton state — or
        None when no decoding slot is constrained (the unmasked fast
        paths stay untouched).  Admitting / prompt-feeding slots are
        excluded: their step output is never kept, so masking it would
        only burn host time."""
        cons = {slot: st["constraint"]
                for slot, st in self._slots.items()
                if st.get("constraint") is not None
                and not st.get("admitting")
                and st["pos"] >= len(st["prompt"]) - 1}
        if not cons:
            return None
        from . import adapters as _ad

        return _ad.mask_logits(cons, self.max_batch, self.cfg.vocab_size)

    def _constraint_push(self, st, t: int) -> bool:
        """Advance the slot's automaton over the token it just emitted;
        True when the constraint is EXHAUSTED (the automaton accepted a
        complete output and allows nothing further) — the slot must
        retire even if max_new/eos/stop say otherwise."""
        cst = st.get("constraint")
        if cst is None:
            return False
        cst.advance(t)
        return cst.exhausted

    def _retire(self, done):
        """Free the finished slots (the end of a tick's book phase)."""
        for slot in done:
            st = self._slots.pop(slot)
            self._results[st["rid"]] = st["generated"]
            if self._paged:
                # blocks return to the pool (prefix-indexed ones stay
                # resident under the index's own reference)
                self._pool.free_slot(slot)
            self._free.append(slot)
            self._tel_retire(st, slot)

    def _book(self, ph, appended, done, t0, steps: int = 1, kind=None):
        """The end of a book phase: the fetched tokens' records, then
        the finished slots; the phase's span says what it booked."""
        self._tel_tokens(appended, t0, steps=steps, kind=kind)
        if self._tel:
            ph.args.update(tokens=sum(n for _, n in appended),
                           retired=[self._slots[s]["rid"] for s in done])
            if self._tick_sp is not None and kind is not None:
                # a speculative round has no dispatch phase to name it
                self._tick_sp.args.setdefault("kind", kind)
        self._retire(done)

    def _refill(self):
        """After a tick's bookkeeping: queued requests take the slots
        just freed (an admit phase of its own), then the gauges."""
        self._admit()
        self._tel_gauges()

    # -- program spans: one serving.tick a tick, disjoint phases below it --

    @contextlib.contextmanager
    def _tick_scope(self):
        """The ``serving.tick`` span around one guarded tick.  Its args
        are known when it closes: ``kind`` (the step kind dispatched, or
        ``admit_only`` / ``fetch_only`` / ``idle``), live slots, queue
        depth, and (as on each phase) ``gc_ms``, the collector's pauses
        over it, and on one tick in ``telemetry.CPU_STAMP_EVERY``
        ``cpu_ms``, the thread's CPU time over it.  The tick's
        ``serving.emit`` record is written inside it.
        A ``tick_block`` that falls back to stepwise ``tick()`` calls
        stays one tick; a tick of a server with nothing pending records
        nothing (a caller polling an idle server must not flush the
        ring)."""
        if not self._tel or self._tick_sp is not None or not (
                self._slots or self._queue or self._inflight is not None):
            yield
            return
        self._cpu_turn = (self._cpu_turn + 1) % _telemetry.CPU_STAMP_EVERY
        with _telemetry.span("serving.tick", gc_ms=True,
                             cpu_ms=not self._cpu_turn) as sp:
            self._tick_sp = sp
            try:
                yield
            finally:
                self._tick_sp = None
                sp.args.setdefault("kind", "idle")
                sp.args.update(slots=len(self._slots),
                               queue=len(self._queue))
                self._emit_flush()

    def _phase(self, name: str, **args):
        """One phase of a tick as a child span, ``serving.tick.<name>``:
        admit, feed, dispatch, wait, book.  Phases never nest, so a
        tick's children are disjoint.  Outside a tick (an admission
        inside ``submit``) the span is ``serving.<name>``."""
        if not self._tel:
            return _telemetry.NO_SPAN
        tick = self._tick_sp
        if tick is None:
            return _telemetry.span("serving." + name, gc_ms=True,
                                   cpu_ms=True, **args)
        if "kind" in args:
            tick.args["kind"] = args["kind"]
        elif name in _KIND_WITHOUT_STEP:
            tick.args.setdefault("kind", _KIND_WITHOUT_STEP[name])
        return _telemetry.span("serving.tick." + name, gc_ms=True,
                               cpu_ms=not self._cpu_turn, **args)

    def _emit_flush(self) -> None:
        """One ``serving.emit`` record for the tokens since the last:
        parallel lists of rids, counts and stamps, from which a reader
        rebuilds every request's token times."""
        if not self._emit:
            return
        rids, ns, ts = zip(*self._emit)
        self._emit = []
        _telemetry.event("serving.emit", min(ts), max(ts), rids=list(rids),
                         n=list(ns), t=list(ts))

    def _tel_first_token(self, st, slot, t_admit: float, kind=None) -> float:
        """A request's first token is on the host (admission logits
        fetched and sampled): TTFT, the ``serving.prefill`` span (slot
        claimed -> now) and the token's emission stamp (the caller
        counts the token).  Returns the stamp."""
        now = time.perf_counter()
        st["t_first"] = st["t_last"] = now
        n = len(st["prompt"])
        self._observe("serving.ttft_ms", (now - st["t_submit"]) * 1e3)
        args = {"rid": st["rid"], "prompt_len": n}
        if kind is not None:
            args["kind"] = kind
            if self._admit_sp is not None:
                self._admit_sp.args.setdefault("kinds", []).append(kind)
        _telemetry.event("serving.prefill", t_admit, now, tid=slot, **args)
        self._emit.append((st["rid"], 1, now))
        if self._tick_sp is None:        # an admission inside submit()
            self._emit_flush()
        return now

    # -- telemetry sampling (host values only — never a device sync) --------

    def _tel_gauges(self):
        """Occupancy gauges off the scheduler's host state: queue depth,
        active slots, slot occupancy, and KV-cache utilization (filled
        rows / window, from the per-slot host ``pos``).  Also the HBM
        sampling point: a rate-limited PJRT memory-stats query (host
        RPC, never a device sync) keeps live bytes_in_use/peak gauges
        next to the occupancy ones."""
        if not self._tel:
            return
        _telemetry.sample_device_stats()
        _telemetry.set_gauge("serving.queue_depth", len(self._queue))
        _telemetry.set_gauge("serving.active_slots", len(self._slots))
        _telemetry.set_gauge("serving.slot_occupancy",
                             len(self._slots) / self.max_batch)
        _telemetry.set_gauge(
            "serving.admitting_slots",
            sum(1 for st in self._slots.values()
                if st.get("admitting")))
        if self._adapters is not None:
            # per-adapter active-slot gauges, Prometheus-labeled
            # (telemetry._prom_name keeps {adapter="..."} intact).
            # Every registered name is written EVERY sample — a
            # retired adapter's gauge drops to 0 instead of freezing
            # at its last nonzero value
            counts: dict[str, int] = {}
            for st in self._slots.values():
                nm = st.get("adapter_name") or "base"
                counts[nm] = counts.get(nm, 0) + 1
            for nm in list(self._adapters.names()) + ["base"]:
                _telemetry.set_gauge(
                    f'adapters.active{{adapter="{nm}"}}',
                    counts.get(nm, 0))
        if self._spec_on and self._spec_prop:
            _telemetry.set_gauge("serving.spec_accept_rate",
                                 self._spec_acc / self._spec_prop)
        if self._spec_tree_n and self._tree_path_cnt:
            _telemetry.set_gauge(
                "serving.spec_tree_accept_len",
                self._tree_path_sum / self._tree_path_cnt)
        # kv_utilization = TRUE occupancy (round 8): under the paged
        # layout, blocks actually mapped / pool size; under contiguous,
        # filled rows / the slab's real (rounded) allocation — the old
        # max_len denominator under-reported whenever init_cache rounded
        # the row count up
        if self._paged:
            used = self._pool.blocks_in_use
            _telemetry.set_gauge("kv_pool.blocks_in_use", used)
            _telemetry.set_gauge("serving.kv_utilization",
                                 used / max(1, self._pool.N))
            # the table entries the paged decode kernel walks this step
            # (each occupied slot's blocks up to its write position),
            # over all that the tables hold
            bs = self._pool.bs
            _telemetry.set_gauge(
                "kv_pool.walk_share",
                sum(-(-(st["pos"] + 1) // bs)
                    for st in self._slots.values())
                / (self.max_batch * self._pool.nmax))
            if self._recurrent:
                # the slots whose recurrent state the next decode step
                # reads and writes, over all it holds: those that decode
                # where the state is advanced in place, every slot where
                # a layer's state is cut out and selected whole
                _telemetry.set_gauge(
                    "kv_pool.state_walk_share",
                    float(self._moe_act().mean())
                    if self._state_in_place else 1.0)
            _telemetry.set_gauge("kv_pool.host_spill_bytes",
                                 self._pool.host_spill_bytes)
            seen = self._pool.prefix_hits + self._pool.prefix_misses
            if seen:
                _telemetry.set_gauge("kv_pool.prefix_hit_rate",
                                     self._pool.prefix_hits / seen)
        else:
            rows = (int(self.cache["k"].shape[2])
                    if self.cache is not None else self.max_len)
            _telemetry.set_gauge(
                "serving.kv_utilization",
                sum(min(st["pos"], rows)
                    for st in self._slots.values())
                / (self.max_batch * rows))

    def _tel_retire(self, st, slot):
        """End-of-lifecycle records for one request: end-to-end latency
        histogram + the submit→retire span on the timeline."""
        if not self._tel:
            return
        now = time.perf_counter()
        t_sub = st.get("t_submit", now)
        self._observe("serving.e2e_ms", (now - t_sub) * 1e3)
        _telemetry.count("serving.requests_completed")
        self._count_local("serving.requests_completed")
        _telemetry.event("serving.request", t_sub, now, tid=slot,
                         rid=st["rid"], prompt_len=len(st["prompt"]),
                         tokens=len(st["generated"]))
        tr = st.get("trace")
        if tr:
            # the request's lifecycle on its trace: one decode span
            # (first token → retire) plus a zero-width retire marker
            self._span_ring.record(
                tr, "decode", st.get("t_first", t_sub), now,
                rid=st["rid"], tokens=len(st["generated"]))
            self._span_ring.record(
                tr, "retire", now, now, rid=st["rid"],
                tokens=len(st["generated"]))

    def _tel_tokens(self, appended, t0, steps: int = 1, kind=None):
        """Per-fetch records from the host bookkeeping that JUST ran on
        the already-fetched token block.  ``appended`` is [(slot state,
        tokens kept)]; all of them reached the host at one stamp, which
        goes into the tick's ``serving.emit`` record.  From the stamps:
        ``serving.decode_gap_ms``, the time since the same request's
        previous tokens (one sample a request a fetch — a batch stalled
        by an admission shows in every live request), and
        ``serving.tpot_ms``, that gap per token (n samples).  First-token
        time for slots whose first kept token arrived here (the
        ``prefill=False`` path — prefill admission stamps TTFT itself).
        ``kind`` names the executable that ran (a speculative round's
        traced slots get a ``spec_round`` span)."""
        if not self._tel:
            return
        now = time.perf_counter()
        if not appended:
            return
        total = 0
        spec = kind is not None and "spec" in kind
        for st, n in appended:
            total += n
            if "t_first" not in st:
                st["t_first"] = now
                self._observe(
                    "serving.ttft_ms",
                    (now - st.get("t_submit", t0)) * 1e3)
                if n > 1:
                    # the first token has no gap; the rest of its block
                    # came a step apart
                    self._observe("serving.tpot_ms",
                                  (now - t0) * 1e3 / max(steps, 1), n=n - 1)
            else:
                gap_ms = (now - st["t_last"]) * 1e3
                self._observe("serving.decode_gap_ms", gap_ms)
                self._observe("serving.tpot_ms", gap_ms / n, n=n)
            st["t_last"] = now
            self._emit.append((st["rid"], n, now))
            if spec and st.get("trace"):
                # one span per traced slot per speculative round:
                # the tick wall bounds every slot's draft+verify work
                self._span_ring.record(
                    st["trace"], "spec_round", t0, now,
                    rid=st["rid"], accepted=n)
        _telemetry.count("serving.tokens_generated", total)
        self._count_local("serving.tokens_generated", total)
        if self._tick_sp is None:        # a drain outside any tick
            self._emit_flush()

    # -- resilience: guarded ticks, the OOM chain, wedge recovery -----------

    def _fault_check(self, kind: str):
        """Deterministic fault-injection hook, placed exactly where a
        real device OOM would surface (just before the jitted step
        call, with no host state mutated yet — so a retried tick is
        bit-exact).  No-op unless ``PADDLE_TPU_FAULTS`` installed.
        Fires REGARDLESS of the resilience switch: with
        ``PADDLE_TPU_RESILIENCE=0`` the injected fault propagates
        uncaught — fail-fast parity is part of the chaos contract."""
        if _faults.active():
            # async dispatch sites do NOT consume wedge faults: their
            # fetch (_process_inflight) has a real hang hook, which is
            # where a wedge belongs.  Sync sites have no hang hook, so
            # there a wedge spec raises InjectedWedge LOUDLY (faults.py's
            # no-silent-no-op promise) instead of vacuously passing a
            # drill — wedge recovery is an async-dispatch feature.
            kinds = (("oom", "error") if kind.startswith("async")
                     else ("oom", "error", "wedge"))
            _faults.check("tick", f"serving.{kind.split('@')[0]}",
                          f"serving.{kind}", kinds=kinds)

    def _guarded(self, fn):
        """Run one tick under the resilience guard: an allocator OOM
        engages the degradation chain (``_oom_degrade``) and re-ticks;
        anything else — or an OOM with the chain exhausted, or the
        cache's donated buffers already consumed — propagates (honest
        fail-fast).  A clean tick after a wedge recovery flips the
        runtime-wedge verdict back to healthy (/healthz 503 -> ok)."""
        if not self._resil or self._in_tick:
            return fn()
        self._in_tick = True
        self._wedge_event = False
        try:
            while True:
                try:
                    out = fn()
                except Exception as e:  # noqa: BLE001 - classified below
                    if _resilience.is_oom(e) and self._oom_degrade(e):
                        continue
                    raise
                if self._wedged and not self._wedge_event:
                    # a full tick completed after the wedge: recovered
                    self._wedged = False
                    _telemetry.clear_runtime_wedge()
                    if self._tel:
                        _telemetry.count("resilience.wedge_recoveries")
                return out
        finally:
            self._in_tick = False

    def _cache_consumed(self) -> bool:
        """True when any cache leaf's donated buffer is already deleted
        (the failing step consumed it): a re-tick would touch dead
        buffers, so the OOM chain must fail fast instead."""
        try:
            return any(getattr(v, "is_deleted", lambda: False)()
                       for c in (self.cache, self._draft_cache)
                       if c is not None for v in c.values())
        except Exception:  # noqa: BLE001 - can't tell = don't retry
            return True

    def _oom_degrade(self, exc) -> bool:
        """One link of the retry-on-OOM chain (the reference allocator's
        retry chain at scheduler granularity).  Returns True when a
        degradation was applied and the tick should retry:

        1. async -> sync dispatch (drains the in-flight step first: its
           tokens are real work, never discarded on this path);
        2. halve the admitted batch (future admissions; active slots
           beyond the cap are evicted back to the queue with their
           progress carried);
        3. evict the lowest-priority slot (ties: youngest first).

        Every engaged link counts ``resilience.oom_retries``."""
        if self._cache_consumed():
            return False
        applied = None
        if self._paged:
            from . import kv_pool as _kv
        # the first rung only relieves POOL exhaustion (prefix eviction
        # returns host-accounted pool blocks, zero device HBM — the pool
        # is preallocated): a real XLA RESOURCE_EXHAUSTED would retry
        # the identical failing dispatch once per batch, so it skips
        # straight to dispatch degradation.  Injected drill OOMs stay
        # routed through the rung so the chaos suite can drive it
        pool_relievable = self._paged and isinstance(
            exc, (_kv.PoolExhausted, _faults.InjectedOOM))
        if pool_relievable and self._evict_or_spill(
                max(_EVICT_BATCH, len(self._slots))) > 0:
            # NEW first rung (round 8): free pool blocks the prefix
            # cache alone holds — pure memory back for zero lost work —
            # before any dispatch degradation.  Batched (LRU-first), not
            # the whole index: the chain retries the tick and re-engages
            # this rung while cold entries remain, so sustained pressure
            # still drains the cache but a single blip keeps the hit rate
            applied = "evict_prefix_cache"
        elif self._async:
            try:
                self._drain_inflight()
            except Exception:  # noqa: BLE001 - the drain itself failing:
                # _drain_inflight already rolled the scheduler back (the
                # in-flight record is cancelled inside), so the retry
                # below re-decodes those steps from consistent host state
                pass
            self._async = False
            applied = "sync_dispatch"
        elif self._admit_cap > 1:
            self._admit_cap = max(1, self._admit_cap // 2)
            self._evict_to_cap()
            applied = f"admit_cap={self._admit_cap}"
        elif len(self._slots) > 1:
            self._evict_one()
            applied = "evict"
        if applied is None:
            return False
        if self._tel:
            _telemetry.count("resilience.oom_retries")
            _telemetry.set_gauge("resilience.admit_cap", self._admit_cap)
            _telemetry.event("resilience.oom_degrade",
                             time.perf_counter(), time.perf_counter(),
                             action=applied, error=str(exc)[:200])
        return True

    def _evict_one(self) -> bool:
        """Evict the lowest-priority (ties: youngest) active slot back
        to the FRONT of the queue with its progress carried — on
        re-admission its prompt is original-prompt + generated-so-far,
        so a greedy request still produces its exact full generation."""
        if not self._slots:
            return False
        slot = min(self._slots,
                   key=lambda s: (self._slots[s].get("priority", 0),
                                  -self._slots[s].get("t_submit", 0.0)))
        st = self._slots.pop(slot)
        if self._paged:
            self._pool.free_slot(slot)
        self._free.append(slot)
        # requeue aging (the starvation bound): a request evicted more
        # than PADDLE_TPU_EVICT_REQUEUE_MAX times is losing every race
        # for a slot — fail it HONESTLY (status "error", counted) so
        # the client learns, instead of the evict/re-admit/evict loop
        # burning its progress forever while higher-priority work keeps
        # arriving.  The slot still frees either way (the OOM chain got
        # what it came for).
        evictions = st.get("evictions", 0) + 1
        cap = _flags.requeue_max()
        if cap and evictions > cap:
            rid = st["rid"]
            self._status[rid] = "error"
            self._err_reason[rid] = (
                f"evicted {evictions} times (> "
                f"PADDLE_TPU_EVICT_REQUEUE_MAX={cap}); giving up")
            if self._tel:
                _telemetry.count("serving.requests_failed")
                _telemetry.count("resilience.evict_requeue_overflows")
                _telemetry.event("serving.request_failed",
                                 st.get("t_submit", time.perf_counter()),
                                 time.perf_counter(), tid=slot, rid=rid,
                                 reason="evict_requeue_overflow")
            return True
        # full sequence = ORIGINAL prompt + generated (prompt[:base]
        # strips a previous eviction's carry — generated already holds
        # it, so a double-evicted request must not duplicate it)
        base = st.get("base", len(st["prompt"]))
        self._queue.insert(0, {
            "rid": st["rid"],
            "prompt": st["prompt"][:base] + st["generated"],
            "max_new": st["max_new"], "stop": st.get("stop", []),
            "temperature": st.get("temperature", 0.0),
            "top_k": st.get("top_k", 0), "top_p": st.get("top_p", 1.0),
            "ttl": st.get("ttl"), "priority": st.get("priority", 0),
            "tenant": st.get("tenant"),
            # adapter id + constraint SPEC survive the requeue; _admit
            # recompiles the automaton and replays the carry through it
            "adapter": st.get("adapter", 0),
            "adapter_name": st.get("adapter_name"),
            "constraint": st.get("constraint_spec"),
            "evictions": evictions,
            "carry": list(st["generated"]),
            "t_submit": st.get("t_submit", time.perf_counter()),
            # fresh queue-entry clock: TTL bounds queue wait, and this
            # request's wait starts over (see _shed_expired)
            "t_enqueue": time.perf_counter(),
        })
        if self._tel:
            _telemetry.count("resilience.oom_evictions")
        return True

    def _evict_to_cap(self):
        while len(self._slots) > self._admit_cap:
            if not self._evict_one():
                break

    def _cancel_record(self, rec):
        """Roll the host scheduler back as if ``rec`` (an in-flight
        dispatch record) was never dispatched: every still-active slot's
        pos returns to its fed position and the PRNG step counter
        rewinds, so a re-dispatch replays the SAME steps (greedy:
        bit-identical tokens and cache rows; sampled: the same fold_in
        schedule)."""
        if rec is None:
            return
        for slot, st, i in rec["snap"]:
            if self._slots.get(slot) is st:
                st["pos"] = min(st["pos"], i)
        if "step_no0" in rec:
            self._step_no = min(self._step_no, rec["step_no0"])

    def _drain_inflight(self):
        """Fetch and process the pending async dispatch NOW (the
        async -> sync degradation path: its tokens are real work).  If
        the FETCH fails, the dispatch record is cancelled (slot pos +
        step counter rolled back) before re-raising, so the caller's
        retry re-decodes from consistent host state."""
        prev = self._inflight
        self._inflight = None
        if prev is not None:
            self._process_inflight(prev)

    def _recover_wedge(self, prev, exc):
        """The watchdog tripped: the async fetch blew its wall budget.
        Mark the process wedged (/healthz answers 503), cancel BOTH
        in-flight dispatches (the unfetched ``prev`` and the one
        dispatched this tick), and roll every affected slot back to its
        earliest dispatched position — the next ticks re-decode those
        steps, so unaffected requests still finish with bit-identical
        tokens (greedy decode is a deterministic function of the host
        state just restored).  The hung fetch thread is abandoned
        (daemon); its late result, if any, is discarded."""
        self._wedge_event = True
        self._wedged = True
        _telemetry.set_runtime_wedge(str(exc))
        self._cancel_record(self._inflight)
        self._inflight = None
        self._cancel_record(prev)
        if self._tel:
            _telemetry.event("resilience.wedge", time.perf_counter(),
                             time.perf_counter(), error=str(exc)[:200])

    def _rss_guard(self):
        """Host-RSS watchdog hook (``PADDLE_TPU_KV_SPILL_RSS_MB``):
        every 16th scheduler tick reads ``/proc`` and, over the
        threshold, runs ONE bounded allocator relief round (oldest
        spilled chains, then evict-cold LRU) — see
        ``PagedAllocator.rss_watchdog``.  Off (a single int compare)
        unless the flag armed the allocator."""
        pool = self._pool
        if pool is None or not pool.rss_limit_bytes:
            return
        self._rss_tick = (self._rss_tick + 1) & 15
        if not self._rss_tick:
            pool.rss_watchdog()

    def tick(self):
        if self._adm is not None:
            # the SLO control loop rides the scheduler tick: at most
            # one evaluation per PADDLE_TPU_SLO_WINDOW_S (control_tick
            # self-gates), so this is a float compare on idle ticks
            self._adm.control_tick(
                idle=not self._slots and not self._queue)
        self._rss_guard()
        if self._experts:
            self._share_ticks += 1
            if self._share_ticks >= self._share_drain_every:
                self._drain_share_counts()
        with self._tick_scope():
            self._guarded(self._tick_impl)

    def _tick_impl(self):
        if self._spec_on:
            # speculative routing sits ABOVE the dispatch modes: a
            # ready batch runs a draft-then-verify round (sync — async
            # servers drain their in-flight step inside), anything
            # else (prompt feeding, window edge, every slot fallen
            # back) takes the plain path below unchanged
            if not self._slots and not self._async:
                self._admit()
            if self._slots and self._spec_ready():
                self._tick_spec()
                return
            if self._slots:
                self._spec_plain_steps += 1
        if self._async:
            if not self._slots:
                self._admit()
            if self._constrained_active():
                # constrained slots cannot pipeline: the NEXT step's
                # mask is a function of the token the in-flight step
                # has not fetched yet.  Drain the pipeline and fall
                # through to the sync path — same tokens, one tick of
                # lost overlap per constrained batch
                self._drain_inflight()
                if self._tel:
                    _telemetry.count("constraint.sync_fallbacks")
            else:
                self._tick_async()
                return
        if not self._slots:
            self._admit()
            if not self._slots:
                return
        # budgeted admission: at most ONE prefill chunk per round,
        # before the decode step — the stall-free interleaving
        self._advance_admitting()
        if not self._slots or all(st.get("admitting")
                                  for st in self._slots.values()):
            return   # nothing decodable this round (pure admission)
        t0 = time.perf_counter()
        with self._phase("feed"):
            self._ensure_decode_blocks(1)
            tok, pos = self._feed_arrays()
            temp, tk, tp = self._sampling_arrays()
            mask = self._mask_array()
        n = self._step_no
        # the step kind, then its call (enqueue only): every branch
        # leaves the tokens on the device as ``nxt``
        logits = None
        if self._adapters is not None:
            # pool attached: every step gathers per-slot (a, b) pairs
            # by id — base-only batches gather row 0 (the zero delta)
            # and reproduce the plain server's tokens
            kind = ("adapter_sample_step" if temp.any() or mask is not None
                    else "adapter_step")
        elif mask is not None:
            # constrained decode without a pool: the plain step plus
            # the [B, V] mask input.  Greedy slots take the masked
            # argmax inside _sample_batched, so this path consumes the
            # fold_in(n) key like the sampled path (all-greedy batches
            # draw nothing from it)
            kind = "masked_step"
        elif temp.any():
            kind = ("moe_sample_step" if self.cfg.moe is not None
                    else "sample_step")
        else:
            kind = "step"
        with self._phase("dispatch", kind=kind):
            self._fault_check(kind)
            if kind == "adapter_sample_step":
                fn = _get_adapter_sample_step_fn(
                    self.cfg, self._adapters.pool_key(), self._paged,
                    self._shard)
                if mask is None:
                    # the executable takes the mask unconditionally
                    # (ONE compiled shape); all-zeros is the identity
                    mask = np.zeros(
                        (self.max_batch, self.cfg.vocab_size),
                        np.float32)
                nxt, self.cache = fn(
                    self.params, self.cache, self._adapters.stacks(),
                    jnp.asarray(self._gather_adapter_ids()),
                    jnp.asarray(tok), jnp.asarray(pos),
                    jax.random.fold_in(self._base_key, n),
                    jnp.asarray(temp), jnp.asarray(tk),
                    jnp.asarray(tp), jnp.asarray(mask))
            elif kind == "adapter_step":
                fn = _get_adapter_step_fn(
                    self.cfg, self._adapters.pool_key(), self._paged,
                    self._shard)
                logits, self.cache = fn(
                    self.params, self.cache, self._adapters.stacks(),
                    jnp.asarray(self._gather_adapter_ids()),
                    jnp.asarray(tok), jnp.asarray(pos))
            elif kind == "masked_step":
                fn = _get_masked_step_fn(self.cfg, self._paged,
                                         self._shard)
                nxt, self.cache = fn(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(pos),
                    jax.random.fold_in(self._base_key, n),
                    jnp.asarray(temp), jnp.asarray(tk), jnp.asarray(tp),
                    jnp.asarray(mask))
            elif kind == "step":
                logits, self.cache = self._step(self.params, self.cache,
                                                jnp.asarray(tok),
                                                jnp.asarray(pos))
            else:
                if kind == "moe_sample_step":
                    fn = self._moe_wrap(_get_moe_sample_step_fn(
                        self.cfg, self._paged, self._shard))
                else:
                    fn = _get_sample_step_fn(self.cfg, self._paged,
                                             self._shard)
                nxt, self.cache = fn(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(pos),
                    jax.random.fold_in(self._base_key, n),
                    jnp.asarray(temp), jnp.asarray(tk), jnp.asarray(tp))
            if logits is not None:
                nxt = jnp.argmax(logits, axis=-1)
        with self._phase("wait"):
            nxt = np.asarray(nxt)     # the one device-to-host fetch
        # the step counter advances only AFTER the step call returned:
        # a failed call (real or injected OOM) leaves host state exactly
        # as before the tick, so the guard's retry is bit-exact
        self._step_no = n + 1
        _count_sample_steps(temp, tk, tp)
        # NaN guard on the tick logits (greedy path only — the sampled
        # path fetches tokens, not logits).  The full-logits fetch is
        # extra host traffic, so it only engages when a fault targets
        # logits or the operator opted in (PADDLE_TPU_NAN_GUARD_SERVING)
        nan_slots: set = set()
        if (logits is not None and self._resil
                and (_faults.active()
                     or _os.environ.get("PADDLE_TPU_NAN_GUARD_SERVING",
                                        "") == "1")):  # noqa: E129
            lnp = np.asarray(logits)
            if _faults.active():
                lnp = _faults.corrupt_nan("logits", lnp)
            finite = np.isfinite(lnp).all(axis=-1)
            nan_slots = {s for s in self._slots if not finite[s]}
        done = []
        failed = []
        appended = []
        with self._phase("book") as ph:
            for slot, st in self._slots.items():
                if st.get("admitting"):
                    # rode the step at its prefill frontier: pos is owned
                    # by the admission machinery, the output token
                    # discarded, and a (mathematically valid,
                    # differently-rounded) logits row must not trip the
                    # NaN guard collaterally
                    continue
                i = st["pos"]
                st["pos"] = i + 1
                if i < len(st["prompt"]) - 1:
                    continue            # still feeding prompt; logits unused
                if slot in nan_slots:
                    # AFTER the prompt-feed skip: a mid-prompt slot never
                    # consumes this tick's logits, so a non-finite row
                    # there must not kill it collaterally
                    failed.append(slot)
                    continue
                t = int(nxt[slot])
                st["generated"].append(t)
                appended.append((st, 1))
                fin = self._constraint_push(st, t)
                if self._finished(st, t) or fin:
                    done.append(slot)
            for slot in failed:
                st = self._slots.pop(slot)
                self._fail_request(st, slot, "non-finite tick logits")
            self._book(ph, appended, done, t0, kind=kind)
        self._refill()

    # -- async dispatch: one step/block in flight ---------------------------

    def _dispatch_feed(self, prev, block: int = 1):
        """Host-side feed snapshot for an async dispatch.

        Returns (host_tok, prev_mask, pos, temp, tk, tp, snap): per slot,
        the feed token comes from the host (prompt, or a generated token
        already fetched) unless it is the output of the still-in-flight
        previous dispatch — then ``prev_mask`` routes the DEVICE array
        through the jitted select instead (no host round trip).  ``snap``
        records (slot, st, fed_pos) for the deferred bookkeeping; each
        slot's pos advances by ``block`` optimistically (a slot that
        finishes mid-block retires at process time, where its stale pos
        no longer matters)."""
        B = self.max_batch
        ht = np.zeros((B,), np.int32)
        pm = np.zeros((B,), bool)
        pos = np.zeros((B,), np.int32)
        temp = np.zeros((B,), np.float32)
        tk = np.zeros((B,), np.int32)
        tp = np.ones((B,), np.float32)
        snap = []
        for slot, st in self._slots.items():
            i = st["pos"]
            n_p = len(st["prompt"])
            base = st.get("base", n_p)   # see _feed_arrays
            if st.get("admitting"):
                # mid-admission ride: feed the prefill frontier (the
                # written row is rewritten by the slot's next chunk).
                # NO snap entry and NO pos advance — the admission
                # machinery owns this slot's pos, its dispatch output
                # is never kept, and rollback/cancel must not touch it
                ht[slot] = st["prompt"][i]
                pos[slot] = i
                continue
            if i < n_p:
                ht[slot] = st["prompt"][i]
            elif i - base < len(st["generated"]):
                ht[slot] = st["generated"][i - base]
            else:
                # the feed token is the previous dispatch's output —
                # still on device, unfetched
                assert prev is not None, "in-flight feed without inflight"
                pm[slot] = True
            if i >= n_p - 1:  # the step at i produces a kept token
                temp[slot] = st["temperature"]
                tk[slot] = st["top_k"]
                tp[slot] = st["top_p"]
            pos[slot] = i
            snap.append((slot, st, i))
            st["pos"] = i + block
        return ht, pm, pos, temp, tk, tp, snap

    def _prev_feed(self, prev):
        """The [B] device token array feeding off the in-flight dispatch
        (step: its tokens; block: the block's last column)."""
        if prev is None:
            feed = jnp.zeros((self.max_batch,), jnp.int32)
            if isinstance(self._shard, _ShardCtx):
                # same type as a step's own output under the mesh, so
                # the first tick and every later one share an executable
                feed = jax.device_put(feed, self._shard.repl)
            return feed
        return prev["feed"]

    def _rollback_dispatch(self, snap, n):
        """Undo one ``_dispatch_feed``'s optimistic advances after the
        dispatch call itself failed (e.g. an injected/real OOM): the
        jitted fn raised, so neither the cache nor ``self.cache`` was
        reassigned — restoring pos and the step counter makes the retry
        bit-exact."""
        for slot, st, i in snap:
            if self._slots.get(slot) is st:
                st["pos"] = i
        self._step_no = n

    def _dispatch_step_async(self, prev):
        with self._phase("feed"):
            self._ensure_decode_blocks(1)
            ht, pm, pos, temp, tk, tp, snap = self._dispatch_feed(prev)
        n = self._step_no
        self._step_no = n + 1
        # async pipelining composes with the adapter pool (gather rides
        # the in-flight select); constrained slots never reach here —
        # _tick_impl drains to sync first
        fname = ("adapter_async_step" if self._adapters is not None
                 else "moe_async_step" if self.cfg.moe is not None
                 else "async_step")
        try:
            with self._phase("dispatch", kind=fname):
                self._fault_check(fname)
                if self._adapters is not None:
                    fn = _get_adapter_async_step_fn(
                        self.cfg, self._adapters.pool_key(), self._paged,
                        self._shard)
                    lead = (self._adapters.stacks(),
                            jnp.asarray(self._gather_adapter_ids()))
                elif self.cfg.moe is not None:
                    fn = self._moe_wrap(_get_moe_async_step_fn(
                        self.cfg, self._paged, self._shard))
                    lead = ()
                else:
                    fn = _get_async_step_fn(self.cfg, self._paged,
                                            self._shard)
                    lead = ()
                nxt, self.cache = fn(
                    self.params, self.cache, *lead, jnp.asarray(ht),
                    jnp.asarray(pm),
                    self._prev_feed(prev), jnp.asarray(pos),
                    jax.random.fold_in(self._base_key, n),
                    jnp.asarray(temp),
                    jnp.asarray(tk), jnp.asarray(tp))
        except Exception:
            self._rollback_dispatch(snap, n)
            raise
        _count_sample_steps(temp, tk, tp)
        self._inflight = {"kind": "step", "toks": nxt, "feed": nxt,
                          "fn": fname, "step_no0": n,
                          "snap": snap, "t_disp": time.perf_counter()}

    def _dispatch_block_async(self, prev, block: int):
        with self._phase("feed"):
            self._ensure_decode_blocks(block)
            ht, pm, pos, temp, tk, tp, snap = self._dispatch_feed(prev,
                                                                  block)
        n = self._step_no
        self._step_no = n + block
        fname = (f"async_sample_block@{block}" if temp.any()
                 else f"async_block@{block}")
        try:
            with self._phase("dispatch", kind=fname):
                self._fault_check(fname)
                if temp.any():
                    fn = _get_async_sample_block_fn(
                        self.cfg, block, self._paged, self._shard)
                    toks, self.cache = fn(
                        self.params, self.cache, jnp.asarray(ht),
                        jnp.asarray(pm),
                        self._prev_feed(prev), jnp.asarray(pos),
                        self._base_key,
                        jnp.asarray(n), jnp.asarray(temp),
                        jnp.asarray(tk), jnp.asarray(tp))
                    feed = toks[:, -1]  # the block's last token per slot
                else:
                    fn = _get_async_block_fn(self.cfg, block, self._paged,
                                             self._shard)
                    toks, self.cache, feed, _ = fn(
                        self.params, self.cache, jnp.asarray(ht),
                        jnp.asarray(pm),
                        self._prev_feed(prev), jnp.asarray(pos))
        except Exception:
            self._rollback_dispatch(snap, n)
            raise
        _count_sample_steps(temp, tk, tp, block)
        self._inflight = {"kind": "block", "toks": toks, "feed": feed,
                          "fn": fname, "snap": snap, "block": block,
                          "step_no0": n, "t_disp": time.perf_counter()}

    def _process_inflight(self, prev):
        """Fetch a completed dispatch's tokens and run the deferred host
        bookkeeping.  Slots whose request retired (or was replaced by a
        new tenant) since the dispatch are skipped — their tokens are
        the overrun the async pipeline trades for overlap."""
        # the ONLY device->host fetch — watchdogged when a wall budget is
        # set (PADDLE_TPU_STEP_BUDGET_S): a wedged device step must not
        # hang the scheduler forever, so the fetch runs under
        # resilience.call_with_budget and a blown budget triggers
        # _recover_wedge instead of blocking.  Budget 0 (default) is the
        # plain inline fetch — zero overhead, today's behavior.
        try:
            with self._phase("wait"):
                if self._resil and (self._step_budget > 0
                                    or _faults.active()):
                    def _fetch():
                        _faults.hang("tick", "serving.fetch")
                        return np.asarray(prev["toks"])

                    toks = _resilience.call_with_budget(
                        _fetch, self._step_budget, name="serving.fetch")
                else:
                    toks = np.asarray(prev["toks"])
        except _resilience.WedgeError as e:
            self._recover_wedge(prev, e)
            return
        except Exception:
            # the fetch surfaced a device error (plain path included):
            # roll the scheduler back so host state matches the last
            # processed step, then let the guard classify (OOM chain or
            # propagate).  Any SUCCESSOR dispatched this tick is
            # cancelled too — its host bookkeeping assumed this record's
            # tokens would land first, and draining it after this
            # rollback would append its tokens out of order ahead of
            # the re-decoded ones
            self._cancel_record(self._inflight)
            self._inflight = None
            self._cancel_record(prev)
            raise
        done = []
        appended = []
        with self._phase("book") as ph:
            for slot, st, i in prev["snap"]:
                if self._slots.get(slot) is not st:
                    continue  # retired/replaced while this was in flight
                if prev["kind"] == "step":
                    if i < len(st["prompt"]) - 1:
                        continue  # still feeding prompt; token unused
                    t = int(toks[slot])
                    st["generated"].append(t)
                    appended.append((st, 1))
                    # constrained slots never dispatch async (the sync
                    # fallback gate) — the push is a no-op kept for the
                    # drain-on-transition edge
                    fin = self._constraint_push(st, t)
                    if self._finished(st, t) or fin:
                        done.append(slot)
                else:
                    kept = 0
                    for j in range(prev["block"]):
                        t = int(toks[slot, j])
                        st["generated"].append(t)
                        kept += 1
                        fin = self._constraint_push(st, t)
                        if self._finished(st, t) or fin:
                            done.append(slot)
                            break
                    appended.append((st, kept))
            # latency window: dispatch -> this fetch (the async
            # pipeline's real step time, overlap included)
            self._book(ph, appended, done,
                       prev.get("t_disp", time.perf_counter()),
                       steps=prev.get("block", 1), kind=prev.get("fn"))
        self._refill()

    def _tick_async(self):
        """One async tick: dispatch step N+1 FIRST (feeding the in-flight
        step's device tokens), then block on step N for bookkeeping —
        the device is never idle while the host schedules.  The last
        dispatch before a drain is overrun work whose results are simply
        never fetched."""
        prev = self._inflight
        self._inflight = None
        if not self._slots:
            self._admit()
            if not self._slots:
                return
        try:
            # one prefill chunk per round, before the dispatch (the
            # chunk chains on the in-flight step's cache future; device
            # order is step-then-chunk, so the frontier row the step
            # wrote is rewritten before anything attends it)
            self._advance_admitting()
        except Exception:
            # the chunk failed before any host state moved: restore
            # prev (its tokens are still fetchable) so the OOM chain's
            # sync fallback can drain it instead of losing a step
            self._inflight = prev
            raise
        if not self._slots or all(st.get("admitting")
                                  for st in self._slots.values()):
            if prev is not None:
                self._process_inflight(prev)
            return
        try:
            self._dispatch_step_async(prev)
        except Exception:
            # the dispatch failed before replacing the pipeline: restore
            # prev (see above)
            self._inflight = prev
            raise
        if prev is not None:
            self._process_inflight(prev)

    def _tick_block_async(self, block: int):
        """Async tick_block: one BLOCK in flight (see _tick_async).  The
        stepwise-prompt fallback first drains the in-flight dispatch —
        single async ticks then pipeline among themselves."""
        prev = self._inflight
        self._inflight = None
        if not self._slots:
            self._admit()
            if not self._slots:
                return
        if self._adapters is not None or self._constrained_active() \
                or self.cfg.moe is not None \
                or any(st["pos"] < len(st["prompt"]) - 1
                       or st.get("admitting")
                       for st in self._slots.values()):
            # adapter/constrained/MoE batches take stepwise async ticks
            # (the adapter async STEP pipelines; an async adapter BLOCK
            # executable isn't built; constrained slots need every
            # token fetched before the next mask; an MoE block would
            # freeze the occupancy mask across k steps while the async
            # overrun keeps retired slots contending — the stepwise
            # moe_async_step re-reads occupancy every tick) — same
            # tokens, the documented fallback
            if prev is not None:
                self._process_inflight(prev)
            for _ in range(block):
                self.tick()
                if not self._slots:
                    break
            return
        try:
            self._dispatch_block_async(prev, block)
        except Exception:
            self._inflight = prev   # see _tick_async
            raise
        if prev is not None:
            self._process_inflight(prev)

    # -- warmup: pre-compile what this server will serve --------------------

    def warmup(self, prompt_lens=None, blocks=(), sample: bool = False,
               constrained: bool = False):
        """Pre-compile the executables this server will serve, so the
        first request pays device time only (and re-launches hit the
        persistent compilation cache — framework.platform
        .init_compile_cache, called here).

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
        That invariant only holds for requests admitted AFTER warmup, so
        warming an idle server is enforced: an active slot's already-
        prefilled rows would be silently corrupted.  The PRNG step
        counter is NOT advanced, so a warmed server produces
        bit-identical tokens to a cold one.

        Returns {executable: seconds} compile+first-run timings."""
        return _engine.ENGINE.warmup(
            self, prompt_lens=prompt_lens, blocks=blocks,
            sample=sample, constrained=constrained)

    def tick_block(self, block: int = 8):
        """``block`` greedy decode steps with ONE host round trip.

        Requires every active slot to be past its prompt (prefill
        admission guarantees this); when some slot is still consuming
        its prompt token-by-token (``prefill=False``), falls back to
        ``block`` single ticks — per-token host feedback is the whole
        point of that path.  Slots finishing mid-block overrun on device;
        the host discards their surplus tokens here.  MoE servers run
        the joint-routing ``moe_block`` kind for greedy batches (the
        occupancy mask frozen at dispatch) and fall back to stepwise
        ticks for sampled ones."""
        block = int(block)
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        if self._adm is not None:
            self._adm.control_tick(
                idle=not self._slots and not self._queue)
        self._rss_guard()
        with self._tick_scope():
            self._guarded(lambda: self._tick_block_impl(block))

    def _tick_block_impl(self, block: int):
        if self._spec_on:
            if not self._slots and not self._async:
                self._admit()
            if self._slots and self._spec_ready():
                # a block of N plain steps yields N tokens/slot; spec
                # rounds yield up to K each, so ceil(N/K) rounds covers
                # the block's work with the same one-fetch-per-dispatch
                # cadence (early exit when slots retire or the window
                # edge forces plain ticks)
                for _ in range(max(1, -(-block // self._spec_chunk()))):
                    if not self._slots or not self._spec_ready():
                        break
                    self._tick_spec()
                return
            if self._slots and not any(
                    st["pos"] < len(st["prompt"]) - 1
                    or st.get("admitting")
                    for st in self._slots.values()):
                # the prompt-feeding case (admitting included) falls
                # through to stepwise tick()s below, which count their
                # own plain steps
                self._spec_plain_steps += block
        if self._async:
            self._tick_block_async(block)
            return
        if not self._slots:
            self._admit()
            if not self._slots:
                return
        # a slot at pos == len(prompt)-1 is fine for block decode (its feed
        # token is the prompt's last; everything after is feedback) — only
        # slots with logits-discarded prompt positions left need stepwise.
        # Admitting slots force stepwise too: one prefill chunk per tick is
        # exactly the budgeted interleaving.  Constrained slots force
        # stepwise always (the mask for step j+1 needs step j's token on
        # the host), as do SAMPLED slots under an adapter pool (no
        # adapter sample-block executable — the stepwise path draws the
        # same fold_in(n) schedule, so tokens match tick() exactly)
        if self._constrained_active() \
                or ((self._adapters is not None
                     or self.cfg.moe is not None)
                    and any(st.get("temperature", 0.0) > 0.0
                            for st in self._slots.values())) \
                or any(st["pos"] < len(st["prompt"]) - 1
                       or st.get("admitting")
                       for st in self._slots.values()):
            for _ in range(block):
                self.tick()
                if not self._slots:
                    break
            return
        t0 = time.perf_counter()
        with self._phase("feed"):
            self._ensure_decode_blocks(block)
            tok, pos = self._feed_arrays()
            temp, tk, tp = self._sampling_arrays()
        n = self._step_no
        kind = ("adapter_block" if self._adapters is not None
                else "sample_block" if temp.any()
                else "moe_block" if self.cfg.moe is not None
                else "block") + f"@{block}"
        with self._phase("dispatch", kind=kind):
            self._fault_check(kind)
            if self._adapters is not None:
                # greedy adapter block: gather once per step inside the
                # on-device scan — one host fetch for ``block`` tokens
                fn = _get_adapter_block_fn(
                    self.cfg, block, self._adapters.pool_key(),
                    self._paged, self._shard)
                toks, self.cache, _, _ = fn(
                    self.params, self.cache, self._adapters.stacks(),
                    jnp.asarray(self._gather_adapter_ids()),
                    jnp.asarray(tok), jnp.asarray(pos))
            elif temp.any():
                fn = _get_sample_block_fn(self.cfg, block, self._paged,
                                          self._shard)
                toks, self.cache = fn(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(pos), self._base_key, jnp.asarray(n),
                    jnp.asarray(temp), jnp.asarray(tk), jnp.asarray(tp))
            elif self.cfg.moe is not None:
                # greedy MoE block: k joint-routing steps, the occupancy
                # mask frozen at dispatch (every slot here is past its
                # prompt — see the fallback above — so occupancy only
                # shrinks mid-block, the documented block-overrun
                # tradeoff)
                fn = self._moe_wrap(_get_moe_block_fn(
                    self.cfg, block, self._paged, self._shard))
                toks, self.cache, _, _ = fn(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(pos))
            else:
                fn = _get_block_fn(self.cfg, block, self._paged,
                                   self._shard)
                toks, self.cache, _, _ = fn(
                    self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(pos))
        self._step_no = n + block   # after the call: see _tick_impl
        _count_sample_steps(temp, tk, tp, block)
        with self._phase("wait"):
            toks = np.asarray(toks)  # the block's one device->host fetch
        done = []
        appended = []
        with self._phase("book") as ph:
            for slot, st in self._slots.items():
                kept = 0
                for j in range(block):
                    t = int(toks[slot, j])
                    st["generated"].append(t)
                    st["pos"] += 1
                    kept += 1
                    if self._finished(st, t):
                        done.append(slot)
                        break
                appended.append((st, kept))
            self._book(ph, appended, done, t0, steps=block, kind=kind)
        self._refill()
