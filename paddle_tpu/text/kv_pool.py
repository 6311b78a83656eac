"""Paged KV-cache subsystem: block pool, block tables, prefix reuse.

The serving cache was one contiguous ``[L, max_batch, rows, Hkv, hd]``
slab — every slot provisioned for the worst-case context, and identical
prompt prefixes (system prompts, few-shot headers) prefilled and stored
once per request.  This module reproduces the reference's allocator
stack (auto-growth best-fit chunks, retry-on-OOM chains) at KV-cache
granularity, in the mold of vLLM's PagedAttention and SGLang's
RadixAttention:

* **block pool** — device leaves ``[L, num_blocks, block_size, Hkv*hd]``:
  a row's heads side by side in the last axis (head ``h`` in lanes
  ``[h*hd, (h+1)*hd)``), so that a page ``leaf[layer, page]`` is stored
  the way the paged kernel copies it and every reader — the kernel, the
  prefill's slot gather, the row scatter — addresses the whole leaf by
  (layer, page): no step cuts a layer's slice out of it or re-lays it
  out (int8 scale planes ``[L, N, bs, Hkv]`` ride along exactly as in
  the contiguous layout), shared by every slot;
* **block tables** — an int32 ``[max_batch, nmax]`` leaf mapping each
  slot's logical block to a physical pool block (-1 = unmapped), carried
  in the cache pytree so the jitted steps stay pure pytree-in/pytree-out
  and donation composes unchanged;
* **free-list allocator with refcounts** (:class:`PagedAllocator`, host
  side) — blocks are allocated as a slot's ``pos`` crosses block
  boundaries instead of reserving ``max_len`` rows up front, and freed or
  dereferenced on retire;
* **radix prefix index** — requests sharing a prompt prefix map their
  leading table entries to the SAME physical blocks (exact token-chain
  keys, refcounted), so shared prefixes are prefilled once; the first
  divergent write to a shared block copies it (copy-on-write).  Matching
  is token-granular: a prompt sharing only part of an indexed block's
  tokens SPLITS that node (``PADDLE_TPU_KV_RADIX``) instead of missing,
  so admission adopts the longest *token* prefix;
* **host-RAM spill tier** — the evict-cold rung can demote cold prefix
  chains to host buffers (one batched ``device_get`` per round,
  ``PADDLE_TPU_KV_SPILL_MB``) and admission restores them with one
  batched ``device_put`` through the existing :func:`inject_rows`
  buckets instead of a recompute walk.

Device math lives here too: :func:`paged_decode_step_batched` is the
pooled twin of ``serving.decode_step_batched`` (einsum fallback =
per-slot ``generate._cached_block`` on a gathered view — bit-identical
to the slab path holding the same rows; kernel route =
``ops/decode_attention.paged_decode_attention``, whose grid cell walks
one slot's live blocks through the table), and
:func:`paged_prefill_chunk` is the pooled ``generate.prefill_slot_chunk``.
The contiguous layout stays the default (``PADDLE_TPU_KV_LAYOUT``).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from . import generate, gpt, woq
from . import mla as _mla
from . import moe as _moe
from . import ssm as _ssm
from .. import flags as _flags
from .. import telemetry as _telemetry

__all__ = [
    "PoolExhausted", "PagedAllocator", "round_len", "init_paged_cache",
    "paged_decode_step_batched", "paged_prefill_chunk",
    "paged_verify_chunk_batched", "paged_tree_verify_chunk_batched",
    "paged_tree_commit", "copy_blocks", "inject_rows",
]

# a latent-attention config's pool: ONE leaf of latent rows
# [2 * L, N, bs, lanes] in place of "k" and "v" (an attention sublayer,
# not a layer, indexes its first axis; a row is [c | kr], zero-padded to
# whole lane tiles), and an expert share's device-side counts
# (moe.SHARE_COUNTS) carried with the cache
LATENT = "latent"
COUNTS = "moe_counts"
# the value/scale leaves of a pooled cache: what block tables map
POOL_LEAVES = ("k", "v", "k_s", "v_s", LATENT)
# a recurrent mixer's per-slot leaves [L, batch, ...] (no block table maps
# them) and the [batch] bool leaf that says which slots a decode step
# advances; held beside the pool when the config has an ssm mixer.  Under
# a stated layer pattern (cfg.layer_types) the two kinds of leaf differ in
# depth: "k" / "v" hold the attention layers only and the state leaves
# the mamba layers only, each indexed by a layer's place among its own
# kind (cfg.layer_slots)
STATE_LEAVES = _ssm.STATE_LEAVES
LIVE = "live"


class PoolExhausted(RuntimeError):
    """KV block pool has no free block.  The message carries the literal
    ``RESOURCE_EXHAUSTED`` marker so ``resilience.is_oom`` classifies it
    exactly like a real allocator OOM — the serving tick's retry chain
    (evict cold prefix entries -> degrade dispatch -> evict slots)
    engages on it."""

    def __init__(self, need: int = 1, total: int = 0):
        super().__init__(
            f"RESOURCE_EXHAUSTED: KV block pool exhausted "
            f"(need {need} more block(s), pool size {total})")


def round_len(max_len: int, block_size: int) -> int:
    """A paged cache's per-slot logical row count: the contiguous
    layout's kernel-tileable rounding, then up to a whole number of
    blocks (so a slot's gathered view is exactly ``nmax * bs`` rows —
    pick ``block_size`` dividing ``generate._round_cache_len(max_len)``
    when bit-parity with a contiguous cache of the same window
    matters)."""
    T = generate._round_cache_len(max_len)
    bs = int(block_size)
    return -(-T // bs) * bs


def init_paged_cache(cfg: gpt.GPTConfig, batch: int, max_len: int,
                     block_size: int | None = None,
                     num_blocks: int | None = None) -> dict:
    """The pooled cache pytree (``generate.init_cache(layout="paged")``):
    value leaves ``[L, N, bs, Hkv*hd]`` (+ int8 scale planes
    ``[L, N, bs, Hkv]``) and an int32 ``tables`` leaf ``[batch, nmax]``
    initialized unmapped (-1).  ``num_blocks`` defaults to full
    provisioning (``batch * nmax`` — slab-equivalent capacity, the
    parity-safe default); operators shrink it to the budget actual
    traffic needs, which is the whole point of paging."""
    bs = _flags.kv_block_size() if block_size is None else int(block_size)
    if bs < 8 or bs % 8:
        raise ValueError(f"block_size {bs}: must be a positive multiple "
                         f"of 8 (the decode kernel's row tile)")
    T = round_len(max_len, bs)
    nmax = T // bs
    # `is None` (not falsy): num_blocks=0 must hit the validation below,
    # not silently provision the full slab-equivalent pool
    N = batch * nmax if num_blocks is None else int(num_blocks)
    if N < 1:
        raise ValueError(f"num_blocks must be >= 1, got {N}")
    L, H, hd = cfg.num_layers, cfg.kv_heads, cfg.head_dim
    dt = generate._kv_store_dtype(cfg)
    if cfg.mla is not None:
        if dt == jnp.int8:
            raise NotImplementedError(
                "an int8 pool of latent rows is not supported yet: the "
                "scale planes are per head, and a latent row has none")
        return {LATENT: jnp.zeros((_mla.SUBLAYERS * L, N, bs,
                                   latent_lanes(cfg)), dt),
                "tables": jnp.full((batch, nmax), -1, jnp.int32),
                LIVE: jnp.zeros((batch,), bool),
                COUNTS: jnp.zeros((len(_moe.SHARE_COUNTS),), jnp.int32)}
    if cfg.layer_types is not None:
        if dt == jnp.int8:
            raise NotImplementedError(
                "an int8 pool under a layer pattern is not supported yet: "
                "the pattern's prefill chunk and step were never held to "
                "the scale planes")
        if not cfg.layers_of("attention"):
            raise NotImplementedError(
                "a layer pattern without an attention layer has no K/V "
                "leaf for the block tables to map")
    La = cfg.layers_of("attention")
    shape = (La, N, bs, H * hd)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
             "tables": jnp.full((batch, nmax), -1, jnp.int32)}
    if dt == jnp.int8:
        cache["k_s"] = jnp.zeros((La, N, bs, H), jnp.float32)
        cache["v_s"] = jnp.zeros((La, N, bs, H), jnp.float32)
    if cfg.ssm is not None:
        # two kinds of state, one pytree: the mixer's state is per SLOT
        # and of fixed size, so it is provisioned for every slot and
        # donated and returned with the pool
        cache.update(_ssm.init_state(cfg.ssm, cfg.layers_of("mamba"),
                                     batch, cfg.dtype))
    if cfg.experts is not None:
        cache[COUNTS] = jnp.zeros((len(_moe.SHARE_COUNTS),), jnp.int32)
    if cfg.ssm is not None or cfg.experts is not None:
        cache[LIVE] = jnp.zeros((batch,), bool)
    return cache


def latent_lanes(cfg: gpt.GPTConfig) -> int:
    """Lanes a stored latent row takes: ``[c | kr]`` and zeros up to a
    whole lane tile (the chip's layout pads the last axis to 128 whatever
    is asked for, and the paged kernel copies whole tiles)."""
    return -(-cfg.mla.row_width // 128) * 128


def _geometry(cache: dict):
    """(num_blocks, block_size, nmax) of a pooled cache pytree."""
    leaf = cache["k"] if "k" in cache else cache[LATENT]
    return leaf.shape[1], leaf.shape[2], cache["tables"].shape[1]


def _gather_slot(pool: dict, li, trow, cfg: gpt.GPTConfig) -> dict:
    """One slot's contiguous view of layer ``li`` of every pool leaf:
    leaves [L, N, bs, ...] + table row [nmax] -> {"k", "v":
    [1, nmax*bs, Hkv, hd], scales: [1, nmax*bs, Hkv]}, gathered from the
    whole leaf by (layer, page) and given its heads after the gather.
    Delegates to the kernel module's batched gather — ONE copy of the
    unmapped-entry (clamp-to-block-0, causally-masked) semantics shared
    with the oracle/fallback paths."""
    from ..ops import decode_attention as da

    with jax.named_scope("kv_gather"):
        view = {n: da.gather_paged_view(v, li, trow[None])
                for n, v in pool.items()}
        for n in ("k", "v"):
            view[n] = view[n].reshape(view[n].shape[:2]
                                      + (cfg.kv_heads, cfg.head_dim))
    return view


def _row_index(arr, layers, phys):
    """The (layer, block, row) index of physical row numbers ``phys`` [R]
    in a pool leaf ``arr`` [L, N, bs, ...] as it is stored, for
    ``layers``: one layer's number (-> [R] rows) or None for every layer
    (-> [L, R]).  Every one of the three is an index of the gather or
    scatter and a row its window: a window over the layers makes the
    chip's compiler copy the whole leaf into a layout with the layers
    inside a tile, and back."""
    bs = arr.shape[2]
    if layers is None:
        layers = jnp.arange(arr.shape[0])[:, None]
    return layers, phys // bs, phys % bs


def take_rows(arr, phys):
    """Rows ``phys`` [R] (physical row numbers, in bounds) of every layer
    of a pool leaf [L, N, bs, ...] -> [L, R, ...]."""
    return arr[_row_index(arr, None, phys)]


def _put_rows(arr, layers, phys, val):
    """``arr`` [L, N, bs, ...] with the rows ``val`` written at physical
    row numbers ``phys`` [R] (int32, out-of-bounds = dropped — the
    overrun/unmapped sink) of ``layers``: one layer's number (``val``
    [R, Hkv(, hd)]) or None for every layer (``val`` [L, R, Hkv(, hd)]).
    The rows take the leaf's own row shape (a K/V row's heads side by
    side) and the leaf is indexed as it is stored: in place under
    donation, no reshape and no other layout of the pool."""
    idx = _row_index(arr, layers, phys)
    lead = jnp.broadcast_shapes(jnp.shape(idx[0]), phys.shape)
    val = val.reshape(lead + arr.shape[3:]).astype(arr.dtype)
    return arr.at[idx].set(val, mode="drop")


def _scatter_rows(cache: dict, rows: dict, phys) -> dict:
    """Write per-layer row leaves into the pool at physical row indices
    ``phys`` (int32, out-of-bounds = dropped — the overrun/unmapped
    sink).  ``rows`` leaves [L, R, Hkv(, hd)] (or already in the pool's
    row shape, [L, R, Hkv*hd]) against pool leaves [L, N, bs, ...]; the
    single row-write every paged decode/prefill path funnels through
    (the ``generate._write_rows`` twin)."""
    out = dict(cache)
    with jax.named_scope("kv_gather"):
        for name, val in rows.items():
            out[name] = _put_rows(cache[name], None, phys, val)
    return out


def _kernel_route(cache, batch: int, cfg: gpt.GPTConfig) -> bool:
    """Whether a K/V cache's decode step runs its layers at top level
    around the paged kernel (:func:`_paged_step_kernel`) and not per slot
    under a vmap: the flag, the static shapes and the backend."""
    from ..ops import decode_attention as da

    return _flags.flash_decode() and da.paged_available(
        (batch, 1, cfg.num_heads, cfg.head_dim), cache["k"].shape)


def state_in_place(cache, cfg: gpt.GPTConfig) -> bool:
    """Whether the decode step of this cache advances the recurrent state
    where it is stored, for the decoding slots alone
    (``ssm.mixer_step_pooled`` through ``ops/ssm_update``), and does not
    cut a layer's state out for every slot: what the step's own routing
    reads, for the host's gauge ``kv_pool.state_walk_share``."""
    from ..ops import ssm_update

    if STATE_LEAVES[0] not in cache:
        return False
    leaf = cache[STATE_LEAVES[0]]
    return (ssm_update.available(leaf.shape, leaf.dtype, cfg.ssm.n_groups)
            and (cfg.layer_types is not None
                 or _kernel_route(cache, leaf.shape[1], cfg)))


def paged_decode_step_batched(params, cache, token, pos,
                              cfg: gpt.GPTConfig):
    """``serving.decode_step_batched`` on the pooled layout: token [B]
    int32, pos [B] int32 (each slot's write position), cache a
    :func:`init_paged_cache` tree -> (logits [B, V], cache).

    Fallback route (any backend): vmap over slots of the EXACT per-slot
    ``generate._cached_block`` math on a table-gathered view — the same
    ops at the same shapes as the contiguous step, so greedy decode is
    bit-identical to a slab holding the same rows.  Kernel route (TPU /
    interpret, ``PADDLE_TPU_FLASH_DECODE``): fresh rows scatter into the
    pool first, then ``ops/decode_attention.paged_decode_attention``
    copies each slot's live blocks inside that slot's grid cell — no
    [B, T] gather is ever materialized."""
    if LATENT in cache:
        return _latent_step(params, cache, token, pos, cfg)
    if cfg.layer_types is not None:
        return _pattern_step(params, cache, token, pos, cfg)
    N, bs, nmax = _geometry(cache)
    B = token.shape[0]
    if _kernel_route(cache, B, cfg):
        return _paged_step_kernel(params, cache, token, pos, cfg)

    tables = cache["tables"]
    pool = {n: cache[n] for n in POOL_LEAVES if n in cache}
    state = None
    if STATE_LEAVES[0] in cache:
        with jax.named_scope("ssm"):
            state = _ssm.from_zero({n: cache[n] for n in STATE_LEAVES},
                                   pos, 1)

    def one(tok_b, pos_b, trow, st_b):
        dt = cfg.dtype
        x = generate._embed_step(params, tok_b[None], pos_b, cfg)

        def body(x, layer):
            p, li, st = layer
            csl = _gather_slot(pool, li, trow, cfg)
            if st is None:
                x, rows = generate._cached_block(x, p, csl, pos_b, cfg)
                return x, (rows, None)
            x, rows, st = generate._cached_block(
                x, p, csl, pos_b, cfg,
                state={n: v[None] for n, v in st.items()})
            return x, (rows, {n: v[0] for n, v in st.items()})

        x, (rows, st_b) = jax.lax.scan(
            body, x, (params["blocks"], jnp.arange(cfg.num_layers), st_b))
        x = gpt._norm(x, params, "ln_f", cfg)
        logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)[:, 0]
        return logits[0].astype(jnp.float32), rows, st_b

    logits, rows, new_state = jax.vmap(
        one, in_axes=(0, 0, 0, 1), out_axes=(0, 0, 1))(
        token, pos, tables, state)
    if new_state is not None:
        with jax.named_scope("ssm"):
            cache = dict(cache, **_ssm.keep_idle(
                new_state, {n: cache[n] for n in STATE_LEAVES},
                cache[LIVE], 1))
    # rows leaves [B, L, 1, Hkv(, hd)] -> [L, B, Hkv(, hd)]; physical row
    # per slot through the table (unmapped -> out of bounds -> dropped,
    # the slab path's clamp-into-masked-rows equivalent)
    tb = tables[jnp.arange(B), pos // bs]
    phys = jnp.where(tb >= 0, tb * bs + pos % bs, N * bs)
    stacked = {n: jnp.moveaxis(v[:, :, 0], 0, 1) for n, v in rows.items()}
    return logits, _scatter_rows(cache, stacked, phys)


def _paged_step_kernel(params, cache, token, pos, cfg: gpt.GPTConfig):
    """Kernel route of :func:`paged_decode_step_batched` — the layer
    loop runs at top level so the paged kernel sees the whole batch
    (a grid cell a slot, which walks that slot's live blocks); the
    per-slot pre/post math stays vmapped
    (norm/projections/rope/MoE routing at the contiguous step's B=1
    shapes)."""
    from ..ops import decode_attention as da

    N, bs, nmax = _geometry(cache)
    B = token.shape[0]
    dt = cfg.dtype
    hd = cfg.head_dim
    tables = cache["tables"]
    tb = tables[jnp.arange(B), pos // bs]
    phys = jnp.where(tb >= 0, tb * bs + pos % bs, N * bs)
    pool = {n: cache[n] for n in POOL_LEAVES if n in cache}
    # the recurrent state rides the carry like the pool (written in place,
    # a layer at a time), never the scan's stacked outputs
    state = ({n: cache[n] for n in STATE_LEAVES}
             if STATE_LEAVES[0] in cache else None)

    def embed_one(tok_b, pos_b):
        return generate._embed_step(params, tok_b[None], pos_b, cfg)

    x = jax.vmap(embed_one)(token, pos)                  # [B, 1, 1, D]

    def body(carry, layer):
        x, pool, state = carry
        p, li = layer
        h = mix = None
        if state is not None:
            h = jax.vmap(lambda xb: gpt._norm(xb, p, "ln1", cfg))(x)
            mix, state = _ssm.mixer_step_pooled(h[:, 0], p, cfg, state, li,
                                                cache[LIVE], pos)
            mix = mix[:, None]                           # [B, 1, 1, D]

        def pre(xb, pos_b, hb):
            return generate._block_pre_attn(xb, p, pos_b, cfg, h=hb)

        q3, rows = jax.vmap(pre)(x, pos, h)  # q3 [B,1,1,H,hd]
        # scatter the fresh rows into layer li BEFORE attending: the
        # kernel then reads exactly what later steps will read back
        # (scatter-then-attend == the slab path's splice-then-write).
        # The kernel is handed the whole leaves and the layer's number:
        # nothing here has a layer's slice of the pool as its result
        with jax.named_scope("kv_gather"):
            pool = dict(pool, **{n: _put_rows(pool[n], li, phys, val[:, 0])
                                 for n, val in rows.items()})
        q = q3.reshape(B, 1, cfg.num_heads, hd)
        with jax.named_scope("attn"):
            attn = da.paged_decode_attention(
                q, pool["k"], pool["v"], tables, pos, li,
                k_scale=pool.get("k_s"), v_scale=pool.get("v_s"))
        attn = attn.astype(dt).reshape(B, 1, 1, cfg.num_heads * hd)

        def post(xb, ab, mb):
            return generate._block_post_attn(xb, ab, p, cfg, mix=mb)

        x = jax.vmap(post)(x, attn, mix)
        return (x, pool, state), None

    (x, pool, state), _ = jax.lax.scan(
        body, (x, pool, state),
        (params["blocks"], jnp.arange(cfg.num_layers)))

    def fin(xb):
        xb = gpt._norm(xb, params, "ln_f", cfg)
        return woq.logits(xb, params, dt, cfg.lm_head_multiplier)[0, 0]

    logits = jax.vmap(fin)(x)
    return logits.astype(jnp.float32), dict(cache, **pool, **(state or {}))


def _pad_lanes(x, lanes: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - x.shape[-1])])


def _latent_step(params, cache, token, pos, cfg: gpt.GPTConfig):
    """:func:`paged_decode_step_batched` for a latent-attention config:
    the whole batch a layer at a time ([B, D] rows through
    ``gpt.latent_block``), so that the expert layer routes the step's
    tokens together.  An attention sublayer writes its fresh latent rows
    into the pool by (sublayer, page) and attends in the absorbed form:
    the paged kernel, each slot's live pages and nothing else, where it
    runs; the per-slot whole view through the tables elsewhere.  Slots
    the ``live`` leaf does not name select no expert and count nowhere."""
    from ..ops import decode_attention as da

    N, bs, nmax = _geometry(cache)
    B = token.shape[0]
    m, dt, H = cfg.mla, cfg.dtype, cfg.num_heads
    tables, live = cache["tables"], cache[LIVE]
    tb = tables[jnp.arange(B), pos // bs]
    phys = jnp.where(tb >= 0, tb * bs + pos % bs, N * bs)
    lanes = cache[LATENT].shape[3]
    kernel = (_flags.flash_decode() and da.paged_available(
        (B, 1, H, lanes), cache[LATENT].shape, m.kv_lora_rank))
    attend_pool = da.paged_decode_attention if kernel else da._xla_paged
    # the residual stream in float32 (the sublayers' inputs are normed
    # into the compute dtype): [B, D]
    x = woq.embed(params, token, dt,
                  cfg.embedding_multiplier).astype(jnp.float32)

    # the layers in a Python loop, not a scan: a layer's weights are then
    # read where they are stored (see moe.init_expert_share)
    pool, counts = cache[LATENT], cache[COUNTS]
    for li in range(cfg.num_layers):
        box = [pool]

        def attend(i, n, p_i, li=li):
            sub = _mla.SUBLAYERS * li + i
            q_nope, q_rope, rows = _mla.project(n, p_i, cfg, pos)
            # scatter-then-attend, as the K/V kernel route does
            with jax.named_scope("kv_gather"):
                box[0] = _put_rows(box[0], sub, phys,
                                   _pad_lanes(rows, lanes))
            with jax.named_scope("attn"):
                q_lat = _pad_lanes(_mla.absorb_q(q_nope, q_rope, p_i, cfg),
                                   lanes)
                lat = attend_pool(
                    q_lat[:, None], box[0], None, tables, pos,
                    jnp.asarray(sub, jnp.int32), None, None,
                    1.0 / math.sqrt(m.qk_head_dim), m.kv_lora_rank)
                out = _mla.absorb_out(lat[:, 0], p_i, cfg)
            return _mla.out_proj(out, p_i, cfg)

        x, c = gpt.latent_block(x, _moe.layer_of(params["blocks"], li),
                                cfg, attend, valid=live)
        pool, counts = box[0], counts + c
    x = gpt._norm(x, params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)
    return logits.astype(jnp.float32), dict(
        cache, **{LATENT: pool, COUNTS: counts})


def _latent_prefill_chunk(params, cache, tokens, pos0, length, slot,
                          cfg: gpt.GPTConfig):
    """:func:`paged_prefill_chunk` for a latent-attention config: the
    chunk's queries attend the slot's table-gathered latent rows [0, pos0)
    and the chunk's own, up-projected (``mla.attend_chunk``); rows
    [pos0, pos0 + length) are written through the table, a sublayer at a
    time.  Padded positions select no expert and write no row."""
    from ..ops import decode_attention as da

    N, bs, nmax = _geometry(cache)
    trow = cache["tables"][slot]                          # [nmax]
    dt, C = cfg.dtype, tokens.shape[1]
    lanes = cache[LATENT].shape[3]
    x = woq.embed(params, tokens[0], dt,
                  cfg.embedding_multiplier).astype(jnp.float32)    # [C, D]
    valid = jnp.arange(C) < length
    logi = pos0 + jnp.arange(C)
    tb = trow[jnp.clip(logi // bs, 0, nmax - 1)]
    phys = jnp.where(valid & (tb >= 0) & (logi // bs < nmax),
                     tb * bs + logi % bs, N * bs)

    pool = cache[LATENT]
    for li in range(cfg.num_layers):
        box = [pool]

        def attend(i, n, p_i, li=li):
            sub = _mla.SUBLAYERS * li + i
            q_nope, q_rope, rows = _mla.project(n, p_i, cfg, logi)
            rows = _pad_lanes(rows, lanes).astype(pool.dtype)
            with jax.named_scope("kv_gather"):
                view = da.gather_paged_view(box[0], sub, trow[None])[0]
                full = jax.lax.dynamic_update_slice(view, rows, (pos0, 0))
                box[0] = _put_rows(box[0], sub, phys, rows)
            with jax.named_scope("attn"):
                a = _mla.attend_chunk(q_nope, q_rope,
                                      full[:, :cfg.mla.row_width], pos0,
                                      p_i, cfg)
            return _mla.out_proj(a, p_i, cfg)

        x, _ = gpt.latent_block(x, _moe.layer_of(params["blocks"], li),
                                cfg, attend, valid=valid)
        pool = box[0]
    last = jax.lax.dynamic_slice(x, (length - 1, 0), (1, cfg.hidden_size))
    last = gpt._norm(last, params, "ln_f", cfg)
    logits = woq.logits(last, params, dt, cfg.lm_head_multiplier)[0]
    return logits.astype(jnp.float32), dict(cache, **{LATENT: pool})


def _pattern_step(params, cache, token, pos, cfg: gpt.GPTConfig):
    """:func:`paged_decode_step_batched` for a stated layer pattern
    (``cfg.layer_types``): the whole batch a layer at a time ([B, D] rows
    through ``gpt.pattern_block``), so that the expert layer routes the
    step's tokens together.  A mamba layer advances its own layer of the
    state leaves (i, the layer's place among the mamba layers;
    ``ssm.mixer_step_pooled``: in place for the slots that decode where
    ``ops/ssm_update`` runs): a slot that feeds a first position starts
    from zero, an idle slot keeps its state bit for bit.  An attention
    layer writes its fresh rows into
    its own leaf of the pool (``pool[n][j]``, j its place among the
    attention layers) and attends through the tables, no position applied
    to q or k: the paged kernel, each slot's live pages, where it runs;
    the gathered view elsewhere.  Slots the ``live`` leaf does not name
    select no expert and count nowhere."""
    from ..ops import decode_attention as da

    N, bs, nmax = _geometry(cache)
    B = token.shape[0]
    dt, H, hd = cfg.dtype, cfg.num_heads, cfg.head_dim
    tables, live = cache["tables"], cache[LIVE]
    tb = tables[jnp.arange(B), pos // bs]
    phys = jnp.where(tb >= 0, tb * bs + pos % bs, N * bs)
    kernel = (_flags.flash_decode()
              and da.paged_available((B, 1, H, hd), cache["k"].shape))
    attend_pool = da.paged_decode_attention if kernel else da._xla_paged
    x = woq.embed(params, token, dt,
                  cfg.embedding_multiplier).astype(jnp.float32)    # [B, D]

    # the layers in a Python loop: a layer's weights, its leaf of the pool
    # and its leaf of the state are read and written where they are stored
    box = {"pool": {n: cache[n] for n in ("k", "v")},
           "state": {n: cache[n] for n in STATE_LEAVES if n in cache}}
    counts = cache[COUNTS]
    for li, (kind, i) in enumerate(cfg.layer_slots):
        p = gpt.pattern_layer(params["blocks"], cfg, li)

        def mamba(n, p=p, i=i):
            out, box["state"] = _ssm.mixer_step_pooled(
                n[:, None], p, cfg, box["state"], i, live, pos)
            return out[:, 0]

        def attention(n, p=p, i=i):
            q, k, v = gpt._project_qkv(n[:, None], p, cfg, repeat_kv=False)
            rows = generate._store_rows(k[:, 0], v[:, 0], cfg)
            # scatter-then-attend, as the K/V kernel route does
            with jax.named_scope("kv_gather"):
                box["pool"] = {k_: _put_rows(box["pool"][k_], i, phys, val)
                               for k_, val in rows.items()}
            with jax.named_scope("attn"):
                attn = attend_pool(
                    q, box["pool"]["k"], box["pool"]["v"], tables, pos,
                    jnp.asarray(i, jnp.int32), None, None,
                    cfg.softmax_scale)
            return gpt._attn_out(attn.astype(dt).reshape(B, H * hd), p, cfg)

        x, c = gpt.pattern_block(
            x, p, cfg, mamba if kind == "mamba" else attention, valid=live)
        counts = counts + c
    x = gpt._norm(x, params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)
    return logits.astype(jnp.float32), dict(
        cache, **box["pool"], **box["state"], **{COUNTS: counts})


def _pattern_prefill_chunk(params, cache, tokens, pos0, length, slot,
                           cfg: gpt.GPTConfig):
    """:func:`paged_prefill_chunk` for a stated layer pattern: the chunk's
    rows [C, D] a layer at a time.  A mamba layer continues the slot's
    state over the chunk's first ``length`` positions by the chunked scan
    (from zero where the chunk opens the sequence) and writes it back; an
    attention layer's queries attend the slot's table-gathered rows
    [0, pos0) and the chunk's own, and rows [pos0, pos0 + length) are
    written through the table.  Padded positions advance no state, write
    no row and select no expert."""
    N, bs, nmax = _geometry(cache)
    trow = cache["tables"][slot]                          # [nmax]
    dt, C = cfg.dtype, tokens.shape[1]
    x = woq.embed(params, tokens[0], dt,
                  cfg.embedding_multiplier).astype(jnp.float32)    # [C, D]
    valid = jnp.arange(C) < length
    logi = pos0 + jnp.arange(C)
    tb = trow[jnp.clip(logi // bs, 0, nmax - 1)]
    phys = jnp.where(valid & (tb >= 0) & (logi // bs < nmax),
                     tb * bs + logi % bs, N * bs)

    box = {"pool": {n: cache[n] for n in ("k", "v")},
           "state": {n: cache[n] for n in STATE_LEAVES if n in cache}}
    for li, (kind, i) in enumerate(cfg.layer_slots):
        p = gpt.pattern_layer(params["blocks"], cfg, li)

        def mamba(n, p=p, i=i):
            with jax.named_scope("ssm"):
                # the slot's state of this layer, cut from the leaf as it
                # is stored (a layer's slice first would be copied whole)
                start = {k: jnp.where(
                             pos0 == 0, jnp.zeros((), v.dtype),
                             jax.lax.dynamic_slice(
                                 v, (i, slot) + (0,) * (v.ndim - 2),
                                 (1, 1) + v.shape[2:])[0])
                         for k, v in box["state"].items()}
            out, new = _ssm.mixer_chunk(n[None], p, cfg, start,
                                        length=length)
            with jax.named_scope("ssm"):
                box["state"] = {
                    k: jax.lax.dynamic_update_slice(
                        v, new[k][None],
                        (i, slot) + (0,) * (v.ndim - 2))
                    for k, v in box["state"].items()}
            return out[0]

        def attention(n, p=p, i=i):
            q, k, v = gpt._project_qkv(n[None], p, cfg, repeat_kv=False)
            rows = generate._store_rows(k, v, cfg)        # [1, C, Hkv, hd]
            csl = _gather_slot(box["pool"], i, trow, cfg)
            full = {k_: jax.lax.dynamic_update_slice(
                        csl[k_], val, (0, pos0, 0, 0))
                    for k_, val in rows.items()}
            with jax.named_scope("kv_gather"):
                box["pool"] = {
                    k_: _put_rows(box["pool"][k_], i, phys, val[0])
                    for k_, val in rows.items()}
            attn = generate._attend_cache(q, full, pos0, cfg)
            return gpt._attn_out(attn, p, cfg)[0]

        x, _ = gpt.pattern_block(
            x, p, cfg, mamba if kind == "mamba" else attention, valid=valid)
    last = jax.lax.dynamic_slice(x, (length - 1, 0), (1, cfg.hidden_size))
    last = gpt._norm(last, params, "ln_f", cfg)
    logits = woq.logits(last, params, dt, cfg.lm_head_multiplier)[0]
    return logits.astype(jnp.float32), dict(
        cache, **box["pool"], **box["state"])


def paged_prefill_chunk(params, cache, tokens, pos0, length, slot,
                        cfg: gpt.GPTConfig):
    """``generate.prefill_slot_chunk`` on the pooled layout: one chunk of
    a prompt at positions [pos0, pos0+C) for one slot, attending the
    slot's table-gathered cache rows [0, pos0) plus within-chunk
    causally (``generate._chunk_attend_block`` — the shared chunk math),
    writing rows [pos0, pos0+length) through the table (pads and
    unmapped entries dropped), returning (logits at the chunk's last
    valid position [V], cache).

    With a shared prefix adopted into the table, ``pos0`` starts at the
    first unshared row — the shared blocks are ATTENDED through the
    gather but never recomputed, which is where the prefix cache's
    prefill FLOPs saving comes from."""
    if LATENT in cache:
        return _latent_prefill_chunk(params, cache, tokens, pos0, length,
                                     slot, cfg)
    if cfg.layer_types is not None:
        return _pattern_prefill_chunk(params, cache, tokens, pos0, length,
                                      slot, cfg)
    N, bs, nmax = _geometry(cache)
    tables = cache["tables"]
    trow = tables[slot]                                   # [nmax]
    pool = {n: cache[n] for n in POOL_LEAVES if n in cache}
    dt = cfg.dtype
    C = tokens.shape[1]
    x = woq.embed(params, tokens, dt, cfg.embedding_multiplier)
    if cfg.pos_embed == "learned":
        x = x + jax.lax.dynamic_slice(
            params["wpe"], (pos0, 0), (C, cfg.hidden_size)).astype(dt)[None]
    valid_mask = (jnp.arange(C) < length)[None, :]        # [1, C]
    # the slot's recurrent state [L, 1, ...]: zero where the chunk opens
    # the sequence (an admission starts from the zero state), else what
    # the chunk before left; pads advance nothing (ssm.mixer_chunk)
    state = None
    if STATE_LEAVES[0] in cache:
        with jax.named_scope("ssm"):
            state = {n: jnp.where(
                         pos0 == 0, jnp.zeros((), cache[n].dtype),
                         jax.lax.dynamic_slice_in_dim(cache[n], slot, 1, 1))
                     for n in STATE_LEAVES}

    def body(x, layer):
        p, li, st = layer
        csl = _gather_slot(pool, li, trow, cfg)
        if st is None:
            x, rows = generate._chunk_attend_block(x, p, csl, pos0, cfg,
                                                   valid=valid_mask)
            return x, (rows, None)
        x, rows, st = generate._chunk_attend_block(
            x, p, csl, pos0, cfg, valid=valid_mask, state=st, length=length)
        return x, (rows, st)

    x, (rows, state) = jax.lax.scan(
        body, x, (params["blocks"], jnp.arange(cfg.num_layers), state))
    if state is not None:
        with jax.named_scope("ssm"):
            cache = dict(cache, **{
                n: jax.lax.dynamic_update_slice_in_dim(cache[n], state[n],
                                                       slot, 1)
                for n in STATE_LEAVES})
    logi = pos0 + jnp.arange(C)
    tb = trow[jnp.clip(logi // bs, 0, nmax - 1)]
    phys = jnp.where((jnp.arange(C) < length) & (tb >= 0)
                     & (logi // bs < nmax), tb * bs + logi % bs, N * bs)
    cache = _scatter_rows(cache, {n: v[:, 0] for n, v in rows.items()},
                          phys)
    last = jax.lax.dynamic_slice(x, (0, length - 1, 0),
                                 (1, 1, cfg.hidden_size))
    last = gpt._norm(last, params, "ln_f", cfg)
    logits = woq.logits(last, params, dt, cfg.lm_head_multiplier)[0, 0]
    return logits.astype(jnp.float32), cache


def paged_verify_chunk_batched(params, cache, tokens, pos, cfg):
    """``generate.verify_chunk`` on the pooled layout, batched over
    slots: tokens [B, K] int32 scored at per-slot positions
    [pos_b, pos_b + K) -> (logits [B, K, V] fp32, cache).

    Per slot this is the EXACT chunk math ``paged_prefill_chunk`` runs —
    ``generate._chunk_attend_block`` over the slot's table-gathered view
    — so row 0 of the verify logits equals the plain decode step's
    logits for the same feed token (greedy serving parity rests on
    this).  K/V rows for the whole chunk scatter through the block
    table; rejected rows land at/past the slot's position pointer where
    the causal mask hides them and the next round overwrites them (the
    stale-row invariant — no masked write needed).  Unmapped or
    past-the-table entries drop (the standard out-of-bounds sink).

    Kernel route (TPU / interpret, ``PADDLE_TPU_FLASH_DECODE``): the
    layer loop moves to top level and ``paged_decode_attention`` streams
    the whole batch at Tq=K — the ROADMAP "flash-verify" item."""
    from ..ops import decode_attention as da

    N, bs, nmax = _geometry(cache)
    B, K = tokens.shape
    if (_flags.flash_decode()
            and da.paged_available((B, K, cfg.num_heads, cfg.head_dim),
                                   cache["k"].shape)):
        return _paged_verify_kernel(params, cache, tokens, pos, cfg)
    tables = cache["tables"]
    pool = {n: cache[n] for n in POOL_LEAVES if n in cache}
    dt = cfg.dtype

    def one(tok_k, p0, trow):
        x = woq.embed(params, tok_k[None], dt,
                      cfg.embedding_multiplier)              # [1, K, D]
        if cfg.pos_embed == "learned":
            x = x + jax.lax.dynamic_slice(
                params["wpe"], (p0, 0),
                (K, cfg.hidden_size)).astype(dt)[None]

        def body(x, layer):
            p, li = layer
            csl = _gather_slot(pool, li, trow, cfg)
            x, rows = generate._chunk_attend_block(x, p, csl, p0, cfg)
            return x, rows

        x, rows = jax.lax.scan(
            body, x, (params["blocks"], jnp.arange(cfg.num_layers)))
        x = gpt._norm(x, params, "ln_f", cfg)
        logits = woq.logits(x, params, dt,
                            cfg.lm_head_multiplier)[0]      # [K, V]
        return logits.astype(jnp.float32), rows

    logits, rows = jax.vmap(one, in_axes=(0, 0, 0),
                            out_axes=(0, 0))(tokens, pos, tables)
    # rows leaves [B, L, 1, K, Hkv(, hd)] -> [L, B*K, Hkv(, hd)];
    # physical row per (slot, j) through the table
    logi = pos[:, None] + jnp.arange(K)[None, :]          # [B, K]
    tb = jnp.take_along_axis(tables, jnp.clip(logi // bs, 0, nmax - 1),
                             axis=1)
    phys = jnp.where((tb >= 0) & (logi // bs < nmax),
                     tb * bs + logi % bs, N * bs).reshape(B * K)
    stacked = {}
    for n, v in rows.items():
        v = jnp.moveaxis(v[:, :, 0], 0, 1)                # [L, B, K, ...]
        stacked[n] = v.reshape((v.shape[0], B * K) + v.shape[3:])
    return logits, _scatter_rows(cache, stacked, phys)


def paged_tree_verify_chunk_batched(params, cache, tokens, amask, depth,
                                    pos, cfg: gpt.GPTConfig):
    """``generate.tree_verify_chunk`` on the pooled layout, batched over
    slots: tokens [B, N] int32 (node 0 = feed token), amask [B, N, N]
    ancestor-or-self bool, depth [B, N] int32, pos [B] — ONE pass over
    each slot's token tree stored at table-translated rows
    [pos_b, pos_b + N) -> (logits [B, N, V] fp32, cache).

    Per slot this runs ``generate._tree_attend_block`` over the slot's
    table-gathered view — the EXACT shared tree math the contiguous
    route runs, so the two layouts cannot drift (and a chain tree
    reduces to ``paged_verify_chunk_batched``'s fallback bit-for-bit).
    Topology is a runtime argument; only N is a compiled shape.  Always
    the einsum route: the flash kernels assume causal masks (see
    ``generate._attend_cache_tree``).  Rejected nodes land at/past the
    slot's pointer through the table where the next round overwrites
    them — the stale-row invariant, unchanged; unmapped or
    past-the-table entries drop (the standard out-of-bounds sink)."""
    N, bs, nmax = _geometry(cache)
    B, K = tokens.shape
    tables = cache["tables"]
    pool = {n: cache[n] for n in POOL_LEAVES if n in cache}
    dt = cfg.dtype
    T = nmax * bs

    def one(tok_k, am, dp, p0, trow):
        x = woq.embed(params, tok_k[None], dt,
                      cfg.embedding_multiplier)              # [1, K, D]
        if cfg.pos_embed == "learned":
            x = x + jnp.take(params["wpe"], p0 + dp,
                             axis=0).astype(dt)[None]
        tmask = jnp.broadcast_to(jnp.arange(T)[None, None, :] < p0,
                                 (1, K, T))
        tmask = jax.lax.dynamic_update_slice(tmask, am[None], (0, 0, p0))

        def body(x, layer):
            p, li = layer
            csl = _gather_slot(pool, li, trow, cfg)
            x, rows = generate._tree_attend_block(x, p, csl, p0, dp,
                                                  tmask, cfg)
            return x, rows

        x, rows = jax.lax.scan(
            body, x, (params["blocks"], jnp.arange(cfg.num_layers)))
        x = gpt._norm(x, params, "ln_f", cfg)
        logits = woq.logits(x, params, dt,
                            cfg.lm_head_multiplier)[0]      # [K, V]
        return logits.astype(jnp.float32), rows

    logits, rows = jax.vmap(one, in_axes=(0, 0, 0, 0, 0),
                            out_axes=(0, 0))(tokens, amask, depth, pos,
                                             tables)
    logi = pos[:, None] + jnp.arange(K)[None, :]          # [B, K]
    tb = jnp.take_along_axis(tables, jnp.clip(logi // bs, 0, nmax - 1),
                             axis=1)
    phys = jnp.where((tb >= 0) & (logi // bs < nmax),
                     tb * bs + logi % bs, N * bs).reshape(B * K)
    stacked = {}
    for n, v in rows.items():
        v = jnp.moveaxis(v[:, :, 0], 0, 1)                # [L, B, K, ...]
        stacked[n] = v.reshape((v.shape[0], B * K) + v.shape[3:])
    return logits, _scatter_rows(cache, stacked, phys)


def paged_tree_commit(cache, src, pos):
    """``generate.tree_commit_rows`` on the pooled layout: per slot b,
    copy the pool rows at logical positions ``pos_b + src_b[i]`` to
    logical ``pos_b + 1 + i`` (both sides translated through the block
    table).  Gather-then-scatter per leaf, so in-place aliasing under
    donation is safe even when source and destination rows share a
    block; identity entries rewrite themselves and out-of-bounds /
    unmapped destinations drop (source rows are inside the window the
    serving tick just ensured blocks for)."""
    N, bs, nmax = _geometry(cache)
    B, M = src.shape
    tables = cache["tables"]

    def phys_of(logi):
        tb = jnp.take_along_axis(
            tables, jnp.clip(logi // bs, 0, nmax - 1), axis=1)
        return jnp.where((tb >= 0) & (logi // bs < nmax),
                         tb * bs + logi % bs, N * bs)

    src_p = phys_of(pos[:, None] + src).reshape(B * M)
    dst_p = phys_of(pos[:, None] + 1
                    + jnp.arange(M)[None, :]).reshape(B * M)
    src_p = jnp.clip(src_p, 0, N * bs - 1)
    out = dict(cache)
    for name in POOL_LEAVES:
        if name in cache:
            out[name] = _put_rows(cache[name], None, dst_p,
                                  take_rows(cache[name], src_p))
    return out


def _paged_verify_kernel(params, cache, tokens, pos, cfg: gpt.GPTConfig):
    """Kernel route of :func:`paged_verify_chunk_batched` — the
    :func:`_paged_step_kernel` structure at Tq=K: layer loop at top
    level so the paged kernel sees the whole batch per layer, per-slot
    pre/post math vmapped at the fallback's [1, K, D] shapes
    (``generate._chunk_pre_attn`` — rope needs per-slot offsets), and
    the chunk's fresh rows scattered through the tables BEFORE attending
    (scatter-then-attend == the fallback's splice-then-write; rejected
    rows stay hidden behind the position pointer as ever)."""
    from ..ops import decode_attention as da

    N, bs, nmax = _geometry(cache)
    B, K = tokens.shape
    dt = cfg.dtype
    H, hd = cfg.num_heads, cfg.head_dim
    tables = cache["tables"]
    pool = {n: cache[n] for n in POOL_LEAVES if n in cache}
    logi = pos[:, None] + jnp.arange(K)[None, :]          # [B, K]
    tb = jnp.take_along_axis(tables, jnp.clip(logi // bs, 0, nmax - 1),
                             axis=1)
    phys = jnp.where((tb >= 0) & (logi // bs < nmax),
                     tb * bs + logi % bs, N * bs).reshape(B * K)

    def embed_one(tok_k, p0):
        x = woq.embed(params, tok_k[None], dt,
                      cfg.embedding_multiplier)              # [1, K, D]
        if cfg.pos_embed == "learned":
            x = x + jax.lax.dynamic_slice(
                params["wpe"], (p0, 0),
                (K, cfg.hidden_size)).astype(dt)[None]
        return x

    x = jax.vmap(embed_one)(tokens, pos)                  # [B, 1, K, D]

    def body(carry, layer):
        x, pool = carry
        p, li = layer

        def pre(xb, p0):
            return generate._chunk_pre_attn(xb, p, p0, cfg)

        q3, rows = jax.vmap(pre)(x, pos)  # q3 [B, 1, K, H, hd]
        with jax.named_scope("kv_gather"):
            pool = dict(pool, **{n: _put_rows(pool[n], li, phys, val[:, 0])
                                 for n, val in rows.items()})
        with jax.named_scope("attn"):
            attn = da.paged_decode_attention(
                q3.reshape(B, K, H, hd), pool["k"], pool["v"], tables, pos,
                li, k_scale=pool.get("k_s"), v_scale=pool.get("v_s"))
        attn = attn.astype(dt).reshape(B, 1, K, H * hd)

        def post(xb, ab):
            return generate._block_post_attn(xb, ab, p, cfg)

        return (jax.vmap(post)(x, attn), pool), None

    (x, pool), _ = jax.lax.scan(
        body, (x, pool), (params["blocks"], jnp.arange(cfg.num_layers)))

    def fin(xb):
        xb = gpt._norm(xb, params, "ln_f", cfg)
        return woq.logits(xb, params, dt,
                          cfg.lm_head_multiplier)[0]        # [K, V]

    logits = jax.vmap(fin)(x)
    return logits.astype(jnp.float32), dict(cache, **pool)


def inject_rows(cache: dict, rows: dict, start, length, slot) -> dict:
    """Write externally computed cache rows (a prefill worker's output —
    leaves ``[L, 1, C, Hkv(, hd)]`` or, from a paged worker or the spill
    tier, ``[L, 1, C, Hkv*hd]``; valid through ``length``) into one
    slot's rows [start, length) through its block table — the paged
    half of the fleet's prefill/decode handoff
    (``generate._merge_slot_rows`` is the contiguous twin).  ``start``
    skips rows an adopted prefix already holds (shared blocks must
    never be rewritten); pad rows beyond ``length`` and unmapped table
    entries drop (the standard out-of-bounds sink); the caller has
    already allocated/COW'd the write range (``ensure_rows``)."""
    N, bs, nmax = _geometry(cache)
    trow = cache["tables"][slot]                          # [nmax]
    C = rows["k"].shape[2]
    logi = jnp.arange(C)
    tb = trow[jnp.clip(logi // bs, 0, nmax - 1)]
    phys = jnp.where((logi >= start) & (logi < length) & (tb >= 0)
                     & (logi // bs < nmax),
                     tb * bs + logi % bs, N * bs)
    return _scatter_rows(cache, {n: v[:, 0] for n, v in rows.items()},
                         phys)


def copy_blocks(cache: dict, src, dst) -> dict:
    """Copy physical blocks ``src`` -> ``dst`` (int32 [P]) across every
    pool leaf — the device half of copy-on-write.  Destinations are
    freshly allocated (never in ``src``), so the gather/scatter pair has
    no ordering hazard; callers jit + donate the cache so the pool
    updates in place."""
    out = dict(cache)
    for name in POOL_LEAVES:
        if name in cache:
            arr = cache[name]
            out[name] = arr.at[:, dst].set(arr[:, src])
    return out


# ---------------------------------------------------------------------------
# host allocator: free list + refcounts + radix prefix index + spill tier
# ---------------------------------------------------------------------------


def _read_rss_bytes() -> int:
    """Current process resident set in bytes — ``/proc/self/statm``
    (field 2, pages) on Linux, ``getrusage`` peak-RSS as the portable
    fallback, 0 when neither is readable (watchdog disarms rather than
    guessing)."""
    try:
        with open("/proc/self/statm", "rb") as f:
            pages = int(f.read().split()[1])
        import resource

        return pages * resource.getpagesize()
    except (OSError, ValueError, IndexError, ImportError):
        try:
            import resource

            # ru_maxrss is KiB on Linux (bytes on macOS — either way a
            # conservative upper bound, which is the safe direction for
            # a pressure watchdog)
            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss << 10
        except Exception:
            return 0


def prefix_fingerprint(tokens) -> int:
    """Deterministic fingerprint of a token run for the router-side
    prefix summaries (crc32 over the int64 bytes — Python's ``hash()``
    is salted per process, so it can never be compared across a fleet's
    replicas)."""
    return zlib.crc32(np.asarray(tuple(tokens), np.int64).tobytes())


class _PrefixEntry:
    """One indexed radix node: the physical pool block, its LRU clock,
    its position in the interned tree (``key`` = the intern-table key
    ``(parent chain id, token run)``, ``parent`` = the previous node's
    chain id, 0 at the root) and ``end`` — the cumulative token count of
    the chain through this node.  A node's run never crosses a block
    boundary, and its block holds bit-valid rows for in-block offsets
    ``[0, end - 1 mod bs]`` — split siblings share a block precisely
    because their common rows are identical."""

    __slots__ = ("block", "last_hit", "key", "parent", "end")

    def __init__(self, block: int, tick: int, key, parent: int,
                 end: int):
        self.block = block
        self.last_hit = tick
        self.key = key
        self.parent = parent
        self.end = end


class PagedAllocator:
    """Host-side block accounting for one pooled cache: the free list,
    per-block refcounts, the per-slot table mirror (pushed to the device
    leaf when dirty), pending COW copies, and the prefix index.

    Prefix identity is an INTERNED parent-id RADIX tree (round 9 built
    the linear chain; this round generalizes it): a node's chain id is
    interned under ``(parent_chain_id, token_run)`` where the run never
    crosses a block boundary, and siblings under one parent always
    diverge on their FIRST token (``_children`` maps parent ->
    {first token -> child id}), so lookup walks O(n) tokens with O(1)
    child steps.  A prompt sharing only part of a node's run SPLITS the
    node (:meth:`_split_entry`): a new parent takes the shared tokens
    and an extra refcount on the SAME physical block — the shared rows
    are bit-identical by the chain invariant, so no device copy happens
    at split time; the adopter's first divergent write copies the block
    through the normal COW drain.  The no-collision guarantee is
    unchanged: interning is an exact dict on (parent id, token run), and
    by induction a chain id corresponds to exactly one token chain.

    The index holds its own reference on every registered block (one
    per node — split siblings stack refs on a shared block, mirrored in
    ``_blk_ents``), so a retired request's prefix blocks survive for
    the next request until :meth:`evict_cold` / :meth:`spill_cold` (the
    OOM chain's first rung) or :meth:`close` releases them.  With
    ``PADDLE_TPU_KV_SPILL_MB`` set, :meth:`spill_cold` demotes cold
    block-aligned chains to host RAM instead of dropping them and
    :meth:`adopt_prefix` restores them on the next match — the restore
    rows ride :meth:`take_restores` to the caller's batched
    ``inject_rows`` scatter."""

    def __init__(self, num_blocks: int, block_size: int, nmax: int,
                 max_batch: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.N = int(num_blocks)
        self.bs = int(block_size)
        self.nmax = int(nmax)
        self.max_batch = int(max_batch)
        self.tables = np.full((max_batch, nmax), -1, np.int32)
        # pop() takes from the end: keep ids ascending-on-pop for
        # deterministic layouts in tests
        self._free = list(range(self.N - 1, -1, -1))
        self._ref = np.zeros(self.N, np.int64)
        self._blk_ents = np.zeros(self.N, np.int64)  # index entries per block
        self._prefix: dict = {}              # chain id -> _PrefixEntry
        self._interned: dict = {}            # (parent id, run) -> chain id
        self._children: dict = {}            # chain id -> {tok0 -> child id}
        self._next_chain = 1                 # 0 is the root sentinel
        self._pending_copies: list = []      # [(src, dst)] for copy_blocks
        self._tick = 0                       # LRU clock for the index
        self.dirty = True                    # tables need a device push
        self.radix_on = _flags.kv_radix()
        self.restore_on = _flags.kv_restore()
        self.spill_limit_bytes = _flags.kv_spill_mb() << 20
        self.spill_batch = _flags.kv_spill_batch()
        self.rss_limit_bytes = _flags.kv_spill_rss_mb() << 20
        self._spilled: dict = {}   # full chain tokens -> (host rows, nbytes)
        self._pending_restores: list = []    # [(slot, start, rows, block)]
        # host mirrors of the telemetry counters (tests read these
        # without the registry)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.cow_copies = 0
        self.peak_blocks_in_use = 0
        self.radix_splits = 0
        self.spilled_blocks = 0
        self.restored_blocks = 0
        self.host_spill_bytes = 0
        self.chain_migrations = 0
        self.rss_spills = 0

    # -- pool accounting ----------------------------------------------------

    @property
    def blocks_in_use(self) -> int:
        return self.N - len(self._free)

    def _alloc_block(self) -> int:
        """One block off the free list (ref 1) — every allocation path
        funnels through here."""
        if not self._free:
            raise PoolExhausted(1, self.N)
        b = self._free.pop()
        self._ref[b] = 1
        _telemetry.count("kv_pool.blocks_allocated")
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        return b

    def _decref_free(self, b: int) -> None:
        """Drop one reference; a block reaching zero returns to the free
        list — the single release path (slot retire, COW remap, prefix
        eviction all delegate here).  Pending COW pairs whose destination
        just died are discarded with it: a stale (src, dst) surviving
        into a later drain could copy into a REALLOCATED dst and corrupt
        another request's rows (the failure-path free between a COW and
        its _apply_pool_ops drain)."""
        self._ref[b] -= 1
        if self._ref[b] < 0:
            raise AssertionError(f"block {b} refcount went negative")
        if self._ref[b] == 0:
            self._free.append(b)
            if self._pending_copies:
                self._pending_copies = [p for p in self._pending_copies
                                        if p[1] != b]
            if self._pending_restores:
                # same rule for undrained restores: injecting into a
                # REALLOCATED block would corrupt another request's rows
                self._pending_restores = [r for r in
                                          self._pending_restores
                                          if r[3] != b]
            _telemetry.count("kv_pool.blocks_freed")

    def _cow_block(self, slot: int, li: int) -> int:
        """Copy-on-write: the slot is about to write into a block some
        other holder (another slot or the prefix index) also references
        — allocate a fresh block, queue the device copy, remap the table
        entry, and drop the shared reference."""
        src = int(self.tables[slot, li])
        dst = self._alloc_block()
        self._pending_copies.append((src, dst))
        self.tables[slot, li] = dst
        self._decref_free(src)
        self.dirty = True
        self.cow_copies += 1
        _telemetry.count("kv_pool.cow_copies")
        return dst

    def ensure_rows(self, slot: int, start: int, stop: int) -> None:
        """Make rows [start, stop) of ``slot`` writable: allocate
        unmapped logical blocks, copy-on-write shared ones.  Raises
        :exc:`PoolExhausted` when the free list runs dry (the caller's
        OOM chain evicts and retries); row indices clamp to the slot's
        logical window (block-decode overrun rows write nowhere, the
        slab path's masked-rows equivalent)."""
        if stop <= start:
            return
        lo = max(0, start // self.bs)
        hi = min(self.nmax - 1, (stop - 1) // self.bs)
        for li in range(lo, hi + 1):
            b = int(self.tables[slot, li])
            if b < 0:
                self.tables[slot, li] = self._alloc_block()
                self.dirty = True
            elif self._ref[b] > 1:
                self._cow_block(slot, li)

    def free_slot(self, slot: int) -> None:
        """Retire a slot: every mapped block loses the slot's reference
        (prefix-indexed blocks stay resident under the index's own
        ref)."""
        for li in range(self.nmax):
            b = int(self.tables[slot, li])
            if b >= 0:
                self._decref_free(b)
        self.tables[slot] = -1
        self.dirty = True

    def take_copies(self) -> list:
        """Drain the pending COW (src, dst) pairs for ``copy_blocks``."""
        out, self._pending_copies = self._pending_copies, []
        return out

    # -- radix prefix index -------------------------------------------------

    def adopt_prefix(self, slot: int, prompt) -> int:
        """Map the longest indexed TOKEN prefix of ``prompt`` into
        ``slot``'s table (one incref per mapped block) and return the
        shared row count, capped at ``len(prompt) - 1`` so admission
        always computes at least the last token's logits (a fully
        shared prompt COWs its final block on that one-row write).

        The walk descends the radix tree one node per step (children
        are keyed by first token, runs compared tokenwise — O(n) total
        over the prompt).  A node matching only partially is SPLIT at
        the divergence point (``PADDLE_TPU_KV_RADIX``) so the shared
        head still adopts; a missing child may instead be RESTORED from
        the host spill tier.  Hits/misses count in TOKEN rows: the
        hit-rate gauge is the fraction of adoptable rows admission did
        not have to recompute."""
        n = len(prompt)
        self._tick += 1
        matched = 0
        parent = 0
        deepest = {}                 # block index -> deepest node's block
        while matched < n:
            cid = self._children.get(parent, {}).get(prompt[matched])
            if cid is None and self.restore_on:
                cid = self._restore_spilled(slot, parent, prompt, matched)
            if cid is None:
                break
            ent = self._prefix[cid]
            run = ent.key[1]
            lim = min(len(run), n - matched)
            m = 0
            while m < lim and run[m] == prompt[matched + m]:
                m += 1
            if m == len(run):
                ent.last_hit = self._tick
                matched += m
                deepest[(ent.end - 1) // self.bs] = ent.block
                parent = cid
                continue
            # partial match: split iff it buys adoptable rows
            if self.radix_on and m and min(matched + m, n - 1) > matched:
                scid = self._split_entry(cid, m)
                sent = self._prefix[scid]
                sent.last_hit = self._tick
                matched += m
                deepest[(sent.end - 1) // self.bs] = sent.block
            break
        for bi, b in deepest.items():
            self._ref[b] += 1
            self.tables[slot, bi] = b
        if deepest:
            self.dirty = True
        shared = min(matched, n - 1)
        if shared > 0:
            self.prefix_hits += shared
            _telemetry.count("kv_pool.prefix_hits", shared)
        missed = (n - 1) - shared
        if missed > 0:
            self.prefix_misses += missed
            _telemetry.count("kv_pool.prefix_misses", missed)
        return shared

    def register_prefix(self, slot: int, prompt) -> None:
        """Index ``slot``'s full prompt blocks for future sharing (the
        index takes its own reference per node).  The owner never
        rewrites a full prompt block — decode writes start at
        ``len(prompt)`` — so registered blocks are immutable until
        released; partial tail blocks are never registered.  The walk
        descends existing nodes, splits at mid-run divergence (the new
        sibling is backed by the slot's own block) and interns the
        remainder one block-run per node — O(n) over the prompt."""
        self._tick += 1
        n_full = (len(prompt) // self.bs) * self.bs
        off = 0
        parent = 0
        while off < n_full:
            b = int(self.tables[slot, off // self.bs])
            if b < 0:
                break
            stop = (off // self.bs + 1) * self.bs
            run = tuple(prompt[off:stop])
            cid = self._children.get(parent, {}).get(run[0])
            if cid is None:
                key = (parent, run)
                cid = self._next_chain
                self._next_chain += 1
                self._interned[key] = cid
                self._prefix[cid] = _PrefixEntry(b, self._tick, key,
                                                 parent, stop)
                self._children.setdefault(parent, {})[run[0]] = cid
                self._blk_ents[b] += 1
                self._ref[b] += 1
                parent = cid
                off = stop
                continue
            ent = self._prefix[cid]
            erun = ent.key[1]
            # a node's run never crosses a block boundary, so erun fits
            # inside run's remainder
            m = 0
            while m < len(erun) and erun[m] == run[m]:
                m += 1
            if m == len(erun):
                ent.last_hit = self._tick
                parent = cid
                off += m
                continue
            if not (self.radix_on and m):
                # block-granular baseline: a mid-run divergence is a
                # stop (same-first-token siblings need the split)
                break
            parent = self._split_entry(cid, m)
            self._prefix[parent].last_hit = self._tick
            off += m

    def _split_entry(self, cid: int, m: int) -> int:
        """COW-split an indexed node at run offset ``m``: a new parent
        node takes tokens ``[:m]`` and an extra refcount on the SAME
        physical block (rows up to the split point are bit-identical by
        the chain invariant), while the deep node keeps its chain id
        with tokens ``[m:]`` — its descendants' parent pointers stay
        valid, so a split never orphans children.  No device copy
        happens here: the first writer adopting the split node sees the
        stacked refcount and copies through the normal COW drain.
        Returns the new parent's chain id."""
        ent = self._prefix[cid]
        run = ent.key[1]
        parent = ent.parent
        skey = (parent, run[:m])
        scid = self._next_chain
        self._next_chain += 1
        self._interned[skey] = scid
        self._prefix[scid] = _PrefixEntry(ent.block, self._tick, skey,
                                          parent,
                                          ent.end - (len(run) - m))
        self._blk_ents[ent.block] += 1
        self._ref[ent.block] += 1
        # re-key the deep node under the split node (same cid)
        del self._interned[ent.key]
        ent.key = (scid, run[m:])
        ent.parent = scid
        self._interned[ent.key] = cid
        self._children.setdefault(parent, {})[run[0]] = scid
        self._children[scid] = {run[m]: cid}
        self.radix_splits += 1
        _telemetry.count("kv_pool.radix_splits")
        return scid

    @property
    def prefix_entries(self) -> int:
        return len(self._prefix)

    def _drop_entry(self, cid: int) -> None:
        """Remove one index entry plus its intern record (and its
        parent's child-map slot) — the single removal path eviction,
        spill and close share, keeping entry/intern/children
        consistent."""
        ent = self._prefix.pop(cid)
        self._interned.pop(ent.key, None)
        pm = self._children.get(ent.parent)
        if pm is not None:
            tok0 = ent.key[1][0]
            if pm.get(tok0) == cid:
                del pm[tok0]
            if not pm:
                del self._children[ent.parent]
        self._blk_ents[ent.block] -= 1
        self._decref_free(ent.block)

    def _cold_leaves(self, max_entries: int | None) -> list:
        """Eviction/spill candidates: tree LEAVES no live slot
        references, coldest (LRU) first.  "No slot references" means
        every ref on the block is index-held (``_blk_ents`` — split
        siblings stack refs on one shared block); only leaves are
        candidates because dropping an inner node would orphan its
        descendants' chain ids."""
        cold = sorted(
            (ent.last_hit, cid) for cid, ent in self._prefix.items()
            if self._ref[ent.block] == self._blk_ents[ent.block]
            and not self._children.get(cid))
        return cold if max_entries is None else cold[:max_entries]

    def evict_cold(self, max_entries: int | None = None) -> int:
        """Drop cold prefix-cache leaves — the OOM retry chain's FIRST
        rung, and admission's last resort before parking a request back
        in the queue.  Returns the number of entries actually dropped.

        A cold inner block's whole subtree is cold too (a slot adopting
        a child block always adopted its parents), so repeated
        engagements drain chains tail-first."""
        freed = 0
        for _, cid in self._cold_leaves(max_entries):
            self._drop_entry(cid)
            freed += 1
        if freed:
            _telemetry.count("kv_pool.prefix_evictions", freed)
        return freed

    # -- host-RAM spill tier ------------------------------------------------

    def _chain_tokens(self, cid: int) -> tuple:
        """Full token chain of a node, root to ``cid`` — the spill-store
        key.  Parents are always live: only childless nodes are ever
        dropped."""
        parts = []
        while cid:
            ent = self._prefix[cid]
            parts.append(ent.key[1])
            cid = ent.parent
        return tuple(t for run in reversed(parts) for t in run)

    def spill_cold(self, max_entries: int | None = None,
                   fetch=None) -> int:
        """The evict-cold rung, spill-aware: demote cold block-aligned
        leaf chains to host RAM before freeing their blocks — ``fetch``
        (the caller's ONE batched ``device_get`` over the pool leaves)
        is called once per round with the block list and must return
        ``{leaf: [L, P, bs, ...]}``.  Entries falling outside the spill
        contract (mid-block split remnants, blocks with undrained
        copies/restores, past the ``PADDLE_TPU_KV_SPILL_BATCH`` cap or
        the ``PADDLE_TPU_KV_SPILL_MB`` budget) drop exactly as
        :meth:`evict_cold` would.  Returns entries freed (the OOM
        chain's contract)."""
        if fetch is None or not self.spill_limit_bytes:
            return self.evict_cold(max_entries)
        cold = self._cold_leaves(max_entries)
        if not cold:
            return 0
        # blocks whose device rows are not authoritative yet: pending
        # COW destinations and pending restore targets — spilling one
        # would capture garbage
        pend = {d for _, d in self._pending_copies}
        pend.update(r[3] for r in self._pending_restores)
        spill, drop = [], []
        for _, cid in cold:
            ent = self._prefix[cid]
            if (len(spill) < self.spill_batch and ent.end % self.bs == 0
                    and ent.block not in pend):
                spill.append(cid)
            else:
                drop.append(cid)
        if spill:
            rows = fetch([self._prefix[cid].block for cid in spill])
            kept = 0
            for j, cid in enumerate(spill):
                rec = {name: np.asarray(arr[:, j])
                       for name, arr in rows.items()}
                nb = sum(a.nbytes for a in rec.values())
                key = self._chain_tokens(cid)
                old = self._spilled.pop(key, None)
                if old is not None:
                    self.host_spill_bytes -= old[1]
                if self.host_spill_bytes + nb > self.spill_limit_bytes:
                    self._drop_entry(cid)    # over budget: plain drop
                    continue
                self._spilled[key] = (rec, nb)
                self.host_spill_bytes += nb
                self._drop_entry(cid)
                kept += 1
            if kept:
                self.spilled_blocks += kept
                _telemetry.count("kv_pool.spilled_blocks", kept)
        for cid in drop:
            self._drop_entry(cid)
        freed = len(spill) + len(drop)
        if freed:
            _telemetry.count("kv_pool.prefix_evictions", freed)
        return freed

    def rss_watchdog(self, rss_bytes: int | None = None) -> int:
        """Host-memory relief rung (``PADDLE_TPU_KV_SPILL_RSS_MB``):
        when the process resident set exceeds the threshold, release up
        to ``spill_batch`` entries — OLDEST host-spilled chains first
        (the spill store is the host tier this watchdog guards;
        insertion order is spill order, so the front of the dict is the
        LRU end), then cold device-index leaves through the plain
        :meth:`evict_cold` rung.  Bounded work per engagement: a server
        over the threshold sheds pressure across ticks instead of
        stalling one.  ``rss_bytes`` overrides the ``/proc`` read
        (tests; schedulers with their own sampler).  Returns entries
        released; counts ``kv_pool.rss_spills``."""
        if not self.rss_limit_bytes:
            return 0
        rss = _read_rss_bytes() if rss_bytes is None else int(rss_bytes)
        if rss <= self.rss_limit_bytes:
            return 0
        freed = 0
        while self._spilled and freed < self.spill_batch:
            key = next(iter(self._spilled))
            _, nb = self._spilled.pop(key)
            self.host_spill_bytes -= nb
            freed += 1
        if freed < self.spill_batch:
            freed += self.evict_cold(self.spill_batch - freed)
        if freed:
            self.rss_spills += freed
            _telemetry.count("kv_pool.rss_spills", freed)
        return freed

    def _restore_spilled(self, slot: int, parent: int, prompt,
                         matched: int):
        """Adoption-side promotion of one spilled chain block: re-intern
        the node on a fresh block and queue its host rows for the
        caller's batched ``device_put`` + ``inject_rows`` table scatter
        (:meth:`take_restores` — zero new executable families).  Chains
        restore block-by-block as the adopt walk descends.  Returns the
        new chain id, or None when nothing matches."""
        if not self._spilled or not self._free or matched % self.bs:
            return None
        end = matched + self.bs
        if end > len(prompt):
            return None
        item = self._spilled.pop(tuple(prompt[:end]), None)
        if item is None:
            return None
        rec, nb = item
        self.host_spill_bytes -= nb
        b = self._alloc_block()              # the index's own ref
        run = tuple(prompt[matched:end])
        key = (parent, run)
        cid = self._next_chain
        self._next_chain += 1
        self._interned[key] = cid
        self._prefix[cid] = _PrefixEntry(b, self._tick, key, parent, end)
        self._children.setdefault(parent, {})[run[0]] = cid
        self._blk_ents[b] += 1
        self._pending_restores.append((slot, matched, rec, b))
        self.restored_blocks += 1
        _telemetry.count("kv_pool.restored_blocks")
        return cid

    def take_restores(self) -> list:
        """Drain the pending restore records ``(slot, start_row, rows,
        block)`` for the caller's batched device_put + inject scatter
        (``serving._drain_restores``)."""
        out, self._pending_restores = self._pending_restores, []
        if out:
            _telemetry.count("kv_pool.restore_drains")
        return out

    # -- cross-replica chain migration --------------------------------------

    def migrate_out(self, prompt) -> list:
        """Detach every spilled chain that prefixes ``prompt`` for
        shipment to another replica's pool (the router calls this on
        every OTHER replica right before a dispatch, so a tenant's
        spilled KV follows its traffic to wherever prefix-aware
        routing now sends it).  A move, not a copy: the chains leave
        this pool's spill store and budget.  Returns wire-ready
        entries ``{"tokens": [...], "rows": {leaf: [L, bs, ...]}}`` —
        ndarray leaves, so the fleet codec ships them as raw buffer
        frames (``kv_pool.chain_migrations_out``)."""
        if not self._spilled:
            return []
        pl = tuple(int(t) for t in prompt)
        out = []
        for key in list(self._spilled):
            if len(key) <= len(pl) and pl[:len(key)] == key:
                rec, nb = self._spilled.pop(key)
                self.host_spill_bytes -= nb
                out.append({"tokens": list(key), "rows": rec})
        if out:
            _telemetry.count("kv_pool.chain_migrations_out", len(out))
        return out

    def migrate_in(self, entries) -> int:
        """Adopt migrated chains into THIS pool's spill store: the next
        admission's ``adopt_prefix`` walk promotes them through
        :meth:`_restore_spilled` → the caller's batched ``device_put``
        + ``inject_rows`` scatter — the exact restore path local spill
        uses, so migrated rows land bit-identically to rows this
        replica spilled itself.  Entries over the host budget drop
        (the prompt recomputes, never corrupts).  Returns chains kept
        (``kv_pool.chain_migrations``)."""
        added = 0
        for ent in entries:
            key = tuple(int(t) for t in ent["tokens"])
            if not key or len(key) % self.bs:
                continue          # not a block-aligned chain: refuse
            rec = {name: np.asarray(v)
                   for name, v in ent["rows"].items()}
            nb = sum(a.nbytes for a in rec.values())
            old = self._spilled.pop(key, None)
            if old is not None:
                self.host_spill_bytes -= old[1]
            if self.spill_limit_bytes \
                    and self.host_spill_bytes + nb \
                    > self.spill_limit_bytes:
                continue
            self._spilled[key] = (rec, nb)
            self.host_spill_bytes += nb
            added += 1
        if added:
            self.chain_migrations += added
            _telemetry.count("kv_pool.chain_migrations", added)
        return added

    # -- routing summary ----------------------------------------------------

    def prefix_summary(self, max_roots: int = 16) -> list:
        """Compact shape of the index for prefix-aware routing: per
        root-fanout subtree, ``(run_len, fingerprint, resident_tokens)``
        — the router matches a prompt's head against the fingerprint and
        uses resident tokens as the expected-overlap bound.  Top
        ``max_roots`` subtrees by resident tokens."""
        out = []
        for cid in self._children.get(0, {}).values():
            run = self._prefix[cid].key[1]
            resident = 0
            stack = [cid]
            while stack:
                c = stack.pop()
                resident += len(self._prefix[c].key[1])
                stack.extend(self._children.get(c, {}).values())
            out.append((len(run), prefix_fingerprint(run), resident))
        out.sort(key=lambda t: (-t[2], t[1]))
        return out[:max_roots]

    def close(self) -> None:
        """Release the whole index, every table, and the spill store
        (server shutdown)."""
        for cid in list(self._prefix):
            if cid in self._prefix:
                self._drop_entry(cid)
        for slot in range(self.max_batch):
            if (self.tables[slot] >= 0).any():
                self.free_slot(slot)
        self._spilled.clear()
        self._pending_restores.clear()
        self.host_spill_bytes = 0

    def stats(self) -> dict:
        return {
            "num_blocks": self.N, "block_size": self.bs,
            "blocks_in_use": self.blocks_in_use,
            "peak_blocks_in_use": self.peak_blocks_in_use,
            "prefix_entries": self.prefix_entries,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "cow_copies": self.cow_copies,
            "radix_splits": self.radix_splits,
            "spilled_blocks": self.spilled_blocks,
            "restored_blocks": self.restored_blocks,
            "spilled_entries": len(self._spilled),
            "host_spill_bytes": self.host_spill_bytes,
            "chain_migrations": self.chain_migrations,
        }
