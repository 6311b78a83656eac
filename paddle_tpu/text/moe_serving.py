"""MoE serving: expert-parallel decode through the Engine (round 19).

``text/moe.py`` gave the framework GShard-style expert layers for
training; this module makes MoE targets SERVABLE.  Three pieces:

* **Joint-routing step bodies** — ``moe_decode_step_batched`` (and its
  sample/block/async twins, registered as Engine kinds in
  ``text/engine.py``) run the batch's slot tokens through the expert FFN
  in ONE routing call per layer: attention stays per-slot (the shared
  ``generate._block_pre_attn`` half, vmapped over slots exactly like the
  dense step), but ``generate._block_post_attn`` is called once on the
  whole [B, 1, D] batch with ``valid=act`` (the occupied-slot mask, a
  runtime input — free and mid-admission slots claim NO expert capacity)
  and ``capacity=None`` (the CONFIGURED capacity-factor bound, not the
  prefill path's dropless override).  Under pjit with the expert dim
  sharded P('ep', ...) the dispatch/combine einsums inside
  ``moe.moe_ffn`` lower to all_to_all over the ``ep`` axis — token→expert
  dispatch and combine run INSIDE the jitted step.

* **Device-side drop accounting** — every step threads a
  ``{"dropped": int32, "load": int32 [E]}`` accumulator (built by
  :func:`moe_stats_init`) through the jit like the cache: the routing
  delta is computed from the dispatch mask itself (``moe.moe_ffn``'s
  ``with_stats``), so ``moe.dropped_tokens`` / ``moe.expert_load`` report
  what the device ACTUALLY dropped, not a host estimate.
  :func:`drain_drop_stats` publishes the counters.

* **Regex partition rules** — :func:`match_partition_rules` +
  :func:`moe_decode_rules` generalize ``generate._decode_param_specs``
  to cover the ``moe_param_shardings`` leaves with an explicit,
  mesh-aware ``ep`` axis (the EasyLM/named-shard idiom: first matching
  regex wins, scalars replicate, no match is an error).  On dense leaves
  the table is pinned equal to ``_decode_param_specs`` by test.

Routing semantics worth knowing (documented, test-pinned):

* A single occupied slot can never drop for ANY capacity factor: one
  token claims at most one capacity slot per expert and C >= 1.
* At a dropless capacity factor (cf >= E / top_k, i.e. C >= B) the
  joint step's tokens equal per-slot solo routing token-for-token, so
  {tick, block, async} x {contiguous, paged} all match the densely
  evaluated reference.
* Below the dropless bound, batch-mates contend for capacity — tick
  and block schedules may then legitimately differ (a block keeps
  retired slots contending until the host fetch); drop-accounting
  tests therefore pin the tick path.

The dense-eval REFERENCE (:func:`dense_eval_decode_step` /
:func:`dense_reference_greedy`) computes every expert for every token
and mixes with the renormalized top-k gate weights — the capacity-free
ground truth the Engine-served tokens are pinned against.  It runs
EAGERLY on purpose: references must not populate (or depend on) the
step cache they are auditing, and the ENGINE lint keeps ``jax.jit``
out of this module anyway.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from . import generate, gpt, moe, woq
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# regex partition rules (SNIPPETS.md [1] shape, own implementation)
# ---------------------------------------------------------------------------


def match_partition_rules(rules, tree, sep: str = "/"):
    """Resolve a PartitionSpec per leaf of ``tree`` by regex table.

    ``rules`` is an ordered list of ``(pattern, PartitionSpec)``; each
    leaf's ``sep``-joined key path is matched with ``re.search`` and the
    FIRST hit wins.  Scalar (ndim 0) leaves short-circuit to replicated
    — partitioning a scalar is never meaningful.  A leaf no rule covers
    raises ``ValueError`` naming it: silent replication of a tensor the
    table forgot is exactly the bug regex tables exist to surface."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]

    def _name(path):
        out = []
        for kp in path:
            out.append(str(getattr(kp, "key", getattr(kp, "idx", kp))))
        return sep.join(out)

    specs = {}
    for path, leaf in flat:
        name = _name(path)
        if getattr(leaf, "ndim", 0) == 0:
            specs[name] = P()
            continue
        for pat, spec in rules:
            if re.search(pat, name):
                specs[name] = spec
                break
        else:
            raise ValueError(
                f"no partition rule matches param {name!r} — extend "
                f"moe_decode_rules (silent replication would hide a "
                f"sharding bug)")
    # rebuild the tree shape from the resolved dict
    treedef = jax.tree_util.tree_structure(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [specs[_name(p)] for p, _ in flat])


def moe_decode_rules(cfg: gpt.GPTConfig, mp: str | None = "mp",
                     ep: str | None = None):
    """The decode-param rule table: dense leaves carry EXACTLY the
    ``generate._decode_param_specs`` placements (Megatron column/row,
    scales and LoRA pairs replicated, vocab-parallel embedding) and the
    ``blocks/moe/*`` leaves carry ``moe.moe_param_shardings`` with the
    caller's ``ep``/``mp`` axes — ``ep=None`` replicates the expert dim
    (pure-TP serving of an MoE model), a named axis shards experts over
    it (expert parallelism, composing with ``mp`` inside each expert).

    Order matters: quantization scales and LoRA pairs match before the
    weight rules so ``w_in_s`` never takes ``w_in``'s spec."""
    l = None  # decode params have no pipeline axis
    rules = [
        # quant scales + LoRA low-rank pairs: replicated, highest priority
        (r"_s$", P()),
        (r"_lora_[ab]$", P()),
        # expert leaves (stacked per layer: leading L axis unsharded)
        (r"blocks/moe/router_w$", P(l, None, None)),
        (r"blocks/moe/w_in$", P(l, ep, None, mp)),
        (r"blocks/moe/b_in$", P(l, ep, mp)),
        (r"blocks/moe/w_out$", P(l, ep, mp, None)),
        (r"blocks/moe/b_out$", P(l, ep, None)),
        # dense block leaves — generate._decode_param_specs's placements
        (r"blocks/ln[12]_[gb]$", P(l, None)),
        (r"blocks/qkv_w$", P(l, None, None, mp)),
        (r"blocks/qkv_b$", P(l, None, mp)),
        (r"blocks/q_w$", P(l, None, mp)),
        (r"blocks/q_b$", P(l, mp)),
        (r"blocks/kv_w$", P(l, None, None, mp)),
        (r"blocks/kv_b$", P(l, None, mp)),
        (r"blocks/proj_w$", P(l, mp, None)),
        (r"blocks/proj_b$", P(l, None)),
        (r"blocks/fc_w$", P(l, None, mp)),
        (r"blocks/fc_b$", P(l, mp)),
        (r"blocks/gate_w$", P(l, None, mp)),
        (r"blocks/gate_b$", P(l, mp)),
        (r"blocks/out_w$", P(l, mp, None)),
        (r"blocks/out_b$", P(l, None)),
        # top-level leaves
        (r"^wte$", P(mp, None)),
        (r"^wpe$", P(None, None)),
        (r"^ln_f_[gb]$", P(None)),
    ]
    return rules


def moe_decode_param_specs(params, cfg: gpt.GPTConfig, mp: str = "mp",
                           ep: str | None = None):
    """A PartitionSpec tree for ``params`` resolved through the regex
    table — the ``_decode_param_specs`` generalization the _ShardCtx
    uses for MoE configs.  Dense-leaf equality with the legacy resolver
    is pinned by test (same tree for any dense model)."""
    return match_partition_rules(moe_decode_rules(cfg, mp=mp, ep=ep),
                                 params)


# ---------------------------------------------------------------------------
# device-side routing stats
# ---------------------------------------------------------------------------


def moe_stats_init(num_experts: int):
    """The device accumulator every MoE step threads like the cache:
    cumulative dropped token→expert assignments plus per-expert kept
    load, int32 (x64 is disabled process-wide)."""
    return {"dropped": jnp.zeros((), jnp.int32),
            "load": jnp.zeros((int(num_experts),), jnp.int32)}


def drain_drop_stats(stats, counted: int = 0, tel: bool = True):
    """Fetch the accumulator to host and publish the ``moe.*``
    telemetry: ``moe.dropped_tokens`` counts the DELTA since the last
    drain (``counted`` — the caller keeps the high-water mark so the
    counter is monotone and exact), ``moe.expert_load`` gauges report
    each expert's cumulative kept assignments.

    Returns ``(dropped_total, load_list)`` host ints."""
    st = jax.device_get(stats)
    dropped = int(st["dropped"])
    load = [int(v) for v in st["load"]]
    if tel:
        delta = dropped - int(counted)
        if delta > 0:
            _telemetry.count("moe.dropped_tokens", delta)
        for e, n in enumerate(load):
            _telemetry.set_gauge(f"moe.expert_load{{expert={e}}}", n)
    return dropped, load


# ---------------------------------------------------------------------------
# joint-routing decode steps (the Engine's moe_* kind bodies)
# ---------------------------------------------------------------------------


def moe_decode_step_batched(params, cache, token, pos, act, stats,
                            cfg: gpt.GPTConfig):
    """``serving.decode_step_batched`` with JOINT expert routing: token
    [B] int32, pos [B] int32, ``act`` [B] bool (occupied-slot mask),
    ``stats`` the :func:`moe_stats_init` accumulator ->
    (logits [B, V] fp32, cache, stats').

    Attention is the dense step's math exactly — per-slot
    ``_block_pre_attn`` + splice-then-attend, vmapped over slots — but
    each layer's FFN tail runs ONCE over the whole batch:
    ``_block_post_attn(valid=act, capacity=None)`` routes the B tokens
    together under C = ceil(B * top_k / E * cf), with inactive slots
    masked out of routing, capacity, and the load statistics.  A pooled
    cache (``tables`` leaf) routes to the paged twin — the same
    structure-branch the dense step uses."""
    if "tables" in cache:
        return _moe_paged_step_batched(params, cache, token, pos, act,
                                       stats, cfg)
    dt = cfg.dtype

    def embed_one(tok_b, pos_b):
        return generate._embed_step(params, tok_b[None], pos_b, cfg)

    x = jax.vmap(embed_one)(token, pos)                  # [B, 1, 1, D]

    def body(carry, layer):
        x, stats = carry
        p, csl = layer          # csl leaves [B, T, Hkv(, hd)]
        csl1 = {n: v[:, None] for n, v in csl.items()}   # [B, 1, T, ...]

        def pre(xb, cslb, pos_b):
            q3, rows = generate._block_pre_attn(xb, p, pos_b, cfg)
            full = {n: jax.lax.dynamic_update_slice(
                        cslb[n], v[:, None],
                        (0, pos_b) + (0,) * (cslb[n].ndim - 2))
                    for n, v in rows.items()}
            return generate._attend_cache(q3, full, pos_b, cfg), rows

        attn, rows = jax.vmap(pre)(x, csl1, pos)
        # joint FFN: ONE routing call over the batch's B tokens
        x2, stats = generate._block_post_attn(
            x[:, 0], attn[:, 0], p, cfg, valid=act, capacity=None,
            stats=stats)
        return (x2[:, None], stats), rows

    (x, stats), rows = jax.lax.scan(body, (x, stats),
                                    (params["blocks"], cache))
    # rows leaves [L, B, 1, Hkv(, hd)] -> per-slot frontier write
    new_cache = generate._write_rows_batched(cache, rows, pos)
    x = gpt._norm(x[:, 0], params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)[:, 0]
    return logits.astype(jnp.float32), new_cache, stats


def _moe_paged_step_batched(params, cache, token, pos, act, stats,
                            cfg: gpt.GPTConfig):
    """Paged twin of :func:`moe_decode_step_batched`: per-slot attention
    over table-gathered views (splice-then-attend on the view, exactly
    ``kv_pool.paged_decode_step_batched``'s fallback route), joint FFN
    per layer, one `_scatter_rows` through the tables at the end.  The
    einsum attention route serves every backend; the flash paged kernel
    stays dense-serving-only for now (its layer loop composes the same
    way — ROADMAP follow-up)."""
    from . import kv_pool

    N, bs, nmax = kv_pool._geometry(cache)
    B = token.shape[0]
    dt = cfg.dtype
    tables = cache["tables"]
    pool = {n: cache[n] for n in kv_pool.POOL_LEAVES if n in cache}

    def embed_one(tok_b, pos_b):
        return generate._embed_step(params, tok_b[None], pos_b, cfg)

    x = jax.vmap(embed_one)(token, pos)                  # [B, 1, 1, D]

    def body(carry, layer):
        x, stats = carry
        p, li = layer

        def pre(xb, pos_b, trow):
            csl = kv_pool._gather_slot(pool, li, trow, cfg)  # [1, T, ...]
            q3, rows = generate._block_pre_attn(xb, p, pos_b, cfg)
            full = {n: jax.lax.dynamic_update_slice(
                        csl[n], v[:, None],
                        (0, pos_b) + (0,) * (csl[n].ndim - 2))
                    for n, v in rows.items()}
            return generate._attend_cache(q3, full, pos_b, cfg), rows

        attn, rows = jax.vmap(pre)(x, pos, tables)
        x2, stats = generate._block_post_attn(
            x[:, 0], attn[:, 0], p, cfg, valid=act, capacity=None,
            stats=stats)
        return (x2[:, None], stats), rows

    (x, stats), rows = jax.lax.scan(
        body, (x, stats), (params["blocks"], jnp.arange(cfg.num_layers)))
    # rows leaves [L, B, 1, Hkv(, hd)]; physical row per slot through the
    # table (unmapped -> out of bounds -> dropped, the slab clamp twin)
    tb = tables[jnp.arange(B), pos // bs]
    phys = jnp.where(tb >= 0, tb * bs + pos % bs, N * bs)
    new_cache = kv_pool._scatter_rows(
        cache, {n: v[:, :, 0] for n, v in rows.items()}, phys)
    x = gpt._norm(x[:, 0], params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)[:, 0]
    return logits.astype(jnp.float32), new_cache, stats


def moe_sample_step_batched(params, cache, tok, pos, key, temp, topk,
                            topp, act, stats, cfg: gpt.GPTConfig):
    """Sampling twin: joint-routing step + the shared per-slot sampler
    (``serving._sample_batched`` — same pipeline, same key schedule as
    the dense path) -> (tokens [B], cache, stats')."""
    from . import serving

    logits, cache, stats = moe_decode_step_batched(params, cache, tok,
                                                   pos, act, stats, cfg)
    return (serving._sample_batched(logits, key, temp, topk, topp),
            cache, stats)


def moe_decode_block_batched(params, cache, tok, pos, act, stats, k: int,
                             cfg: gpt.GPTConfig):
    """``k`` greedy joint-routing steps on device, one host fetch (the
    ``decode_block_batched`` twin).  ``act`` is the DISPATCH-time
    occupancy: a slot retiring mid-block keeps contending for capacity
    until the fetch (the standard block-overrun tradeoff — at a dropless
    capacity factor this is unobservable, which is why block-mode parity
    is asserted there and drop accounting pins the tick path).
    Returns (tokens [B, k], cache, next_tok [B], next_pos [B], stats')."""
    def body(carry, _):
        cache, tok, pos, stats = carry
        logits, cache, stats = moe_decode_step_batched(
            params, cache, tok, pos, act, stats, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt, pos + 1, stats), nxt

    (cache, tok, pos, stats), toks = jax.lax.scan(
        body, (cache, tok, pos, stats), None, length=k)
    return toks.T, cache, tok, pos, stats


# ---------------------------------------------------------------------------
# densely-evaluated reference (all experts, gate-weighted) — the parity
# ground truth.  Eager by design: the reference must not touch the step
# caches it audits (and jax.jit is lint-banned outside engine.py).
# ---------------------------------------------------------------------------


def _dense_eval_ffn_tail(x, p, cfg: gpt.GPTConfig):
    """The capacity-free MoE tail: EVERY expert computed for every
    token, mixed by the renormalized top-k gate weights (non-top-k
    weights exactly zero).  At a dropless capacity the routed tail
    computes the same sum in a different einsum order — token-level
    equality is what the parity tests pin."""
    mcfg = cfg.moe
    dt = x.dtype
    h = gpt._norm(x, p, "ln2", cfg)
    orig = h.shape
    D = orig[-1]
    xf = h.reshape(-1, D)
    n_tok = xf.shape[0]
    E = mcfg.num_experts
    logits = xf.astype(jnp.float32) @ p["moe"]["router_w"]
    w, idx, _probs = moe._top_k_gating(logits, mcfg.top_k)
    n_ix = jnp.arange(n_tok)[:, None].repeat(mcfg.top_k, 1)
    wfull = jnp.zeros((n_tok, E), jnp.float32).at[n_ix, idx].add(w)
    w_in = woq.w(p["moe"], "w_in", dt)                   # [E, D, F]
    w_out = woq.w(p["moe"], "w_out", dt)                 # [E, F, D]
    he = jax.nn.gelu(jnp.einsum("nd,edf->nef", xf, w_in)
                     + p["moe"]["b_in"][None].astype(dt))
    ye = jnp.einsum("nef,efd->ned", he, w_out) \
        + p["moe"]["b_out"][None].astype(dt)
    y = jnp.einsum("ne,ned->nd", wfull.astype(dt), ye)
    return x + y.reshape(orig)


def dense_eval_decode_step(params, cache, token, pos, cfg: gpt.GPTConfig):
    """``generate.decode_step`` with the expert FFN densely evaluated —
    token [B] int32 at scalar ``pos`` -> (logits [B, V] fp32, cache).
    Attention reuses the shared decode halves verbatim (MoE changes
    nothing above the FFN tail)."""
    if cfg.moe is None:
        raise ValueError("dense_eval_decode_step is the MoE reference — "
                         "use generate.decode_step for dense models")
    dt = cfg.dtype
    x = generate._embed_step(params, token, pos, cfg)

    def body(x, layer):
        p, csl = layer
        q3, rows = generate._block_pre_attn(x, p, pos, cfg)
        full = {n: jax.lax.dynamic_update_slice(
                    csl[n], v[:, None],
                    (0, pos) + (0,) * (csl[n].ndim - 2))
                for n, v in rows.items()}
        attn = generate._attend_cache(q3, full, pos, cfg)
        a = woq.mm(attn, p, "proj_w", dt) + p["proj_b"].astype(dt)
        return _dense_eval_ffn_tail(x + a, p, cfg), rows

    x, rows = jax.lax.scan(body, x, (params["blocks"], cache))
    new_cache = generate._write_rows(cache, rows, pos)
    x = gpt._norm(x, params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)[:, 0]
    return logits.astype(jnp.float32), new_cache


def dense_reference_greedy(params, cfg: gpt.GPTConfig, prompt,
                           max_new: int, max_len: int,
                           eos_id: int | None = None) -> list:
    """Greedy continuation of ONE prompt under the dense-eval reference:
    a solo contiguous cache fed token-by-token (the capacity-free ground
    truth — no batching, no paging, no Engine executables).  Returns the
    generated token list (stops at ``eos_id`` like the server)."""
    cache = generate.init_cache(cfg, 1, max_len)
    toks = [int(t) for t in prompt]
    for i in range(len(toks) - 1):
        _, cache = dense_eval_decode_step(
            params, cache, jnp.asarray([toks[i]], jnp.int32), i, cfg)
    feed, pos = toks[-1], len(toks) - 1
    out: list = []
    for _ in range(int(max_new)):
        logits, cache = dense_eval_decode_step(
            params, cache, jnp.asarray([feed], jnp.int32), pos, cfg)
        feed = int(jnp.argmax(logits[0]))
        out.append(feed)
        pos += 1
        if eos_id is not None and feed == eos_id:
            break
    return out
