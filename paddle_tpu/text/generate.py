"""Autoregressive GPT generation with a KV cache.

Beyond-reference capability (the v2.1 reference ships no generate API): a
TPU-first decode loop — the whole generation is ONE ``lax.scan`` over
positions with per-layer K/V caches updated via ``dynamic_update_slice``,
so XLA compiles a single program per (batch, max_len) and every decode step
is a fixed-shape cached-attention block (no re-running the prefix).

Works with the dense `gpt.GPTConfig` models (tied embeddings); sampling is
greedy or temperature/top-k off an explicit PRNG key.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import engine as _engine
from . import gpt, woq
from .. import flags as _flags

__all__ = ["init_cache", "decode_step", "generate"]


def _kv_store_dtype(cfg: gpt.GPTConfig):
    """The cache STORAGE dtype (flags.kv_cache_dtype): '' = the model's
    compute dtype (the default, pre-flag behavior)."""
    name = _flags.kv_cache_dtype()
    if name == "fp32":
        return jnp.float32
    if name == "bf16":
        return jnp.bfloat16
    if name == "int8":
        return jnp.int8
    return cfg.dtype


def _round_cache_len(n: int) -> int:
    """Round a cache length up to a flash-decode-tileable size (8-multiple
    up to 512, 128-multiple beyond): the row count is pure ALLOCATION —
    the causal mask hides rows past the write position — so padding a few
    rows costs a sliver of HBM while an unaligned length would silently
    pin every decode of that cache on the einsum fallback (callers pass
    arbitrary prompt+max_new totals)."""
    n = max(int(n), 1)
    if n <= 512:
        return -(-n // 8) * 8
    return -(-n // 128) * 128


def init_cache(cfg: gpt.GPTConfig, batch: int, max_len: int,
               layout: str = "contiguous", block_size: int | None = None,
               num_blocks: int | None = None):
    """Per-layer K/V cache [L, B, T, Hkv, hd] with T = ``max_len`` rounded
    up to a kernel-tileable length (_round_cache_len — extra rows stay
    masked); the caller tracks the write position (generate's scan
    carries it implicitly).  Under GQA (cfg.num_kv_heads) the cache holds
    only the Hkv shared heads — the num_heads/Hkv decode-memory saving is
    the feature's point.

    ``PADDLE_TPU_KV_DTYPE`` selects the storage dtype; int8 caches carry
    per-(position, head) fp32 scale planes ``k_s``/``v_s``
    [L, B, T, Hkv] beside the values (~hd x smaller), written by
    the same row writes and dequantized at the attention site (inside
    the flash-decode kernel, or before the XLA einsum).

    ``layout="paged"`` returns the pooled format instead (text/kv_pool:
    value leaves [L, num_blocks, block_size, Hkv, hd] + an int32
    ``tables`` leaf [batch, nmax], same pytree API — HBM scales with
    blocks actually mapped, not worst-case context).  The serving layer
    owns the allocator; the contiguous slab stays the default
    (``PADDLE_TPU_KV_LAYOUT`` flips ``DecodeServer``'s default)."""
    if layout == "paged":
        from . import kv_pool

        return kv_pool.init_paged_cache(cfg, batch, max_len,
                                        block_size=block_size,
                                        num_blocks=num_blocks)
    if layout not in ("contiguous", None, ""):
        raise ValueError(
            f"layout {layout!r}: expected 'contiguous' or 'paged'")
    if cfg.ssm is not None:
        raise NotImplementedError(
            "a config with an ssm mixer decodes through the paged cache "
            "only (layout='paged'): the contiguous slab has no per-slot "
            "recurrent state leaves")
    if cfg.mla is not None:
        raise NotImplementedError(
            "a latent-attention config decodes through the paged cache "
            "only (layout='paged'): the contiguous slab has no latent "
            "row format")
    if cfg.layer_types is not None:
        raise NotImplementedError(
            "a layer pattern decodes through the paged cache only "
            "(layout='paged'): the contiguous slab's leaves are as deep "
            "as the model, not as a kind of layer")
    L, H, hd = cfg.num_layers, cfg.kv_heads, cfg.head_dim
    dt = _kv_store_dtype(cfg)
    shape = (L, batch, _round_cache_len(max_len), H, hd)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if dt == jnp.int8:
        cache["k_s"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_s"] = jnp.zeros(shape[:-1], jnp.float32)
    return cache


def _store_rows(k_rows, v_rows, cfg: gpt.GPTConfig) -> dict:
    """Compute-dtype K/V rows [..., Hkv, hd] → cache-storage leaves (the
    dict mirrors the cache structure minus the time axis handling): int8
    quantizes per-(row, head) and adds the scale leaves."""
    from ..ops import decode_attention as da

    dt = _kv_store_dtype(cfg)
    if dt == jnp.int8:
        qk, sk = da.quantize_kv(k_rows)
        qv, sv = da.quantize_kv(v_rows)
        return {"k": qk, "v": qv, "k_s": sk, "v_s": sv}
    return {"k": k_rows.astype(dt), "v": v_rows.astype(dt)}


def _use_decode_kernel(cfg: gpt.GPTConfig, q_shape, kv_shape) -> bool:
    """Route this cached-attention site through the split-KV Pallas
    kernel?  Flag + backend/shape gate (ops/decode_attention.available).
    False keeps the site on its original einsum math — bit-identical to
    pre-kernel behavior (and the only path off-TPU outside interpret
    tests)."""
    from ..ops import decode_attention as da

    return _flags.flash_decode() and da.available(q_shape, kv_shape)


def _attend_cache(q, full, pos, cfg: gpt.GPTConfig):
    """Cached attention for a Tq-row query block against one layer's
    cache slice ``full`` (rows through the current positions already
    written): q [B, Tq, H, hd], full leaves k/v [B, T, Hkv, hd]
    (+ scales), row i of batch b attends rows t <= pos + i.  Returns
    [B, Tq, H*hd] in the compute dtype.

    Kernel path: ops/decode_attention (GQA-aware split-KV streaming,
    int8 dequant in-kernel).  Fallback: the original grouped einsum —
    int8 caches dequantize via the shared helper first."""
    with jax.named_scope("attn"):
        B, Tq, H, hd = q.shape
        dt = cfg.dtype
        k_all, v_all = full["k"], full["v"]
        ks, vs = full.get("k_s"), full.get("v_s")
        if _use_decode_kernel(cfg, q.shape, k_all.shape):
            from ..ops import decode_attention as da

            pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
            out = da.decode_attention(q, k_all, v_all, pos_b,
                                      k_scale=ks, v_scale=vs,
                                      scale=cfg.attention_multiplier)
            return out.astype(dt).reshape(B, Tq, H * hd)
        if ks is not None:
            from ..ops import decode_attention as da

            k_all = da.dequantize_kv(k_all, ks, dt)
            v_all = da.dequantize_kv(v_all, vs, dt)
        # a non-compute storage dtype (fp32/bf16 flag) joins the einsums in
        # the COMPUTE dtype — the residual stream's dtype is a scan-carry
        # invariant, and mixed-dtype einsums would silently promote it
        k_all = k_all.astype(dt)
        v_all = v_all.astype(dt)
        T = k_all.shape[1]
        Hkv = k_all.shape[2]
        g = H // Hkv
        qg = q.reshape(B, Tq, Hkv, g, hd)
        scores = jnp.einsum("bikgd,btkd->bkgit", qg, k_all)
        if cfg.attention_multiplier is None:
            scores = scores / jnp.sqrt(
                jnp.asarray(hd, jnp.float32)).astype(dt)
        else:       # a stated softmax scale (a pattern config's)
            scores = scores * jnp.asarray(cfg.attention_multiplier, dt)
        mask = (jnp.arange(T)[None, :]
                <= pos + jnp.arange(Tq)[:, None])[None, None, None]
        scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        return jnp.einsum("bkgit,btkd->bikgd", w, v_all).reshape(B, Tq, -1)


def _embed_step(params, token, pos, cfg: gpt.GPTConfig):
    """Embed one decode step's tokens [B] at position ``pos`` ->
    [B, 1, D] — the single embed+wpe shared by the contiguous decode
    step and the paged (kv_pool) routes."""
    x = woq.embed(params, token, cfg.dtype,
                  cfg.embedding_multiplier)[:, None]
    if cfg.pos_embed == "learned":
        x = x + jax.lax.dynamic_slice(
            params["wpe"], (pos, 0),
            (1, cfg.hidden_size)).astype(cfg.dtype)[None]
    return x


def _block_pre_attn(x, p, pos, cfg: gpt.GPTConfig, h=None):
    """Pre-attention half of one decode block on a single position
    [B, 1, D]: ln1 -> qkv projection (the Hkv heads kept, never
    repeated) -> rope at ``pos`` -> storage-dtype rows.  Returns
    (q3, rows); every cached-decode route (contiguous AND paged kernel)
    shares this, so the per-layer math can never drift between them.
    ``h``: the block's normed input where the caller already has it (a
    parallel mixer reads the same one)."""
    B = x.shape[0]
    hd = cfg.head_dim
    if h is None:
        h = gpt._norm(x, p, "ln1", cfg)
    q3, k3, v3 = gpt._project_qkv(h, p, cfg, repeat_kv=False)
    if cfg.pos_embed == "rope":
        # rotate q and the NEW key row at this position; the cache holds
        # already-rotated keys (rope's relative-offset property makes
        # them valid forever)
        pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
        q3 = gpt.apply_rope(q3, pos_arr, cfg.rope_theta)
        k3 = gpt.apply_rope(k3, pos_arr, cfg.rope_theta)
    k_new = k3.reshape(B, -1, hd)   # Hkv rows under GQA, H otherwise
    v_new = v3.reshape(B, -1, hd)
    return q3, _store_rows(k_new, v_new, cfg)


def _block_post_attn(x, attn, p, cfg: gpt.GPTConfig, valid=None,
                     capacity=gpt._LEGACY, stats=None, mix=None):
    """Post-attention half: output projection + residual + FFN tail
    (the other shared side of :func:`_block_pre_attn`).  The MoE serving
    step calls this ONCE for the whole batch (``valid``/``capacity``/
    ``stats`` forwarded to :func:`gpt._ffn_tail`) so the slot tokens
    route jointly under the configured capacity factor — the same layer
    math as the dense route, a different token grouping.  ``mix``: the
    parallel ssm mixer's output on the same normed input, added to the
    residual beside the attention's (h + attn + ssm)."""
    a = gpt._attn_out(attn, p, cfg)
    if mix is not None:
        a = a + mix
    return gpt._ffn_tail(x + a, p, cfg, valid=valid, capacity=capacity,
                         stats=stats)


def _cached_block(x, p, csl, pos, cfg: gpt.GPTConfig, state=None):
    """One block on a SINGLE position [B, 1, D] against one layer's cache
    slice ``csl`` (leaves k/v [B, T, Hkv, hd], plus scales for int8).
    Returns (x, rows): storage-dtype row leaves for the caller to write
    at pos.  ``state`` (a config with a parallel ssm mixer): this layer's
    recurrent state, advanced by the position; the call then returns
    (x, rows, new_state)."""
    h = mix = None
    if state is not None:
        from . import ssm as _ssm

        h = gpt._norm(x, p, "ln1", cfg)
        mix, state = _ssm.mixer_step(h, p, cfg, state)
    q3, rows = _block_pre_attn(x, p, pos, cfg, h=h)
    # attend over cache rows [B, max_len, Hkv, hd] with the fresh row at
    # pos — spliced in STORAGE form, so what this step attends is exactly
    # what later steps will read back (int8 included)
    full = {name: jax.lax.dynamic_update_slice(
                csl[name], val[:, None],
                (0, pos) + (0,) * (csl[name].ndim - 2))
            for name, val in rows.items()}
    attn = _attend_cache(q3, full, pos, cfg)           # [B, 1, H * hd]
    out = _block_post_attn(x, attn, p, cfg, mix=mix)
    return (out, rows) if state is None else (out, rows, state)


def _write_rows(cache: dict, rows: dict, pos) -> dict:
    """Write stacked per-layer rows (leaves [L, B, P?, Hkv(, hd)]) into
    the cache at time index ``pos`` — the single row-write every decode/
    verify path funnels through.  Rows without a time axis (single-token
    decode: [L, B, Hkv(, hd)]) get one inserted."""
    out = {}
    for name, val in rows.items():
        arr = cache[name]
        if val.ndim == arr.ndim - 1:
            val = jnp.expand_dims(val, 2)
        out[name] = jax.lax.dynamic_update_slice(
            arr, val.astype(arr.dtype),
            (0, 0, pos) + (0,) * (arr.ndim - 3))
    return out


def decode_step(params, cache, token, pos, cfg: gpt.GPTConfig):
    """token [B] int32 at position pos → (logits [B, V], updated cache).

    MoE models decode too: the expert FFN routes the step's B tokens
    jointly (GShard capacity from the call's token count, C =
    ceil(B*top_k/E*cf)) — at B == 1 nothing can drop; at B > 1 batch rows
    contend for capacity exactly as training tokens do, so a batched
    sequence's tokens can depend on its batch-mates (inherent to
    capacity-bounded routing, not a cache artifact)."""
    dt = cfg.dtype
    x = _embed_step(params, token, pos, cfg)

    def body(x, layer):
        p, csl = layer
        x, rows = _cached_block(x, p, csl, pos, cfg)
        return x, rows

    x, rows = jax.lax.scan(body, x, (params["blocks"], cache))
    new_cache = _write_rows(cache, rows, pos)
    x = gpt._norm(x, params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)[:, 0]
    return logits.astype(jnp.float32), new_cache


# round 15: the Engine (text/engine.py) is the single step-compilation
# authority — the LRU cache class, the cfg/flags key, cache donation, and
# the recompile-watch wrapper all live there now.  These names stay as
# aliases because half the test surface (and downstream callers) address
# them here, and because _GEN_CACHE must keep being THE object tests
# clear() between flag flips — it aliases the Engine's gen-domain cache.
_LRU = _engine._LRU
_GEN_CACHE = _engine.ENGINE._gen
_donate_cache = _engine.donate_cache
_watch_jit = _engine._watch_jit
_cfg_key = _engine.cfg_key


def _get_generate_fn(cfg, max_new_tokens, top_k, top_p=1.0):
    """Engine shim: one executable per (config VALUE, gen params) —
    GPTConfig is closed over (dataclass isn't hashable for
    static_argnames); the 'generate' registry entry folds the knobs
    into the key after ``cfg_key``."""
    return _engine.ENGINE.get("generate", _engine.StepSpec(
        cfg=cfg, extra=(max_new_tokens, top_k, float(top_p))))


def _generate_impl(params, prompt, key, temperature, *, cfg,
                   max_new_tokens, top_k, top_p):
    B, P = prompt.shape
    total = P + max_new_tokens
    cache = init_cache(cfg, B, total)
    tokens = jnp.zeros((B, total), jnp.int32)
    tokens = tokens.at[:, :P].set(prompt)

    def step(carry, pos):
        tokens, cache, key = carry
        tok = jax.lax.dynamic_slice(tokens, (0, pos), (B, 1))[:, 0]
        logits, cache = decode_step(params, cache, tok, pos, cfg)
        key, sub = jax.random.split(key)
        # the canonical temperature -> top-k -> nucleus pipeline
        # (_filter_logits is the single implementation all samplers
        # share; advisor r4: temperature must scale BEFORE the nucleus
        # cut).  Skipped entirely when both filters are statically off —
        # the plain-sampling path then pays no vocab sorts per step.
        if top_k > 0 or top_p < 1.0:
            logits = _filter_logits(logits, temperature, top_k, top_p)
        else:
            logits = _scale_logits(logits, temperature)
        nxt = jax.lax.cond(
            jnp.asarray(temperature) > 0.0,
            lambda: jax.random.categorical(sub, logits),
            lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))
        nxt = nxt.astype(jnp.int32)
        # prompt positions keep their given token; past-prompt write samples
        write = jnp.where(pos + 1 < P, tokens[:, pos + 1], nxt)
        tokens = jax.lax.dynamic_update_slice(
            tokens, write[:, None], (0, pos + 1))
        return (tokens, cache, key), None

    (tokens, cache, _), _ = jax.lax.scan(
        step, (tokens, cache, key), jnp.arange(total - 1))
    return tokens


def generate(params, cfg: gpt.GPTConfig, prompt, max_new_tokens=32,
             temperature=0.0, top_k=0, top_p=1.0, key=None):
    """prompt [B, P] int → [B, P + max_new_tokens] tokens (greedy when
    temperature == 0).  ``top_k`` keeps the k highest logits; ``top_p``
    (nucleus) keeps the smallest probability-mass prefix reaching p —
    both compose (k filter first, then p over what survives)."""
    import numpy as np

    prompt = jnp.asarray(np.asarray(prompt), jnp.int32)
    total = prompt.shape[1] + int(max_new_tokens)
    if total > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) = {total} exceeds cfg.max_seq_len "
            f"{cfg.max_seq_len}: positions past the table would silently "
            "reuse the last positional embedding")
    if key is None:
        key = jax.random.PRNGKey(0)
    top_k = min(int(top_k), cfg.vocab_size)  # top-k over the whole vocab
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    fn = _get_generate_fn(cfg, int(max_new_tokens), top_k, top_p)
    return fn(params, prompt, key, jnp.asarray(float(temperature)))


# ---------------------------------------------------------------------------
# beam search — width-k max-probability decoding (serving staple)
# ---------------------------------------------------------------------------


def _beam_impl(params, prompt, *, cfg, max_new_tokens, num_beams,
               length_penalty, eos_id):
    B, P = prompt.shape
    W = num_beams
    V = cfg.vocab_size
    total = P + max_new_tokens
    NEG = jnp.float32(-1e30)

    # every beam shares the prompt: run it once at beam-batch width so the
    # cache is already [B*W] and generation never reshapes it
    cache = init_cache(cfg, B * W, total)
    toks = jnp.zeros((B, W, total), jnp.int32)
    toks = toks.at[:, :, :P].set(prompt[:, None, :])

    def feed(carry, pos):
        cache, = carry
        tok = jnp.repeat(prompt[:, pos], W)            # [B*W]
        _, cache = decode_step(params, cache, tok, pos, cfg)
        return (cache,), None

    if P > 1:
        (cache,), _ = jax.lax.scan(feed, (cache,), jnp.arange(P - 1))

    # scores: beam 0 seeds the search; duplicates start at -inf so the
    # first expansion yields W DISTINCT continuations
    scores = jnp.full((B, W), NEG).at[:, 0].set(0.0)
    alive = jnp.ones((B, W), bool)
    lengths = jnp.zeros((B, W), jnp.int32)

    def step(carry, pos):
        cache, toks, scores, alive, lengths = carry
        tok = jax.lax.dynamic_slice(
            toks, (0, 0, pos), (B, W, 1)).reshape(B * W)
        logits, cache = decode_step(params, cache, tok, pos, cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logp = logp.reshape(B, W, V)
        if eos_id is not None:
            # a finished beam must survive unexpanded: exactly one
            # candidate (continue with eos) at zero added score
            only_eos = jnp.full((V,), NEG).at[eos_id].set(0.0)
            logp = jnp.where(alive[:, :, None], logp, only_eos)
        cand = scores[:, :, None] + logp               # [B, W, V]
        new_scores, idx = jax.lax.top_k(cand.reshape(B, W * V), W)
        parent = idx // V                              # [B, W]
        new_tok = (idx % V).astype(jnp.int32)
        gather = lambda a: jnp.take_along_axis(a, parent, axis=1)  # noqa
        toks = jnp.take_along_axis(
            toks, parent[:, :, None], axis=1)
        toks = jax.lax.dynamic_update_slice(
            toks, new_tok[:, :, None], (0, 0, pos + 1))
        # cache rows follow their beam: gather along the B*W axis
        flat_parent = (jnp.arange(B)[:, None] * W + parent).reshape(-1)
        cache = {k: jnp.take(v, flat_parent, axis=1)
                 for k, v in cache.items()}
        alive = gather(alive)
        lengths = gather(lengths)
        if eos_id is not None:
            lengths = jnp.where(alive, lengths + 1, lengths)
            alive = alive & (new_tok != eos_id)
        else:
            lengths = lengths + 1
        return (cache, toks, new_scores, alive, lengths), None

    (cache, toks, scores, alive, lengths), _ = jax.lax.scan(
        step, (cache, toks, scores, alive, lengths),
        P - 1 + jnp.arange(max_new_tokens))
    norm = scores / jnp.power(jnp.maximum(lengths, 1).astype(jnp.float32),
                              length_penalty)
    best = jnp.argmax(norm, axis=1)                    # [B]
    return (jnp.take_along_axis(toks, best[:, None, None], axis=1)[:, 0],
            jnp.take_along_axis(norm, best[:, None], axis=1)[:, 0])


def beam_search(params, cfg: gpt.GPTConfig, prompt, max_new_tokens=32,
                num_beams=4, length_penalty: float = 0.0,
                eos_id: int | None = None):
    """Width-``num_beams`` beam search → (tokens [B, P+max_new], score [B]).

    TPU-first shape: ONE jitted program — the prompt feeds at beam-batch
    width (cache is [B*W] from step 0, no mid-flight reshape), each
    generation step is a batched cached-attention decode + top-k over the
    W*V joint candidates, and beam reordering is a gather on the cache's
    batch axis.  Static shapes throughout; finished beams (``eos_id``)
    survive unexpanded via a single zero-delta eos candidate.

    ``length_penalty`` alpha normalizes final scores by generated-length
    ** alpha (0 = pure sum-logprob).  With ``num_beams`` >= V**max_new
    the search is exhaustive — the tests use that to prove optimality.
    Beyond-reference capability: the v2.1 reference ships no generation
    API at all (text/gpt.py docstring)."""
    import numpy as np

    prompt = jnp.asarray(np.asarray(prompt), jnp.int32)
    total = prompt.shape[1] + int(max_new_tokens)
    if total > cfg.max_seq_len:
        raise ValueError(
            f"prompt + max_new_tokens = {total} exceeds cfg.max_seq_len "
            f"{cfg.max_seq_len}")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {num_beams}")
    fn = _engine.ENGINE.get("beam", _engine.StepSpec(
        cfg=cfg, extra=(int(max_new_tokens), int(num_beams),
                        float(length_penalty), eos_id)))
    return fn(params, prompt)


# ---------------------------------------------------------------------------
# tensor-parallel (sharded) decode — serving models too big for one chip
# ---------------------------------------------------------------------------


def _decode_param_specs(params, cfg: gpt.GPTConfig, mp: str):
    """A PartitionSpec tree matching ``params`` — float OR weight-only
    quantized (text/woq.py) OR LoRA-adapted (text/lora.py): quantized
    weights take their float twin's Megatron spec (same shape), while the
    small ``*_s`` scale tensors and ``*_lora_a``/``*_lora_b`` low-rank
    adapter pairs replicate (PartitionSpec() is rank-agnostic 'all
    replicated'; the adapter delta is recomputed per rank — rank-r
    matmuls are noise next to the sharded base weights, and GSPMD
    reshards the delta to match the consumer)."""
    from jax.sharding import PartitionSpec as P

    base = gpt.param_shardings(cfg, mp=mp)
    blocks = {}
    for name, v in params["blocks"].items():
        if (name.endswith("_s") or name.endswith("_lora_a")
                or name.endswith("_lora_b")):
            blocks[name] = P()
        else:
            blocks[name] = base["blocks"][name]
    out = {k: (base[k] if k in base else P()) for k in params if k != "blocks"}
    out["blocks"] = blocks
    return out


def sharded_cache_specs(cfg: gpt.GPTConfig, cache: dict, mesh,
                        mp: str = "mp") -> dict:
    """PartitionSpec per cache leaf for tensor-parallel decode — ONE
    rule for both layouts: the heads' axis shards over ``mp`` when
    divisible, everything else replicates.  It is axis 3 of the
    contiguous slab ``[L, B, T, Hkv(, hd)]`` AND of the paged pool, whose
    K/V leaves ``[L, N, bs, Hkv*hd]`` hold a row's heads side by side (a
    shard of that axis is a contiguous range of whole heads) and whose
    scale planes are ``[L, N, bs, Hkv]``; the paged ``tables`` leaf
    (host-scheduler state, int32 indices) always replicates."""
    from jax.sharding import PartitionSpec as P

    mp_size = mesh.shape[mp]

    def _spec(name, arr):
        if name == "tables" or cfg.kv_heads % mp_size:
            return P()
        return P(*([None] * 3 + [mp] + [None] * (arr.ndim - 4)))

    return {name: _spec(name, arr) for name, arr in cache.items()}


def build_sharded_decode(params, cfg: gpt.GPTConfig, mesh, mp: str = "mp",
                         layout: str | None = None,
                         block_size: int | None = None):
    """Megatron-sharded single-token decode over ``mesh`` (the serving
    analog of gpt_hybrid's TP training: reference mp_layers.py shards
    projections by hand + NCCL; here the SAME decode_step is pjit'd under
    the param PartitionSpecs and XLA inserts the collectives over ICI).

    The KV cache shards over the head axis when the mesh divides it —
    with GQA this composes: Hkv heads spread across mp ranks, so a 13B
    model's cache splits like its weights.  ``layout`` (default: the
    ``PADDLE_TPU_KV_LAYOUT`` flag) picks the cache format: the pooled
    layout (round 9) shards the pool's Hkv axis exactly the way the slab
    shards its head axis (``sharded_cache_specs`` — one rule for both),
    tables replicate, and the step routes through
    ``kv_pool.paged_decode_step_batched`` with the scalar ``pos``
    broadcast per slot.  Returns ``(sharded_params, make_cache,
    decode_fn)``:
        sharded_params     params placed per the Megatron specs
        make_cache(B, T, num_blocks=None)   sharded cache
        decode_fn(p, cache, token [B] int32, pos scalar) -> (logits, cache)
    Weight-only int8/int4 params (woq.quantize_gpt_*) shard identically —
    scales replicate.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if cfg.moe is not None:
        raise NotImplementedError("sharded decode supports dense models")
    lay = _flags.kv_layout() if layout is None else layout
    if lay not in ("contiguous", "paged"):
        raise ValueError(f"layout {lay!r}: expected 'contiguous' or "
                         f"'paged'")
    pspecs = _decode_param_specs(params, cfg, mp)
    ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    sharded_params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, ns(s)), params, pspecs,
        is_leaf=lambda v: not isinstance(v, dict))

    bs = None
    if lay == "paged":
        from . import kv_pool as _kvp

        bs = _flags.kv_block_size() if block_size is None \
            else int(block_size)
        template = _kvp.init_paged_cache(cfg, 1, 1, block_size=bs)
    else:
        template = init_cache(cfg, 1, 1)
    cache_specs = sharded_cache_specs(cfg, template, mesh, mp)
    cache_shardings = {name: ns(s) for name, s in cache_specs.items()}
    repl = P()

    def _step(p, cache, token, pos):
        from ..ops import _pallas

        # kernels run per shard of the heads axis (GSPMD cannot
        # partition a Mosaic call)
        with _pallas.partitioned(mesh, heads=mp):
            if lay == "paged":
                from . import kv_pool as _kvp

                pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                         token.shape)
                return _kvp.paged_decode_step_batched(p, cache, token,
                                                      pos_b, cfg)
            return decode_step(p, cache, token, pos, cfg)

    # the sharded cache is donated like the single-chip steps' — in and
    # out shardings match, so aliasing is exact per shard
    decode_fn = _engine.ENGINE.get("sharded_decode", _engine.StepSpec(
        cfg=cfg, extra=(lay, bs),
        payload=(_step, dict(
            in_shardings=(jax.tree_util.tree_map(
                ns, pspecs, is_leaf=lambda s: isinstance(s, P)),
                cache_shardings,
                ns(repl), ns(repl)),
            out_shardings=(ns(repl), cache_shardings),
            donate_argnums=_donate_cache()))))

    def make_cache(batch: int, max_len: int,
                   num_blocks: int | None = None):
        # the builder pins the FLAG-derived layout/block at build time
        # (the explicit-argument form is the caller's own contract): a
        # flag flip after build would otherwise be silently ignored
        # here while every OTHER init_cache site in the process honors
        # it — fail loudly instead of serving two layouts at once
        if layout is None and _flags.kv_layout() != lay:
            raise ValueError(
                f"PADDLE_TPU_KV_LAYOUT changed since "
                f"build_sharded_decode (built {lay!r}, flag now "
                f"{_flags.kv_layout()!r}); rebuild the sharded decoder")
        if lay == "paged" and block_size is None \
                and _flags.kv_block_size() != bs:
            raise ValueError(
                f"PADDLE_TPU_KV_BLOCK changed since "
                f"build_sharded_decode (built {bs}, flag now "
                f"{_flags.kv_block_size()}); rebuild the sharded "
                f"decoder")
        fresh = init_cache(cfg, batch, max_len, layout=lay,
                           block_size=bs, num_blocks=num_blocks)
        if set(fresh) != set(cache_shardings):
            # init_cache re-reads PADDLE_TPU_KV_DTYPE at call time
            # (layout/block flips were caught above), but decode_fn
            # baked the build-time structure into its
            # in_shardings/donation — a flag flip in between must fail
            # loudly here, not as a pytree mismatch inside the jit
            raise ValueError(
                "PADDLE_TPU_KV_DTYPE changed since build_sharded_decode "
                f"(built {sorted(cache_shardings)}, now {sorted(fresh)}); "
                "rebuild the sharded decoder")
        if lay == "paged" and fresh["k"].shape[2] != bs:
            raise ValueError(
                f"PADDLE_TPU_KV_BLOCK changed since build_sharded_decode "
                f"(built block_size={bs}, now {fresh['k'].shape[2]}); "
                "rebuild the sharded decoder")
        return {name: jax.device_put(arr, cache_shardings[name])
                for name, arr in fresh.items()}

    return sharded_params, make_cache, decode_fn


# ---------------------------------------------------------------------------
# chunked prefill — whole-prompt cache fill in one step
# ---------------------------------------------------------------------------


def _prefill_block(x, p, cfg: gpt.GPTConfig, valid=None):
    """One block over a PADDED prompt chunk [B, P, D] with within-chunk
    causal attention (the cache is empty at prefill: pos0 == 0), returning
    (x, rows) — storage-dtype row leaves for the caller to merge.
    ``valid`` [B, P]: pad mask forwarded to the MoE router (pads claim no
    expert capacity); dense models ignore it."""
    B, P, _ = x.shape
    H = cfg.num_heads
    dt = cfg.dtype
    h = gpt._norm(x, p, "ln1", cfg)
    # project ONCE (unrepeated); derive GQA attention copies by repeat
    q, k_rows, v_rows = gpt._project_qkv(h, p, cfg, repeat_kv=False)
    if cfg.pos_embed == "rope":
        pos_arr = jnp.arange(P)
        q = gpt.apply_rope(q, pos_arr, cfg.rope_theta)
        k_rows = gpt.apply_rope(k_rows, pos_arr, cfg.rope_theta)
    rows = _store_rows(k_rows, v_rows, cfg)
    # attend the STORAGE view of the fresh rows (the sibling sites'
    # attend-what-you-store invariant): under int8 the admission path
    # sees exactly the rows later decode steps will read back, so
    # prefill and token-by-token feeding stay in lockstep
    if "k_s" in rows:
        from ..ops import decode_attention as da

        k_att = da.dequantize_kv(rows["k"], rows["k_s"], dt)
        v_att = da.dequantize_kv(rows["v"], rows["v_s"], dt)
    else:
        k_att = rows["k"].astype(dt)
        v_att = rows["v"].astype(dt)
    rep = H // k_att.shape[2]
    k = jnp.repeat(k_att, rep, axis=2) if rep > 1 else k_att
    v = jnp.repeat(v_att, rep, axis=2) if rep > 1 else v_att
    from ..ops.attention import attention_array

    attn = attention_array(q, k, v, is_causal=True).reshape(
        B, P, cfg.q_size)
    return gpt._ffn_tail(x + gpt._attn_out(attn, p, cfg), p, cfg,
                         valid=valid), rows


def prefill_slot(params, cache, tokens, length, slot, cfg: gpt.GPTConfig):
    """Process one request's whole (padded) prompt in a single step.

    tokens [1, P] int32 padded to P; ``length`` (traced scalar) = valid
    prompt tokens; ``slot`` (traced scalar) = batch row of the serving
    cache [L, B, T, Hkv, hd].  Writes cache rows [0, length) for that slot
    (padded rows are NOT written — stale tenants' data beyond ``length``
    stays hidden by the decode-time causal mask until overwritten) and
    returns (greedy logits at position length-1 [V], cache).

    MoE models prefill too (round-5 verdict Next #4): the pad mask
    reaches every block's router, where padding claims no expert
    capacity, and the per-chunk capacity is the dropless bound — so the
    padded chunk routes exactly like feeding the prompt token-by-token
    (tests/test_serving.py MoE prefill parity)."""
    dt = cfg.dtype
    P = tokens.shape[1]
    x = woq.embed(params, tokens, dt, cfg.embedding_multiplier)
    if cfg.pos_embed == "learned":
        x = x + params["wpe"][:P].astype(dt)[None]
    valid_mask = (jnp.arange(P) < length)[None, :]       # [1, P]

    def body(x, p):
        x, rows = _prefill_block(x, p, cfg, valid=valid_mask)
        return x, rows

    x, rows = jax.lax.scan(body, x, params["blocks"])
    # masked merge into this slot's rows [0, P): only the valid prefix
    cache = _merge_slot_rows(cache, rows, slot, jnp.asarray(0), valid_mask)
    # slice the last valid row before the (per-row) final norm
    last = jax.lax.dynamic_slice(x, (0, length - 1, 0),
                                 (1, 1, cfg.hidden_size))
    last = gpt._norm(last, params, "ln_f", cfg)
    logits = woq.logits(last, params, dt, cfg.lm_head_multiplier)[0, 0]
    return logits.astype(jnp.float32), cache


def _chunk_pre_attn(x, p, pos0, cfg: gpt.GPTConfig, h=None):
    """Pre-attention half of one block on a K-token chunk [B, K, D] at
    positions [pos0, pos0+K): ln1 -> qkv projection (Hkv heads kept) ->
    rope over the chunk's positions -> storage-dtype rows.  Returns
    (q [B, K, H, hd], rows); :func:`_chunk_attend_block` and the batched
    kernel verify routes (here and kv_pool) all project through this
    one copy, so the chunk math can never drift between the einsum and
    flash routes.  ``h``: the block's normed input where the caller
    already has it (a parallel mixer reads the same one)."""
    K = x.shape[1]
    if h is None:
        h = gpt._norm(x, p, "ln1", cfg)
    q, k_new, v_new = gpt._project_qkv(h, p, cfg, repeat_kv=False)
    if cfg.pos_embed == "rope":
        chunk_pos = pos0 + jnp.arange(K)
        q = gpt.apply_rope(q, chunk_pos, cfg.rope_theta)
        k_new = gpt.apply_rope(k_new, chunk_pos, cfg.rope_theta)
    return q, _store_rows(k_new, v_new, cfg)


def _chunk_attend_block(x, p, csl, pos0, cfg: gpt.GPTConfig,
                        valid=None, state=None, length=None):
    """One transformer block over a K-token chunk at positions
    [pos0, pos0+K) against a per-layer cache slice ``csl`` (leaves k/v
    [B, T, Hkv, hd] + scales) whose rows [0, pos0) are already filled:
    row i attends cache rows t <= pos0 + i.  THE shared body of
    verify_chunk and prefill_slot_chunk (one copy of the chunk-attention
    math).  PRECONDITION: pos0 + K <= T — dynamic_update_slice CLAMPS
    start indices, so an overrunning window would silently write the
    chunk's rows at a shifted offset while the mask/positions still use
    pos0 (callers guarantee the bound; the serving walk overlaps its
    last window instead of overrunning).  Returns (x_out, rows).

    ``state`` (a config with a parallel ssm mixer): this layer's recurrent
    state of the chunk's sequence; the mixer continues it over the chunk's
    first ``length`` positions (the rest is padding) and the call returns
    (x_out, rows, new_state)."""
    h = gpt._norm(x, p, "ln1", cfg)
    q, rows = _chunk_pre_attn(x, p, pos0, cfg, h=h)
    full = {name: jax.lax.dynamic_update_slice(
                csl[name], val, (0, pos0) + (0,) * (csl[name].ndim - 2))
            for name, val in rows.items()}
    attn = _attend_cache(q, full, pos0, cfg)           # [B, K, H * hd]
    a = gpt._attn_out(attn, p, cfg)
    if state is None:
        return gpt._ffn_tail(x + a, p, cfg, valid=valid), rows
    from . import ssm as _ssm

    mix, state = _ssm.mixer_chunk(h, p, cfg, state, length=length)
    return gpt._ffn_tail(x + a + mix, p, cfg, valid=valid), rows, state


def _merge_slot_rows(cache, rows, slot, pos0, valid):
    """Masked write of per-layer chunk row leaves [L, 1, P, Hkv(, hd)]
    into one slot's cache rows [pos0, pos0+P): only rows where ``valid``
    [1, P] is True are written (pads leave the old tenant's rows
    untouched — the stale-row invariant).  Shared by prefill_slot
    (pos0 == 0) and prefill_slot_chunk; int8 scale planes merge under
    the same mask."""
    P = rows["k"].shape[2]
    out = dict(cache)
    for name, val in rows.items():
        arr = cache[name]
        start = (0, slot, pos0) + (0,) * (arr.ndim - 3)
        old = jax.lax.dynamic_slice(
            arr, start, (arr.shape[0], 1, P) + arr.shape[3:])
        vmask = valid.reshape((1, 1, P) + (1,) * (arr.ndim - 3))
        merged = jnp.where(vmask, val.astype(arr.dtype), old)
        out[name] = jax.lax.dynamic_update_slice(arr, merged, start)
    return out


def prefill_slot_chunk(params, cache, tokens, pos0, length, slot,
                       cfg: gpt.GPTConfig):
    """One FIXED-SIZE chunk of a prompt at positions [pos0, pos0+P) for
    one serving slot — the multi-chunk admission step (round-5): long
    prompts feed as a sequence of these, each attending the slot's
    already-filled cache rows [0, pos0), so activation memory is bounded
    by the chunk and ONE executable serves any prompt length (vs one
    compile per power-of-two bucket).

    tokens [1, P] int32 (pad tail beyond ``length``); ``pos0``/``length``
    /``slot`` are traced scalars.  PRECONDITION pos0 + P <= cache rows
    (and the wpe table) — see _chunk_attend_block; DecodeServer's walk
    overlaps the last window rather than overrunning.  Writes cache rows
    [pos0, pos0+length) (pads unwritten, and routed nowhere under MoE —
    the valid mask + dropless capacity, exactly prefill_slot's rule);
    returns (logits at the chunk's last valid position [V], cache)."""
    dt = cfg.dtype
    P = tokens.shape[1]
    x = woq.embed(params, tokens, dt, cfg.embedding_multiplier)
    if cfg.pos_embed == "learned":
        x = x + jax.lax.dynamic_slice(
            params["wpe"], (pos0, 0), (P, cfg.hidden_size)).astype(dt)[None]
    valid_mask = (jnp.arange(P) < length)[None, :]       # [1, P]
    # this slot's cache rows [L, 1, T, Hkv(, hd)] per leaf
    sl = {name: jax.lax.dynamic_slice(
              arr, (0, slot) + (0,) * (arr.ndim - 2),
              (arr.shape[0], 1) + arr.shape[2:])
          for name, arr in cache.items()}

    def body(x, layer):
        p, csl = layer
        x, rows = _chunk_attend_block(x, p, csl, pos0, cfg,
                                      valid=valid_mask)
        return x, rows

    x, rows = jax.lax.scan(body, x, (params["blocks"], sl))
    cache = _merge_slot_rows(cache, rows, slot, pos0, valid_mask)
    # slice the last valid row FIRST: the final norm is per-row, so
    # normalizing all P rows per chunk would be pure waste
    last = jax.lax.dynamic_slice(x, (0, length - 1, 0),
                                 (1, 1, cfg.hidden_size))
    last = gpt._norm(last, params, "ln_f", cfg)
    logits = woq.logits(last, params, dt, cfg.lm_head_multiplier)[0, 0]
    return logits.astype(jnp.float32), cache


# ---------------------------------------------------------------------------
# speculative decoding (greedy): draft proposes, target verifies in 1 chunk
# ---------------------------------------------------------------------------


def verify_chunk(params, cache, tokens, pos0, cfg: gpt.GPTConfig):
    """Score K tokens in one pass against an existing cache.

    tokens [1, K] int32 fed at positions [pos0, pos0+K); attends cache
    rows [0, pos0) plus within-chunk causally; writes the chunk's K/V rows
    at [pos0, pos0+K) (rows past an eventual rejection point stay hidden
    behind the caller's position pointer until overwritten — the same
    stale-row invariant the serving slots rely on).  Returns
    (logits [1, K, V], cache).

    MoE: the K chunk tokens route JOINTLY (capacity C from N=K), so a
    chunk can drop tokens a one-at-a-time decode would not — chunked
    verification is therefore not bit-equal to stepwise decode for MoE;
    speculative_generate rejects MoE targets for exactly this reason."""
    dt = cfg.dtype
    B, K = tokens.shape
    x = woq.embed(params, tokens, dt, cfg.embedding_multiplier)
    if cfg.pos_embed == "learned":
        x = x + jax.lax.dynamic_slice(
            params["wpe"], (pos0, 0), (K, cfg.hidden_size)).astype(dt)[None]

    def body(x, layer):
        p, csl = layer
        x, rows = _chunk_attend_block(x, p, csl, pos0, cfg)
        return x, rows

    x, rows = jax.lax.scan(body, x, (params["blocks"], cache))
    new_cache = _write_rows(cache, rows, pos0)
    x = gpt._norm(x, params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)
    return logits.astype(jnp.float32), new_cache


def _write_rows_batched(cache: dict, rows: dict, pos) -> dict:
    """Per-slot-offset form of :func:`_write_rows`: stacked chunk row
    leaves [L, B, K, Hkv(, hd)] land at each slot's own positions
    [pos_b, pos_b+K) (pos [B] int32) — the contiguous-layout write the
    batched verify kernel route needs, since its slots sit at different
    frontiers."""
    out = {}
    for name, val in rows.items():
        arr = cache[name]

        def one(arr_b, val_b, p0, _a=arr):
            return jax.lax.dynamic_update_slice(
                arr_b, val_b.astype(_a.dtype),
                (0, p0) + (0,) * (arr_b.ndim - 2))

        out[name] = jax.vmap(one, in_axes=(1, 1, 0), out_axes=1)(
            arr, val, pos)
    return out


def verify_chunk_batched(params, cache, tokens, pos, cfg: gpt.GPTConfig):
    """Batched :func:`verify_chunk` with the layer loop at TOP level so
    the Tq>=1 flash-decode kernel sees the whole batch per layer (ONE
    kernel launch over [B, K] query rows per block instead of a vmapped
    per-slot einsum — the ROADMAP "flash-verify" item): tokens [B, K]
    int32 scored at per-slot positions [pos_b, pos_b+K) ->
    (logits [B, K, V] fp32, cache).

    The per-slot pre/post math stays vmapped at the fallback's [1, K, D]
    shapes (:func:`_chunk_pre_attn` — rope needs each slot's own
    offsets); only the attention itself batches, with the fresh rows
    spliced into each slot's cache slice BEFORE attending so the kernel
    reads exactly what later rounds read back (splice-then-write, the
    :func:`_chunk_attend_block` rule).  Callers gate on
    :func:`_use_decode_kernel` at q [B, K, H, hd] — off-kernel the
    vmapped einsum route stays the (bit-identical-to-decode) default."""
    from ..ops import decode_attention as da

    dt = cfg.dtype
    B, K = tokens.shape
    H, hd = cfg.num_heads, cfg.head_dim

    def embed_one(tok_k, p0):
        x = woq.embed(params, tok_k[None], dt,
                      cfg.embedding_multiplier)              # [1, K, D]
        if cfg.pos_embed == "learned":
            x = x + jax.lax.dynamic_slice(
                params["wpe"], (p0, 0),
                (K, cfg.hidden_size)).astype(dt)[None]
        return x

    x = jax.vmap(embed_one)(tokens, pos)                  # [B, 1, K, D]

    def body(x, layer):
        p, csl = layer                # csl leaves [B, T, Hkv(, hd)]

        def pre(xb, p0):
            return _chunk_pre_attn(xb, p, p0, cfg)

        q3, rows = jax.vmap(pre)(x, pos)  # q3 [B, 1, K, H, hd]

        def splice(arr_b, val_b, p0):
            return jax.lax.dynamic_update_slice(
                arr_b, val_b.astype(arr_b.dtype),
                (p0,) + (0,) * (arr_b.ndim - 1))

        full = {name: jax.vmap(splice)(csl[name], val[:, 0], pos)
                for name, val in rows.items()}
        with jax.named_scope("attn"):
            attn = da.decode_attention(
                q3.reshape(B, K, H, hd), full["k"], full["v"], pos,
                k_scale=full.get("k_s"), v_scale=full.get("v_s"))
        attn = attn.astype(dt).reshape(B, 1, K, H * hd)

        def post(xb, ab):
            return _block_post_attn(xb, ab, p, cfg)

        return jax.vmap(post)(x, attn), rows

    x, rows = jax.lax.scan(body, x, (params["blocks"], cache))
    # rows leaves [L, B, 1, K, ...] -> per-slot offset write
    new_cache = _write_rows_batched(
        cache, {n: v[:, :, 0] for n, v in rows.items()}, pos)

    def fin(xb):
        xb = gpt._norm(xb, params, "ln_f", cfg)
        return woq.logits(xb, params, dt,
                          cfg.lm_head_multiplier)[0]        # [K, V]

    logits = jax.vmap(fin)(x)
    return logits.astype(jnp.float32), new_cache


# ---------------------------------------------------------------------------
# tree speculation: ONE verify pass over a branching token tree
# ---------------------------------------------------------------------------


def tree_depths(parent):
    """Per-node depths [N] int32 of a parent-index tree (parent[0] == -1
    is the root/feed node; parents precede children — every propose
    layout in this repo is topologically ordered).  Pure host work."""
    import numpy as np

    n = len(parent)
    d = np.zeros(n, np.int32)
    for j in range(1, n):
        d[j] = d[parent[j]] + 1
    return d


def tree_ancestor_mask(parent):
    """Ancestor-or-self mask [N, N] bool of a parent-index tree:
    ``m[j, t]`` is True iff node t lies on node j's root path (j
    included) — the within-chunk half of the tree-attention mask.  Built
    host-side (numpy, one |= per node off the parent's finished row);
    the device only ever sees the finished mask as a RUNTIME argument,
    so per-round topology changes never retrace."""
    import numpy as np

    n = len(parent)
    m = np.zeros((n, n), bool)
    for j in range(n):
        m[j, j] = True
        if parent[j] >= 0:
            m[j] |= m[parent[j]]
    return m


def _attend_cache_tree(q, full, tmask, cfg: gpt.GPTConfig):
    """:func:`_attend_cache` with the causal ``t <= pos + i`` rule
    replaced by an explicit per-row visibility mask ``tmask`` [B, N, T]
    (True = attend): each tree node sees the committed prefix plus its
    OWN ancestor path, nothing from sibling branches.  Einsum-only on
    purpose — the flash-decode kernels assume causal masks, so tree
    verify keeps one route that exists on every backend (an on-device
    tree kernel is a ROADMAP follow-up)."""
    B, Tq, H, hd = q.shape
    dt = cfg.dtype
    k_all, v_all = full["k"], full["v"]
    ks, vs = full.get("k_s"), full.get("v_s")
    if ks is not None:
        from ..ops import decode_attention as da

        k_all = da.dequantize_kv(k_all, ks, dt)
        v_all = da.dequantize_kv(v_all, vs, dt)
    k_all = k_all.astype(dt)
    v_all = v_all.astype(dt)
    Hkv = k_all.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Tq, Hkv, g, hd)
    scores = jnp.einsum("bikgd,btkd->bkgit", qg, k_all) / jnp.sqrt(
        jnp.asarray(hd, jnp.float32)).astype(dt)
    scores = jnp.where(tmask[:, None, None], scores.astype(jnp.float32),
                       -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("bkgit,btkd->bikgd", w, v_all).reshape(B, Tq, -1)


def _tree_pre_attn(x, p, pos0, depth, cfg: gpt.GPTConfig):
    """:func:`_chunk_pre_attn` for a tree chunk: node j ropes at its
    LOGICAL position ``pos0 + depth[j]`` (depth [N] int32), not its
    storage index ``pos0 + j`` — siblings at one depth share a
    position.  Rope's relative-offset property keeps the stored key
    rows valid after the post-acceptance permute moves a node to the
    storage index matching its logical position."""
    q, k_new, v_new = gpt._project_qkv(
        gpt._norm(x, p, "ln1", cfg), p, cfg, repeat_kv=False)
    if cfg.pos_embed == "rope":
        node_pos = pos0 + depth
        q = gpt.apply_rope(q, node_pos, cfg.rope_theta)
        k_new = gpt.apply_rope(k_new, node_pos, cfg.rope_theta)
    return q, _store_rows(k_new, v_new, cfg)


def _tree_attend_block(x, p, csl, pos0, depth, tmask, cfg: gpt.GPTConfig):
    """One transformer block over an N-node tree chunk stored at rows
    [pos0, pos0+N) against a per-layer cache slice ``csl`` (leaves k/v
    [B, T, Hkv, hd] + scales): node j ropes at ``pos0 + depth[j]`` and
    attends exactly ``tmask[:, j]``.  THE shared body of the contiguous
    and paged tree verify routes — one copy of the tree math, the
    :func:`_chunk_attend_block` rule, same PRECONDITION pos0 + N <= T
    (dynamic_update_slice clamps; callers guarantee the bound)."""
    q, rows = _tree_pre_attn(x, p, pos0, depth, cfg)
    full = {name: jax.lax.dynamic_update_slice(
                csl[name], val, (0, pos0) + (0,) * (csl[name].ndim - 2))
            for name, val in rows.items()}
    attn = _attend_cache_tree(q, full, tmask, cfg)     # [B, N, D]
    a = gpt._attn_out(attn, p, cfg)
    return gpt._ffn_tail(x + a, p, cfg), rows


def tree_verify_chunk(params, cache, tokens, amask, depth, pos0,
                      cfg: gpt.GPTConfig):
    """Score one slot's N-node token tree in ONE pass: tokens [1, N]
    int32 stored at cache rows [pos0, pos0+N) (node 0 = the feed token
    = the tree root); ``amask`` [1, N, N] bool (ancestor-or-self) and
    ``depth`` [1, N] int32 describe the topology as RUNTIME arguments —
    only N is a compiled shape, so per-round topology changes never
    retrace.  Node j attends the committed rows [0, pos0) plus its own
    ancestor path inside the chunk; rejected nodes just stay at/past
    the caller's position pointer as stale rows (the PR 11 invariant),
    so no rollback executable exists — acceptance off the trunk is a
    row PERMUTE (:func:`tree_commit_rows`), not an unwrite.  Returns
    (logits [1, N, V] fp32, cache).  Unused node slots (short trees pad
    with self-only mask rows) write garbage rows past every live node's
    visibility — stale by the same invariant.

    MoE: the N nodes would route jointly (the verify_chunk caveat,
    worse under branching); serving rejects MoE targets before this."""
    dt = cfg.dtype
    B, N = tokens.shape
    T = cache["k"].shape[2]
    x = woq.embed(params, tokens, dt, cfg.embedding_multiplier)
    if cfg.pos_embed == "learned":
        x = x + jnp.take(params["wpe"], pos0 + depth[0],
                         axis=0).astype(dt)[None]
    tmask = jnp.broadcast_to(jnp.arange(T)[None, None, :] < pos0,
                             (B, N, T))
    tmask = jax.lax.dynamic_update_slice(tmask, amask, (0, 0, pos0))

    def body(x, layer):
        p, csl = layer
        x, rows = _tree_attend_block(x, p, csl, pos0, depth[0], tmask,
                                     cfg)
        return x, rows

    x, rows = jax.lax.scan(body, x, (params["blocks"], cache))
    new_cache = _write_rows(cache, rows, pos0)
    x = gpt._norm(x, params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)
    return logits.astype(jnp.float32), new_cache


def tree_verify_chunk_batched(params, cache, tokens, amask, depth, pos,
                              cfg: gpt.GPTConfig):
    """Batched :func:`tree_verify_chunk` over per-slot frontiers:
    tokens [B, N], amask [B, N, N], depth [B, N], pos [B] int32 ->
    (logits [B, N, V] fp32, cache).  vmapped at the per-slot [1, N]
    shapes (rope and the committed-prefix boundary need each slot's own
    offset); einsum-only — see :func:`_attend_cache_tree`."""

    def one(tok, am, dp, csl, p0):
        sl = {name: v[:, None] for name, v in csl.items()}
        lg, nc = tree_verify_chunk(params, sl, tok[None], am[None],
                                   dp[None], p0, cfg)
        return lg[0], {n: v[:, 0] for n, v in nc.items()}

    logits, new_cache = jax.vmap(
        one, in_axes=(0, 0, 0, 1, 0), out_axes=(0, 1))(
        tokens, amask, depth, cache, pos)
    return logits.astype(jnp.float32), new_cache


def tree_commit_rows(cache, src, pos):
    """Post-acceptance KV permute for tree speculation on the contiguous
    layout: per slot b, gather rows ``pos_b + src_b[i]`` and write them
    back at ``pos_b + 1 + i`` for i in [0, M) (src [B, M] int32, pos [B]
    the slot's pre-round pointer).  An accepted root-to-leaf path is
    strictly increasing in node index and every source row sits at or
    past ``pos_b + 1``, so gather-then-scatter over ALL M rows is
    alias-safe and needs no keep-mask: identity entries rewrite
    themselves, and rows past the accepted pointer are stale either way
    (the PR 11 invariant).  Cache-only — the Engine donates the cache
    like ``kv_copy``; host code skips the dispatch entirely when every
    slot accepted a trunk prefix (src == identity everywhere)."""
    out = {}
    for name, arr in cache.items():

        def one(arr_b, s, p0, _a=arr):
            rows = jnp.take(arr_b, p0 + s, axis=1)
            return jax.lax.dynamic_update_slice(
                arr_b, rows.astype(_a.dtype),
                (0, p0 + 1) + (0,) * (arr_b.ndim - 2))

        out[name] = jax.vmap(one, in_axes=(1, 0, 0), out_axes=1)(
            arr, src, pos)
    return out


def _jit_by_cfg(tag: str, fn, cfg):
    """Engine shim: value-keyed jit cache (the _GEN_CACHE rationale:
    per-call jax.jit wrappers would recompile per invocation and leak
    executables).  The cache (arg 1) is DONATED — callers reassign it
    from the return.  ``tag`` pins the step fn's identity, so ``fn``
    rides in the spec's un-keyed payload."""
    return _engine.ENGINE.get("jit_by_cfg", _engine.StepSpec(
        cfg=cfg, extra=(tag,), payload=fn))


def _key_seed(key):
    """np.random seed material from a jax PRNG key (typed keys need
    key_data; raw PRNGKey uint32 arrays convert directly)."""
    import numpy as np

    try:
        return np.asarray(jax.random.key_data(key)).ravel()
    except Exception:  # noqa: BLE001 - raw uint32 key array
        return np.asarray(key).ravel()


def _bc(a, dt, lead, xp):
    """A scalar or per-row sampling parameter against [..., V] logits."""
    return xp.broadcast_to(xp.asarray(a, dt), lead)[..., None]


def _scale_logits(logits, temperature, xp=jnp):
    """Logits over their temperature; temperature == 0 leaves them
    unscaled (greedy callers take the argmax).  The first stage of
    ``_filter_logits`` and, alone, the law of a sampler with both
    filters off: a plain sampled step sorts nothing."""
    t = _bc(temperature, xp.float32, logits.shape[:-1], xp)
    return xp.where(t > 0, logits / xp.maximum(t, 1e-6), logits)


def _filter_logits(logits, temperature, top_k, top_p, xp=jnp):
    """THE temperature → top-k → nucleus filter over [..., V] logits —
    the single source of truth for every sampler: ``_generate_impl``
    (device, scalar params), ``serving._sample_batched`` (device,
    per-slot param arrays), and ``_filtered_probs`` (host mirror for the
    speculative rejection rule, ``xp=numpy``).  Backend-agnostic on
    purpose: one formula cannot drift between the three call sites (the
    chi-square tests additionally pin host and device statistically).

    temperature/top_k/top_p broadcast over the leading dims; top_k == 0
    and top_p == 1 disable their stages; temperature == 0 leaves logits
    unscaled (greedy callers take the argmax, which every stage
    preserves — the top token always survives).

    The vocabulary is sorted ONCE: what top-k masks is a suffix of the
    descending order, so the nucleus stage reads the same sorted row
    with that suffix at the mask value (``adapters.NEG_INF``, the floor
    of any masked logit) instead of sorting the masked logits again."""
    V = logits.shape[-1]
    lead = logits.shape[:-1]
    tk = _bc(top_k, xp.int32, lead, xp)
    tp = _bc(top_p, xp.float32, lead, xp)
    x = _scale_logits(logits, temperature, xp)
    srt = xp.sort(x, axis=-1)[..., ::-1]               # descending
    kth = xp.take_along_axis(srt, xp.clip(tk - 1, 0, V - 1), axis=-1)
    x = xp.where((tk > 0) & (x < kth), -1e30, x)
    srt = xp.where((tk > 0) & (srt < kth), -1e30, srt)
    e = xp.exp(srt - srt[..., :1])
    probs = e / xp.sum(e, axis=-1, keepdims=True)
    keep = xp.cumsum(probs, axis=-1) - probs < tp  # mass BEFORE the token
    kth_idx = xp.sum(keep, axis=-1, keepdims=True) - 1
    cutoff = xp.take_along_axis(srt, kth_idx, axis=-1)
    return xp.where((tp < 1.0) & (x < cutoff), -1e30, x)


def _filtered_probs(logits, temperature, top_k, top_p):
    """Host-side probability vector of the sampling law on a [V] logit
    vector — evaluates the SAME ``_filter_logits`` formula under numpy
    (float64), then normalizes.  The rejection-sampling accept/resample
    math needs q and p as explicit vectors."""
    import numpy as np

    x = _filter_logits(np.asarray(logits, np.float64), float(temperature),
                       int(top_k), float(top_p), xp=np)
    e = np.exp(x - x.max())
    return e / e.sum()


def ngram_propose(sequence, k, max_order=3, window=256):
    """Model-free draft proposals: match the sequence's trailing n-gram
    (longest order first, down to a single token) against its most
    recent earlier occurrence and copy the continuation — the
    "self-drafting" / prompt-lookup decoding trick (zero extra model
    FLOPs, pure host work).  Returns k proposed tokens, or None when no
    order matches (the caller speculates nothing that round).  Short
    continuations pad by repeating the last copied token — a cheap
    guess the verify step rejects at worst.  ``window`` bounds the
    backward scan so long contexts stay O(window) per call."""
    seq = list(sequence)
    n = len(seq)
    if n < 2:
        return None
    lo = max(0, n - int(window))
    for order in range(min(int(max_order), n - 1), 0, -1):
        tail = tuple(seq[n - order:])
        for s in range(n - order - 1, lo - 1, -1):
            if tuple(seq[s:s + order]) == tail:
                out = list(seq[s + order:s + order + k])
                while len(out) < k:
                    out.append(out[-1])
                return out
    return None


def ngram_propose_tree(sequence, nodes, branch=2, max_order=3,
                       window=256):
    """Tree-shaped self-drafting: like :func:`ngram_propose`, but
    instead of stopping at the first (most recent, longest-order) n-gram
    match, collect up to ``branch`` DISTINCT continuations and merge
    them into a prefix trie of at most ``nodes`` node slots — branching
    exactly where the history itself disagrees about what comes next.
    Node slot 0 is reserved for the feed token (the caller owns it); the
    first continuation becomes the TRUNK, laid out as nodes 1..D before
    any alternate, so a trunk-prefix acceptance needs no KV permute.

    Returns ``(tokens, parent)`` lists — ``tokens[0]`` is None,
    ``parent[0] == -1``, parents precede children (topological order,
    what :func:`tree_ancestor_mask` assumes) — or None when no order
    matches.  May return fewer than ``nodes`` entries; callers pad the
    device arrays with self-only mask rows (stale, never selected)."""
    seq = list(sequence)
    n = len(seq)
    if n < 2:
        return None
    lo = max(0, n - int(window))
    cap = int(nodes) - 1                     # token-bearing node slots
    branch = max(1, int(branch))
    if cap < 1:
        return None
    conts, seen = [], set()
    for order in range(min(int(max_order), n - 1), 0, -1):
        tail = tuple(seq[n - order:])
        for s in range(n - order - 1, lo - 1, -1):
            if tuple(seq[s:s + order]) == tail:
                c = tuple(seq[s + order:s + order + cap])
                if c and c not in seen:
                    seen.add(c)
                    conts.append(list(c))
                    if len(conts) >= branch:
                        break
        if len(conts) >= branch:
            break
    if not conts:
        return None
    # the trunk is NOT padded (unused node slots stay idle, masked
    # self-only by the caller) and leaves one slot per alternate so a
    # long first match can't starve the branches out of the budget
    trunk = conts[0][:max(1, cap - (len(conts) - 1))]
    tokens, parent = [None], [-1]
    children = {0: {}}
    for i, t in enumerate(trunk):
        tokens.append(int(t))
        parent.append(i)                     # trunk node i+1's parent
        children[i][int(t)] = i + 1
        children[i + 1] = {}
    for c in conts[1:]:                      # graft where they diverge
        cur = 0
        for t in c:
            t = int(t)
            nxt = children[cur].get(t)
            if nxt is None:
                if len(tokens) >= int(nodes):
                    break
                tokens.append(t)
                parent.append(cur)
                nxt = len(tokens) - 1
                children[cur][t] = nxt
                children[nxt] = {}
            cur = nxt
    return tokens, parent


def speculative_generate(tparams, tcfg, dparams, dcfg, prompt,
                         max_new_tokens=32, k=4, temperature=0.0,
                         top_k=0, top_p=1.0, key=None):
    """Speculative decoding: a small DRAFT model proposes ``k``
    tokens per round (k cheap decode steps), the TARGET verifies them in
    ONE verify_chunk pass.

    Greedy (``temperature == 0``): accept the longest prefix where the
    target's own greedy choice agrees, substituting its token at the
    first disagreement.  Output is EXACTLY the target's greedy
    generation — the draft only changes how many target passes it takes.

    Sampling (``temperature > 0``, round-5 verdict Next #3): the draft
    SAMPLES each proposal from its filtered distribution q (same
    temperature/top-k/top-p pipeline as ``generate``); token j is
    accepted with probability min(1, p_j(x_j)/q_j(x_j)) against the
    target's filtered p_j, and the first rejection resamples from the
    residual max(p_j - q_j, 0) — the standard rejection rule, whose
    per-token marginal is exactly p_j, so the OUTPUT DISTRIBUTION equals
    target-only sampling (proven statistically in
    tests/test_speculative.py by chi-square against the target's exact
    next-token law).  No bonus token is drawn on a fully-accepted round:
    a round yields at most k tokens, which keeps the draft-cache
    stale-row invariant identical to the greedy path (a bonus token
    would leave a K/V hole at the last draft position).

    Both models keep KV caches; rejected rows in either cache stay hidden
    behind the position pointers and are overwritten on the next round
    (the serving slots' stale-row invariant).  Returns a python list of
    the generated tokens (no prompt)."""
    import numpy as np

    prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
    if not prompt:
        raise ValueError("empty prompt")
    if tcfg.moe is not None or dcfg.moe is not None:
        # verify_chunk routes K tokens jointly while plain decode routes
        # 1: capacity drops could make "accepted" tokens differ from the
        # target's own greedy decode, silently breaking the exactness
        # guarantee this function exists for
        raise NotImplementedError(
            "speculative decoding requires dense models (MoE capacity "
            "routing differs between chunked verify and stepwise decode)")
    total = len(prompt) + max_new_tokens
    if total > min(tcfg.max_seq_len, dcfg.max_seq_len):
        raise ValueError("prompt + max_new_tokens exceeds a model's window")
    if temperature > 0.0:
        return _speculative_sample(tparams, tcfg, dparams, dcfg, prompt,
                                   max_new_tokens, k, temperature,
                                   min(int(top_k), tcfg.vocab_size),
                                   float(top_p), key, total)
    t_step = _jit_by_cfg("decode", decode_step, tcfg)
    d_step = _jit_by_cfg("decode", decode_step, dcfg)
    t_verify = _jit_by_cfg("verify", verify_chunk, tcfg)
    t_cache = init_cache(tcfg, 1, total)
    d_cache = init_cache(dcfg, 1, total)

    # prompt: feed both models token-by-token (simple; prefill would also
    # work) — target logits at the last prompt position seed generation
    t_logits = None
    for pos in range(len(prompt)):
        tok = jnp.asarray([prompt[pos]], jnp.int32)
        t_logits, t_cache = t_step(tparams, t_cache, tok, pos)
        _, d_cache = d_step(dparams, d_cache, tok, pos)

    out = [int(np.asarray(jnp.argmax(t_logits, -1))[0])]
    t_pos = len(prompt)          # target cache rows [0, t_pos) are final
    while len(out) < max_new_tokens:
        kk = min(k, max_new_tokens - len(out), total - 1 - t_pos)
        if kk <= 0:
            break
        # 1) draft proposes kk tokens from the current accepted tail
        draft = []
        cur = out[-1]
        for j in range(kk):
            dl, d_cache = d_step(dparams, d_cache,
                                 jnp.asarray([cur], jnp.int32), t_pos + j)
            cur = int(np.asarray(jnp.argmax(dl, -1))[0])
            draft.append(cur)
        # 2) target scores [out[-1], draft[0..kk-2]] in one chunk: row j's
        # logits are the target's choice AFTER seeing draft[j-1]
        chunk = jnp.asarray([[out[-1]] + draft[:-1]], jnp.int32)
        vl, t_cache = t_verify(tparams, t_cache, chunk, t_pos)
        tchoice = np.asarray(jnp.argmax(vl[0], -1))
        for j in range(kk):
            out.append(int(tchoice[j]))
            t_pos += 1
            if int(tchoice[j]) != draft[j]:
                break   # target disagrees: its token wins, round ends
        # no draft-cache resync is needed: after a rejection the draft's
        # first stale row sits exactly at the new t_pos — the position the
        # next round's first proposal overwrites (fed the corrected
        # out[-1]); rows before it were fed accepted (= identical) tokens
    return out[:max_new_tokens]


def _speculative_sample(tparams, tcfg, dparams, dcfg, prompt,
                        max_new_tokens, k, temperature, top_k, top_p,
                        key, total):
    """Rejection-sampling speculative decode body (see speculative_generate).

    Host-side control flow with fetched logit vectors (the framework's
    reference implementation: tests run tiny models; a production server
    would keep accept/resample on device).  The draft-cache invariant is
    the greedy path's: accepted tokens equal the draft's own proposals,
    so draft rows up to the rejection point were fed the true sequence,
    and the next round's first feed overwrites the first stale row."""
    import numpy as np

    if key is None:
        key = jax.random.PRNGKey(0)
    # one host RNG drives draft draws, accept draws, and resamples —
    # deterministic per key
    rng = np.random.default_rng(_key_seed(key))

    t_step = _jit_by_cfg("decode", decode_step, tcfg)
    d_step = _jit_by_cfg("decode", decode_step, dcfg)
    t_verify = _jit_by_cfg("verify", verify_chunk, tcfg)
    t_cache = init_cache(tcfg, 1, total)
    d_cache = init_cache(dcfg, 1, total)

    t_logits = None
    for pos in range(len(prompt)):
        tok = jnp.asarray([prompt[pos]], jnp.int32)
        t_logits, t_cache = t_step(tparams, t_cache, tok, pos)
        _, d_cache = d_step(dparams, d_cache, tok, pos)

    def draw(p):
        return int(rng.choice(len(p), p=p))

    p0 = _filtered_probs(np.asarray(t_logits)[0], temperature, top_k, top_p)
    out = [draw(p0)]
    t_pos = len(prompt)
    while len(out) < max_new_tokens:
        kk = min(k, max_new_tokens - len(out), total - 1 - t_pos)
        if kk <= 0:
            break
        # 1) draft proposes kk tokens, each SAMPLED from its filtered q
        draft, qs = [], []
        cur = out[-1]
        for j in range(kk):
            dl, d_cache = d_step(dparams, d_cache,
                                 jnp.asarray([cur], jnp.int32), t_pos + j)
            q = _filtered_probs(np.asarray(dl)[0], temperature, top_k,
                                top_p)
            cur = draw(q)
            draft.append(cur)
            qs.append(q)
        # 2) target scores the proposals in one chunk: row j's (filtered)
        # distribution is p_j — the law of the token at position t_pos+j
        chunk = jnp.asarray([[out[-1]] + draft[:-1]], jnp.int32)
        vl, t_cache = t_verify(tparams, t_cache, chunk, t_pos)
        ps = [_filtered_probs(np.asarray(vl)[0, j], temperature, top_k,
                              top_p) for j in range(kk)]
        # 3) accept x_j with prob min(1, p_j/q_j); first rejection
        # resamples from the residual (p_j - q_j)+ and ends the round
        for j in range(kk):
            x = draft[j]
            if rng.uniform() < min(1.0, ps[j][x] / max(qs[j][x], 1e-300)):
                out.append(x)
                t_pos += 1
                continue
            resid = np.maximum(ps[j] - qs[j], 0.0)
            mass = resid.sum()
            # degenerate residual (q == p to rounding): draw from p itself
            out.append(draw(resid / mass) if mass > 0 else draw(ps[j]))
            t_pos += 1
            break
    return out[:max_new_tokens]
