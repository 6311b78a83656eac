"""Multi-tenant adapter serving: batched multi-LoRA decode + constrained
(grammar/JSON) sampling.

Beyond-reference capability: the reference's 47k-LoC inference layer was
a *platform* — one engine, many products, each with its own weights and
output contract (per-product AnalysisPredictor pools).  Here one
``DecodeServer`` batch serves N products over ONE base model:

* **AdapterPool** (Punica/S-LoRA shape): up to ``max_adapters`` LoRA
  deltas held as stacked pytree leaves (``<name>_lora_a``
  [A, L, ..., in, r] / ``<name>_lora_b`` [A, L, ..., r, out] — lora.py's
  naming and zero-init-b semantics, one stack row per adapter).  Row 0
  is reserved for the base model and stays all-zero, so a slot with
  adapter id 0 computes ``out + 0.0`` — token-identical to the base.
  Stacks are allocated at FULL [max_adapters+1, ...] shape up front and
  registration writes a row in place, so registering an adapter after
  ``warmup()`` never changes a traced shape (zero mid-serving retraces).

* **Batched gather (BGMV semantics)**: the adapter-aware step functions
  below take the stacks plus per-slot int32 ids ``[B]``, gather each
  slot's ``(a, b)`` pair INSIDE the jitted step, and merge them into
  ``params["blocks"]`` before running the existing per-slot block math
  — ``woq.w`` already adds the low-rank delta after (de)quantization,
  so the base matmul runs once for the whole batch (vmap of a matmul
  against a broadcast weight is one batched matmul) and only the
  rank-r delta einsums are per-slot.  generate.py / kv_pool.py math is
  reused verbatim; nothing is forked.

* **Constrained decoding** (Outlines shape): ``submit(..., constraint=)``
  takes a :class:`TokenSetConstraint` (raw allowed-token escape hatch),
  a :class:`RegexConstraint` (regex -> NFA -> lazy token-level DFA), or
  a :class:`JsonSchemaConstraint` (JSON schema -> regex -> same engine).
  The automaton advances ON HOST from already-fetched tokens; the
  allowed-token bitmask becomes an additive ``[B, V]`` float mask (0
  allowed, -1e30 banned) fed to the jitted sample — a plain array
  input, so constraint state never retraces anything.

Route notes (deliberate scope):

* The adapter-aware PAGED step/verify twins mirror kv_pool's vmap
  fallback routes only; the flash-decode kernel routes
  (``_paged_step_kernel`` / ``_paged_verify_kernel`` /
  ``generate.verify_chunk_batched``) are skipped when a pool is
  attached — they hoist the layer loop above the batch, which would
  need a kernel-side adapter gather (future work; the kernel gate is
  off on CPU anyway, and servers WITHOUT a pool are untouched).
* ``woq._w4_qualifies`` rejects adapted weights, so a W4-packed base
  drops to the dequant+delta path while a pool is attached — the
  documented per-slot cost of QLoRA-style serving.
* Speculative serving composes: the verify pass gathers the SAME
  per-slot adapter (greedy output = the adapter-aware target's argmax
  regardless of what the base-model draft proposed).  In LINEAR spec
  mode constrained slots still force plain stepping for the tick
  (``DecodeServer._spec_ready`` falls back and counts
  ``constraint.spec_fallbacks``); TREE mode instead speculates them:
  :func:`constraint_lookahead` walks the token DFA over the proposed
  tree WITHOUT mutating the request's live state (the lazy
  ``_TokenMachine.table`` is exactly a lookahead table), grammar-banned
  branches are pruned before the verify pass, and acceptance applies
  the state's allowed-mask to each node's logits — so low-entropy
  JSON/regex traffic, the best speculation target there is, stops
  paying the fallback.
"""
from __future__ import annotations

import json
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from . import generate, gpt, lora, woq
from .. import telemetry as _telemetry

__all__ = [
    "AdapterPool", "stacked_pool_specs", "TokenSetConstraint",
    "RegexConstraint", "JsonSchemaConstraint", "compile_constraint",
    "constraint_lookahead", "mask_logits", "apply_constraint_host",
    "NEG_INF",
]

# additive mask value for banned tokens: large-negative instead of true
# -inf so a fully-banned row still softmaxes to a number (categorical
# over all--inf logits is NaN); 1e30 underflows to exactly 0 probability
# in fp32 against any in-support logit
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# adapter-aware step math (the BGMV gather-and-merge core)
# ---------------------------------------------------------------------------

def _select_adapters(stacks: dict, ids):
    """Gather per-slot adapter leaves: {name: [A, L, ...]} + ids [B]
    -> {name: [B, L, ...]}.  A plain stack index — XLA lowers it to one
    gather per leaf, the whole cost of per-slot adapter routing."""
    return {n: s[ids] for n, s in stacks.items()}


def _merge_params(params: dict, gad: dict) -> dict:
    """One slot's adapted param tree: the gathered [L, ...] lora leaves
    ride ``params["blocks"]`` (and therefore the per-layer ``lax.scan``
    xs) exactly like lora.join_lora's output — ``woq.w`` applies the
    delta, every consumer downstream is unchanged."""
    return dict(params, blocks=dict(params["blocks"], **gad))


def adapter_decode_step_batched(params, cache, stacks, ids, token, pos,
                                cfg: gpt.GPTConfig):
    """``serving.decode_step_batched`` with per-slot adapters: token [B]
    int32, pos [B] int32, ids [B] int32 (0 = base) -> (logits [B, V],
    cache).  Contiguous: vmap of the scalar-pos ``generate.decode_step``
    with the slot's gathered adapter pair merged into the blocks tree.
    Paged (a ``tables`` leaf): the block-table twin below."""
    g = _select_adapters(stacks, ids)
    if "tables" in cache:
        return _paged_adapter_step(params, cache, g, token, pos, cfg)

    def one(tok, csl, p, gad):
        pp = _merge_params(params, gad)
        sl = {name: v[:, None] for name, v in csl.items()}
        logits, new = generate.decode_step(pp, sl, tok[None], p, cfg)
        return logits[0], {name: v[:, 0] for name, v in new.items()}

    logits, new = jax.vmap(one, in_axes=(0, 1, 0, 0), out_axes=(0, 1))(
        token, cache, pos, g)
    return logits, new


def _paged_adapter_step(params, cache, g, token, pos, cfg: gpt.GPTConfig):
    """kv_pool.paged_decode_step_batched's vmap fallback route with the
    per-slot adapter merge (kernel route skipped — see module doc)."""
    from . import kv_pool

    N, bs, nmax = kv_pool._geometry(cache)
    B = token.shape[0]
    tables = cache["tables"]
    pool = {n: cache[n] for n in kv_pool.POOL_LEAVES if n in cache}

    def one(tok_b, pos_b, trow, gad):
        dt = cfg.dtype
        x = generate._embed_step(params, tok_b[None], pos_b, cfg)
        merged = dict(params["blocks"], **gad)

        def body(x, layer):
            p, li = layer
            csl = kv_pool._gather_slot(pool, li, trow, cfg)
            x, rows = generate._cached_block(x, p, csl, pos_b, cfg)
            return x, rows

        x, rows = jax.lax.scan(
            body, x, (merged, jnp.arange(cfg.num_layers)))
        x = gpt._norm(x, params, "ln_f", cfg)
        logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)[:, 0]
        return logits[0].astype(jnp.float32), rows

    logits, rows = jax.vmap(one, in_axes=(0, 0, 0, 0),
                            out_axes=(0, 0))(token, pos, tables, g)
    tb = tables[jnp.arange(B), pos // bs]
    phys = jnp.where(tb >= 0, tb * bs + pos % bs, N * bs)
    stacked = {n: jnp.moveaxis(v[:, :, 0], 0, 1) for n, v in rows.items()}
    return logits, kv_pool._scatter_rows(cache, stacked, phys)


def adapter_sample_step_batched(params, cache, stacks, ids, tok, pos, key,
                                temp, topk, topp, mask,
                                cfg: gpt.GPTConfig):
    """Adapter-aware ``sample_step_batched`` with the constraint mask:
    mask [B, V] float32 additive (all-zero = unconstrained; pass None to
    skip), greedy slots (temp 0) take the argmax of the MASKED logits so
    one executable serves constrained-greedy and constrained-sampled."""
    from . import serving as _serving

    logits, cache = adapter_decode_step_batched(params, cache, stacks,
                                                ids, tok, pos, cfg)
    return _serving._sample_batched(logits, key, temp, topk, topp,
                                    mask=mask), cache


def adapter_decode_block_batched(params, cache, stacks, ids, tok, pos,
                                 k: int, cfg: gpt.GPTConfig):
    """Adapter-aware ``decode_block_batched``: k greedy steps on device,
    each re-gathering from the (loop-invariant) stacks — XLA hoists the
    gather out of the scan."""
    def body(carry, _):
        cache, tok, pos = carry
        logits, cache = adapter_decode_step_batched(
            params, cache, stacks, ids, tok, pos, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt, pos + 1), nxt

    (cache, tok, pos), toks = jax.lax.scan(body, (cache, tok, pos), None,
                                           length=k)
    return toks.T, cache, tok, pos


def adapter_spec_verify_batched(params, cache, stacks, ids, tokens, pos,
                                cfg: gpt.GPTConfig):
    """Adapter-aware ``spec_verify_batched``: the verify pass gathers
    the SAME per-slot adapter the decode step uses, so accepted draft
    tokens are exactly the adapter-aware target's tokens.  vmap fallback
    routes only (kernel form hoists the layer loop above the batch)."""
    g = _select_adapters(stacks, ids)
    if "tables" in cache:
        return _paged_adapter_verify(params, cache, g, tokens, pos, cfg)

    def one(tok, csl, p, gad):
        pp = _merge_params(params, gad)
        sl = {name: v[:, None] for name, v in csl.items()}
        logits, new = generate.verify_chunk(pp, sl, tok[None], p, cfg)
        return logits[0], {name: v[:, 0] for name, v in new.items()}

    logits, new = jax.vmap(one, in_axes=(0, 1, 0, 0), out_axes=(0, 1))(
        tokens, cache, pos, g)
    return logits, new


def _paged_adapter_verify(params, cache, g, tokens, pos,
                          cfg: gpt.GPTConfig):
    """kv_pool.paged_verify_chunk_batched's vmap fallback route with the
    per-slot adapter merge."""
    from . import kv_pool

    N, bs, nmax = kv_pool._geometry(cache)
    B, K = tokens.shape
    tables = cache["tables"]
    pool = {n: cache[n] for n in kv_pool.POOL_LEAVES if n in cache}
    dt = cfg.dtype

    def one(tok_k, p0, trow, gad):
        x = woq.embed(params, tok_k[None], dt,
                      cfg.embedding_multiplier)              # [1, K, D]
        if cfg.pos_embed == "learned":
            x = x + jax.lax.dynamic_slice(
                params["wpe"], (p0, 0),
                (K, cfg.hidden_size)).astype(dt)[None]
        merged = dict(params["blocks"], **gad)

        def body(x, layer):
            p, li = layer
            csl = kv_pool._gather_slot(pool, li, trow, cfg)
            x, rows = generate._chunk_attend_block(x, p, csl, p0, cfg)
            return x, rows

        x, rows = jax.lax.scan(
            body, x, (merged, jnp.arange(cfg.num_layers)))
        x = gpt._norm(x, params, "ln_f", cfg)
        logits = woq.logits(x, params, dt,
                            cfg.lm_head_multiplier)[0]      # [K, V]
        return logits.astype(jnp.float32), rows

    logits, rows = jax.vmap(one, in_axes=(0, 0, 0, 0),
                            out_axes=(0, 0))(tokens, pos, tables, g)
    logi = pos[:, None] + jnp.arange(K)[None, :]          # [B, K]
    tb = jnp.take_along_axis(tables, jnp.clip(logi // bs, 0, nmax - 1),
                             axis=1)
    phys = jnp.where((tb >= 0) & (logi // bs < nmax),
                     tb * bs + logi % bs, N * bs).reshape(B * K)
    stacked = {}
    for n, v in rows.items():
        v = jnp.moveaxis(v[:, :, 0], 0, 1)                # [L, B, K, ...]
        stacked[n] = v.reshape((v.shape[0], B * K) + v.shape[3:])
    return logits, kv_pool._scatter_rows(cache, stacked, phys)


def adapter_prefill_slot(params, cache, stacks, aid, tokens, length, slot,
                         cfg: gpt.GPTConfig):
    """``generate.prefill_slot`` under one slot's adapter (scalar int32
    ``aid``): gather-and-merge once at the top, no vmap needed."""
    return generate.prefill_slot(
        _merge_params(params, {n: s[aid] for n, s in stacks.items()}),
        cache, tokens, length, slot, cfg)


def adapter_prefill_slot_chunk(params, cache, stacks, aid, tokens, pos0,
                               length, slot, cfg: gpt.GPTConfig):
    """``generate.prefill_slot_chunk`` under one slot's adapter."""
    return generate.prefill_slot_chunk(
        _merge_params(params, {n: s[aid] for n, s in stacks.items()}),
        cache, tokens, pos0, length, slot, cfg)


def adapter_paged_prefill_chunk(params, cache, stacks, aid, tokens, pos0,
                                length, slot, cfg: gpt.GPTConfig):
    """``kv_pool.paged_prefill_chunk`` under one slot's adapter — the
    merged [L, ...] leaves ride the function's own per-layer scan."""
    from . import kv_pool

    return kv_pool.paged_prefill_chunk(
        _merge_params(params, {n: s[aid] for n, s in stacks.items()}),
        cache, tokens, pos0, length, slot, cfg)


# ---------------------------------------------------------------------------
# AdapterPool — the registry the server gathers from
# ---------------------------------------------------------------------------

class AdapterPool:
    """Fixed-capacity registry of LoRA adapters as stacked device leaves.

    Stacks are preallocated ZERO at [max_adapters + 1, ...] (row 0 = the
    base model, permanently zero) so the traced shapes — and therefore
    every jit cache key derived from :meth:`pool_key` — are fixed at
    construction: registering adapter #3 after ``warmup()`` is a row
    write, never a retrace.

        pool = AdapterPool(params, cfg, rank=8, max_adapters=4)
        pool.register("product-a", lora.split_lora(adapted)[1])
        srv = DecodeServer(params, cfg, ..., adapter_pool=pool)
        srv.submit(prompt, adapter="product-a")

    ``targets`` follows lora.lora_init's default (the attention
    projections); only targets actually present in ``params["blocks"]``
    get stacks, and every registered adapter must carry exactly that
    target set at this pool's rank (the same-rank/same-targets pool
    validation ISSUE'd from lora.stack_adapters)."""

    def __init__(self, params: dict, cfg: gpt.GPTConfig, rank: int = 8,
                 max_adapters: int = 8,
                 targets: tuple = ("qkv_w", "q_w", "kv_w", "proj_w")):
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        if max_adapters < 1:
            raise ValueError(
                f"max_adapters must be >= 1, got {max_adapters}")
        blocks = params["blocks"]
        self.cfg = cfg
        self.rank = int(rank)
        self.max_adapters = int(max_adapters)
        self.targets = tuple(t for t in targets if t in blocks)
        if not self.targets:
            raise ValueError(
                f"none of targets {targets} present in params['blocks'] "
                f"(names: {sorted(blocks)[:8]}...)")
        A = self.max_adapters + 1                   # row 0 = base (zeros)
        self._stacks = {}
        for t in self.targets:
            shp = tuple(blocks[t].shape)            # [L, ..., in, out]
            self._stacks[t + lora._SUFFIX_A] = jnp.zeros(
                (A,) + shp[:-1] + (self.rank,), jnp.float32)
            self._stacks[t + lora._SUFFIX_B] = jnp.zeros(
                (A,) + shp[:-2] + (self.rank, shp[-1]), jnp.float32)
        self._ids: dict[str, int] = {}              # name -> row (>= 1)
        self._tenant_default: dict[Any, str] = {}

    # -- registration -------------------------------------------------

    def register(self, name: str, adapters: dict) -> int:
        """Write one adapter into the pool; returns its int id (>= 1).

        ``adapters`` is lora.py's adapter sub-tree ({"qkv_w_lora_a":
        [L, ..., r], ...} — ``split_lora(tree)[1]``), or a full adapted
        param tree (the ``blocks`` lora leaves are extracted).
        Re-registering a name overwrites its row in place."""
        if not name or not isinstance(name, str):
            raise ValueError(f"adapter name must be a non-empty string, "
                             f"got {name!r}")
        if isinstance(adapters, dict) and "blocks" in adapters:
            adapters = lora.split_lora(adapters)[1]
        want = set(self._stacks)
        got = set(adapters)
        if got != want:
            raise ValueError(
                f"adapter {name!r} target/leaf mismatch: pool holds "
                f"{sorted(want)}, adapter has {sorted(got)} (same "
                f"rank/targets across the pool — see lora.stack_adapters)")
        for leaf, stack in self._stacks.items():
            arr = jnp.asarray(adapters[leaf], jnp.float32)
            if tuple(arr.shape) != tuple(stack.shape[1:]):
                raise ValueError(
                    f"adapter {name!r} leaf {leaf}: shape "
                    f"{tuple(arr.shape)} != pool row {tuple(stack.shape[1:])}"
                    f" (rank {self.rank})")
        i = self._ids.get(name)
        if i is None:
            if len(self._ids) >= self.max_adapters:
                raise ValueError(
                    f"adapter pool full ({self.max_adapters}); evict or "
                    f"size the pool for the product set")
            i = len(self._ids) + 1
        for leaf in self._stacks:
            self._stacks[leaf] = self._stacks[leaf].at[i].set(
                jnp.asarray(adapters[leaf], jnp.float32))
        self._ids[name] = i
        if _telemetry.enabled():
            _telemetry.count("adapters.registered")
        return i

    # -- lookups ------------------------------------------------------

    def resolve(self, name: str | None) -> int:
        """Adapter id for ``name`` (None -> 0, the base model)."""
        if name is None:
            return 0
        i = self._ids.get(name)
        if i is None:
            raise ValueError(f"unknown adapter {name!r} "
                             f"(registered: {sorted(self._ids)})")
        return i

    def names(self) -> list:
        return sorted(self._ids)

    def name_of(self, aid: int) -> str:
        for n, i in self._ids.items():
            if i == aid:
                return n
        return "base"

    def stacks(self) -> dict:
        """The live stacked leaves (device arrays; never donated)."""
        return dict(self._stacks)

    def pool_key(self) -> tuple:
        """Jit-cache key fragment: the pool GEOMETRY (capacity, rank,
        targets) — everything that shapes the traced stacks.  Contents
        (which adapters are registered) deliberately excluded: a row
        write must not split executables."""
        return ("adapters", self.max_adapters + 1, self.rank, self.targets)

    # -- tenancy ------------------------------------------------------

    def set_tenant_default(self, tenant, name: str | None) -> None:
        """Map a tenant to its default adapter: ``submit(tenant=...)``
        without an explicit ``adapter=`` resolves through this (the PR
        13 tenant key buys both rate limits and weights)."""
        if name is not None:
            self.resolve(name)                      # validate now
        if name is None:
            self._tenant_default.pop(tenant, None)
        else:
            self._tenant_default[tenant] = name

    def default_for(self, tenant) -> str | None:
        return self._tenant_default.get(tenant)


def stacked_pool_specs(pool: AdapterPool, mp: str = "mp") -> dict:
    """PartitionSpecs for the pool's stacked ``[A, ...]`` leaves under
    tensor-parallel (``mesh=``) serving — derived from each TARGET's
    Megatron spec (gpt.param_shardings) with the leading stack axis
    replicated.

    The rule mirrors the base weight it adapts: ``*_lora_a``
    ``[A, ..., in, r]`` keeps the base spec's dims up to (and
    including) the input dim and replicates the rank dim; ``*_lora_b``
    ``[A, ..., r, out]`` replicates the rank dim and keeps the base
    OUTPUT dim's spec.  A column-parallel target (out over ``mp``)
    therefore gets a replicated ``a`` and an out-sharded ``b`` — the
    gathered delta lands sharded exactly like the base weight, so
    GSPMD adds it without a reshard; row-parallel targets mirror on
    the input side."""
    from jax.sharding import PartitionSpec as P

    base = gpt.param_shardings(pool.cfg, mp=mp)["blocks"]
    specs = {}
    for t in pool.targets:
        dims = tuple(base[t])                 # matches the base leaf rank
        specs[t + lora._SUFFIX_A] = P(None, *dims[:-1], None)
        specs[t + lora._SUFFIX_B] = P(None, *dims[:-2], None, dims[-1])
    return specs


# ---------------------------------------------------------------------------
# constrained decoding: regex -> NFA -> lazy token-level DFA
# ---------------------------------------------------------------------------

class _Regex:
    """Thompson-NFA compiler for the regex subset constraints need:
    literals, ``\\`` escapes, ``.``, ``[...]`` classes (ranges,
    negation), grouping, ``|``, ``* + ?``.  Char-level moves run through
    a lazily built subset-construction DFA (frozenset states, cached
    per (state, char)) — no dependency, no backtracking, O(len) per
    token walk."""

    def __init__(self, pattern: str):
        self._pat = pattern
        self._trans: list = []   # per state: [(pred, dst), ...]
        self._eps: list = []     # per state: [dst, ...]
        self._pos = 0
        s, e = self._parse_alt()
        if self._pos != len(pattern):
            raise ValueError(f"regex {pattern!r}: trailing input at "
                             f"{self._pos}")
        self._start, self._accept = s, e
        self.start_state = frozenset(self._closure({s}))
        self._moves: dict = {}

    # -- NFA construction ---------------------------------------------

    def _new(self) -> int:
        self._trans.append([])
        self._eps.append([])
        return len(self._trans) - 1

    def _peek(self):
        return self._pat[self._pos] if self._pos < len(self._pat) else None

    def _parse_alt(self):
        frags = [self._parse_cat()]
        while self._peek() == "|":
            self._pos += 1
            frags.append(self._parse_cat())
        if len(frags) == 1:
            return frags[0]
        s, e = self._new(), self._new()
        for fs, fe in frags:
            self._eps[s].append(fs)
            self._eps[fe].append(e)
        return s, e

    def _parse_cat(self):
        frags = []
        while self._peek() is not None and self._peek() not in "|)":
            frags.append(self._parse_rep())
        if not frags:
            s = self._new()
            return s, s                              # empty match
        s, e = frags[0]
        for fs, fe in frags[1:]:
            self._eps[e].append(fs)
            e = fe
        return s, e

    def _parse_rep(self):
        s, e = self._parse_atom()
        c = self._peek()
        if c == "*":
            self._pos += 1
            ns, ne = self._new(), self._new()
            self._eps[ns] += [s, ne]
            self._eps[e] += [s, ne]
            return ns, ne
        if c == "+":
            self._pos += 1
            ne = self._new()
            self._eps[e] += [s, ne]
            return s, ne
        if c == "?":
            self._pos += 1
            ns, ne = self._new(), self._new()
            self._eps[ns] += [s, ne]
            self._eps[e].append(ne)
            return ns, ne
        return s, e

    def _parse_atom(self):
        c = self._peek()
        if c is None:
            raise ValueError(f"regex {self._pat!r}: unexpected end")
        if c == "(":
            self._pos += 1
            s, e = self._parse_alt()
            if self._peek() != ")":
                raise ValueError(f"regex {self._pat!r}: unclosed group")
            self._pos += 1
            return s, e
        if c == "[":
            return self._parse_class()
        if c == "\\":
            self._pos += 2
            if self._pos > len(self._pat):
                raise ValueError(f"regex {self._pat!r}: dangling escape")
            return self._lit(("char", self._pat[self._pos - 1]))
        if c == ".":
            self._pos += 1
            return self._lit(("any",))
        if c in "*+?|)":
            raise ValueError(f"regex {self._pat!r}: unexpected {c!r} at "
                             f"{self._pos}")
        self._pos += 1
        return self._lit(("char", c))

    def _parse_class(self):
        self._pos += 1                               # consume '['
        neg = self._peek() == "^"
        if neg:
            self._pos += 1
        chars, ranges = set(), []
        first = True
        while True:
            c = self._peek()
            if c is None:
                raise ValueError(f"regex {self._pat!r}: unclosed class")
            if c == "]" and not first:
                self._pos += 1
                break
            first = False
            if c == "\\":
                self._pos += 2
                c = self._pat[self._pos - 1]
            else:
                self._pos += 1
            if self._peek() == "-" and self._pos + 1 < len(self._pat) \
                    and self._pat[self._pos + 1] != "]":
                self._pos += 1
                hi = self._peek()
                if hi == "\\":
                    self._pos += 1
                    hi = self._peek()
                self._pos += 1
                ranges.append((c, hi))
            else:
                chars.add(c)
        return self._lit(("class", frozenset(chars), tuple(ranges), neg))

    def _lit(self, pred):
        s, e = self._new(), self._new()
        self._trans[s].append((pred, e))
        return s, e

    # -- simulation ---------------------------------------------------

    @staticmethod
    def _match(pred, ch: str) -> bool:
        kind = pred[0]
        if kind == "any":
            return True
        if kind == "char":
            return ch == pred[1]
        _, chars, ranges, neg = pred
        hit = ch in chars or any(lo <= ch <= hi for lo, hi in ranges)
        return hit != neg

    def _closure(self, states: set) -> set:
        stack, seen = list(states), set(states)
        while stack:
            for nxt in self._eps[stack.pop()]:
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append(nxt)
        return seen

    def move(self, dstate: frozenset, ch: str) -> frozenset:
        """One char step of the lazy DFA (cached)."""
        key = (dstate, ch)
        out = self._moves.get(key)
        if out is None:
            nxt = set()
            for s in dstate:
                for pred, dst in self._trans[s]:
                    if self._match(pred, ch):
                        nxt.add(dst)
            out = frozenset(self._closure(nxt)) if nxt else frozenset()
            self._moves[key] = out
        return out

    def accepting(self, dstate: frozenset) -> bool:
        return self._accept in dstate

    def walk(self, dstate: frozenset, text: str) -> frozenset:
        for ch in text:
            if not dstate:
                return dstate
            dstate = self.move(dstate, ch)
        return dstate


class _TokenMachine:
    """Token-level transition table over a char regex: per DFA state,
    which token ids keep the automaton alive (prefix-viable — every NFA
    state Thompson builds can reach accept, so a viable prefix always
    completes), and where each allowed token lands.  Built lazily per
    state and cached on the SPEC (shared across requests/servers)."""

    def __init__(self, rx: _Regex, vocab: list, eos_id: int | None):
        self.rx = rx
        self.vocab = [str(t) for t in vocab]
        self.eos_id = eos_id
        self._table: dict = {}   # dstate -> (mask np.bool_[V], {tid: nxt})

    def table(self, dstate: frozenset):
        ent = self._table.get(dstate)
        if ent is None:
            V = len(self.vocab)
            mask = np.zeros(V, bool)
            nxt = {}
            for tid, text in enumerate(self.vocab):
                if tid == self.eos_id:
                    continue                         # handled below
                if not text:
                    continue                         # empty token: stall
                land = self.rx.walk(dstate, text)
                if land:
                    mask[tid] = True
                    nxt[tid] = land
            if self.eos_id is not None and self.rx.accepting(dstate):
                mask[self.eos_id] = True
            ent = (mask, nxt)
            self._table[dstate] = ent
        return ent


class Constraint:
    """Base class for ``submit(..., constraint=)`` specs.  A spec is a
    compiled, shareable TEMPLATE; :meth:`start` mints the per-request
    state machine the server advances from fetched tokens."""

    def start(self, vocab_size: int) -> "ConstraintState":
        raise NotImplementedError


class ConstraintState:
    """One request's live automaton position.

    ``allowed_mask()`` -> np.bool_[V] (True = allowed next token);
    ``advance(t)`` moves past an appended token; ``exhausted`` means no
    continuation exists (finished language, or eos consumed) — the
    server retires the slot."""

    def __init__(self, mask, machine: _TokenMachine | None,
                 state: frozenset | None, eos_id: int | None):
        self._fixed = mask                           # token-set form
        self._m = machine
        self._state = state
        self._eos = eos_id
        self.exhausted = False

    def allowed_mask(self) -> np.ndarray:
        if self._m is None:
            return self._fixed
        mask, _ = self._m.table(self._state)
        return mask

    def advance(self, t: int) -> None:
        if self.exhausted:
            return
        if self._eos is not None and int(t) == self._eos:
            self.exhausted = True
            return
        if self._m is None:
            return
        _, nxt = self._m.table(self._state)
        land = nxt.get(int(t))
        if land is None:
            # the model emitted a banned token (only possible if the
            # caller bypassed the mask); die closed rather than emit
            # invalid output forever
            self.exhausted = True
            return
        self._state = land
        mask, _ = self._m.table(self._state)
        if not mask.any():
            self.exhausted = True                    # finished language


class ConstraintLookahead:
    """A NON-MUTATING cursor over a :class:`ConstraintState`'s automaton
    — the tree-speculation primitive.  Pruning a proposed token tree
    needs the DFA advanced down *several* branches from the request's
    current position without committing any of them; ``child(t)`` mints
    an independently-advanced cursor (die-closed exactly like
    ``ConstraintState.advance``), so one cursor per live tree node walks
    the whole trie while the request's real state stays untouched until
    acceptance.  The per-state token table is the machine's lazy cache,
    shared with the live state — lookahead costs no extra table builds
    beyond states the walk actually visits.

    Duck-types ``allowed_mask()``/``exhausted`` with ConstraintState, so
    :func:`apply_constraint_host` masks accept-time logit rows through a
    cursor unchanged."""

    __slots__ = ("_fixed", "_m", "_state", "_eos", "exhausted")

    def __init__(self, fixed, machine, state, eos_id, exhausted=False):
        self._fixed = fixed
        self._m = machine
        self._state = state
        self._eos = eos_id
        self.exhausted = exhausted

    def allowed_mask(self) -> np.ndarray:
        if self._m is None:
            return self._fixed
        mask, _ = self._m.table(self._state)
        return mask

    def allows(self, t: int) -> bool:
        """Would the automaton accept ``t`` here?  (eos rides the mask:
        allowed exactly when the current state admits ending.)"""
        if self.exhausted:
            return False
        return bool(self.allowed_mask()[int(t)])

    def child(self, t: int) -> "ConstraintLookahead":
        """A NEW cursor advanced past ``t`` — ``self`` is untouched, so
        sibling branches each get their own continuation."""
        if self.exhausted:
            return self
        if self._eos is not None and int(t) == self._eos:
            return ConstraintLookahead(self._fixed, self._m, self._state,
                                       self._eos, exhausted=True)
        if self._m is None:
            return self                              # token-set: static
        _, nxt = self._m.table(self._state)
        land = nxt.get(int(t))
        if land is None:                             # banned: die closed
            return ConstraintLookahead(self._fixed, self._m, self._state,
                                       self._eos, exhausted=True)
        mask, _ = self._m.table(land)
        return ConstraintLookahead(self._fixed, self._m, land, self._eos,
                                   exhausted=not mask.any())


def constraint_lookahead(cst: ConstraintState) -> ConstraintLookahead:
    """Mint a lookahead cursor positioned at a live request state."""
    return ConstraintLookahead(cst._fixed, cst._m, cst._state, cst._eos,
                               exhausted=cst.exhausted)


class TokenSetConstraint(Constraint):
    """Raw allowed-token-set escape hatch: every generated token must be
    in ``allowed`` (``eos_id``, when given, is always allowed so the
    request can end)."""

    def __init__(self, allowed: Iterable[int], eos_id: int | None = None):
        self.allowed = sorted({int(t) for t in allowed})
        if not self.allowed:
            raise ValueError("empty allowed-token set")
        self.eos_id = eos_id

    def start(self, vocab_size: int) -> ConstraintState:
        if self.allowed[-1] >= vocab_size or self.allowed[0] < 0:
            raise ValueError(
                f"allowed token ids {self.allowed[0]}..{self.allowed[-1]} "
                f"out of vocab range [0, {vocab_size})")
        mask = np.zeros(vocab_size, bool)
        mask[self.allowed] = True
        if self.eos_id is not None:
            mask[self.eos_id] = True
        return ConstraintState(mask, None, None, self.eos_id)


class RegexConstraint(Constraint):
    """Regex-automaton constraint: ``vocab[i]`` is token i's decoded
    text; generated text must stay a viable prefix of ``pattern``, and
    eos (when the server has one) is allowed exactly at accepting
    states.  The token table is built lazily per automaton state and
    shared across every request using this spec."""

    def __init__(self, pattern: str, vocab: list,
                 eos_id: int | None = None):
        self.pattern = pattern
        self._machine = _TokenMachine(_Regex(pattern), vocab, eos_id)
        self.eos_id = eos_id

    @property
    def vocab_size(self) -> int:
        return len(self._machine.vocab)

    def start(self, vocab_size: int) -> ConstraintState:
        if self.vocab_size != vocab_size:
            raise ValueError(
                f"constraint vocab has {self.vocab_size} entries, model "
                f"vocab is {vocab_size}")
        st = ConstraintState(None, self._machine,
                             self._machine.rx.start_state, self.eos_id)
        if not st.allowed_mask().any():
            raise ValueError(
                f"pattern {self.pattern!r}: no vocab token is a viable "
                f"first step")
        return st


def _rx_escape(text: str) -> str:
    return "".join("\\" + c if c in r"\.[]()|*+?^{}-" else c
                   for c in text)


def _schema_to_regex(schema: dict) -> str:
    """JSON schema -> regex over the COMPACT serialization (no
    whitespace — ``json.dumps(..., separators=(',', ':'))`` form).

    Supported: object (all listed properties required, in listing
    order), string (escape-free), integer, number, boolean, null, enum
    (any JSON-dumpable values), array-of-items.  That is the product-
    output-contract subset; anything else raises."""
    if not isinstance(schema, dict):
        raise ValueError(f"schema must be a dict, got {type(schema)}")
    if "enum" in schema:
        opts = [_rx_escape(json.dumps(v, separators=(",", ":")))
                for v in schema["enum"]]
        return "(" + "|".join(opts) + ")"
    t = schema.get("type")
    if t == "object":
        props = schema.get("properties", {})
        body = ",".join(
            _rx_escape(json.dumps(k)) + ":" + _schema_to_regex(v)
            for k, v in props.items())
        return r"\{" + body + r"\}"
    if t == "array":
        item = _schema_to_regex(schema.get("items", {"type": "integer"}))
        return r"\[(" + item + "(," + item + r")*)?\]"
    if t == "string":
        return r'"[^"\\]*"'
    if t == "integer":
        return r"-?(0|[1-9][0-9]*)"
    if t == "number":
        return r"-?(0|[1-9][0-9]*)(\.[0-9]+)?"
    if t == "boolean":
        return "(true|false)"
    if t == "null":
        return "null"
    raise ValueError(f"unsupported schema node: {schema!r}")


class JsonSchemaConstraint(RegexConstraint):
    """JSON-schema constraint — the common product contract: compiles
    the schema to a regex over the compact serialization and rides the
    regex automaton engine.  Decoded output (``"".join(vocab[t] for t
    in tokens)``) is guaranteed parseable JSON matching the schema's
    shape once the automaton reaches accept (finite schemas — enums,
    booleans, bounded objects — are guaranteed to terminate; string/
    number fields terminate when the model closes them)."""

    def __init__(self, schema: dict, vocab: list,
                 eos_id: int | None = None):
        self.schema = schema
        super().__init__(_schema_to_regex(schema), vocab, eos_id)


def compile_constraint(spec, vocab_size: int) -> ConstraintState:
    """Normalize a ``submit(constraint=)`` argument to a per-request
    state: a :class:`Constraint` spec, or a bare iterable of token ids
    (sugar for :class:`TokenSetConstraint` without eos)."""
    if isinstance(spec, Constraint):
        return spec.start(vocab_size)
    if isinstance(spec, ConstraintState):
        raise ValueError(
            "constraint= takes the spec, not a started state (states are "
            "per-request)")
    try:
        return TokenSetConstraint(spec).start(vocab_size)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"constraint= must be a Constraint or an iterable of token "
            f"ids: {e}") from None


# ---------------------------------------------------------------------------
# host-side mask builders (the telemetry-counted constraint hot path)
# ---------------------------------------------------------------------------

def mask_logits(constraints: dict, batch: int, vocab_size: int):
    """Build the per-tick additive mask [batch, vocab] float32 from
    {slot: ConstraintState} (slots absent = unconstrained, row stays
    zero).  0 = allowed, NEG_INF = banned; counts
    ``constraint.masked_tokens`` (banned vocab entries this tick — the
    Prometheus counter operators watch for constraint pressure)."""
    m = np.zeros((batch, vocab_size), np.float32)
    banned = 0
    for b, st in constraints.items():
        a = st.allowed_mask()
        m[b, ~a] = NEG_INF
        banned += int(vocab_size - a.sum())
    if banned and _telemetry.enabled():
        _telemetry.count("constraint.masked_tokens", banned)
    return m


def apply_constraint_host(logits_row: np.ndarray,
                          state: ConstraintState) -> np.ndarray:
    """Mask ONE host-side logits row (the admission first-token draw
    happens on host, before any device mask exists); counts
    ``constraint.masked_tokens`` like the batched builder."""
    a = state.allowed_mask()
    if _telemetry.enabled():
        _telemetry.count("constraint.masked_tokens",
                         int(a.size - a.sum()))
    return np.where(a, logits_row, np.float32(NEG_INF))
