"""Global flags: ``paddle.set_flags`` / ``get_flags``.

Reference capability: ~35 gflags in platform/flags.cc exposed through
pybind/global_value_getter_setter.cc and settable as FLAGS_* env vars or
``paddle.set_flags``.  TPU-native mapping: flags that correspond to XLA/JAX
config knobs forward there; framework-behavior flags (nan/inf checking, GC,
allocator-strategy equivalents that PJRT owns) live in a plain registry consulted
by the runtime pieces.
"""
from __future__ import annotations

import os
from typing import Any, Iterable, Mapping

_JAX_MAPPED = {
    # reference FLAGS_check_nan_inf (platform/flags.cc:44): XLA-level nan
    # trap on every jitted computation
    "FLAGS_check_nan_inf": "jax_debug_nans",
    # escape hatch: run ops eagerly without compilation
    "FLAGS_disable_jit": "jax_disable_jit",
    # matmul precision on the MXU (bf16 passes vs fp32): 'default'|'high'|'highest'
    "FLAGS_matmul_precision": "jax_default_matmul_precision",
}

_REGISTRY: dict[str, Any] = {
    "FLAGS_check_nan_inf": False,
    "FLAGS_disable_jit": False,
    "FLAGS_matmul_precision": None,
    # host-side step-level nan scan (framework/details/nan_inf_utils role,
    # implemented in framework.debugger for train steps)
    "FLAGS_check_nan_inf_host": False,
    "FLAGS_benchmark": False,
    "FLAGS_allocator_strategy": "pjrt",  # informational: PJRT owns HBM
}

# env seeding, like the reference's FLAGS_* env support — routed through
# set_flags below so JAX-mapped flags actually take effect
_ENV_SEEDED = {}
for _k in list(_REGISTRY):
    if _k in os.environ:
        v = os.environ[_k]
        _ENV_SEEDED[_k] = {"true": True, "false": False, "1": True,
                           "0": False}.get(v.lower(), v)


def set_flags(flags: Mapping[str, Any]):
    import jax

    for k, v in flags.items():
        if k not in _REGISTRY:
            raise ValueError(f"unknown flag {k!r}; known: {sorted(_REGISTRY)}")
        _REGISTRY[k] = v
        if k in _JAX_MAPPED and v is not None:
            jax.config.update(_JAX_MAPPED[k], v)


def get_flags(flags: str | Iterable[str]):
    if isinstance(flags, str):
        flags = [flags]
    return {k: _REGISTRY[k] for k in flags}


def flag(name: str, default=None):
    """Internal accessor used by framework code."""
    return _REGISTRY.get(name, default)


def async_train() -> bool:
    """Sync-free ``Model.fit`` loop (ON by default).

    When on, the fit loop keeps every per-step loss ON DEVICE and only
    drains (host-fetches) it at ``log_freq`` boundaries and epoch end, so
    steady-state train steps issue zero synchronous host<->device round
    trips and JAX async dispatch keeps the device saturated.
    ``PADDLE_TPU_ASYNC_TRAIN=0`` is the escape hatch (per-step float
    losses, the pre-PR-2 behavior).  Read at ``Model.prepare`` /
    ``TrainStep`` construction — like ``PADDLE_TPU_DONATE_DECODE`` it is
    part of the step's construction key (``train_step_key``): flipping it
    mid-process affects new TrainSteps, never a live one."""
    v = os.environ.get("PADDLE_TPU_ASYNC_TRAIN", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def train_grad_accum() -> int:
    """Default microbatch count for in-jit gradient accumulation
    (``TrainStep(grad_accum=...)``); ``PADDLE_TPU_GRAD_ACCUM=N`` sets the
    default for TrainSteps that don't pass it explicitly (1 = off).

    Accumulation is a ``lax.scan`` baked into the compiled step program
    at trace time, so the value is part of ``train_step_key``: flipping
    the env mid-process changes newly built steps (retrace), never a
    compiled one."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_GRAD_ACCUM", "1")))
    except ValueError:
        return 1


def fit_prefetch() -> bool:
    """Route ``Model.fit``'s batch stream through ``io.DevicePrefetcher``
    (ON by default): host batch assembly + the host->device transfer run
    in a background thread ``prefetch_factor`` batches ahead, overlapping
    the running step.  ``PADDLE_TPU_FIT_PREFETCH=0`` is the escape hatch
    (synchronous per-step uploads)."""
    v = os.environ.get("PADDLE_TPU_FIT_PREFETCH", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def train_step_key() -> tuple:
    """The trace-time training-flag tuple — the ``_cfg_key`` analog for
    the training hot path.  Everything here is BAKED into a TrainStep at
    construction (accumulation scan shape, async drain mode, prefetch
    routing, the non-finite skip guard, and any in-jit fault injection —
    the last two change the compiled program); today the TrainStep
    INSTANCE is the only cache (each construction re-reads the flags, so
    flipping an env var affects new steps and never a compiled one).
    Any future cross-instance cache of compiled train steps must fold
    this tuple into its key, exactly like the decode cache folds
    ``PADDLE_TPU_DONATE_DECODE``."""
    from . import faults as _faults

    return (train_grad_accum(), async_train(), fit_prefetch(),
            nan_guard(), _faults.spec_string())


def resilience_enabled() -> bool:
    """Resilience layer master switch (ON by default).

    When on, the runtime SURVIVES faults instead of dying on them:
    ``resilience.retry`` engages bounded backoff chains, ``DecodeServer``
    sheds expired requests / runs the OOM retry chain / recovers wedged
    async steps, ``TrainStep`` skips non-finite steps, and the
    ``DevicePrefetcher`` retries transient reader errors.
    ``PADDLE_TPU_RESILIENCE=0`` restores today's fail-fast behavior
    everywhere (retry = one attempt, every degradation chain skipped).
    Host-side scheduling only — never part of a decode jit-cache key;
    the one resilience knob that changes a compiled program
    (:func:`nan_guard`) folds into ``train_step_key`` itself."""
    v = os.environ.get("PADDLE_TPU_RESILIENCE", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def nan_guard() -> bool:
    """In-jit non-finite train-step guard (ON whenever resilience is on).

    When on, ``jit.TrainStep`` compiles a guard around the optimizer
    update: a step whose loss or gradients are non-finite applies NO
    update (params/opt state carried through unchanged) and bumps an
    on-device skip counter, drained by ``Model.fit`` at its existing
    host-fetch boundaries (``train.nonfinite_skips``).  Trace-time: the
    guard is baked into the compiled program, so it is part of
    ``train_step_key``.  ``PADDLE_TPU_NAN_GUARD=0`` disables just the
    guard while keeping the rest of the resilience layer."""
    if not resilience_enabled():
        return False
    v = os.environ.get("PADDLE_TPU_NAN_GUARD", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def nan_restore_k() -> int:
    """``PADDLE_TPU_NAN_RESTORE_K=K``: after K CONSECUTIVE non-finite
    (skipped) train steps, ``Model.fit`` restores the TrainStep from its
    last-good host snapshot (taken at drain boundaries while healthy).
    0 (default) = never restore — skipping alone is usually enough, and
    the snapshot costs a host copy of params+opt state, so it is strictly
    opt-in."""
    try:
        return max(0, int(os.environ.get("PADDLE_TPU_NAN_RESTORE_K", "0")))
    except ValueError:
        return 0


def request_ttl_s() -> float | None:
    """Default per-request serving deadline (``PADDLE_TPU_REQUEST_TTL_S``
    seconds, None = off): a request still QUEUED this long after submit
    is shed with the ``timeout`` status instead of occupying a slot
    (``DecodeServer.submit(ttl_s=...)`` overrides per request).  Host
    scheduling only — never a jit-cache key."""
    v = os.environ.get("PADDLE_TPU_REQUEST_TTL_S", "").strip()
    if not v:
        return None
    try:
        ttl = float(v)
    except ValueError:
        return None
    return ttl if ttl > 0 else None


def step_budget_s() -> float:
    """Wall budget for one async serving step's token fetch
    (``PADDLE_TPU_STEP_BUDGET_S`` seconds, 0 = watchdog off, the
    default): past it the wedge watchdog marks the server wedged
    (``/healthz`` 503), cancels the in-flight dispatch, rolls the slots
    back, and re-decodes — unaffected requests finish with bit-identical
    tokens.  The budget must comfortably exceed a worst-case honest step
    (compile excluded — warm up first)."""
    try:
        return max(0.0, float(
            os.environ.get("PADDLE_TPU_STEP_BUDGET_S", "0")))
    except ValueError:
        return 0.0


def prefetch_retries() -> int:
    """Bounded re-read retries for a ``DevicePrefetcher`` worker whose
    source iterator raises a transient error
    (``PADDLE_TPU_PREFETCH_RETRIES``, default 2; resilience off = 0)."""
    if not resilience_enabled():
        return 0
    try:
        return max(0, int(os.environ.get("PADDLE_TPU_PREFETCH_RETRIES",
                                         "2")))
    except ValueError:
        return 2


def donate_decode() -> bool:
    """KV-cache buffer donation on the decode/serving hot path (ON by
    default).

    When on, every jitted decode/prefill/sample step donates its cache
    argument (``donate_argnums``), so XLA aliases the [L, B, T, Hkv, hd]
    K/V buffers in place instead of allocating + copying them per token.
    ``PADDLE_TPU_DONATE_DECODE=0`` is the escape hatch — donation is
    baked into the compiled executable at trace time, so the flag is
    part of the decode jit-cache key (generate._cfg_key): flipping it
    mid-process retraces rather than silently reusing the other
    routing's executable."""
    v = os.environ.get("PADDLE_TPU_DONATE_DECODE", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def flash_decode() -> bool:
    """Split-KV Pallas decode attention on the cached-decode hot path (ON
    by default).

    When on (and the backend is a TPU), every cached
    attention site — single-token decode, batched serving ticks, verify
    chunks, chunked prefill — routes through
    ``ops/decode_attention.decode_attention`` instead of the XLA einsum
    over the full cache; off-TPU the einsum path is used regardless, so
    CPU tests see no change.  ``PADDLE_TPU_FLASH_DECODE=0`` is the escape
    hatch — like donation, the routing is baked into the compiled
    executable at trace time, so the flag is part of the decode jit-cache
    key (``decode_jit_key``): flipping it mid-process retraces."""
    v = os.environ.get("PADDLE_TPU_FLASH_DECODE", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def kv_cache_dtype() -> str:
    """KV-cache STORAGE dtype: '' (default — the model's compute dtype,
    the pre-flag behavior), 'fp32', 'bf16', or 'int8'.

    Selected at ``generate.init_cache`` time; int8 stores per-(position,
    head) scales beside the cache (``decode_attention.quantize_kv``) and
    dequantizes inside the decode kernel — decode HBM reads drop 4x vs
    fp32 (2x vs bf16) and the cache footprint shrinks the same factor.
    Composes with donation: shapes and dtypes are fixed per config, so
    the aliased buffers never change layout.  Part of ``decode_jit_key``
    (trace-time: the storage dtype changes the compiled program)."""
    v = os.environ.get("PADDLE_TPU_KV_DTYPE", "").strip().lower()
    if v in ("", "fp32", "float32"):
        return "" if v == "" else "fp32"
    if v in ("bf16", "bfloat16"):
        return "bf16"
    if v == "int8":
        return "int8"
    raise ValueError(
        f"PADDLE_TPU_KV_DTYPE={v!r}: expected fp32|bf16|int8 (or empty "
        f"for the model compute dtype)")


def kv_layout() -> str:
    """KV-cache LAYOUT for serving: 'contiguous' (default — one
    [L, max_batch, rows, Hkv, hd] slab, every slot provisioned for the
    worst-case context) or 'paged' (``text/kv_pool.py`` — a fixed pool of
    [block_size]-row blocks shared by all slots through per-slot block
    tables, with refcounted prefix reuse and copy-on-write).

    ``PADDLE_TPU_KV_LAYOUT=paged`` flips the ``DecodeServer`` default;
    ``generate.init_cache(layout=...)`` / ``DecodeServer(layout=...)``
    override per call.  Trace-time: the two layouts compile different
    step programs (the cache pytree structure differs), so the flag is
    part of ``decode_jit_key`` — flipping it mid-process retraces
    instead of silently reusing the other layout's executable."""
    v = os.environ.get("PADDLE_TPU_KV_LAYOUT", "").strip().lower()
    if v in ("", "contiguous", "slab"):
        return "contiguous"
    if v == "paged":
        return "paged"
    raise ValueError(
        f"PADDLE_TPU_KV_LAYOUT={v!r}: expected contiguous|paged")


def kv_block_size() -> int:
    """Rows per KV-cache block under the paged layout
    (``PADDLE_TPU_KV_BLOCK``, default 16).  Smaller blocks waste less
    tail memory per request and share finer prefixes; larger blocks cut
    table/grid overhead.  Must be a multiple of 8 (the decode kernel's
    row tile).  Part of ``decode_jit_key`` — the block geometry is baked
    into the compiled paged step."""
    v = os.environ.get("PADDLE_TPU_KV_BLOCK", "16")
    try:
        bs = int(v)
    except ValueError:
        # raise like the sibling flags (kv_layout, kv_cache_dtype): a
        # typo'd geometry must not silently compile a different one
        raise ValueError(
            f"PADDLE_TPU_KV_BLOCK={v!r}: expected an integer multiple "
            f"of 8")
    if bs < 8 or bs % 8:
        raise ValueError(
            f"PADDLE_TPU_KV_BLOCK={bs}: must be a positive multiple of 8")
    return bs


def kv_radix() -> bool:
    """Token-granular radix matching in the paged prefix index (ON by
    default).  When on, a prompt sharing only PART of an indexed block's
    tokens splits that node (the new parent shares the physical block
    under an extra refcount; the adopter's first write copies it through
    the normal COW drain) so admission adopts the longest *token*
    prefix.  ``PADDLE_TPU_KV_RADIX=0`` restores the whole-block
    matching (tests/test_kv_pool.py compares the two hit rates).
    Host-side index bookkeeping only — adoption depth changes
    which rows prefill recomputes, never the compiled programs, so this
    is NOT part of any jit-cache key."""
    v = os.environ.get("PADDLE_TPU_KV_RADIX", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def kv_spill_mb() -> int:
    """Host-RAM spill tier capacity in MiB for cold prefix-cache blocks
    (``PADDLE_TPU_KV_SPILL_MB``, default 0 = spill off).  When set, the
    OOM chain's evict-cold rung demotes cold block-aligned prefix chains
    to host buffers (one batched ``device_get`` per eviction round)
    instead of dropping them, and admission restores a spilled chain
    with one batched ``device_put`` + table scatter instead of a
    recompute walk.  Host scheduling only — NEVER a jit-cache key: the
    restore scatter rides the existing ``inject_rows`` executable
    buckets, so flipping spill on/off adds zero executable families."""
    try:
        return max(0, int(os.environ.get("PADDLE_TPU_KV_SPILL_MB", "0")))
    except ValueError:
        return 0


def kv_spill_batch() -> int:
    """Max prefix blocks demoted per spill round
    (``PADDLE_TPU_KV_SPILL_BATCH``, default 8) — the batching factor of
    the one ``device_get`` each evict-cold engagement pays.  Candidates
    beyond the batch fall back to a plain drop.  Host scheduling only,
    never a jit-cache key."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_KV_SPILL_BATCH",
                                         "8")))
    except ValueError:
        return 8


def kv_spill_rss_mb() -> int:
    """Host-RSS watchdog threshold in MiB
    (``PADDLE_TPU_KV_SPILL_RSS_MB``, default 0 = watchdog off).  When
    the process resident set crosses the threshold, the paged
    allocator's per-tick watchdog (:meth:`PagedAllocator.rss_watchdog`)
    engages one BOUNDED relief round: the oldest host-spilled prefix
    chains are released first (the spill store is the host tier the
    watchdog guards), then cold device-index leaves demote through the
    normal evict-cold LRU rung — at most ``PADDLE_TPU_KV_SPILL_BATCH``
    entries per round, so a hot server sheds pressure over ticks
    instead of stalling one.  Host scheduling only — NEVER a jit-cache
    key."""
    try:
        return max(0, int(os.environ.get("PADDLE_TPU_KV_SPILL_RSS_MB",
                                         "0")))
    except ValueError:
        return 0


def kv_restore() -> bool:
    """Restore policy for spilled prefix chains (ON by default).
    ``PADDLE_TPU_KV_RESTORE=0`` keeps the spill store write-only —
    admission recomputes instead of promoting host rows back, which
    turns the tier into a pure pressure-relief valve (a drill/debug
    posture).  Host scheduling only, never a jit-cache key."""
    v = os.environ.get("PADDLE_TPU_KV_RESTORE", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def fleet_prefill_threshold() -> int:
    """Prompt length (tokens) at which the fleet router hands admission
    prefill to a dedicated prefill worker instead of the decode
    replica's own admission path (``PADDLE_TPU_FLEET_PREFILL_THRESHOLD``,
    default 0 = every prompt when a worker is attached).  Host
    scheduling only — never a jit-cache key; the handoff's injected
    rows are bit-identical to local prefill either way, the threshold
    only picks WHERE the prefill FLOPs run."""
    try:
        return max(0, int(os.environ.get(
            "PADDLE_TPU_FLEET_PREFILL_THRESHOLD", "0")))
    except ValueError:
        return 0


def fleet_tick_block() -> int:
    """Decode steps per replica tick in the fleet router's serve loop
    (``PADDLE_TPU_FLEET_TICK_BLOCK``, default 1): >1 routes each
    replica's tick through ``tick_block(k)`` — fewer host round trips
    per token at block-granular retirement.  Host scheduling only."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_FLEET_TICK_BLOCK",
                                         "1")))
    except ValueError:
        return 1


def spec_k() -> int:
    """Draft tokens proposed per speculative serving round
    (``PADDLE_TPU_SPEC_K``, default 0 = speculation off).  When a
    ``DecodeServer`` is built without an explicit ``spec_k=`` this is
    the value it resolves; the batched verify executable bakes K into
    its shapes, so the raw env string is part of ``decode_jit_key`` —
    flipping it mid-process retraces instead of silently reusing the
    other K's executable."""
    v = os.environ.get("PADDLE_TPU_SPEC_K", "0")
    try:
        k = int(v)
    except ValueError:
        raise ValueError(f"PADDLE_TPU_SPEC_K={v!r}: expected an integer "
                         f">= 0 (0 disables speculation)")
    if k < 0:
        raise ValueError(f"PADDLE_TPU_SPEC_K={k}: must be >= 0")
    return k


def spec_tree() -> int:
    """Node budget of the tree-speculation round
    (``PADDLE_TPU_SPEC_TREE``, default 0 = tree mode off).  When > 0 a
    ``DecodeServer`` built without an explicit ``spec_tree=`` proposes a
    token TREE of this many node slots per round (node 0 is the feed
    token) and verifies it in one tree-masked pass; mutually exclusive
    with linear ``spec_k``.  The node count is baked into the tree
    verify executable's shapes — the raw env string is part of
    ``decode_jit_key`` — but the tree's TOPOLOGY (ancestor mask +
    depths) is a runtime argument, so per-round shape changes never
    retrace."""
    v = os.environ.get("PADDLE_TPU_SPEC_TREE", "0")
    try:
        n = int(v)
    except ValueError:
        raise ValueError(f"PADDLE_TPU_SPEC_TREE={v!r}: expected an "
                         f"integer >= 0 (0 disables tree speculation)")
    if n < 0 or n == 1:
        raise ValueError(f"PADDLE_TPU_SPEC_TREE={n}: must be 0 (off) or "
                         f">= 2 (node 0 carries the feed token, so a "
                         f"1-node tree proposes nothing)")
    return n


def spec_branch() -> int:
    """Branching factor of tree-speculation proposals
    (``PADDLE_TPU_SPEC_BRANCH``, default 2): how many sibling
    candidates a propose step may fan out per node — top-b from the
    draft model, or distinct n-gram match continuations when
    self-drafting.  Host proposal shaping only — the verify executable
    sees topology as a runtime mask, so this is never a jit-cache
    key."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_SPEC_BRANCH", "2")))
    except ValueError:
        return 2


def prefill_budget() -> int:
    """Per-scheduler-round admission prefill token budget
    (``PADDLE_TPU_PREFILL_BUDGET``, default 0 = monolithic admission).
    When > 0, ``DecodeServer`` admission becomes incremental: a
    request's prefill advances at most this many tokens per scheduler
    round, interleaved with decode steps, so a long-prompt admission
    never stalls the decoding slots (Sarathi-style chunked-prefill
    co-scheduling).  The budget is the chunk WIDTH of the admission
    executables — a compiled shape — so the raw env string is part of
    ``decode_jit_key``; flipping it mid-process retraces instead of
    silently reusing the other width's program."""
    v = os.environ.get("PADDLE_TPU_PREFILL_BUDGET", "0")
    try:
        b = int(v)
    except ValueError:
        raise ValueError(
            f"PADDLE_TPU_PREFILL_BUDGET={v!r}: expected an integer >= 0 "
            f"(0 keeps monolithic admission)")
    if b < 0:
        raise ValueError(
            f"PADDLE_TPU_PREFILL_BUDGET={b}: must be >= 0")
    return b


def admission_enabled() -> bool:
    """SLO-driven admission control master switch (ON by default).

    When on, ``DecodeServer`` and ``fleet.Router`` construct an
    :class:`paddle_tpu.text.admission.AdmissionController`: per-tenant
    token-bucket rate limits, bounded per-class queues with
    shed-lowest-class-first overload policy, and the SLO degradation
    ladder (admit cap -> prefill-budget rung -> speculation fallback ->
    shed) driven by the TTFT/TPOT histograms.  ``PADDLE_TPU_ADMISSION=0``
    restores today's greedy FIFO admission EXACTLY (bit-parity: no
    controller is constructed, no request is ever ``rejected``).  Host
    scheduling only — never a jit-cache key; the budget ladder switches
    among PRE-WARMED chunk widths, it never flips the env."""
    v = os.environ.get("PADDLE_TPU_ADMISSION", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def adaptive_budget() -> bool:
    """Adaptive prefill budget (ON by default): the admission
    controller's TPOT objective (the ``serving.decode_gap_ms``
    histogram) drives prefill-budget rung switches on its OWN counter,
    finer than the coarse degradation ladder — one breached window
    shrinks the budget one rung WITHOUT halving the admit cap or
    forcing speculation off; healthy windows grow it back one rung,
    an idle window resets it.  The budget only ever moves between the
    ``ladder_widths`` rungs warmup() pre-compiled, so an adaptive move
    never retraces.  ``PADDLE_TPU_ADAPTIVE_BUDGET=0`` restores the
    ladder-only coupling."""
    v = os.environ.get("PADDLE_TPU_ADAPTIVE_BUDGET", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def _float_or_none(name: str) -> float | None:
    v = os.environ.get(name)
    if v is None or not v.strip():
        return None
    try:
        f = float(v)
    except ValueError:
        raise ValueError(f"{name}={v!r}: expected a number")
    return f if f > 0 else None


def slo_ttft_ms() -> float | None:
    """TTFT SLO in milliseconds (``PADDLE_TPU_SLO_TTFT_MS``; unset/0 =
    no TTFT objective).  The admission controller compares the WINDOWED
    ``serving.ttft_ms`` p99 against this each control tick; a breach
    climbs the degradation ladder."""
    return _float_or_none("PADDLE_TPU_SLO_TTFT_MS")


def slo_tpot_ms() -> float | None:
    """TPOT/decode-gap SLO in milliseconds (``PADDLE_TPU_SLO_TPOT_MS``;
    unset/0 = no TPOT objective).  Compared against the windowed
    ``serving.decode_gap_ms`` p99 — the stall metric budgeted admission
    bounds — each control tick."""
    return _float_or_none("PADDLE_TPU_SLO_TPOT_MS")


def slo_window_s() -> float:
    """SLO evaluation window in seconds (``PADDLE_TPU_SLO_WINDOW_S``,
    default 2.0): the controller re-reads the histograms at most once
    per window, degrades one rung per breached window, and recovers one
    rung per fully healthy window (symmetric by construction)."""
    try:
        return max(0.05, float(os.environ.get("PADDLE_TPU_SLO_WINDOW_S",
                                              "2.0")))
    except ValueError:
        return 2.0


def tenant_rate() -> float | None:
    """Per-tenant token-bucket refill rate, in admitted tokens
    (prompt + max_new) per second (``PADDLE_TPU_TENANT_RATE``; unset/0
    = no per-tenant rate limiting).  A submit whose tenant bucket
    cannot cover its cost is rejected with ``resilience.Overloaded``
    and counted ``admission.tenant_throttles``."""
    return _float_or_none("PADDLE_TPU_TENANT_RATE")


def tenant_burst() -> float | None:
    """Per-tenant token-bucket capacity (``PADDLE_TPU_TENANT_BURST``;
    default 2x the rate): how many tokens a quiet tenant may burst
    before the refill rate binds."""
    return _float_or_none("PADDLE_TPU_TENANT_BURST")


def admission_queue_cap() -> int:
    """Bounded per-class admission queues
    (``PADDLE_TPU_ADMISSION_QUEUE_CAP``, default 0 = unbounded): when
    the total queued work exceeds this cap, the LOWEST priority class
    sheds first (``rejected`` status, ``admission.sheds_class*``
    counters) — overload answers at the door instead of stacking
    queues until TTLs fire."""
    try:
        return max(0, int(os.environ.get(
            "PADDLE_TPU_ADMISSION_QUEUE_CAP", "0")))
    except ValueError:
        return 0


def requeue_max() -> int:
    """Eviction-count aging bound for the OOM-evict requeue path
    (``PADDLE_TPU_EVICT_REQUEUE_MAX``, default 8; 0 = unbounded, the
    pre-bound behavior).  An evicted request re-queues at the FRONT
    with a fresh TTL clock — under sustained pressure that can starve
    the rest of the queue forever, so after this many evictions the
    request fails honestly with the ``error`` status
    (``resilience.evict_requeue_overflows``) instead of cycling."""
    try:
        return max(0, int(os.environ.get("PADDLE_TPU_EVICT_REQUEUE_MAX",
                                         "8")))
    except ValueError:
        return 8


def spec_min_accept() -> float:
    """Rolling per-request acceptance rate below which a speculating
    slot falls back to plain decode (``PADDLE_TPU_SPEC_MIN_ACCEPT``,
    default 0.3).  Below ~1/3 acceptance a K-token verify does more
    target work per emitted token than plain stepping, so the slot
    stops paying for proposals it keeps rejecting.  Host scheduling
    only — never a jit-cache key; acceptance resolution happens on
    fetched logits either way."""
    try:
        return min(1.0, max(0.0, float(os.environ.get(
            "PADDLE_TPU_SPEC_MIN_ACCEPT", "0.3"))))
    except ValueError:
        return 0.3


def fleet_tick_workers() -> int:
    """Upper bound on threads the fleet router fans replica ticks out
    over (``PADDLE_TPU_FLEET_TICK_WORKERS``, default 8; 1 restores the
    sequential loop).  Each replica tick blocks on its own device
    round trip, so with N replicas the sequential loop serializes N
    round trips per router tick; the fan-out overlaps them.  Host
    scheduling only."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_FLEET_TICK_WORKERS",
                                         "8")))
    except ValueError:
        return 8


def prefix_route() -> bool:
    """Prefix-aware fleet routing (ON by default).  When on, each
    replica ships a compact prefix summary (root-fanout fingerprints +
    resident-token counts) in ``load_stats()`` and the router scores
    longest-expected-prefix overlap as a leading term beside its load
    triple, so a tenant's traffic lands where its KV already lives.
    ``PADDLE_TPU_PREFIX_ROUTE=0`` restores pure load-order routing.
    Host scheduling only, never a jit-cache key."""
    v = os.environ.get("PADDLE_TPU_PREFIX_ROUTE", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def prefix_route_imbalance() -> int:
    """Load-imbalance cap on prefix affinity: a replica only earns
    affinity credit while its queue depth is within this many requests
    of the least-loaded candidate
    (``PADDLE_TPU_PREFIX_ROUTE_IMBALANCE``, default 2).  The cap is what
    keeps a hot tenant from starving a cold replica — past it the
    router falls back to load order and the cold replica fills.  Host
    scheduling only."""
    try:
        return max(0, int(os.environ.get(
            "PADDLE_TPU_PREFIX_ROUTE_IMBALANCE", "2")))
    except ValueError:
        return 2


def fleet_max_queue() -> int:
    """Queued requests the router will stack on one replica beyond its
    free slots before holding work in the fleet-level queue
    (``PADDLE_TPU_FLEET_MAX_QUEUE``, default 2).  Deeper stacking hides
    admission latency; shallower keeps work re-routable (a request
    still in the FLEET queue can go to any replica when one wedges or
    frees up).  Host scheduling only."""
    try:
        return max(0, int(os.environ.get("PADDLE_TPU_FLEET_MAX_QUEUE",
                                         "2")))
    except ValueError:
        return 2


def stream_chunk_rows() -> int:
    """Prefill rows per streamed handoff chunk
    (``PADDLE_TPU_STREAM_CHUNK_ROWS``, default 256; 0 restores the
    monolithic whole-walk reply).  A prefill worker walks prompts longer
    than this through the offset-aware chunk executables and ships each
    finished chunk's cache rows over the raw transport WHILE computing
    the next one; the decode side injects each chunk through the
    existing pow2 injector buckets between its own ticks — transfer
    overlaps both ends, cutting handoff TTFT.  Host scheduling only,
    never a jit-cache key: the chunk width is rounded to a power of two
    so the executables come from the same bucketed families warmup
    already covers."""
    try:
        return max(0, int(os.environ.get("PADDLE_TPU_STREAM_CHUNK_ROWS",
                                         "256")))
    except ValueError:
        return 256


def fleet_autoscale() -> bool:
    """Telemetry-driven elastic fleet scaling
    (``PADDLE_TPU_FLEET_AUTOSCALE``, default off).  When on, the router
    watches the fleet's worst ``admission_rung`` each tick: sustained
    degradation (>= ``PADDLE_TPU_FLEET_SCALE_RUNG`` for
    ``PADDLE_TPU_FLEET_SCALE_OUT_TICKS`` consecutive ticks) attaches a
    registered spare replica; a sustained fully-idle fleet
    (``PADDLE_TPU_FLEET_SCALE_IN_TICKS`` ticks) drains the youngest
    replica back to the spare pool.  Host scheduling only."""
    v = os.environ.get("PADDLE_TPU_FLEET_AUTOSCALE", "0").strip().lower()
    return v not in ("0", "false", "off", "no", "")


def fleet_scale_rung() -> int:
    """Degradation rung that arms scale-out
    (``PADDLE_TPU_FLEET_SCALE_RUNG``, default 2): the fleet's worst
    replica ``admission_rung`` must sit at or above it.  Host scheduling
    only."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_FLEET_SCALE_RUNG",
                                         "2")))
    except ValueError:
        return 2


def fleet_scale_out_ticks() -> int:
    """Consecutive over-rung router ticks before a spare attaches
    (``PADDLE_TPU_FLEET_SCALE_OUT_TICKS``, default 3) — the sustain
    window that keeps one histogram blip from flapping the fleet.  Host
    scheduling only."""
    try:
        return max(1, int(os.environ.get(
            "PADDLE_TPU_FLEET_SCALE_OUT_TICKS", "3")))
    except ValueError:
        return 3


def fleet_scale_in_ticks() -> int:
    """Consecutive fully-idle router ticks before the youngest replica
    drains back to the spare pool
    (``PADDLE_TPU_FLEET_SCALE_IN_TICKS``, default 50).  Scale-in is
    deliberately much slower than scale-out: attaching a spare is
    cheap, re-warming a drained replica's executables is not.  Host
    scheduling only."""
    try:
        return max(1, int(os.environ.get(
            "PADDLE_TPU_FLEET_SCALE_IN_TICKS", "50")))
    except ValueError:
        return 50


def telemetry_enabled() -> bool:
    """Runtime telemetry master switch (ON by default).

    When on, :mod:`paddle_tpu.telemetry` records serving request spans +
    latency histograms, training step timings, and the jit recompile
    watch.  ``PADDLE_TPU_TELEMETRY=0`` is the escape hatch: every record
    call early-outs and the jit-compile instrumentation wrapper is never
    installed (the hot paths run the raw executables).  Unlike the
    trace-time routing flags this is NOT part of any jit-cache key —
    telemetry never changes a compiled program, only host bookkeeping."""
    v = os.environ.get("PADDLE_TPU_TELEMETRY", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def device_feed_enabled() -> bool:
    """Device-truth telemetry feed (ON by default, nested under the
    telemetry master switch).

    When on, every jit-cache miss routed through
    ``telemetry.instrument_compile`` also captures the executable's
    ``cost_analysis``/``memory_analysis`` (per-step FLOPs, HBM bytes
    moved, argument/output/temp sizes) so ``telemetry.snapshot()`` can
    derive live MFU and roofline gauges, and the serving/fit hot paths
    sample PJRT device memory stats at a rate-limited cadence.  The
    capture costs one extra lowering per compiled executable — never per
    step.  The memory-analysis half additionally needs an AOT recompile,
    paid only where cheap/amortized: on CPU, when the persistent compile
    cache is configured (``DecodeServer.warmup`` configures it), or
    under an explicit ``PADDLE_TPU_DEVICE_FEED=full``; otherwise the
    feed carries FLOPs/bytes from the lowering's cost analysis alone.
    ``PADDLE_TPU_DEVICE_FEED=0`` is the escape hatch; like the telemetry
    master it never changes a compiled program, only host bookkeeping."""
    return device_feed_mode() != "off"


def device_feed_mode() -> str:
    """'off' | 'on' | 'full' — the one parse of ``PADDLE_TPU_DEVICE_FEED``
    (telemetry's capture gate and :func:`device_feed_enabled` both read
    it here, so the value set can't diverge between the two sites)."""
    if not telemetry_enabled():
        return "off"
    v = os.environ.get("PADDLE_TPU_DEVICE_FEED", "1").strip().lower()
    if v in ("0", "false", "off", "no"):
        return "off"
    return "full" if v == "full" else "on"


def hbm_sample_interval_s() -> float:
    """Minimum seconds between PJRT ``memory_stats()`` samples on the
    hot paths (``PADDLE_TPU_HBM_SAMPLE_MS``, default 500).  The stats
    call is a host-side PJRT query — not a device sync — but it is not
    free, so the hot-path sites rate-limit it here."""
    try:
        return max(0.0, float(os.environ.get("PADDLE_TPU_HBM_SAMPLE_MS",
                                             "500"))) / 1e3
    except ValueError:
        return 0.5


def telemetry_log() -> str | None:
    """``PADDLE_TPU_TELEMETRY_LOG=<path>``: append every telemetry span
    as one JSON line (consumed by ``tools/merge_timeline.py`` to build a
    merged Perfetto timeline or a quantile summary).  None = no log."""
    return os.environ.get("PADDLE_TPU_TELEMETRY_LOG") or None


def trace_enabled() -> bool:
    """Fleet distributed-tracing switch (ON by default, nested under
    the telemetry master switch — ``PADDLE_TPU_TELEMETRY=0`` already
    no-ops the whole plane).  ``PADDLE_TPU_TRACE=0`` turns off just the
    trace-context mint at ``Router.submit``: no ``trace`` key rides the
    wire, every span record early-outs on the missing context, and the
    metrics aggregation keeps working.  Host scheduling only — never a
    jit-cache key."""
    v = os.environ.get("PADDLE_TPU_TRACE", "1").strip().lower()
    return v not in ("0", "false", "off", "no")


def trace_ring_spans() -> int:
    """Completed fleet-trace spans each entity's ring holds before new
    spans are dropped (and drop-counted) instead of growing host memory
    (``PADDLE_TPU_TRACE_RING``, default 4096).  Host scheduling only —
    never a jit-cache key."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_TRACE_RING",
                                         "4096")))
    except ValueError:
        return 4096


def trace_piggyback_cap() -> int:
    """Spans a worker/replica ships per reply or stats collection when
    the router drains its span ring (``PADDLE_TPU_TRACE_PIGGYBACK``,
    default 256) — bounds the header-frame growth of any one transport
    message; the remainder rides the next collection.  Host scheduling
    only."""
    try:
        return max(1, int(os.environ.get("PADDLE_TPU_TRACE_PIGGYBACK",
                                         "256")))
    except ValueError:
        return 256


def fleet_metrics_port() -> int | None:
    """``PADDLE_TPU_FLEET_METRICS_PORT=<port>``: start the Router's
    fleet-aggregated metrics endpoint on this port when the Router is
    constructed without an explicit ``metrics_port=`` (0 = ephemeral).
    None = no endpoint unless asked per-Router.  Host scheduling only."""
    v = os.environ.get("PADDLE_TPU_FLEET_METRICS_PORT")
    if v is None or not v.strip():
        return None
    try:
        return max(0, int(v))
    except ValueError:
        return None


def decode_jit_key() -> tuple:
    """The trace-time decode-routing flag tuple — folded into every
    decode/serving jit-cache key (``generate._cfg_key``), so flipping any
    of these env vars mid-process retraces rather than silently reusing
    an executable that baked in the other routing: W4 kernel gate
    (woq.mm), fused LN (gpt._ln), cache donation, flash-decode kernel
    routing, and the KV-cache storage dtype."""
    return (os.environ.get("PADDLE_TPU_W4_KERNEL", ""),
            os.environ.get("PADDLE_TPU_FUSED_LN", ""),
            os.environ.get("PADDLE_TPU_DONATE_DECODE", ""),
            os.environ.get("PADDLE_TPU_FLASH_DECODE", ""),
            kv_cache_dtype(),
            # paged KV cache (text/kv_pool.py): layout + block geometry
            # change the compiled step (block-table gathers vs slab
            # slices), so both key the cache like the dtype does
            kv_layout(), kv_block_size(),
            # speculative serving: K is baked into the batched verify
            # executable's shapes (tokens [B, K], logits [B, K, V])
            os.environ.get("PADDLE_TPU_SPEC_K", ""),
            # tree speculation: the node budget is the tree verify
            # executable's chunk shape (topology itself is a runtime
            # arg — only the count traces)
            os.environ.get("PADDLE_TPU_SPEC_TREE", ""),
            # budgeted admission: the per-round prefill budget is the
            # chunk width of the admission executables
            os.environ.get("PADDLE_TPU_PREFILL_BUDGET", ""))


if _ENV_SEEDED:
    set_flags(_ENV_SEEDED)
