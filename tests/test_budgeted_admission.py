"""Budgeted admission (round-12): token-budgeted chunked-prefill
co-scheduling in the decode tick.  ``prefill_budget=N`` (or
``PADDLE_TPU_PREFILL_BUDGET``) caps the prefill tokens any ONE scheduler
round runs: admission only CLAIMS a slot ("admitting") and each round
advances the oldest admitting slot by one budget-wide chunk, interleaved
with the decode step — a long prompt never stalls the decode loop.

The load-bearing invariant, asserted across the whole matrix: greedy
tokens are BIT-IDENTICAL to monolithic admission — chunked prefill is
exact math (same rows, same logits), only the host schedule changes.
The resilience tests pin the second half of the contract: a
half-prefilled slot is a first-class citizen of the OOM-evict / TTL /
wedge machinery (evict requeues the ORIGINAL prompt; re-admission is
bit-exact)."""
import os
import time

import numpy as np
import pytest

import jax

from paddle_tpu import faults, resilience
from paddle_tpu import flags as _flags
from paddle_tpu import telemetry as tl
from paddle_tpu.framework import monitor
from paddle_tpu.text import gpt, serving


def _cfg(**over):
    kw = dict(vocab_size=64, hidden_size=64, num_layers=2, num_heads=4,
              max_seq_len=128)
    kw.update(over)
    return gpt.GPTConfig(**kw)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = _cfg()
    return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    tl.reset()
    tl.clear_runtime_wedge()
    yield
    faults.reset()
    tl.clear_runtime_wedge()


def _count(name) -> int:
    return int(monitor.get_stat(name).get())


def _prompts(cfg, long_len=40, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(x) for x in rng.integers(1, cfg.vocab_size, n)]
            for n in (long_len, 5, 7)]


def _drive(srv, mode):
    while srv.pending():
        if mode == "tick_block":
            srv.tick_block(4)
        else:
            srv.tick()


def _serve(params, cfg, prompts, budget, mode="tick", max_new=8,
           max_len=64, **kw):
    if mode == "async":
        kw.setdefault("async_dispatch", True)
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=max_len,
                               prefill_budget=budget, **kw)
    rids = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    _drive(srv, mode)
    out = [srv.result(r) for r in rids]
    # no close(): it evicts this config's executables from the shared
    # step cache, recompiling every matrix cell (GC reclaims the KV)
    return out


# ---------------------------------------------------------------------------
# greedy bit-parity matrix: budgeted == monolithic
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("mode", ["tick", "tick_block", "async"])
# 5: many small chunks; 16: a few chunks; 39: two one-token-overlapped
# windows over the 40-token prompt (the final-window ride)
@pytest.mark.parametrize("budget", [5, 16, 39])
def test_budgeted_matches_monolithic(cfg_params, layout, mode, budget):
    cfg, params = cfg_params
    prompts = _prompts(cfg)
    kw = dict(layout=layout)
    if layout == "paged":
        kw["block_size"] = 8
    ref = _serve(params, cfg, prompts, 0, mode=mode, **kw)
    got = _serve(params, cfg, prompts, budget, mode=mode, **kw)
    assert got == ref
    assert _count("serving.admitting_claims") >= 1
    # the 40-token prompt walks ceil(40 / budget) chunks, each counted
    assert _count("serving.prefill_chunks_interleaved") >= -(-40 // budget)


def test_budget_wider_than_prompt_stays_monolithic(cfg_params):
    """Prompts that fit one chunk skip the claim gate entirely — one
    executable call either way, no admitting round-trip."""
    cfg, params = cfg_params
    prompts = _prompts(cfg)
    ref = _serve(params, cfg, prompts, 0)
    got = _serve(params, cfg, prompts, 64)
    assert got == ref
    assert _count("serving.admitting_claims") == 0


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_budgeted_spec_decode_parity(cfg_params, layout):
    """Self-drafting speculative decode over budgeted admission: the
    admitting slot is treated as still prompt-feeding (_spec_ready), so
    spec engages only after graduation — tokens stay bit-identical to
    the monolithic spec run AND to the plain budgeted run."""
    cfg, params = cfg_params
    prompts = _prompts(cfg)
    kw = dict(layout=layout, draft_cfg=cfg, draft_params=params, spec_k=3)
    if layout == "paged":
        kw["block_size"] = 8
    ref = _serve(params, cfg, prompts, 0, **kw)
    got = _serve(params, cfg, prompts, 8, **kw)
    plain = _serve(params, cfg, prompts, 8)
    assert got == ref
    assert got == plain


def test_budgeted_sampled_tick_block_parity(cfg_params):
    """Sampled requests at the same budget: per-token ticks and block
    ticks draw identical samples (the fold_in(base, step) schedule —
    the test_serving.py rule, with admitting rounds in the walk).
    Async stays out (one-step-in-flight shifts the step counter), and
    max_batch fits every prompt: queued admission lands at different
    steps in block mode — both true with or without a budget."""
    cfg, params = cfg_params
    prompts = _prompts(cfg)

    def run(block):
        srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=64,
                                   prefill_budget=8, seed=7)
        rids = [srv.submit(p, max_new_tokens=8, temperature=0.8)
                for p in prompts]
        while srv.pending():
            srv.tick_block(block) if block else srv.tick()
        return [srv.result(r) for r in rids]

    ref = run(None)
    for block in (3, 8):
        assert run(block) == ref, block


# ---------------------------------------------------------------------------
# resilience: half-prefilled slots in the OOM / TTL / wedge machinery
# ---------------------------------------------------------------------------


def test_oom_evicts_half_prefilled_slot_and_finishes_exact(markov_gpt):
    """A tick OOM while a long prompt is mid-admission: the degradation
    chain evicts the (lowest-priority) admitting slot back to the queue
    with its ORIGINAL prompt — no carried garbage rows — and the request
    still finishes with its fault-free tokens."""
    cfg, params = markov_gpt
    rng = np.random.default_rng(4)
    long_p = [int(x) for x in rng.integers(1, 13, 20)]
    short_p = [int(x) for x in rng.integers(1, 13, 4)]
    clean = _serve(params, cfg, [long_p, short_p], 6, max_new=5,
                   max_len=32)
    tl.reset()
    faults.install("oom:tick:2")      # fires while the long is admitting
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                                   prefill_budget=6)
        r_long = srv.submit(long_p, max_new_tokens=5, priority=0)
        r_short = srv.submit(short_p, max_new_tokens=5, priority=1)
        while srv.pending():
            srv.tick()
        got = [srv.result(r_long), srv.result(r_short)]
        srv.close()
    finally:
        faults.reset()
    assert got == clean
    assert _count("resilience.oom_evictions") >= 1
    # the evicted half-prefilled request re-claimed budgeted admission
    assert _count("serving.admitting_claims") >= 2


def test_ttl_sheds_evicted_half_prefilled_request(markov_gpt):
    """An OOM-evicted admitting request with a tiny TTL: its queue clock
    restarts on requeue, and the shed machinery times it out instead of
    re-admitting — the short request is unaffected."""
    cfg, params = markov_gpt
    rng = np.random.default_rng(5)
    long_p = [int(x) for x in rng.integers(1, 13, 20)]
    short_p = [int(x) for x in rng.integers(1, 13, 4)]
    clean_short = _serve(params, cfg, [short_p], 0, max_new=5,
                         max_len=32)[0]
    tl.reset()
    faults.install("oom:tick:2")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                                   prefill_budget=6)
        r_long = srv.submit(long_p, max_new_tokens=5, priority=0,
                            ttl_s=0.05)
        r_short = srv.submit(short_p, max_new_tokens=5, priority=1)
        evicted = False
        while srv.pending():
            srv.tick()
            if not evicted and srv.status(r_long) == "queued":
                evicted = True
                time.sleep(0.08)       # let the requeued TTL expire
        assert evicted, "the admitting slot was never evicted"
        assert srv.status(r_long) == "timeout"
        with pytest.raises(resilience.DeadlineExceeded):
            srv.result(r_long)
        assert srv.result(r_short) == clean_short
        srv.close()
    finally:
        faults.reset()
    assert _count("resilience.deadline_sheds") >= 1


def test_wedge_recovery_with_admitting_slot(monkeypatch, markov_gpt):
    """A wedged async step while a long prompt is mid-admission: the
    watchdog cancels the in-flight work and recovers with the admitting
    slot's chunk walk intact — tokens stay bit-identical to a fault-free
    budgeted async run."""
    cfg, params = markov_gpt
    rng = np.random.default_rng(6)
    long_p = [int(x) for x in rng.integers(1, 13, 20)]
    short_p = [int(x) for x in rng.integers(1, 13, 4)]
    clean = _serve(params, cfg, [long_p, short_p], 6, mode="async",
                   max_new=5, max_len=32)
    tl.reset()
    monkeypatch.setenv("PADDLE_TPU_STEP_BUDGET_S", "0.3")
    monkeypatch.setenv("PADDLE_TPU_FAULT_WEDGE_S", "1.0")
    faults.install("wedge:tick:2")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                                   prefill_budget=6, async_dispatch=True)
        rids = [srv.submit(long_p, max_new_tokens=5),
                srv.submit(short_p, max_new_tokens=5)]
        while srv.pending():
            srv.tick()
        got = [srv.result(r) for r in rids]
        srv.close()
    finally:
        faults.reset()
    assert got == clean
    assert _count("resilience.wedge_detected") >= 1
    assert _count("resilience.wedge_recoveries") >= 1


# ---------------------------------------------------------------------------
# knobs, telemetry surface, jit key
# ---------------------------------------------------------------------------


def test_load_stats_reports_admitting(cfg_params):
    cfg, params = cfg_params
    prompts = _prompts(cfg)
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                               prefill_budget=8)
    for p in prompts[:2]:
        srv.submit(p, max_new_tokens=4)
    srv.tick()
    ls = srv.load_stats()
    assert ls["prefill_budget"] == 8
    assert ls["admitting_slots"] == 1      # the 40-token long is mid-walk
    while srv.pending():
        srv.tick()
    assert srv.load_stats()["admitting_slots"] == 0
    srv.close()


def test_prefill_budget_flag_accessor(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PREFILL_BUDGET", raising=False)
    assert _flags.prefill_budget() == 0
    monkeypatch.setenv("PADDLE_TPU_PREFILL_BUDGET", "128")
    assert _flags.prefill_budget() == 128
    for bad in ("-1", "x", "1.5"):
        monkeypatch.setenv("PADDLE_TPU_PREFILL_BUDGET", bad)
        with pytest.raises(ValueError):
            _flags.prefill_budget()


def test_prefill_budget_rides_decode_jit_key(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PREFILL_BUDGET", raising=False)
    k0 = _flags.decode_jit_key()
    monkeypatch.setenv("PADDLE_TPU_PREFILL_BUDGET", "64")
    assert _flags.decode_jit_key() != k0


def test_constructor_validation(cfg_params):
    cfg, params = cfg_params
    with pytest.raises(ValueError):
        serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                             prefill_budget=-1)
    with pytest.raises(ValueError):
        serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                             prefill=False, prefill_budget=8)
    # budget clamps to the serving window
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                               prefill_budget=10_000)
    assert srv._budget == 32
    srv.close()


def test_warmup_covers_budget_chunk_width(cfg_params):
    """warmup() pre-compiles the budget-width chunk executable, so the
    first long admission after warmup compiles nothing new."""
    cfg, params = cfg_params
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                               prefill_budget=8)
    timings = srv.warmup()
    assert any("prefill" in k for k in timings)
    prompts = _prompts(cfg)
    rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
    while srv.pending():
        srv.tick()
    assert all(len(srv.result(r)) == 4 for r in rids)
    srv.close()


# ---------------------------------------------------------------------------
# fleet composition: budgeted replicas under the Router
# ---------------------------------------------------------------------------


def _drive_router(router, prompts, max_new=6):
    from paddle_tpu.text import fleet  # noqa: F401 — keep import local

    rids = [router.submit(p, max_new_tokens=max_new) for p in prompts]
    deadline = time.time() + 120.0
    while router.pending() and time.time() < deadline:
        router.tick()
        if not any(r._slots or r._queue for r in router.replicas):
            time.sleep(0.002)
    assert not router.pending(), "fleet never drained"
    return [router.result(r) for r in rids]


def test_budgeted_replicas_match_monolithic_fleet(cfg_params):
    """A Router over budgeted replicas (no prefill workers): the long
    prompt chunk-walks inside its owning replica's tick loop and the
    fleet's tokens stay bit-identical to a single monolithic server."""
    from paddle_tpu.text import fleet

    cfg, params = cfg_params
    prompts = _prompts(cfg)
    ref = _serve(params, cfg, prompts, 0, max_new=6)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                              prefill_budget=8) for _ in range(2)])
    got = _drive_router(router, prompts)
    router.close()
    assert got == ref
    assert _count("serving.admitting_claims") >= 1
    assert _count("fleet.prefill_handoffs") == 0


def test_below_threshold_long_coschedules_locally(cfg_params):
    """Budget and prefill_threshold are independent knobs: with a
    worker attached but the threshold ABOVE the long prompt's length,
    the router keeps the prompt local and the replica's budget absorbs
    it (chunk-walked in the decode loop, zero handoffs) — tokens still
    bit-identical to the single monolithic server."""
    from paddle_tpu.text import fleet

    cfg, params = cfg_params
    prompts = _prompts(cfg)          # longest is 40 tokens
    ref = _serve(params, cfg, prompts, 0, max_new=6)
    worker = fleet.PrefillWorker(params, cfg, max_len=64)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                              prefill_budget=8) for _ in range(2)],
        prefill=[worker], prefill_threshold=48)
    got = _drive_router(router, prompts)
    router.close()
    assert got == ref
    assert _count("fleet.prefill_handoffs") == 0
    assert _count("serving.admitting_claims") >= 1


def test_fleet_mixed_gap_bounded_without_workers(cfg_params):
    """The mixed-workload gap bound with workers ABSENT, stated as the
    schedule property that produces it (a wall-clock bound on the CPU
    is no result, and the chip has no mixed-queue cell yet: ROADMAP
    R-W2): while the long prompt is admitting on a budgeted no-worker fleet,
    the co-scheduled short request KEEPS GENERATING — with monolithic
    admission, zero tokens can land during the prefill by construction
    (the whole walk runs inside one replica tick)."""
    from paddle_tpu.text import fleet

    cfg, params = cfg_params
    rng = np.random.default_rng(9)
    long_p = [int(x) for x in rng.integers(1, 60, 48)]
    short_p = [int(x) for x in rng.integers(1, 60, 5)]

    def tokens_during_admission(budget):
        router = fleet.Router(
            [serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                                  prefill_budget=budget)])
        srv = router.replicas[0]
        r_short = router.submit(short_p, max_new_tokens=12)
        r_long = router.submit(long_p, max_new_tokens=4)
        seen = set()
        deadline = time.time() + 120.0
        while router.pending() and time.time() < deadline:
            admitting_before = any(st.get("admitting")
                                   for st in srv._slots.values())
            router.tick()
            if admitting_before:
                for st in srv._slots.values():
                    seen.add((tuple(st["prompt"][:4]), st["pos"]))
        assert not router.pending(), "fleet never drained"
        out = [router.result(r_short), router.result(r_long)]
        router.close()
        # positions observed for the SHORT slot across admitting rounds
        short_key = tuple(short_p[:4])
        positions = sorted(p for k, p in seen if k == short_key)
        return out, positions

    got, positions = tokens_during_admission(8)
    ref, _ = tokens_during_admission(0)
    assert got == ref                     # parity, as everywhere
    # the short slot moved through >= 3 distinct positions while the
    # long was admitting: decode progressed inside the walk
    assert len(positions) >= 3, positions
