"""Chaos suite for the resilience layer (resilience.py + faults.py).

The property under test everywhere: with faults INJECTED (OOM on a tick,
a wedged async step, NaN logits, a prefetcher crash, an expired
deadline), the runtime SURVIVES — the server keeps serving and
unaffected requests finish with bit-identical tokens vs a fault-free
run, training skips the poisoned step instead of corrupting parameters —
while with ``PADDLE_TPU_RESILIENCE=0`` every injected fault fails fast
exactly like the pre-resilience runtime.
"""
import json
import time
import urllib.request

import numpy as np
import pytest

import jax

from paddle_tpu import faults, flags, resilience
from paddle_tpu import telemetry as tl
from paddle_tpu.framework import monitor
from paddle_tpu.text import gpt, serving


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    tl.reset()
    tl.clear_runtime_wedge()
    yield
    faults.reset()
    tl.clear_runtime_wedge()


def _cfg(**over):
    kw = dict(vocab_size=32, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64)
    kw.update(over)
    return gpt.GPTConfig(**kw)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = _cfg()
    return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))


def _count(name) -> int:
    return int(monitor.get_stat(name).get())


def _serve(cfg, params, prompts, max_new=6, spec="", max_batch=2,
           **srv_kw):
    """One full serving pass under an optional fault spec; returns the
    per-request token lists."""
    faults.reset()
    if spec:
        faults.install(spec)
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=max_batch,
                                   max_len=32, **srv_kw)
        rids = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
        while srv.pending():
            srv.tick()
        out = [srv.result(r) for r in rids]
        srv.close()
        return out
    finally:
        faults.reset()


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_backoff_schedule_deterministic_and_capped():
    a = resilience.backoff_schedule(6, base=0.1, factor=2.0,
                                    max_delay=0.5, jitter=0.1, seed=7)
    b = resilience.backoff_schedule(6, base=0.1, factor=2.0,
                                    max_delay=0.5, jitter=0.1, seed=7)
    assert a == b                      # deterministic for a seed
    assert len(a) == 5                 # attempts-1 delays
    for i, d in enumerate(a):
        raw = min(0.1 * 2.0 ** i, 0.5)
        assert raw * 0.9 - 1e-9 <= d <= raw * 1.1 + 1e-9  # jitter bounds
    assert a != resilience.backoff_schedule(6, base=0.1, factor=2.0,
                                            max_delay=0.5, jitter=0.1,
                                            seed=8)
    # jitter 0: the exact capped-exponential series
    flat = resilience.backoff_schedule(4, base=0.1, factor=2.0,
                                       max_delay=0.25, jitter=0.0)
    assert flat == [0.1, 0.2, 0.25]


def test_retry_transient_then_success():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert resilience.retry(flaky, name="t", attempts=4, base=0.01,
                            jitter=0.0, sleep=slept.append) == "ok"
    assert calls["n"] == 3
    assert slept == [0.01, 0.02]
    assert _count("resilience.retries") == 2
    assert _count("resilience.retries.t") == 2


def test_retry_attempts_capped_and_type_bounded():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise OSError("nope")

    with pytest.raises(OSError):
        resilience.retry(always, name="t2", attempts=3, base=0.0,
                         jitter=0.0, sleep=lambda s: None)
    assert calls["n"] == 3
    # a non-matching exception propagates without retrying
    calls["n"] = 0

    def wrong_kind():
        calls["n"] += 1
        raise ValueError("not retryable")

    with pytest.raises(ValueError):
        resilience.retry(wrong_kind, name="t3", attempts=5,
                         retry_on=OSError, sleep=lambda s: None)
    assert calls["n"] == 1


def test_retry_requires_name():
    with pytest.raises(TypeError):
        resilience.retry(lambda: 1)          # name is keyword-required
    with pytest.raises(ValueError):
        resilience.retry(lambda: 1, name="")


def test_retry_disabled_is_fail_fast(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_RESILIENCE", "0")
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise OSError("nope")

    with pytest.raises(OSError):
        resilience.retry(always, name="t4", attempts=5,
                         sleep=lambda s: None)
    assert calls["n"] == 1


def test_deadline():
    d = resilience.Deadline(0.05)
    assert not d.expired
    assert d.remaining() <= 0.05
    time.sleep(0.06)
    assert d.expired
    forever = resilience.Deadline(None)
    assert not forever.expired and forever.remaining() == float("inf")


def test_call_with_budget():
    assert resilience.call_with_budget(lambda: 42, 5.0, name="x") == 42
    assert resilience.call_with_budget(lambda: 43, 0.0, name="x") == 43
    t0 = time.perf_counter()
    with pytest.raises(resilience.WedgeError):
        resilience.call_with_budget(lambda: time.sleep(2.0), 0.1,
                                    name="x")
    assert time.perf_counter() - t0 < 1.0    # detected, not waited out
    assert _count("resilience.wedge_detected") == 1
    with pytest.raises(ZeroDivisionError):   # errors re-raised, not eaten
        resilience.call_with_budget(lambda: 1 / 0, 5.0, name="x")


# ---------------------------------------------------------------------------
# fault registry
# ---------------------------------------------------------------------------

def test_fault_spec_grammar():
    fs = faults.parse_spec("oom:serving.block:2, wedge:tick:1,nan:logits:3")
    assert [(f.kind, f.site, f.nth) for f in fs] == [
        ("oom", "serving.block", 2), ("wedge", "tick", 1),
        ("nan", "logits", 3)]
    for bad in ("oom:tick", "boom:tick:1", "oom::1", "oom:tick:x",
                "oom:tick:-1"):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)
    assert faults.parse_spec("") == []


def test_fault_nth_semantics():
    faults.install("oom:site:2")
    faults.check("site")                     # 1st check: no fire
    with pytest.raises(faults.InjectedOOM):
        faults.check("other", "site")        # 2nd (alias match): fires
    faults.check("site")                     # 3rd: spent, no fire
    faults.install("error:site:0")           # 0 = every check
    for _ in range(3):
        with pytest.raises(faults.InjectedError):
            faults.check("site")


def test_faults_noop_when_unset():
    assert not faults.active()
    faults.check("anything")                 # no-op
    arr = np.ones(3)
    assert faults.corrupt_nan("logits", arr) is arr
    faults.hang("tick")                      # returns immediately


def test_injected_oom_classified():
    faults.install("oom:x:1")
    with pytest.raises(faults.InjectedOOM) as ei:
        faults.check("x")
    assert resilience.is_oom(ei.value)
    assert not resilience.is_oom(ValueError("plain"))


# ---------------------------------------------------------------------------
# serving: deadline shed
# ---------------------------------------------------------------------------

def test_deadline_shed(cfg_params):
    cfg, params = cfg_params
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32)
    rng = np.random.default_rng(0)
    live = [srv.submit(rng.integers(1, 30, 4), max_new_tokens=6)
            for _ in range(2)]          # both slots busy
    doomed = srv.submit(rng.integers(1, 30, 4), max_new_tokens=6,
                        ttl_s=0.001)    # queued behind them
    assert srv.status(doomed) == "queued"
    time.sleep(0.01)
    while srv.pending():
        srv.tick()
    assert srv.status(doomed) == "timeout"
    with pytest.raises(resilience.DeadlineExceeded):
        srv.result(doomed)
    for r in live:                       # the active requests finished
        assert srv.status(r) == "ok" and len(srv.result(r)) == 6
    assert _count("resilience.deadline_sheds") == 1
    assert _count("serving.requests_shed") == 1
    srv.close()


def test_ttl_none_never_sheds(cfg_params):
    cfg, params = cfg_params
    prompts = [np.random.default_rng(3).integers(1, 30, 4)
               for _ in range(3)]
    toks = _serve(cfg, params, prompts)
    assert all(len(t) == 6 for t in toks)
    assert _count("resilience.deadline_sheds") == 0


# ---------------------------------------------------------------------------
# serving: OOM retry chain
# ---------------------------------------------------------------------------

def test_oom_retry_chain_sync_bit_parity(markov_gpt):
    # the markov model on purpose: its next token DEPENDS on the fed
    # token, so a recovery path that re-feeds from the wrong offset
    # cannot hide behind a random-init model's attractor tokens
    cfg, params = markov_gpt
    prompts = np.random.default_rng(1).integers(1, 13, (2, 5))
    clean = _serve(cfg, params, list(prompts))
    tl.reset()
    faulted = _serve(cfg, params, list(prompts), spec="oom:tick:2")
    assert faulted == clean              # survivors bit-identical
    assert _count("resilience.oom_retries") >= 1


def test_oom_chain_async_degrades_to_sync(markov_gpt):
    cfg, params = markov_gpt
    prompts = np.random.default_rng(2).integers(1, 13, (3, 5))
    clean = _serve(cfg, params, list(prompts), async_dispatch=True)
    tl.reset()
    faults.install("oom:tick:3")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                                   async_dispatch=True)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        while srv.pending():
            srv.tick()
        faulted = [srv.result(r) for r in rids]
        assert not srv._async            # degraded to sync dispatch
        srv.close()
    finally:
        faults.reset()
    assert faulted == clean
    assert _count("resilience.oom_retries") >= 1


def test_oom_eviction_requeues_with_progress(markov_gpt):
    """Two consecutive tick OOMs on a sync server: the chain halves the
    admitted batch twice, evicting the lowest-priority slots back to the
    queue with their progress carried; every request STILL finishes with
    its fault-free tokens (greedy decode is batch-mate independent).
    Markov model: carried-progress re-admission re-feeds from an offset
    — the exact bug class an attractor model cannot see (the eviction
    happens MID-GENERATION, so the carry is non-empty)."""
    cfg, params = markov_gpt
    prompts = np.random.default_rng(4).integers(1, 13, (3, 5))
    clean = _serve(cfg, params, list(prompts))
    tl.reset()
    faults.install("oom:tick:2,oom:tick:3")   # two consecutive tick OOMs
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=32)
        rids = [srv.submit(p, max_new_tokens=6, priority=pr)
                for p, pr in zip(prompts, (2, 1, 0))]
        while srv.pending():
            srv.tick()
        assert [srv.result(r) for r in rids] == clean
        assert srv._admit_cap == 1            # 4 -> 2 -> 1
        srv.close()
    finally:
        faults.reset()
    assert _count("resilience.oom_evictions") >= 2
    assert _count("resilience.oom_retries") >= 2


def test_oom_chain_exhausted_fails_fast(cfg_params):
    cfg, params = cfg_params
    faults.install("oom:tick:0")             # EVERY tick OOMs
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32)
        srv.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(faults.InjectedOOM):
            while srv.pending():
                srv.tick()
    finally:
        faults.reset()


def test_resilience_off_fail_fast_parity(monkeypatch, cfg_params):
    """PADDLE_TPU_RESILIENCE=0: the FIRST injected OOM kills the tick —
    no retry, no degradation, no shed (today's behavior)."""
    monkeypatch.setenv("PADDLE_TPU_RESILIENCE", "0")
    cfg, params = cfg_params
    faults.install("oom:tick:1")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32)
        srv.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(faults.InjectedOOM):
            srv.tick()
        assert srv._admit_cap == 2           # chain never engaged
    finally:
        faults.reset()
    assert _count("resilience.oom_retries") == 0


# ---------------------------------------------------------------------------
# serving: wedge watchdog
# ---------------------------------------------------------------------------

def _healthz(port):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_wedge_watchdog_recovery_and_healthz_flip(monkeypatch,
                                                  markov_gpt, tmp_path):
    """An async step exceeding its wall budget: the watchdog marks the
    server wedged (/healthz 503), cancels the in-flight work, recovers
    the loop with slot state intact — and the requests finish with
    bit-identical tokens vs a fault-free async run."""
    cfg, params = markov_gpt
    prompts = np.random.default_rng(5).integers(1, 13, (2, 5))
    clean = _serve(cfg, params, list(prompts), async_dispatch=True)
    tl.reset()
    monkeypatch.setenv("PADDLE_TPU_STEP_BUDGET_S", "0.3")
    monkeypatch.setenv("PADDLE_TPU_FAULT_WEDGE_S", "1.0")
    faults.install("wedge:tick:1")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                                   async_dispatch=True, metrics_port=0)
        port = srv.metrics_server.port
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        code0, _ = _healthz(port)
        assert code0 == 200
        saw_503 = False
        for _ in range(64):
            if not srv.pending():
                break
            srv.tick()
            if srv._wedged and not saw_503:
                code, body = _healthz(port)
                assert code == 503
                assert body["runtime_wedge"]["wedged"]
                saw_503 = True
        assert saw_503, "the injected wedge was never detected"
        faulted = [srv.result(r) for r in rids]
        code, body = _healthz(port)          # recovered: flips back ok
        assert code == 200 and not body["runtime_wedge"]["wedged"]
        srv.close()
    finally:
        faults.reset()
    assert faulted == clean                  # bit-identical survivors
    assert _count("resilience.wedge_detected") >= 1
    assert _count("resilience.wedge_recoveries") >= 1


def test_wedge_on_sync_server_fails_loudly(cfg_params):
    """A wedge spec on a sync server (no hang hook on that path) must
    raise InjectedWedge rather than silently no-op — a chaos drill that
    cannot exercise recovery must not pass vacuously."""
    cfg, params = cfg_params
    faults.install("wedge:tick:1")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32)
        srv.submit([1, 2], max_new_tokens=2)
        with pytest.raises(faults.InjectedWedge):
            srv.tick()
    finally:
        faults.reset()


def test_admission_prefill_failure_restores_request(cfg_params):
    """A failed admission prefill must neither lose the request nor leak
    the slot: both return to their pools before the error surfaces, so
    the next admission attempt serves the request normally."""
    cfg, params = cfg_params
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32)
    real = srv._prefill
    calls = {"n": 0}

    def flaky(bucket):
        fn = real(bucket)

        def wrapped(*a, **k):
            calls["n"] += 1
            if calls["n"] == 1:
                raise faults.InjectedOOM("prefill")
            return fn(*a, **k)

        return wrapped

    srv._prefill = flaky
    with pytest.raises(faults.InjectedOOM):
        srv.submit([1, 2, 3], max_new_tokens=4)   # admission runs inline
    assert len(srv._free) == 2                    # slot NOT leaked
    assert len(srv._queue) == 1                   # request NOT lost
    rid = srv._queue[0]["rid"]
    assert srv.status(rid) == "queued"
    while srv.pending():                          # next attempt succeeds
        srv.tick()
    assert len(srv.result(rid)) == 4
    srv.close()


def test_wedge_budget_off_by_default(cfg_params):
    cfg, params = cfg_params
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                               async_dispatch=True)
    assert srv._step_budget == 0.0
    srv.close()


# ---------------------------------------------------------------------------
# serving: NaN guard
# ---------------------------------------------------------------------------

def test_nan_prefill_logits_fail_cleanly(cfg_params):
    cfg, params = cfg_params
    faults.install("nan:logits:1")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32)
        bad = srv.submit([1, 2, 3], max_new_tokens=4)
        assert srv.status(bad) == "error"    # failed at admission
        with pytest.raises(RuntimeError, match="non-finite"):
            srv.result(bad)
        # the server LIVES: the next request decodes normally
        ok = srv.submit([4, 5, 6], max_new_tokens=4)
        while srv.pending():
            srv.tick()
        assert len(srv.result(ok)) == 4
        srv.close()
    finally:
        faults.reset()
    assert _count("resilience.nan_requests") == 1
    assert _count("serving.requests_failed") == 1


def test_nan_tick_logits_fail_cleanly(cfg_params):
    cfg, params = cfg_params
    # check 1 = admission logits (clean), check 2 = first tick's logits
    faults.install("nan:logits:2")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32)
        bad = srv.submit([1, 2, 3], max_new_tokens=6)
        while srv.pending():
            srv.tick()
        assert srv.status(bad) == "error"
        with pytest.raises(RuntimeError):
            srv.result(bad)
        # server still serving
        ok = srv.submit([7, 8], max_new_tokens=3)
        while srv.pending():
            srv.tick()
        assert len(srv.result(ok)) == 3
        srv.close()
    finally:
        faults.reset()
    assert _count("resilience.nan_requests") == 1


# ---------------------------------------------------------------------------
# serving: pins re-asserted with the resilience layer on
# ---------------------------------------------------------------------------

def test_async_parity_with_resilience_on(cfg_params):
    assert resilience.enabled()
    cfg, params = cfg_params
    prompts = np.random.default_rng(6).integers(1, 30, (3, 5))
    sync_toks = _serve(cfg, params, list(prompts))
    async_toks = _serve(cfg, params, list(prompts), async_dispatch=True)
    assert sync_toks == async_toks


def test_shutdown_idempotent_under_inflight(cfg_params):
    cfg, params = cfg_params
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                               async_dispatch=True, metrics_port=0)
    srv.submit([1, 2, 3], max_new_tokens=8)
    srv.tick()                               # leaves a dispatch in flight
    assert srv._inflight is not None
    srv.shutdown()                           # cancels it, joins metrics
    assert srv._inflight is None
    assert srv.metrics_server is None
    srv.shutdown()                           # idempotent


# ---------------------------------------------------------------------------
# training: non-finite guard
# ---------------------------------------------------------------------------

def _tiny_fit(epochs=1, async_=False, batches=8, lr=1e-2):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.optimizer import AdamW

    paddle.seed(0)
    net = nn.Sequential(nn.Embedding(16, 8), nn.Flatten(),
                        nn.Linear(8 * 4, 16))
    m = Model(net)
    m.prepare(AdamW(learning_rate=lr, parameters=net.parameters()),
              nn.functional.cross_entropy, async_metrics=async_)
    rng = np.random.default_rng(0)
    X = rng.integers(0, 16, (batches * 4, 4))
    Y = rng.integers(0, 16, (batches * 4,))
    hist = m.fit((X, Y), batch_size=4, epochs=epochs, verbose=0,
                 shuffle=False, prefetch_factor=0)
    return m, net, hist


def test_nan_guard_skips_poisoned_step():
    faults.install("nan:train_step:2")
    try:
        m, net, hist = _tiny_fit()
        ts = m._train_step
        assert ts.nan_guard
        assert ts.nonfinite_skips == 1
        for k, p in net.named_parameters():
            assert np.isfinite(np.asarray(p.value)).all(), k
        assert np.isfinite(hist[-1]["loss"])
        # the drain counted it into telemetry
        assert _count("train.nonfinite_skips") == 1
    finally:
        faults.reset()


def test_nan_guard_async_epoch_mean_excludes_skips():
    faults.install("nan:train_step:2")
    try:
        m, net, hist = _tiny_fit(async_=True)
        assert m._train_step.nonfinite_skips == 1
        assert np.isfinite(hist[-1]["loss"])
        for k, p in net.named_parameters():
            assert np.isfinite(np.asarray(p.value)).all(), k
    finally:
        faults.reset()


def test_nan_guard_off_parameters_poisoned(monkeypatch):
    """The fault is REAL: with the guard disabled the same injection
    drives the parameters non-finite (pre-resilience behavior)."""
    monkeypatch.setenv("PADDLE_TPU_NAN_GUARD", "0")
    faults.install("nan:train_step:2")
    try:
        m, net, hist = _tiny_fit()
        assert not m._train_step.nan_guard
        bad = any(not np.isfinite(np.asarray(p.value)).all()
                  for _, p in net.named_parameters())
        assert bad
    finally:
        faults.reset()


def test_nan_guard_no_fault_parity(monkeypatch):
    """The compiled-in guard must not change healthy training.  The
    select itself is exact (where(True, new, old) = new), but guard
    on/off are DIFFERENT executables so XLA may fuse differently —
    the contract is numerical equivalence, plus exact determinism
    within one executable (two guard-on runs are bit-identical)."""
    m1, net1, _ = _tiny_fit()
    m1b, net1b, _ = _tiny_fit()
    monkeypatch.setenv("PADDLE_TPU_NAN_GUARD", "0")
    m2, net2, _ = _tiny_fit()
    p1 = {k: np.asarray(p.value) for k, p in net1.named_parameters()}
    p1b = {k: np.asarray(p.value) for k, p in net1b.named_parameters()}
    p2 = {k: np.asarray(p.value) for k, p in net2.named_parameters()}
    for k in p1:
        np.testing.assert_array_equal(p1[k], p1b[k], err_msg=k)
        np.testing.assert_allclose(p1[k], p2[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)


def test_nan_restore_after_k_consecutive(monkeypatch):
    """K consecutive poisoned steps: fit restores the last-good host
    snapshot at the next drain boundary."""
    monkeypatch.setenv("PADDLE_TPU_NAN_RESTORE_K", "2")
    faults.install("nan:train_step:0")       # EVERY step poisoned
    try:
        import paddle_tpu as paddle
        from paddle_tpu import nn
        from paddle_tpu.hapi import Model
        from paddle_tpu.optimizer import AdamW

        paddle.seed(0)
        net = nn.Sequential(nn.Embedding(16, 8), nn.Flatten(),
                            nn.Linear(8 * 4, 16))
        m = Model(net)
        m.prepare(AdamW(learning_rate=1e-2,
                        parameters=net.parameters()),
                  nn.functional.cross_entropy)
        rng = np.random.default_rng(0)
        X = rng.integers(0, 16, (16, 4))
        Y = rng.integers(0, 16, (16,))
        m.fit((X, Y), batch_size=4, epochs=1, verbose=0, shuffle=False,
              prefetch_factor=0, log_freq=1)
        ts = m._train_step
        assert ts.nonfinite_skips == 4       # every step skipped
        assert _count("train.nonfinite_restores") >= 1
        for k, p in net.named_parameters():
            assert np.isfinite(np.asarray(p.value)).all(), k
    finally:
        faults.reset()


def test_translated_train_step_roundtrip_with_guard(tmp_path):
    """save_program/load_train_program still round-trips with the guard
    compiled in (the exported program grew a trailing good flag)."""
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep, load_train_program
    from paddle_tpu.optimizer import SGD

    net = nn.Linear(4, 3)
    ts = TrainStep(net, nn.functional.mse_loss,
                   SGD(learning_rate=0.1, parameters=net.parameters()))
    assert ts.nan_guard
    x = np.ones((2, 4), np.float32)
    y = np.zeros((2, 3), np.float32)
    ts(x, y)
    prefix = str(tmp_path / "prog")
    ts.save_program(prefix, x, y)
    tts = load_train_program(prefix)
    loss = tts(x, y)
    assert np.isfinite(float(loss.numpy()))


# ---------------------------------------------------------------------------
# prefetcher: crash propagation + bounded retries
# ---------------------------------------------------------------------------

class _FlakyIter:
    """Iterator that raises on chosen pulls and recovers (a transient
    shard-read error — NOT a dead generator)."""

    def __init__(self, items, fail_at=(), err=OSError):
        self._items = list(items)
        self._i = 0
        self._pull = 0
        self._fail_at = set(fail_at)
        self._err = err

    def __iter__(self):
        return self

    def __next__(self):
        self._pull += 1
        if self._pull in self._fail_at:
            raise self._err(f"transient read error on pull {self._pull}")
        if self._i >= len(self._items):
            raise StopIteration
        self._i += 1
        return self._items[self._i - 1]


def test_prefetch_transient_error_retried():
    from paddle_tpu.io.native_reader import DevicePrefetcher

    items = [np.full((2,), i) for i in range(4)]
    pf = DevicePrefetcher(_FlakyIter(items, fail_at=(2,)), depth=2,
                          transform=lambda x: x)
    got = list(pf)
    assert [int(g[0]) for g in got] == [0, 1, 2, 3]   # nothing lost
    assert _count("resilience.prefetch_retries") == 1
    pf.close()


def test_prefetch_worker_crash_propagates_no_hang():
    from paddle_tpu.io.native_reader import DevicePrefetcher

    items = [np.full((2,), i) for i in range(4)]
    # fails on every pull past the first: retries exhaust, the error
    # PROPAGATES to the consumer instead of hanging the bounded queue
    pf = DevicePrefetcher(_FlakyIter(items, fail_at=(2, 3, 4, 5, 6)),
                          depth=1, transform=lambda x: x, retries=2)
    t0 = time.perf_counter()
    with pytest.raises(OSError, match="transient read error"):
        list(pf)
    assert time.perf_counter() - t0 < 10.0
    pf.close()


def test_prefetch_retries_zero_when_disabled(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_RESILIENCE", "0")
    assert flags.prefetch_retries() == 0
    from paddle_tpu.io.native_reader import DevicePrefetcher

    pf = DevicePrefetcher(_FlakyIter([np.zeros(1)], fail_at=(1,)),
                          transform=lambda x: x)
    with pytest.raises(OSError):
        list(pf)
    pf.close()


def test_prefetch_crash_reaches_fit():
    """The chaos path end to end: an injected prefetch fault makes
    Model.fit RAISE (bounded time), never hang."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.optimizer import SGD

    faults.install("error:prefetch:0")       # every pull fails
    try:
        paddle.seed(0)
        net = nn.Linear(4, 2)
        m = Model(net)
        m.prepare(SGD(learning_rate=0.1, parameters=net.parameters()),
                  nn.functional.mse_loss)
        X = np.ones((8, 4), np.float32)
        Y = np.zeros((8, 2), np.float32)
        with pytest.raises(faults.InjectedError):
            m.fit((X, Y), batch_size=4, epochs=1, verbose=0,
                  prefetch_factor=2)
    finally:
        faults.reset()


# ---------------------------------------------------------------------------
# atomic checkpoint save
# ---------------------------------------------------------------------------

def test_atomic_save_retries_transient_io(tmp_path, monkeypatch):
    import os

    from paddle_tpu.framework import io as fio

    path = str(tmp_path / "ckpt.pdparams")
    real_replace = os.replace
    fails = {"n": 1}

    def flaky_replace(src, dst):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise OSError("transient fs error")
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", flaky_replace)
    fio.save({"w": np.arange(4.0)}, path)
    np.testing.assert_array_equal(fio.load(path)["w"], np.arange(4.0))
    assert _count("resilience.retries.checkpoint.save") == 1


def test_crash_mid_save_never_corrupts_last_good(tmp_path, monkeypatch):
    import pickle

    from paddle_tpu.framework import io as fio

    path = str(tmp_path / "ckpt.pdparams")
    fio.save({"w": np.arange(4.0)}, path)    # the last good checkpoint

    real_dump = pickle.dump

    def crashing_dump(obj, f, protocol=None):
        f.write(b"torn")                     # partial bytes, then die
        raise OSError("disk full")

    monkeypatch.setattr(pickle, "dump", crashing_dump)
    with pytest.raises(OSError):
        fio.save({"w": np.arange(8.0)}, path)
    monkeypatch.setattr(pickle, "dump", real_dump)
    # the old checkpoint is INTACT (the torn write hit only the temp)
    np.testing.assert_array_equal(fio.load(path)["w"], np.arange(4.0))
    import os
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


# ---------------------------------------------------------------------------
# lint: every retry/shed site observable
# ---------------------------------------------------------------------------

def test_resilience_lint_rules():
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(
        __file__).resolve().parents[1] / "tools"))
    import check_instrumented as ci

    bad_retry = "import x\nretry(lambda: 1, attempts=3)\n"
    v = ci.scan_resilience_source(bad_retry, "f.py")
    assert len(v) == 1 and "name=" in v[0][2]
    ok_retry = "retry(fn, name='probe', attempts=3)\n"
    assert ci.scan_resilience_source(ok_retry, "f.py") == []
    silent_shed = ("def _shed_expired(self):\n"
                   "    self.queue.clear()\n")
    v = ci.scan_resilience_source(silent_shed, "f.py")
    assert len(v) == 1 and "counter" in v[0][2]
    counted_shed = ("def _shed_expired(self):\n"
                    "    telemetry.count('resilience.deadline_sheds')\n")
    assert ci.scan_resilience_source(counted_shed, "f.py") == []


def test_resilience_lint_repo_clean():
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(
        __file__).resolve().parents[1] / "tools"))
    import check_instrumented as ci

    assert ci.scan_repo() == []


# ---------------------------------------------------------------------------
# one injected-fault round: the OOM chain and a deadline shed on one model
# ---------------------------------------------------------------------------

def test_injected_oom_and_deadline_round():
    """One OOM injected on a serving tick: the retry chain engages AND
    the requests finish with tokens bit-identical to a fault-free pass;
    then an impossible TTL on a request queued behind two saturated
    slots: the next tick sheds it with the timeout status while the
    active requests keep decoding.  Both engaged counters are read."""
    cfg = _cfg(vocab_size=128, hidden_size=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(1, 100, (2, 5))

    clean = _serve(cfg, params, prompts)
    tl.reset()
    faulted = _serve(cfg, params, prompts, spec="oom:tick:2")
    assert faulted == clean
    assert _count("resilience.oom_retries") >= 1

    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32)
    for p in prompts:
        srv.submit(p, max_new_tokens=8)
    rid = srv.submit(prompts[0], max_new_tokens=4, ttl_s=0.001)
    time.sleep(0.01)
    while srv.pending():
        srv.tick()
    assert srv.status(rid) == "timeout"
    assert _count("resilience.deadline_sheds") >= 1
