"""Disaggregated serving fleet (text/fleet.py + the round-9 serving
surface): loopback router fleets must produce greedy tokens
BIT-IDENTICAL to a single ``DecodeServer`` on the same request stream
(both cache layouts, prefill handed off to a dedicated worker or not),
a wedged replica's queued work must re-route to survivors with token
streams intact, TTL shedding and priority must hold at the fleet queue,
and tensor-parallel decode inside the server (``DecodeServer(mesh=)``)
must match the single-chip server on the CPU virtual-device mesh.
Cross-process transports get the ``test_multihost.py`` treatment:
capability-gated, skipped where the sandbox has no localhost sockets.
"""
import os
import socket
import time

import numpy as np
import pytest

import jax

from paddle_tpu import faults, resilience
from paddle_tpu import telemetry as tl
from paddle_tpu.framework import monitor
from paddle_tpu.text import fleet, generate, gpt, serving


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    tl.reset()
    tl.clear_runtime_wedge()
    yield
    faults.reset()
    tl.clear_runtime_wedge()


def _cfg(**over):
    kw = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64)
    kw.update(over)
    return gpt.GPTConfig(**kw)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = _cfg()
    return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))


def _count(name) -> int:
    return int(monitor.get_stat(name).get())


def _layout_kw(layout):
    return ({} if layout == "contiguous"
            else {"layout": "paged", "block_size": 8})


def _prompts(n_short=3, long_len=20, seed=7):
    rng = np.random.default_rng(seed)
    lens = [int(x) for x in rng.integers(3, 8, n_short)] + [long_len]
    return [[int(x) for x in rng.integers(1, 60, n)] for n in lens]


def _single(params, cfg, prompts, max_new=6, max_len=48, **kw):
    srv = serving.DecodeServer(params, cfg, max_batch=len(prompts),
                               max_len=max_len, **kw)
    rids = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    while srv.pending():
        srv.tick()
    out = [srv.result(r) for r in rids]
    srv.close()
    return out


def _drive(router, prompts, max_new=6, timeout_s=120.0):
    rids = [router.submit(p, max_new_tokens=max_new) for p in prompts]
    deadline = time.time() + timeout_s
    while router.pending() and time.time() < deadline:
        router.tick()
        if not any(r._slots or r._queue for r in router.replicas
                   if r is not None):
            # nothing decoding: the fleet is waiting on a prefill
            # worker thread — don't spin the tick loop dry
            time.sleep(0.002)
    assert not router.pending(), "fleet never drained"
    return [router.result(r) for r in rids]


# ---------------------------------------------------------------------------
# loopback fleet: greedy bit-parity vs one DecodeServer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_loopback_fleet_bit_parity(cfg_params, layout):
    """Router + 2 decode replicas + 1 prefill worker == one server, bit
    for bit, on a mixed short/long request stream (the long prompt's
    prefill runs in the worker and injects)."""
    cfg, params = cfg_params
    kw = _layout_kw(layout)
    prompts = _prompts()
    ref = _single(params, cfg, prompts, **kw)
    worker = fleet.PrefillWorker(params, cfg, max_len=48,
                                 layout=layout, block_size=8)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=48, **kw)
         for _ in range(2)],
        prefill=[worker], prefill_threshold=16)
    got = _drive(router, prompts)
    health = router.healthz()
    router.close()
    assert got == ref
    assert health["ok"] and len(health["replicas"]) == 2
    assert _count("fleet.prefill_handoffs") >= 1
    assert _count("fleet.routed") >= len(prompts)
    assert _count("fleet.requests") == len(prompts)


def test_fleet_without_prefill_workers_still_matches(cfg_params):
    """No workers attached: every admission prefill runs on the owning
    replica — still bit-identical to the single server."""
    cfg, params = cfg_params
    prompts = _prompts(seed=11)
    ref = _single(params, cfg, prompts)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
         for _ in range(2)])
    got = _drive(router, prompts)
    router.close()
    assert got == ref


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_submit_prefilled_matches_local_admission(cfg_params, layout):
    """The decode half of the handoff in isolation: rows computed by a
    PrefillWorker and injected via ``submit_prefilled`` decode exactly
    like a locally prefilled request."""
    cfg, params = cfg_params
    kw = _layout_kw(layout)
    prompt = _prompts()[3]               # the long one
    ref = _single(params, cfg, [prompt], **kw)
    worker = fleet.PrefillWorker(params, cfg, max_len=48,
                                 layout=layout, block_size=8)
    rows, logits = worker.prefill(prompt)
    worker.close()
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=48, **kw)
    rid = srv.submit_prefilled(prompt, rows, logits, max_new_tokens=6)
    while srv.pending():
        srv.tick()
    got = srv.result(rid)
    srv.close()
    assert [got] == ref
    assert _count("serving.prefilled_submissions") == 1


def test_submit_prefilled_rejects_mismatched_rows(cfg_params):
    cfg, params = cfg_params
    worker = fleet.PrefillWorker(params, cfg, max_len=48)
    rows, logits = worker.prefill([1, 2, 3])
    worker.close()
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=48)
    with pytest.raises(ValueError, match="cover 3 positions"):
        srv.submit_prefilled([1, 2], rows, logits)
    rows.pop("v")
    with pytest.raises(ValueError, match="leaves"):
        srv.submit_prefilled([1, 2, 3], rows, logits)
    srv.close()


def test_prefill_worker_error_reported_at_router(cfg_params):
    """A raw (window-unknown) endpoint whose worker rejects the prompt
    reports the failure back over the transport: the request retires
    with the ``error`` status instead of hanging the fleet."""
    cfg, params = cfg_params
    lt = fleet.LoopbackTransport()
    worker = fleet.PrefillWorker(params, cfg, max_len=8,   # tiny window
                                 endpoint=lt.worker)
    worker.start()
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=48)],
        prefill=[lt.client], prefill_threshold=10)
    rid = router.submit(list(range(1, 21)), max_new_tokens=4)
    deadline = time.time() + 10.0
    while router.status(rid) == "prefilling" and time.time() < deadline:
        router.tick()
        time.sleep(0.01)
    assert router.status(rid) == "error"
    with pytest.raises(RuntimeError, match="failed"):
        router.result(rid)
    assert _count("fleet.prefill_errors") == 1
    router.close()
    worker.close()


def test_small_window_owned_worker_falls_back_to_local(cfg_params):
    """The router KNOWS an owned worker's window: a prompt that doesn't
    fit skips the handoff and prefills locally on the owning replica —
    a servable request never turns into an error just because a worker
    is small."""
    cfg, params = cfg_params
    long_p = list(range(1, 13))          # 12 tokens > worker's 8
    ref = _single(params, cfg, [long_p])
    worker = fleet.PrefillWorker(params, cfg, max_len=8)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=48)],
        prefill=[worker], prefill_threshold=4)
    rid = router.submit(long_p, max_new_tokens=6)
    while router.pending():
        router.tick()
    assert router.status(rid) == "ok"
    assert router.result(rid) == ref[0]
    assert _count("fleet.prefill_handoffs") == 0
    short = router.submit([1, 2, 3, 4, 5], max_new_tokens=4)
    while router.pending():
        router.tick()
    assert router.status(short) == "ok"  # fitting prompts still hand off
    assert _count("fleet.prefill_handoffs") == 1
    router.close()


def test_injected_prefill_adopts_shared_prefix(cfg_params):
    """Paged handoff reuse: a repeated prompt routed through a prefill
    worker adopts the indexed blocks at injection (prefix hits, no
    duplicate pool copies) and the tokens stay bit-identical."""
    cfg, params = cfg_params
    prompt = _prompts()[3]               # the long one (20 tokens)
    ref = _single(params, cfg, [prompt], layout="paged", block_size=8)
    replica = serving.DecodeServer(params, cfg, max_batch=2, max_len=48,
                                   layout="paged", block_size=8)
    worker = fleet.PrefillWorker(params, cfg, max_len=48,
                                 layout="paged", block_size=8)
    router = fleet.Router([replica], prefill=[worker],
                          prefill_threshold=8)
    first = _drive(router, [prompt])
    hits0 = replica._pool.stats()["prefix_hits"]
    second = _drive(router, [prompt])
    hits1 = replica._pool.stats()["prefix_hits"]
    router.close()
    assert first == ref and second == ref
    assert hits1 > hits0, "repeat injection adopted no indexed blocks"


def test_request_rejected_by_every_replica_errors_not_livelocks(
        cfg_params):
    """A request no replica's pool can EVER hold (permanent rejection,
    not a capacity wait) retires with the ``error`` status instead of
    parking in the fleet queue forever."""
    cfg, params = cfg_params
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                              layout="paged", block_size=8,
                              num_blocks=2)])        # 16-row pool
    rid = router.submit([1] * 30, max_new_tokens=10)  # needs 5 blocks
    for _ in range(8):
        router.tick()
    assert router.status(rid) == "error"
    with pytest.raises(RuntimeError, match="KV blocks"):
        router.result(rid)
    assert _count("fleet.route_errors") == 1
    assert not router.pending()
    router.close()


# ---------------------------------------------------------------------------
# scheduling: TTL shed, priority, load balancing
# ---------------------------------------------------------------------------


def test_router_ttl_shed(cfg_params):
    """A request still fleet-queued past its TTL sheds with the timeout
    status (the replica rule, one level up) and never occupies a slot."""
    cfg, params = cfg_params
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=1, max_len=48)],
        max_queue=0)                      # no stacking: the 2nd queues
    keep = router.submit([1, 2, 3], max_new_tokens=8)
    shed = router.submit([4, 5, 6], max_new_tokens=4, ttl_s=0.001)
    time.sleep(0.01)
    while router.pending():
        router.tick()
    assert router.status(keep) == "ok"
    assert router.status(shed) == "timeout"
    with pytest.raises(resilience.DeadlineExceeded):
        router.result(shed)
    assert _count("fleet.ttl_sheds") == 1
    router.close()


def test_router_priority_dispatches_first(cfg_params):
    """With one busy replica, the higher-priority queued request takes
    the next free slot regardless of submit order."""
    cfg, params = cfg_params
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=1, max_len=48)],
        max_queue=0)
    router.submit([1, 2], max_new_tokens=2)
    low = router.submit([3, 4], max_new_tokens=2, priority=0)
    high = router.submit([5, 6], max_new_tokens=2, priority=5)
    for _ in range(64):
        if router.status(high) != "queued":
            break
        router.tick()
    assert router.status(high) != "queued"
    assert router.status(low) == "queued"
    while router.pending():
        router.tick()
    router.close()


def test_router_load_balances_on_gauge_triple(cfg_params):
    """Four concurrent requests over two 2-slot replicas spread 2/2 —
    the queue-depth/occupancy/kv-utilization score keeps one replica
    from hoarding."""
    cfg, params = cfg_params
    replicas = [serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
                for _ in range(2)]
    router = fleet.Router(replicas)
    for i in range(4):
        router.submit([1 + i, 2 + i], max_new_tokens=4)
    assert [len(r._slots) for r in replicas] == [2, 2]
    while router.pending():
        router.tick()
    router.close()


def test_router_submit_validation(cfg_params):
    cfg, params = cfg_params
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=1, max_len=48)])
    with pytest.raises(ValueError, match="empty prompt"):
        router.submit([], max_new_tokens=4)
    with pytest.raises(ValueError, match="window"):
        router.submit([1] * 40, max_new_tokens=40)
    with pytest.raises(ValueError, match="ttl"):
        router.submit([1], max_new_tokens=1, ttl_s=-1.0)
    router.close()
    with pytest.raises(ValueError, match="at least one"):
        fleet.Router([])


# ---------------------------------------------------------------------------
# prefix-aware routing (round 16): affinity, imbalance cap, snapshot
# ---------------------------------------------------------------------------


def test_router_prefix_affinity_sticks_to_warm_replica(cfg_params):
    """A tenant's repeat requests land on the replica holding its radix
    chain: after the first request registers the shared preamble, every
    follow-up (submitted one at a time so load never disambiguates)
    routes to the same replica via the fingerprint match, and
    ``fleet.prefix_routed`` records each affinity-decided dispatch."""
    cfg, params = cfg_params
    rng = np.random.default_rng(12)
    pre = [int(x) for x in rng.integers(1, 60, 12)]
    replicas = [serving.DecodeServer(params, cfg, max_batch=2, max_len=48,
                                     **_layout_kw("paged"))
                for _ in range(2)]
    router = fleet.Router(replicas)
    rid0 = router.submit(pre + [61], max_new_tokens=2)
    while router.pending():
        router.tick()
    home = router._requests[rid0]["replica"]
    routed0 = _count("fleet.prefix_routed")
    rids = []
    for t in range(3):
        rid = router.submit(pre + [50 + t], max_new_tokens=2)
        while router.pending():
            router.tick()
        rids.append(rid)
    assert [router._requests[r]["replica"] for r in rids] == [home] * 3
    assert _count("fleet.prefix_routed") - routed0 >= 3
    router.close()


def test_router_prefix_affinity_imbalance_cap_fills_cold_replica(
        cfg_params):
    """Affinity credit is capped: a hot tenant's flood pins to its warm
    replica only while that replica stays within
    ``PADDLE_TPU_PREFIX_ROUTE_IMBALANCE`` queued requests of the
    least-loaded candidate — overflow routes to the cold replica by
    load instead of queueing forever behind the warm one."""
    cfg, params = cfg_params
    rng = np.random.default_rng(13)
    pre = [int(x) for x in rng.integers(1, 60, 12)]
    replicas = [serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                                     **_layout_kw("paged"))
                for _ in range(2)]
    router = fleet.Router(replicas, max_queue=4)
    rid0 = router.submit(pre + [61], max_new_tokens=2)
    while router.pending():
        router.tick()
    home = router._requests[rid0]["replica"]
    # six hot requests at once: affinity takes the first few onto the
    # warm replica (slot, then queue depth 1..2), the imbalance cap
    # (default 2) zeroes the overlap once the warm queue runs 3 ahead
    # of the idle replica, and load routing fills the cold one
    rids = [router.submit(pre + [40 + i], max_new_tokens=2)
            for i in range(6)]
    where = [router._requests[r]["replica"] for r in rids]
    assert set(where) == {0, 1}
    assert where.count(home) >= 3          # affinity did lead
    assert where.count(1 - home) >= 2      # the cap did spill
    while router.pending():
        router.tick()
    router.close()


def test_router_snapshots_load_once_per_tick(cfg_params):
    """One ``load_stats()`` read per healthy replica per scheduling
    round, however deep the fleet queue — the per-queued-request
    re-read (which multiplied host overhead by queue depth) is gone."""
    cfg, params = cfg_params
    replicas = [serving.DecodeServer(params, cfg, max_batch=1, max_len=48)
                for _ in range(2)]
    router = fleet.Router(replicas, max_queue=0)
    reads = [0, 0]
    for i, r in enumerate(replicas):
        def wrap(i=i, orig=r.load_stats):
            reads[i] += 1
            return orig()
        r.load_stats = wrap
    rids = [router.submit([1 + i, 2], max_new_tokens=4)
            for i in range(2)]
    extra = [router.submit([7 + i, 8], max_new_tokens=2)
             for i in range(4)]
    assert sum(reads) > 0                  # wrappers are wired in
    reads[0] = reads[1] = 0
    router.tick()                          # 4 requests still queued
    assert max(reads) <= 1
    while router.pending():
        router.tick()
    for r in rids + extra:
        router.result(r)
    router.close()


# ---------------------------------------------------------------------------
# wedge: drain, re-route, aggregated health
# ---------------------------------------------------------------------------


def test_wedged_replica_drains_reroutes_and_healthz_flips(
        cfg_params, monkeypatch):
    """The round-9 acceptance drill: wedge one of two replicas
    mid-stream — its queued request re-routes to the survivor
    (``fleet.reroutes``), the aggregated health flips unhealthy and
    back, and every request's tokens stay bit-identical to a fault-free
    single server on the same stream."""
    cfg, params = cfg_params
    prompts = _prompts(seed=13)
    ref = _single(params, cfg, prompts, async_dispatch=True)
    tl.reset()
    monkeypatch.setenv("PADDLE_TPU_STEP_BUDGET_S", "0.25")
    monkeypatch.setenv("PADDLE_TPU_FAULT_WEDGE_S", "0.8")
    faults.install("wedge:tick:1")
    try:
        # 1-slot replicas: both saturate, the extra requests queue on
        # the replicas — the wedged one's queued work MUST move
        router = fleet.Router(
            [serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                                  async_dispatch=True)
             for _ in range(2)])
        rids = [router.submit(p, max_new_tokens=6) for p in prompts]
        saw_unhealthy = False
        for _ in range(512):
            if not router.pending():
                break
            router.tick()
            if not router.healthz()["ok"]:
                saw_unhealthy = True
        assert not router.pending()
        got = [router.result(r) for r in rids]
        health = router.healthz()
        router.close()
    finally:
        faults.reset()
    assert saw_unhealthy, "the injected wedge never surfaced in healthz"
    assert health["ok"], "the wedged replica never recovered"
    assert got == ref
    assert _count("fleet.drains") >= 1
    assert _count("fleet.reroutes") >= 1
    assert _count("resilience.wedge_detected") >= 1


def test_drain_queue_returns_adoptable_requests(cfg_params):
    """The drain/adopt handshake in isolation: a drained queue entry
    re-enqueues on another server and finishes with the same tokens."""
    cfg, params = cfg_params
    prompt = [5, 9, 2]
    ref = _single(params, cfg, [prompt])
    a = serving.DecodeServer(params, cfg, max_batch=1, max_len=48)
    a.submit([1, 2], max_new_tokens=2)            # occupies the slot
    a.submit(prompt, max_new_tokens=6)            # queued
    drained = a.drain_queue()
    assert len(drained) == 1 and a.load_stats()["queue_depth"] == 0
    b = serving.DecodeServer(params, cfg, max_batch=1, max_len=48)
    rid = b.adopt_request(drained[0])
    while b.pending():
        b.tick()
    assert b.result(rid) == ref[0]
    while a.pending():
        a.tick()
    a.close()
    b.close()


def test_load_stats_reads_the_gauge_triple(cfg_params):
    cfg, params = cfg_params
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
    ls0 = srv.load_stats()
    assert ls0["active_slots"] == 0 and ls0["queue_depth"] == 0
    assert ls0["free_slots"] == 2 and not ls0["wedged"]
    srv.submit([1, 2, 3], max_new_tokens=4)
    ls1 = srv.load_stats()
    assert ls1["active_slots"] == 1
    assert ls1["slot_occupancy"] == 0.5
    assert ls1["kv_utilization"] > 0
    while srv.pending():
        srv.tick()
    srv.close()


# ---------------------------------------------------------------------------
# tensor-parallel decode inside the server (CPU virtual-device mesh)
# ---------------------------------------------------------------------------


def _mesh(n):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("mp",))


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_tp_decode_server_token_parity(markov_gpt, layout):
    """DecodeServer(mesh=): the batched tick runs Megatron-sharded over
    2 CPU devices; on the trained markov model (decisive argmax
    margins) the greedy tokens match the single-chip server, and the
    cache's Hkv axis is genuinely split — pool and slab alike."""
    cfg, params = markov_gpt
    kw = {} if layout == "contiguous" else {"layout": "paged",
                                            "block_size": 8}
    prompts = [[3, 7, 2], [1, 5]]
    ref = _single(params, cfg, prompts, max_new=5, max_len=16, **kw)
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=16,
                               mesh=_mesh(2), **kw)
    rids = [srv.submit(p, max_new_tokens=5) for p in prompts]
    while srv.pending():
        srv.tick()
    got = [srv.result(r) for r in rids]
    k = srv.cache["k"]
    # the heads' axis is 3 in both: slab [L,B,T,Hkv,hd], pool
    # [L,N,bs,Hkv*hd] (a shard holds whole heads, side by side)
    per_head = 1 if layout == "contiguous" else cfg.head_dim
    assert k.sharding.shard_shape(k.shape)[3] == cfg.kv_heads // 2 * per_head
    if layout == "paged":
        t = srv.cache["tables"]
        assert t.sharding.shard_shape(t.shape) == t.shape  # replicated
    srv.close()
    assert got == ref


def test_tp_server_rejects_device_and_bad_axis(markov_gpt):
    cfg, params = markov_gpt
    with pytest.raises(ValueError, match="mutually exclusive"):
        serving.DecodeServer(params, cfg, max_batch=1, max_len=16,
                             mesh=_mesh(2), device=jax.devices()[0])
    with pytest.raises(ValueError, match="no 'mp' axis"):
        from jax.sharding import Mesh

        serving.DecodeServer(params, cfg, max_batch=1, max_len=16,
                             mesh=Mesh(np.array(jax.devices()[:2]),
                                       ("dp",)))


def test_tp_fleet_replicas_compose(markov_gpt):
    """The legs compose: a router over one TP replica and one pinned
    single-chip replica still matches the single server bit-for-bit."""
    cfg, params = markov_gpt
    prompts = [[3, 7, 2], [1, 5], [9, 4]]
    ref = _single(params, cfg, prompts, max_new=5, max_len=16)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=16,
                              mesh=_mesh(2)),
         serving.DecodeServer(params, cfg, max_batch=2, max_len=16,
                              device=jax.devices()[2])])
    got = _drive(router, prompts, max_new=5)
    router.close()
    assert got == ref


def test_build_sharded_decode_paged_pool(markov_gpt):
    """build_sharded_decode(layout='paged'): the pool's Hkv axis shards
    exactly like the slab's head axis, tables replicate, and the step
    matches the unsharded paged step."""
    import jax.numpy as jnp

    from paddle_tpu.text import kv_pool

    cfg, params = markov_gpt
    sp, make_cache, decode = generate.build_sharded_decode(
        params, cfg, _mesh(2), layout="paged", block_size=8)
    cache_s = make_cache(2, 16)
    assert cache_s["k"].sharding.shard_shape(
        cache_s["k"].shape)[3] == cfg.kv_heads // 2 * cfg.head_dim
    assert cache_s["tables"].sharding.shard_shape(
        cache_s["tables"].shape) == cache_s["tables"].shape
    cache_r = generate.init_cache(cfg, 2, 16, layout="paged",
                                  block_size=8)
    ref_step = jax.jit(lambda p, c, t, pb: kv_pool.paged_decode_step_batched(
        p, c, t, pb, cfg))
    for pos, tok in enumerate(([3, 7], [1, 2])):
        tok = jnp.asarray(tok, jnp.int32)
        pos_b = jnp.full((2,), pos, jnp.int32)
        want, cache_r = ref_step(params, cache_r, tok, pos_b)
        got, cache_s = decode(sp, cache_s, tok, jnp.asarray(pos))
        # TP reduction order vs the single-chip reduction: logits agree
        # to fp tolerance (token-level parity is pinned by
        # test_tp_decode_server_token_parity on the same model)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=1e-2)


def test_sharded_make_cache_flag_flip_fails_loudly(markov_gpt,
                                                   monkeypatch):
    """A PADDLE_TPU_KV_LAYOUT / _KV_BLOCK flip between build and
    make_cache must raise, not silently serve the stale layout."""
    cfg, params = markov_gpt
    monkeypatch.delenv("PADDLE_TPU_KV_LAYOUT", raising=False)
    _, make_cache, _ = generate.build_sharded_decode(params, cfg,
                                                     _mesh(1))
    monkeypatch.setenv("PADDLE_TPU_KV_LAYOUT", "paged")
    with pytest.raises(ValueError, match="KV_LAYOUT changed"):
        make_cache(1, 16)
    monkeypatch.setenv("PADDLE_TPU_KV_LAYOUT", "paged")
    monkeypatch.setenv("PADDLE_TPU_KV_BLOCK", "8")
    _, make_cache, _ = generate.build_sharded_decode(params, cfg,
                                                     _mesh(1))
    monkeypatch.setenv("PADDLE_TPU_KV_BLOCK", "16")
    with pytest.raises(ValueError, match="KV_BLOCK changed"):
        make_cache(1, 16)


# ---------------------------------------------------------------------------
# transports (socket leg capability-gated, test_multihost.py pattern)
# ---------------------------------------------------------------------------


def _localhost_sockets_ok() -> bool:
    try:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        s.close()
        return True
    except OSError:
        return False


requires_sockets = pytest.mark.skipif(
    not _localhost_sockets_ok(),
    reason="sandbox has no localhost sockets")


def test_loopback_transport_roundtrip():
    lt = fleet.LoopbackTransport()
    lt.client.send({"rid": 1, "prompt": [1, 2]})
    assert lt.worker.recv(0.1) == {"rid": 1, "prompt": [1, 2]}
    assert lt.worker.recv(0.0) is None          # poll: empty
    lt.worker.send({"rid": 1, "rows": None})
    assert lt.client.recv(0.1)["rid"] == 1


@requires_sockets
def test_socket_transport_frames_and_poll():
    listener = fleet.SocketTransport.listen()
    client = fleet.SocketTransport.connect("127.0.0.1", listener.port)
    server = listener.accept(timeout=5.0)
    payload = {"rid": 3, "rows": {"k": np.arange(8.0).reshape(2, 4)}}
    client.send(payload)
    got = server.recv(5.0)
    assert got["rid"] == 3
    np.testing.assert_array_equal(got["rows"]["k"], payload["rows"]["k"])
    assert server.recv(0.0) is None             # poll: empty, no hang
    server.close()
    client.close()
    listener.close()


@requires_sockets
def test_socket_send_is_one_gathered_write():
    """Round-19 frame batching: a whole message — header + N buffer
    frames — leaves in ONE scatter-gather write (fleet.frame_batches
    counts messages, not frames), partial sendmsg returns resume at the
    exact offset, and the bytes on the wire stay codec-identical (the
    multi-buffer payload round-trips bit-exactly)."""
    before = _count("fleet.frame_batches")
    listener = fleet.SocketTransport.listen()
    client = fleet.SocketTransport.connect("127.0.0.1", listener.port)
    server = listener.accept(timeout=5.0)
    calls = []

    class _SendmsgProxy:
        def __init__(self, sock):
            self._s = sock

        def __getattr__(self, name):
            return getattr(self._s, name)

        def sendmsg(self, views):
            views = list(views)
            calls.append(len(views))
            if len(calls) == 1:
                # force a partial first write: only half the first
                # frame goes out, the resume path must pick up
                # mid-frame
                half = max(1, views[0].nbytes // 2)
                return self._s.sendmsg([views[0][:half]])
            return self._s.sendmsg(views)

    client._sock = _SendmsgProxy(client._sock)
    payload = {"rid": 9, "rows": {"k": np.arange(12.0).reshape(3, 4),
                                  "v": np.arange(6, dtype=np.int32)}}
    # (prefix + header) + 2 x (prefix + buffer) = 6 iovecs, one gather
    client.send(payload)
    got = server.recv(5.0)
    assert calls and calls[0] == 6, calls
    np.testing.assert_array_equal(got["rows"]["k"], payload["rows"]["k"])
    np.testing.assert_array_equal(got["rows"]["v"], payload["rows"]["v"])
    assert got["rows"]["v"].dtype == np.int32
    assert _count("fleet.frame_batches") >= before + 1
    server.close()
    client.close()
    listener.close()


@requires_sockets
def test_socket_fleet_bit_parity(cfg_params):
    """The cross-process deployment shape, in-process: a PrefillWorker
    served over TCP, the router connected as a remote client — tokens
    bit-identical to the single server."""
    cfg, params = cfg_params
    prompts = _prompts(seed=17)
    ref = _single(params, cfg, prompts)
    worker = fleet.PrefillWorker(params, cfg, max_len=48)
    listener = fleet.serve_prefill_worker(worker)
    ep = fleet.SocketTransport.connect("127.0.0.1", listener.port)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
         for _ in range(2)],
        prefill=[ep], prefill_threshold=16)
    got = _drive(router, prompts)
    router.close()
    worker.close()
    listener.close()
    assert got == ref
    assert _count("fleet.prefill_handoffs") >= 1


def test_submit_prefilled_rejects_dtype_drift(cfg_params):
    """Same leaf names, different storage dtype (env drift between a
    worker process and the server): rejected, never silently cast."""
    cfg, params = cfg_params
    worker = fleet.PrefillWorker(params, cfg, max_len=48)
    rows, logits = worker.prefill([1, 2, 3])
    worker.close()
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=48)
    other = (np.float32 if srv.cache["k"].dtype != np.float32
             else np.float16)
    rows = {n: np.asarray(v).astype(other) for n, v in rows.items()}
    with pytest.raises(ValueError, match="dtype drift|stores"):
        srv.submit_prefilled([1, 2, 3], rows, logits)
    srv.close()


def test_prefilling_request_ttl_sheds(cfg_params):
    """A request out at a prefill worker past its TTL sheds with the
    timeout status — a stalled worker can't hold it (or the fleet's
    pending() loop) forever."""
    cfg, params = cfg_params
    lt = fleet.LoopbackTransport()       # no worker ever attached
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=1, max_len=48)],
        prefill=[lt.client], prefill_threshold=1)
    rid = router.submit([1, 2, 3], max_new_tokens=4, ttl_s=0.01)
    assert router.status(rid) == "prefilling"
    time.sleep(0.02)
    router.tick()
    assert router.status(rid) == "timeout"
    with pytest.raises(resilience.DeadlineExceeded):
        router.result(rid)
    assert not router.pending()
    assert _count("fleet.ttl_sheds") == 1
    router.close()


@requires_sockets
def test_dead_socket_worker_fails_requests_not_hangs(cfg_params):
    """A worker process dying mid-job (orderly TCP close, no reply):
    its outstanding prefills retire with the ``error`` status and the
    endpoint leaves the rotation — the drive loop never spins forever."""
    cfg, params = cfg_params
    listener = fleet.SocketTransport.listen()
    client = fleet.SocketTransport.connect("127.0.0.1", listener.port)
    worker_side = listener.accept(timeout=5.0)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=1, max_len=48)],
        prefill=[client], prefill_threshold=1)
    rid = router.submit([1, 2, 3], max_new_tokens=4)
    assert worker_side.recv(5.0)["rid"] == rid   # job arrived
    worker_side.close()                          # worker dies, no reply
    deadline = time.time() + 10.0
    while router.status(rid) == "prefilling" and time.time() < deadline:
        router.tick()
        time.sleep(0.01)
    assert router.status(rid) == "error"
    with pytest.raises(RuntimeError, match="prefill worker"):
        router.result(rid)
    assert not router.pending()
    assert _count("fleet.prefill_errors") == 1
    # the dead endpoint left the rotation: new submits prefill locally
    rid2 = router.submit([4, 5, 6], max_new_tokens=4)
    while router.pending():
        router.tick()
    assert router.status(rid2) == "ok"
    router.close()
    listener.close()


def test_drain_spares_directly_submitted_requests(cfg_params):
    """drain_queue(rids): the router drains only its own work — a
    request submitted DIRECTLY to a router-fronted replica survives the
    wedge drain and still finishes for its submitter."""
    cfg, params = cfg_params
    prompt = [5, 9, 2]
    ref = _single(params, cfg, [prompt])
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=48)
    srv.submit([1, 2], max_new_tokens=2)          # occupies the slot
    direct = srv.submit(prompt, max_new_tokens=6)  # queued, router-unknown
    drained = srv.drain_queue(rids=set())          # the router owns none
    assert drained == [] and srv.load_stats()["queue_depth"] == 1
    while srv.pending():
        srv.tick()
    assert srv.result(direct) == ref[0]
    srv.close()


# ---------------------------------------------------------------------------
# lint: every router scheduling path counts a fleet.* counter
# ---------------------------------------------------------------------------


def test_fleet_lint_catches_silent_reroute():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import check_instrumented as ci

    bad = ("class R:\n"
           "    def _route(self, q):\n"
           "        return q.pop()\n")
    assert ci.scan_fleet_source(bad)
    good = ("class R:\n"
            "    def _shed_expired(self):\n"
            "        count('fleet.ttl_sheds')\n"
            "    def _drain_replica(self, i):\n"
            "        self._shed_expired()\n")
    assert not ci.scan_fleet_source(good)


# ---------------------------------------------------------------------------
# zero-copy KV streaming: wire codec, chunked handoff, elastic fleet
# ---------------------------------------------------------------------------


@pytest.fixture()
def fleet_env(monkeypatch):
    """Env setter that also busts the value-keyed jit caches (the
    kv_env idiom from test_kv_pool.py: KV dtype / chunk flags key the
    traced step fns, but modules cache them across tests)."""
    def set_(**kw):
        for k, v in kw.items():
            if v is None:
                monkeypatch.delenv(k, raising=False)
            else:
                monkeypatch.setenv(k, v)
        generate._GEN_CACHE.clear()
        serving._STEP_CACHE.clear()
    yield set_
    generate._GEN_CACHE.clear()
    serving._STEP_CACHE.clear()


def test_wire_codec_roundtrip_dtypes():
    """The raw-row codec: dtype-tagged header + contiguous buffer
    frames roundtrip bit-exactly for every KV storage dtype (fp32,
    int8, bf16), nested trees included — and the reassembled arrays
    are WRITABLE (the decode side owns fresh buffers, so inject paths
    may pad in place)."""
    ml_dtypes = pytest.importorskip("ml_dtypes")
    rng = np.random.default_rng(3)
    msg = {
        "rid": 7, "op": "chunk", "start": 0, "stop": 4,
        "rows": {
            "k": rng.standard_normal((2, 1, 4, 8)).astype(np.float32),
            "q8": rng.integers(-128, 127, (2, 1, 4), dtype=np.int8),
            "b16": rng.standard_normal((3, 4)).astype(ml_dtypes.bfloat16),
        },
        "meta": [1, "x", None, 2.5],
    }
    hdr, arrays = fleet._encode_msg(msg)
    assert isinstance(hdr, bytes)
    out = fleet._decode_msg(
        hdr, [bytearray(a.reshape(-1).view(np.uint8)) for a in arrays])
    assert out["rid"] == 7 and out["meta"] == [1, "x", None, 2.5]
    for name, ref in msg["rows"].items():
        got = out["rows"][name]
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_array_equal(
            got.view(np.uint8), ref.view(np.uint8))
        assert got.flags.writeable


def test_wire_codec_never_pickles_unknown_types():
    """A non-transportable leaf is a loud TypeError, never a silent
    pickle fallback — the codec's security contract."""
    with pytest.raises(TypeError):
        fleet._encode_msg({"bad": {1, 2, 3}})
    with pytest.raises(TypeError):
        fleet._encode_msg({"fn": lambda: None})


@requires_sockets
def test_socket_torn_frame_budget_and_reset(monkeypatch):
    """Transport failure semantics re-pinned on the raw protocol: a
    peer that stalls MID-FRAME trips the torn-frame budget as a
    ConnectionError (never an infinite buffer wait), and an orderly
    close mid-stream surfaces the same way."""
    monkeypatch.setattr(fleet, "_FRAME_BUDGET_S", 0.05)
    listener = fleet.SocketTransport.listen()
    raw = socket.create_connection(("127.0.0.1", listener.port))
    ep = listener.accept(timeout=5.0)
    try:
        raw.sendall(fleet._FRAME_PREFIX.pack(1, 1000) + b"torn")
        with pytest.raises(ConnectionError):
            ep.recv(1.0)
    finally:
        raw.close()
        ep.close()
        listener.close()
    # orderly close with zero bytes mid-message: ConnectionError too
    listener = fleet.SocketTransport.listen()
    raw = socket.create_connection(("127.0.0.1", listener.port))
    ep = listener.accept(timeout=5.0)
    try:
        raw.close()
        with pytest.raises(ConnectionError):
            ep.recv(1.0)
    finally:
        ep.close()
        listener.close()


@pytest.mark.parametrize("kv", ["fp32", "int8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_chunked_stream_bit_parity(fleet_env, kv, layout):
    """The tentpole claim: a prefill handed off CHUNK BY CHUNK (rows
    injected through the pow2 buckets while the worker computes the
    next chunk) yields tokens bit-identical to one DecodeServer's
    monolithic local admission — {contiguous, paged} x {fp32, int8 KV
    storage}."""
    fleet_env(PADDLE_TPU_STREAM_CHUNK_ROWS="4",
              PADDLE_TPU_KV_DTYPE=None if kv == "fp32" else kv)
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    kw = _layout_kw(layout)
    prompts = _prompts(seed=23)
    ref = _single(params, cfg, prompts, **kw)
    worker = fleet.PrefillWorker(params, cfg, max_len=48,
                                 layout=layout, block_size=8)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=48, **kw)
         for _ in range(2)],
        prefill=[worker], prefill_threshold=16)
    got = _drive(router, prompts)
    router.close()
    assert got == ref
    # the 20-token prompt crossed the wire in >= 2 chunks of raw rows
    assert _count("fleet.stream_chunks") >= 2
    assert _count("fleet.stream_bytes") > 0
    assert _count("serving.stream_claims") >= 1


def test_monolithic_flag_restores_whole_walk(fleet_env, cfg_params):
    """PADDLE_TPU_STREAM_CHUNK_ROWS=0 restores the whole-walk reply
    shape — still bit-identical, zero chunk frames on the wire."""
    fleet_env(PADDLE_TPU_STREAM_CHUNK_ROWS="0")
    cfg, params = cfg_params
    prompts = _prompts(seed=29)
    ref = _single(params, cfg, prompts)
    worker = fleet.PrefillWorker(params, cfg, max_len=48)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
         for _ in range(2)],
        prefill=[worker], prefill_threshold=16)
    got = _drive(router, prompts)
    router.close()
    assert got == ref
    assert _count("fleet.stream_chunks") == 0
    assert _count("fleet.prefill_handoffs") >= 1


@requires_sockets
def test_mid_stream_worker_death_fails_honestly(fleet_env, cfg_params):
    """A worker that dies after ONE chunk (orderly close, no final
    logits frame): the half-streamed request retires with ``error``,
    its claimed replica slot frees, the drive loop never hangs, and the
    replica keeps serving new work."""
    fleet_env(PADDLE_TPU_STREAM_CHUNK_ROWS="4")
    cfg, params = cfg_params
    listener = fleet.SocketTransport.listen()
    client = fleet.SocketTransport.connect("127.0.0.1", listener.port)
    worker_side = listener.accept(timeout=5.0)
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=48)
    router = fleet.Router([srv], prefill=[client], prefill_threshold=1)
    prompt = [int(x) for x in
              np.random.default_rng(31).integers(1, 60, 12)]
    rid = router.submit(prompt, max_new_tokens=4)
    job = worker_side.recv(5.0)
    assert job["rid"] == rid
    # compute real chunks locally, replay only the first, then die
    helper = fleet.PrefillWorker(params, cfg, max_len=48)
    msgs = []
    helper.prefill_stream(job["prompt"], msgs.append, chunk_rows=4)
    helper.close()
    assert len(msgs) >= 2 and msgs[0].get("logits") is None
    worker_side.send(dict(msgs[0], rid=rid))
    deadline = time.time() + 10.0
    while (router._requests[rid]["state"] != "streaming"
           and time.time() < deadline):
        router.tick()                    # absorb the first chunk
        time.sleep(0.01)
    assert router._requests[rid]["state"] == "streaming"
    worker_side.close()                  # worker dies mid-stream
    deadline = time.time() + 10.0
    while (router.status(rid) in ("prefilling", "streaming")
           and time.time() < deadline):
        router.tick()
        time.sleep(0.01)
    assert router.status(rid) == "error"
    with pytest.raises(RuntimeError):
        router.result(rid)
    assert not router.pending()
    assert _count("fleet.stream_aborts") >= 1
    assert not srv._slots and not srv._streams   # the claimed slot freed
    rid2 = router.submit([4, 5], max_new_tokens=2)
    deadline = time.time() + 20.0
    while router.pending() and time.time() < deadline:
        router.tick()
        time.sleep(0.005)
    assert router.status(rid2) == "ok"
    router.close()
    listener.close()


def test_live_add_remove_replica_bit_identical(cfg_params):
    """Elastic topology changes mid-flight: a replica attached LIVE
    joins routing, a replica removed LIVE materializes its in-flight
    results first — every token stream bit-identical to an undisturbed
    single server."""
    cfg, params = cfg_params
    prompts = _prompts(n_short=5, seed=37)
    ref = _single(params, cfg, prompts)
    mk = lambda: serving.DecodeServer(params, cfg, max_batch=2,  # noqa: E731
                                      max_len=48)
    router = fleet.Router([mk(), mk()])
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]
    for _ in range(2):
        router.tick()
    third = router.add_replica(mk())
    assert _count("fleet.replica_adds") == 1
    for _ in range(2):
        router.tick()
    removed = router.remove_replica(0)   # in-flight work materializes
    removed.close()
    assert _count("fleet.replica_removes") == 1
    assert router.replicas[0] is None    # tombstone keeps indices valid
    deadline = time.time() + 120.0
    while router.pending() and time.time() < deadline:
        router.tick()
    got = [router.result(r) for r in rids]
    assert got == ref
    assert int(tl.gauge("fleet.replicas").get()) == 2
    assert router.healthz()["ok"]
    with pytest.raises(KeyError):
        router.remove_replica(0)         # already tombstoned
    router.close()
    assert third == 2


def test_autoscale_drill_out_then_in(fleet_env, cfg_params):
    """The telemetry-driven scaling loop end to end: sustained
    admission rung >= threshold attaches the registered spare
    (fleet.scale_outs), sustained idle drains it back to the pool
    (fleet.scale_ins) — debounced, never flapping on one hot tick; what
    is left serves the same tokens as one server."""
    fleet_env(PADDLE_TPU_FLEET_AUTOSCALE="1",
              PADDLE_TPU_FLEET_SCALE_RUNG="2",
              PADDLE_TPU_FLEET_SCALE_OUT_TICKS="2",
              PADDLE_TPU_FLEET_SCALE_IN_TICKS="3")
    cfg, params = cfg_params
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
    spare = serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
    router = fleet.Router([srv])
    router.register_spare(spare)
    live = lambda: sum(  # noqa: E731
        1 for r in router.replicas if r is not None)
    orig = srv.load_stats
    srv.load_stats = lambda: dict(orig(), admission_rung=2,
                                  queue_depth=1)
    router.tick()                        # hot tick 1: debounced
    assert live() == 1 and _count("fleet.scale_outs") == 0
    router.tick()                        # hot tick 2: spare attaches
    assert live() == 2
    assert _count("fleet.scale_outs") == 1
    assert int(tl.gauge("fleet.replicas").get()) == 2
    srv.load_stats = orig                # load clears: fleet goes idle
    for _ in range(3):
        assert live() == 2               # scale-in debounce holds
        router.tick()
    assert live() == 1
    assert _count("fleet.scale_ins") == 1
    assert router._spares == [spare]     # drained back to the pool
    assert int(tl.gauge("fleet.replicas").get()) == 1
    # the drilled fleet still serves bit for bit
    prompts = _prompts(seed=31)
    got = _drive(router, prompts)        # a removed replica is a None
    router.close()
    assert got == _single(params, cfg, prompts)


def test_chain_migration_follows_the_prompt(fleet_env):
    """Cross-replica spilled-chain migration: a host-RAM chain on
    replica A ships to replica B through the raw wire codec (a MOVE —
    the source forgets it), lands in B's spill store, and B's
    admission restores it bit-identically through its own inject
    buckets (kv_pool.chain_migrations counted)."""
    fleet_env(PADDLE_TPU_KV_SPILL_MB="4")
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompt = [int(x) for x in
              np.random.default_rng(41).integers(1, 60, 16)]
    ref = _single(params, cfg, [prompt], layout="paged", block_size=8)
    mk = lambda: serving.DecodeServer(params, cfg, max_batch=2,  # noqa: E731
                                      max_len=48, layout="paged",
                                      block_size=8)
    a, b = mk(), mk()
    router = fleet.Router([a, b])
    # warm the chain on A (direct submit — the drain-spares contract),
    # then demote it to A's host-RAM spill tier
    r0 = a.submit(prompt, max_new_tokens=6)
    while a.pending():
        a.tick()
    assert a.result(r0) == ref[0]
    for _ in range(8):
        if not a._pool.prefix_entries:
            break
        a._evict_or_spill(8)
    assert a._pool._spilled
    # the routing hook: before B adopts this prompt, A's chain moves
    router._migrate_chains({"prompt": prompt}, 1)
    assert not a._pool._spilled           # a move, not a copy
    assert b._pool._spilled
    assert _count("kv_pool.chain_migrations") >= 1
    assert _count("kv_pool.chain_migrations_out") >= 1
    r1 = b.submit(prompt, max_new_tokens=6)
    while b.pending():
        b.tick()
    warm = b.result(r1)
    stats = b._pool.stats()
    router.close()
    assert warm == ref[0]
    assert stats["restored_blocks"] >= 1
    assert stats["chain_migrations"] >= 1


def test_stream_lint_family_and_pickle_ban():
    """The STREAM lint rules hold on fixtures AND on the shipped tree:
    every stream/scale/migrate-named path counts or delegates, and
    text/fleet.py carries zero pickle sites."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import check_instrumented as ci

    bad = ("class R:\n"
           "    def _stream_chunk(self, m):\n"
           "        return m\n"
           "    def _scale_out(self):\n"
           "        self.n += 1\n")
    assert len(ci.scan_stream_source(bad)) == 2
    good = ("class R:\n"
            "    def _scale_in(self):\n"
            "        count('fleet.scale_ins')\n"
            "    def _migrate_chains(self, req, i):\n"
            "        self._scale_in()\n")
    assert not ci.scan_stream_source(good)
    assert ci.scan_pickle_ban_source("import pickle\n")
    assert ci.scan_pickle_ban_source(
        "def recv(self):\n    return pickle.loads(b'')\n")
    assert not ci.scan_pickle_ban_source(
        "import json\nx = json.loads('{}')\n")
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    for rel in ("paddle_tpu/text/fleet.py", "paddle_tpu/text/kv_pool.py"):
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            assert not ci.scan_stream_source(f.read(), rel)
    with open(os.path.join(root, "paddle_tpu/text/fleet.py"),
              encoding="utf-8") as f:
        assert not ci.scan_pickle_ban_source(f.read(), "fleet.py")
