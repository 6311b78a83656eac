"""Paged KV-cache subsystem (text/kv_pool.py).

The properties that matter: (1) the allocator's free-list/refcount/COW
invariants hold under any interleaving of admissions and retires; (2) a
request served from POOLED blocks — including blocks adopted from
another request's prefix — produces exactly the tokens the contiguous
slab produces (bit-parity across fp32/bf16/int8, tick/block/async); and
(3) the pool degrades observably: exhaustion queues instead of crashing,
an OOM on a tick evicts the cold prefix cache first, and every
allocator mutation counts a telemetry counter (linted).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import faults, flags
from paddle_tpu.framework import monitor
from paddle_tpu.ops import decode_attention as da
from paddle_tpu.text import generate as G
from paddle_tpu.text import gpt, kv_pool, serving


def _cfg(**over):
    kw = dict(vocab_size=32, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64)
    kw.update(over)
    return gpt.GPTConfig(**kw)


@pytest.fixture()
def kv_env(monkeypatch):
    """Env setter that also busts the value-keyed jit caches (the flags
    are part of _cfg_key, but modules cache traced fns across tests)."""
    def set_(**kw):
        for k, v in kw.items():
            if v is None:
                monkeypatch.delenv(k, raising=False)
            else:
                monkeypatch.setenv(k, v)
        G._GEN_CACHE.clear()
        serving._STEP_CACHE.clear()
    yield set_
    G._GEN_CACHE.clear()
    serving._STEP_CACHE.clear()


@pytest.fixture()
def interpret():
    from paddle_tpu.ops import flash_attention as fa

    old_da, old_fa = da._INTERPRET, fa._INTERPRET
    da._INTERPRET, fa._INTERPRET = True, True
    G._GEN_CACHE.clear()
    serving._STEP_CACHE.clear()
    yield
    da._INTERPRET, fa._INTERPRET = old_da, old_fa
    G._GEN_CACHE.clear()
    serving._STEP_CACHE.clear()


# ---------------------------------------------------------------------------
# allocator invariants (pure host)
# ---------------------------------------------------------------------------


def test_alloc_free_refcount_invariants():
    a = kv_pool.PagedAllocator(num_blocks=4, block_size=8, nmax=4,
                               max_batch=2)
    assert a.blocks_in_use == 0
    a.ensure_rows(0, 0, 17)            # rows 0..16 -> 3 blocks
    assert a.blocks_in_use == 3
    assert (a.tables[0, :3] >= 0).all() and a.tables[0, 3] == -1
    a.ensure_rows(0, 0, 17)            # idempotent: already mapped
    assert a.blocks_in_use == 3
    a.free_slot(0)
    assert a.blocks_in_use == 0
    assert (a.tables[0] == -1).all()
    # freed blocks are reusable
    a.ensure_rows(1, 0, 32)
    assert a.blocks_in_use == 4
    with pytest.raises(kv_pool.PoolExhausted):
        a.ensure_rows(0, 0, 8)


def test_pool_exhausted_classifies_as_oom():
    from paddle_tpu import resilience

    assert resilience.is_oom(kv_pool.PoolExhausted(1, 4))


def test_prefix_adopt_register_cap_and_cow():
    bs = 8
    a = kv_pool.PagedAllocator(num_blocks=8, block_size=bs, nmax=4,
                               max_batch=2)
    prompt = list(range(20))           # 2 full blocks + 4-row tail
    a.ensure_rows(0, 0, len(prompt))
    a.register_prefix(0, prompt)
    assert a.prefix_entries == 2       # full blocks only, never the tail
    # index holds its own ref: retiring the owner keeps the blocks
    owned = [int(a.tables[0, i]) for i in range(2)]
    a.free_slot(0)
    assert a.blocks_in_use == 2
    # a second identical prompt adopts both blocks (capped at n-1 rows)
    shared = a.adopt_prefix(1, prompt)
    assert shared == 16
    assert [int(a.tables[1, i]) for i in range(2)] == owned
    assert a.prefix_hits == 16         # token rows, not blocks
    # the adopted blocks are shared (ref 2): a write COWs
    a.ensure_rows(1, 8, 20)
    assert a.cow_copies == 1
    assert int(a.tables[1, 1]) != owned[1]     # remapped
    assert int(a.tables[1, 0]) == owned[0]     # untouched block stays
    src_dst = a.take_copies()
    assert src_dst == [(owned[1], int(a.tables[1, 1]))]
    # divergent prompt: chain key mismatch after block 0
    other = prompt[:8] + [99] * 12
    a2 = kv_pool.PagedAllocator(num_blocks=8, block_size=bs, nmax=4,
                                max_batch=2)
    a2.ensure_rows(0, 0, 20)
    a2.register_prefix(0, prompt)
    assert a2.adopt_prefix(1, other) == 8
    assert a2.prefix_misses >= 1


def test_evict_cold_frees_only_index_held_blocks():
    a = kv_pool.PagedAllocator(num_blocks=8, block_size=8, nmax=4,
                               max_batch=2)
    p1, p2 = list(range(8)), list(range(100, 108))
    a.ensure_rows(0, 0, 8)
    a.register_prefix(0, p1)
    a.ensure_rows(1, 0, 8)
    a.register_prefix(1, p2)
    a.free_slot(0)                      # p1's block now cold (index-only)
    freed = a.evict_cold()
    assert freed == 1                   # p2's block is hot (slot 1 lives)
    assert a.prefix_entries == 1
    a.free_slot(1)
    assert a.evict_cold() == 1
    assert a.blocks_in_use == 0


def test_prefix_index_interned_chain_is_linear():
    """Round 9: the index interns (parent chain id, block tokens) — one
    O(block_size) key per block, so a long prompt costs O(n) host
    memory/hashing where the old exact-chain keys
    (``tuple(prompt[:(li+1)*bs])``) materialized O(n^2/bs)."""
    bs = 4
    a = kv_pool.PagedAllocator(num_blocks=16, block_size=bs, nmax=12,
                               max_batch=2)
    prompt = list(range(40))            # 10 full blocks
    a.ensure_rows(0, 0, 40)
    a.register_prefix(0, prompt)
    assert a.prefix_entries == 10
    assert len(a._interned) == 10
    # every intern key holds ONE block's tokens, never a growing prefix
    assert all(len(tokens) == bs for _, tokens in a._interned)
    # the chain walk still adopts the whole prefix (capped at n-1 rows)
    assert a.adopt_prefix(1, prompt) == 39
    a.close()


def test_interned_chain_keys_never_alias_across_parents():
    """The no-collision guarantee survives interning: identical block
    tokens under DIFFERENT parents are different chain entries, so a
    prompt starting with another prompt's middle block shares nothing."""
    bs = 4
    a = kv_pool.PagedAllocator(num_blocks=16, block_size=bs, nmax=8,
                               max_batch=2)
    p1 = [1, 2, 3, 4, 5, 6, 7, 8]
    a.ensure_rows(0, 0, 8)
    a.register_prefix(0, p1)
    # [5,6,7,8] is indexed only under parent [1,2,3,4] — as a ROOT
    # block it must miss
    p2 = [5, 6, 7, 8, 9, 10, 11, 12]
    assert a.adopt_prefix(1, p2) == 0
    assert a.prefix_misses >= 1
    a.close()


def test_evict_cold_drains_interned_chains_tail_first():
    """Only chain leaves are eviction candidates (an evicted inner
    block would orphan its descendants' ids): repeated engagements
    drain a cold chain one tail block per pass."""
    a = kv_pool.PagedAllocator(num_blocks=16, block_size=4, nmax=8,
                               max_batch=2)
    prompt = list(range(12))            # 3 chained blocks
    a.ensure_rows(0, 0, 12)
    a.register_prefix(0, prompt)
    a.free_slot(0)                      # whole chain cold (index-only)
    for left in (2, 1, 0):
        assert a.evict_cold() == 1      # the current leaf only
        assert a.prefix_entries == left
    assert a.blocks_in_use == 0
    a.close()


def test_close_releases_everything():
    a = kv_pool.PagedAllocator(num_blocks=6, block_size=8, nmax=3,
                               max_batch=2)
    a.ensure_rows(0, 0, 24)
    a.register_prefix(0, list(range(24)))
    a.close()
    assert a.blocks_in_use == 0 and a.prefix_entries == 0


# ---------------------------------------------------------------------------
# cache format
# ---------------------------------------------------------------------------


def test_init_paged_cache_shapes(kv_env):
    cfg = _cfg(num_kv_heads=2)
    c = G.init_cache(cfg, 3, 20, layout="paged", block_size=8)
    # rows round to 24 -> nmax 3; full provisioning 3*3 blocks
    # a row's heads side by side: 2 KV heads of 8
    assert c["k"].shape == c["v"].shape == (2, 9, 8, 2 * 8)
    assert c["tables"].shape == (3, 3)
    assert int(c["tables"].min()) == -1
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    c8 = G.init_cache(cfg, 1, 16, layout="paged", block_size=8,
                      num_blocks=4)
    assert c8["k"].dtype == jnp.int8
    assert c8["k_s"].shape == (2, 4, 8, 2)


def test_random_filled_cache_paged_identity_tables():
    cfg = _cfg()
    c = G.init_cache(cfg, 2, 16, layout="paged", block_size=8)
    filled = da.random_filled_cache(c, jax.random.PRNGKey(0))
    t = np.asarray(filled["tables"])
    assert (t >= 0).all() and len(set(t.ravel().tolist())) == t.size
    assert float(np.abs(np.asarray(filled["k"], np.float32)).max()) > 0


def test_round_len_whole_blocks():
    assert kv_pool.round_len(20, 8) == 24
    assert kv_pool.round_len(32, 16) == 32
    assert kv_pool.round_len(5, 8) == 8


# ---------------------------------------------------------------------------
# paged vs contiguous bit-parity (the acceptance gate)
# ---------------------------------------------------------------------------


def _serve(params, cfg, prompts, layout, max_new=6, tick="tick",
           async_=False, max_len=32, **kw):
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=max_len,
                               layout=layout, async_dispatch=async_, **kw)
    rids = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    while srv.pending():
        if tick == "block":
            srv.tick_block(4)
        else:
            srv.tick()
    out = [srv.result(r) for r in rids]
    stats = srv._pool.stats() if srv._pool is not None else None
    srv.close()
    return out, stats


@pytest.mark.parametrize("kv", ["fp32", "bf16", "int8"])
def test_paged_matches_contiguous_greedy(kv_env, kv, markov_gpt):
    kv_env(PADDLE_TPU_KV_DTYPE=None if kv == "fp32" else kv)
    cfg, params = markov_gpt
    rng = np.random.default_rng(0)
    shared = list(rng.integers(0, 13, 8))
    prompts = [shared + [1, 5], shared + [2], list(rng.integers(0, 13, 5))]
    cont, _ = _serve(params, cfg, prompts, "contiguous")
    paged, stats = _serve(params, cfg, prompts, "paged", block_size=8)
    assert paged == cont
    assert stats["prefix_hits"] > 0      # the shared 8-row block reused


def test_paged_matches_contiguous_block_and_async(markov_gpt):
    cfg, params = markov_gpt
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, 13, n)) for n in (9, 4, 12)]
    ref, _ = _serve(params, cfg, prompts, "contiguous")
    for tick, async_ in (("block", False), ("tick", True),
                         ("block", True)):
        got, _ = _serve(params, cfg, prompts, "paged", tick=tick,
                        async_=async_, block_size=8)
        assert got == ref, (tick, async_)


def test_paged_sampled_parity(markov_gpt):
    """Sampled requests draw from the same fold_in schedule: identical
    tokens for identical step counters across layouts."""
    cfg, params = markov_gpt

    def run(layout):
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                                   layout=layout, block_size=8, seed=7)
        r0 = srv.submit([1, 2, 3], max_new_tokens=6, temperature=0.8,
                        top_k=5)
        r1 = srv.submit([4, 5], max_new_tokens=6)
        while srv.pending():
            srv.tick()
        out = srv.result(r0), srv.result(r1)
        srv.close()
        return out

    assert run("paged") == run("contiguous")


def test_prefix_hit_bit_identical_and_prefill_rows_saved(markov_gpt):
    """A repeated prompt adopts the registered blocks: prefill runs only
    the suffix (FLOPs skipped), tokens stay bit-identical to cold."""
    cfg, params = markov_gpt
    prompt = [int(x) for x in np.random.default_rng(3).integers(0, 13, 18)]
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                               layout="paged", block_size=8)
    rows0 = int(monitor.get_stat("kv_pool.prefill_rows").get())
    r0 = srv.submit(prompt, max_new_tokens=4)
    while srv.pending():
        srv.tick()
    cold = srv.result(r0)
    rows_cold = int(monitor.get_stat("kv_pool.prefill_rows").get()) - rows0
    r1 = srv.submit(prompt, max_new_tokens=4)
    while srv.pending():
        srv.tick()
    warm = srv.result(r1)
    rows_warm = (int(monitor.get_stat("kv_pool.prefill_rows").get())
                 - rows0 - rows_cold)
    stats = srv._pool.stats()
    srv.close()
    assert warm == cold
    assert stats["prefix_hits"] >= 2
    assert rows_warm < rows_cold         # shared blocks never recomputed


def test_cow_on_fully_shared_prompt(markov_gpt):
    """A prompt that is entirely indexed still computes its last token:
    the one-row write into the shared final block copy-on-writes it."""
    cfg, params = markov_gpt
    prompt = [int(x) for x in np.random.default_rng(4).integers(0, 13, 16)]
    out, stats = _serve(params, cfg, [prompt, prompt], "paged",
                        block_size=8)
    assert out[0] == out[1]
    assert stats["cow_copies"] >= 1
    ref, _ = _serve(params, cfg, [prompt, prompt], "contiguous")
    assert out == ref


def test_paged_peak_resident_blocks_at_most_half_the_slab():
    """What the layout is for: the slab provisions ``max_len`` rows for
    EVERY slot, the pool maps blocks as rows are written.  A mixed batch
    of 9-13-token prompts behind a shared 8-token prefix, 6 tokens
    generated each, on two slots of 64 rows: same tokens as the slab, a
    prefix hit, and never more than half the slab's blocks mapped."""
    cfg = _cfg(vocab_size=128, hidden_size=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    shared = [int(x) for x in rng.integers(1, 100, 8)]
    prompts = [shared + [int(x) for x in rng.integers(1, 100, n)]
               for n in (3, 5, 1)]

    kw = dict(tick="block", max_len=64, block_size=8)
    cont, _ = _serve(params, cfg, prompts, "contiguous", **kw)
    paged, stats = _serve(params, cfg, prompts, "paged", **kw)
    assert paged == cont
    assert stats["prefix_hits"] >= 1
    slab_blocks = 2 * (kv_pool.round_len(64, 8) // 8)
    assert stats["peak_blocks_in_use"] <= slab_blocks // 2, stats


def test_pool_exhaustion_queues_until_blocks_free(markov_gpt):
    """A pool too small for two concurrent requests serves them anyway:
    the second waits in the queue until the first retires its blocks."""
    cfg, params = markov_gpt
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                               layout="paged", block_size=8, num_blocks=2)
    p = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    # request 1 owns both blocks; request 2's admission exhausts the
    # pool (even with block 0 adopted) and must PARK, not fail
    rids = [srv.submit(p, max_new_tokens=4) for _ in range(2)]
    assert srv.status(rids[1]) == "queued"
    for _ in range(200):
        if not srv.pending():
            break
        srv.tick()
    outs = [srv.result(r) for r in rids]
    srv.close()
    assert outs[0] == outs[1] and len(outs[0]) == 4


def test_oom_fault_evicts_cold_prefix_cache_first(markov_gpt):
    """PADDLE_TPU_FAULTS=oom:serving.block:1 — the OOM chain's NEW first
    rung drops index-only blocks before degrading dispatch, and the
    faulted pass still yields bit-identical tokens."""
    cfg, params = markov_gpt
    prompt = [int(x) for x in np.random.default_rng(5).integers(0, 13, 12)]

    def run(spec):
        faults.reset()
        try:
            srv = serving.DecodeServer(params, cfg, max_batch=2,
                                       max_len=32, layout="paged",
                                       block_size=8)
            r0 = srv.submit(prompt, max_new_tokens=4)
            while srv.pending():
                srv.tick_block(4)
            # r0 retired: its prefix block is now COLD (index-only) —
            # install the fault so the NEXT block tick OOMs and the
            # chain's first rung has something to evict
            cold_entries = srv._pool.prefix_entries
            if spec:
                faults.install(spec)
            # r1 shares NO prefix with r0, so r0's entry stays cold —
            # exactly what the first rung exists to reclaim
            r1 = srv.submit([int(x) for x in prompt[::-1][:10]],
                            max_new_tokens=4)
            while srv.pending():
                srv.tick_block(4)
            out = (srv.result(r0), srv.result(r1))
            entries_after = srv._pool.prefix_entries
            srv.close()
            return out, cold_entries, entries_after
        finally:
            faults.reset()

    clean, _, _ = run("")
    before = int(monitor.get_stat("kv_pool.prefix_evictions").get())
    faulted, cold_entries, after = run("oom:serving.block:1")
    evictions = (int(monitor.get_stat("kv_pool.prefix_evictions").get())
                 - before)
    assert cold_entries >= 1
    assert evictions >= 1
    assert faulted == clean
    assert int(monitor.get_stat("resilience.oom_retries").get()) >= 1


def test_donation_safety_of_pooled_leaves(kv_env):
    """The paged step donates its cache like the slab step: the passed
    leaves are consumed (deleted) and the returned tree is fresh."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=16,
                               layout="paged", block_size=8)
    srv.submit([1, 2, 3], max_new_tokens=4)
    old = srv.cache
    srv.tick()
    assert flags.donate_decode()
    assert old["k"].is_deleted() and old["v"].is_deleted()
    assert not srv.cache["k"].is_deleted()
    srv.close()


def test_kv_utilization_gauge_true_occupancy(markov_gpt):
    """Satellite: paged reports blocks-in-use / pool size; contiguous
    reports filled rows over the slab's REAL (rounded) row count."""
    from paddle_tpu import telemetry as tl

    if not tl.enabled():
        pytest.skip("telemetry off")
    cfg, params = markov_gpt
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=20,
                               layout="paged", block_size=8,
                               num_blocks=8)
    srv.submit([1, 2, 3, 4, 5], max_new_tokens=8)
    srv.tick()
    g = tl.snapshot()["gauges"]
    used = srv._pool.blocks_in_use
    assert g["serving.kv_utilization"] == pytest.approx(used / 8)
    assert g["kv_pool.blocks_in_use"] == used
    # the table entries the paged kernel walks: one slot's blocks up to
    # its write position, of 2 slots x 3 entries
    (st,) = srv._slots.values()
    assert g["kv_pool.walk_share"] == pytest.approx(
        -(-(st["pos"] + 1) // 8) / (2 * 3))
    assert 0 < g["kv_pool.walk_share"] < 1
    srv.close()
    # contiguous: rows denominator is the rounded allocation (24), not
    # max_len (20)
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=20)
    srv.submit([1, 2, 3, 4, 5], max_new_tokens=8)
    srv.tick()
    rows = int(srv.cache["k"].shape[2])
    pos = [st["pos"] for st in srv._slots.values()]
    g = tl.snapshot()["gauges"]
    assert rows == 24
    assert g["serving.kv_utilization"] == pytest.approx(
        sum(pos) / (2 * rows))
    srv.close()


def test_jit_key_covers_layout_flags(kv_env):
    base = flags.decode_jit_key()
    kv_env(PADDLE_TPU_KV_LAYOUT="paged")
    paged = flags.decode_jit_key()
    assert paged != base and "paged" in paged
    kv_env(PADDLE_TPU_KV_LAYOUT=None, PADDLE_TPU_KV_BLOCK="32")
    assert flags.decode_jit_key() != base
    kv_env(PADDLE_TPU_KV_BLOCK=None)
    assert flags.decode_jit_key() == base


def test_layout_flag_flips_server_default(kv_env, markov_gpt):
    cfg, params = markov_gpt
    kv_env(PADDLE_TPU_KV_LAYOUT="paged")
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=16)
    assert srv._paged and "tables" in srv.cache
    srv.close()


# ---------------------------------------------------------------------------
# paged kernel (interpret mode: the real Pallas body on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_paged_kernel_matches_gathered_oracle(interpret, kv):
    B, Hkv, G_, hd = 2, 2, 2, 64
    bs, nmax, N = 8, 4, 10
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, Hkv * G_, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (1, N, bs, Hkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (1, N, bs, Hkv, hd), jnp.float32)
    tables = jnp.asarray([[3, 5, 1, -1], [0, 7, -1, -1]], jnp.int32)
    pos = jnp.asarray([17, 9], jnp.int32)
    ksc = vsc = None
    if kv == "int8":
        kp, ksc = da.quantize_kv(kp)
        vp, vsc = da.quantize_kv(vp)
    kp, vp = (x.reshape(1, N, bs, Hkv * hd) for x in (kp, vp))
    out = da.paged_decode_attention(q, kp, vp, tables, pos, 0,
                                    k_scale=ksc, v_scale=vsc)
    ref = da._xla_paged(q, kp, vp, tables, pos, 0, ksc, vsc, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("scenario",
                         ["hd64", "decode", "verify", "prefix", "cow", "mp2"])
def test_paged_kernel_route_greedy_tokens(interpret, kv_env, monkeypatch,
                                          scenario):
    """Through the server: the paged KERNEL route (rows scattered into the
    whole leaf, then the kernel reading it at the layer's number) yields
    the same greedy tokens as the slab's kernel route — through plain
    decode, speculation's verify step (Tq = K), an adopted prefix and a
    copy-on-write, three layers deep.  ``hd64`` is off the kernel's gate:
    the same servers through the gather-einsum route.  ``mp2`` shards the
    pool's heads over two devices: each shard's kernel is handed its own
    contiguous half of every row's lanes."""
    hd = 64 if scenario == "hd64" else 128
    cfg = _cfg(hidden_size=2 * hd, num_heads=2, vocab_size=16, num_layers=3)
    params = gpt.init_params(cfg, jax.random.PRNGKey(1))
    calls = []
    real = da._paged_call
    monkeypatch.setattr(
        da, "_paged_call",
        lambda *a, **k: calls.append(a[1].shape) or real(*a, **k))
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(1, 15, 10)), list(rng.integers(1, 15, 5))]
    kw = {}
    if scenario == "verify":
        kw = {"spec_k": 3}
    elif scenario == "prefix":
        shared = list(rng.integers(1, 15, 8))
        prompts = [shared + [1, 5], shared + [2], prompts[1]]
    elif scenario == "cow":
        prompts = [list(rng.integers(1, 15, 16))] * 2
    ref, _ = _serve(params, cfg, prompts, "contiguous", max_new=5, **kw)
    if scenario == "mp2":
        from jax.sharding import Mesh

        kw = {"mesh": Mesh(np.array(jax.devices()[:2]), ("mp",))}
    got, stats = _serve(params, cfg, prompts, "paged", max_new=5,
                        block_size=8, **kw)
    assert got == ref
    assert bool(calls) == (scenario != "hd64")
    if calls:
        lanes = cfg.kv_heads * hd // (2 if scenario == "mp2" else 1)
        assert set(calls) == {(3, 2 * 4, 8, lanes)}
    if scenario == "prefix":
        assert stats["prefix_hits"] > 0
    if scenario == "cow":
        assert stats["cow_copies"] >= 1


def _written_rows(tables, pos, n, bs):
    """[N, bs] bool: the physical rows a step writes, slot b's ``n`` rows
    from ``pos[b]`` on through its table row."""
    mask = np.zeros((int(tables.max()) + 1, bs), bool)
    for b, p0 in enumerate(pos):
        for t in range(int(p0), int(p0) + n):
            mask[tables[b, t // bs], t % bs] = True
    return mask


@pytest.mark.parametrize("step,route,kv", [
    ("decode", "einsum", "fp32"), ("decode", "kernel", "fp32"),
    ("decode", "einsum", "int8"), ("decode", "kernel", "int8"),
    ("verify", "einsum", "fp32"), ("verify", "kernel", "fp32"),
    ("verify", "kernel", "int8"), ("tree", "einsum", "fp32"),
    ("prefill", "einsum", "fp32"), ("prefill", "einsum", "int8")])
def test_paged_step_writes_its_rows_and_no_other(interpret, kv_env, step,
                                                 route, kv):
    """One step over a pool of three layers: in every layer the rows the
    step writes are new, each layer's its own, and every other row of
    every leaf — the other pages of the layer, the same pages of the
    other layers — reads as before, to the bit.  The kernel route writes
    what the gather-einsum route writes."""
    kv_env(PADDLE_TPU_KV_DTYPE=None if kv == "fp32" else kv)
    cfg = _cfg(hidden_size=256, num_heads=2, num_layers=3, vocab_size=32)
    params = gpt.init_params(cfg, jax.random.PRNGKey(2))
    B, bs, K = 2, 8, 4
    old = da.random_filled_cache(
        G.init_cache(cfg, B, 64, layout="paged", block_size=bs),
        jax.random.PRNGKey(3), amp=0.3)
    assert old["k"].shape == (3, B * 8, bs, 2 * 128)
    pos = jnp.asarray([19, 42], jnp.int32)
    tok = jnp.asarray([[3, 7, 1, 9], [5, 2, 8, 4]], jnp.int32)

    def run():
        if step == "decode":
            return kv_pool.paged_decode_step_batched(params, old, tok[:, 0],
                                                     pos, cfg)
        if step == "verify":
            return kv_pool.paged_verify_chunk_batched(params, old, tok, pos,
                                                      cfg)
        if step == "tree":
            chain = jnp.broadcast_to(jnp.tril(jnp.ones((K, K), bool)),
                                     (B, K, K))
            depth = jnp.broadcast_to(jnp.arange(K), (B, K))
            return kv_pool.paged_tree_verify_chunk_batched(
                params, old, tok, chain, depth, pos, cfg)
        return kv_pool.paged_prefill_chunk(
            params, old, tok[1:], pos[1], jnp.asarray(K), jnp.asarray(1),
            cfg)

    calls = []
    real = da._paged_call
    da._paged_call = lambda *a, **k: calls.append(1) or real(*a, **k)
    try:
        kv_env(PADDLE_TPU_FLASH_DECODE="1" if route == "kernel" else "0")
        logits, new = run()
        assert bool(calls) == (route == "kernel")
        if route == "kernel":
            kv_env(PADDLE_TPU_FLASH_DECODE="0")
            want_logits, want = run()
    finally:
        da._paged_call = real
    tables = np.asarray(old["tables"])
    if step == "prefill":
        mask = _written_rows(tables[1:], np.asarray(pos[1:]), K, bs)
    else:
        mask = _written_rows(tables, np.asarray(pos),
                             1 if step == "decode" else K, bs)
    mask = np.pad(mask, ((0, old["k"].shape[1] - mask.shape[0]), (0, 0)))
    for name in kv_pool.POOL_LEAVES:
        if name not in old:
            continue
        before, after = np.asarray(old[name]), np.asarray(new[name])
        np.testing.assert_array_equal(after[:, ~mask], before[:, ~mask])
        for li in range(3):
            assert (after[li][mask] != before[li][mask]).any(), (name, li)
        assert (after[0][mask] != after[1][mask]).any()
        assert (after[1][mask] != after[2][mask]).any()
        if route == "kernel":
            # the first layer's rows see no attention: the same to the
            # bit; deeper ones to the storage dtype's rounding
            got, ref = (np.asarray(x).astype(np.float32)
                        for x in (after, want[name]))
            np.testing.assert_array_equal(got[0], ref[0])
            np.testing.assert_allclose(
                got, ref,
                atol=2 if name in ("k", "v") and kv == "int8" else 2e-2)
    if route == "kernel":
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(want_logits), atol=3e-2,
                                   rtol=3e-2)


# ---------------------------------------------------------------------------
# lint: every allocator mutation path counts a telemetry counter
# ---------------------------------------------------------------------------


def test_check_instrumented_kv_rule_catches_silent_alloc():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import check_instrumented as ci

    bad = ("class P:\n"
           "    def alloc_block(self):\n"
           "        return self.free.pop()\n")
    assert ci.scan_kv_pool_source(bad)
    good = ("class P:\n"
            "    def alloc_block(self):\n"
            "        count('kv_pool.blocks_allocated')\n"
            "        return self.free.pop()\n"
            "    def free_slot(self):\n"
            "        self.alloc_block()\n")
    assert not ci.scan_kv_pool_source(good)


def test_check_instrumented_repo_clean():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import check_instrumented as ci

    assert ci.scan_repo() == []


# ---------------------------------------------------------------------------
# radix tree: token-granular splits + host-RAM spill tier (round 16)
# ---------------------------------------------------------------------------


def test_radix_split_adopts_mid_block_and_evicts_cleanly():
    """A prompt diverging MID-BLOCK splits the node WITHOUT a device
    copy: both halves share the physical block (the shared rows are
    bit-identical by the chain invariant), the adopter maps the split
    node's block, and evict-all drains the shared-block chain with no
    orphaned children or leaked refs."""
    bs = 8
    a = kv_pool.PagedAllocator(num_blocks=8, block_size=bs, nmax=4,
                               max_batch=2)
    prompt = list(range(20))           # blocks 0,1 full; 4-row tail
    a.ensure_rows(0, 0, 20)
    a.register_prefix(0, prompt)
    a.free_slot(0)
    other = prompt[:12] + [99] * 8     # diverges INSIDE block 1
    shared = a.adopt_prefix(1, other)
    assert shared == 12                # token-granular, not block-granular
    assert a.radix_splits == 1
    assert a.prefix_entries == 3       # block0, split node S, re-keyed X
    # S and X share ONE physical block: no copy was queued by the split
    assert a.take_copies() == []
    blocks = [e.block for e in a._prefix.values()]
    assert len(blocks) == 3 and len(set(blocks)) == 2
    # the adopter's first write into the shared block COWs as usual
    # (admission prefills from the adopted offset, not row 0)
    a.ensure_rows(1, 12, 20)
    assert a.cow_copies == 1
    a.register_prefix(1, other)
    # evict-all: the ref==entries-per-block rule must drain split-shared
    # blocks too (a plain ref==1 candidate rule would pin them forever)
    a.free_slot(1)
    for _ in range(16):
        if not a.prefix_entries:
            break
        a.evict_cold()
    assert a.prefix_entries == 0
    assert a.blocks_in_use == 0
    assert not a._children
    assert not a._blk_ents.any()


def test_spill_restore_allocator_roundtrip(kv_env):
    """Allocator-level spill->restore: cold block-aligned chains demote
    leaf-first to host records, adoption restores them block-by-block,
    and the queued restore rows are bit-identical to what was fetched
    at spill time."""
    kv_env(PADDLE_TPU_KV_SPILL_MB="4")
    bs = 8
    a = kv_pool.PagedAllocator(num_blocks=8, block_size=bs, nmax=4,
                               max_batch=2)
    prompt = list(range(24))           # 3 full blocks, aligned
    a.ensure_rows(0, 0, 24)
    a.register_prefix(0, prompt)
    chain = [int(a.tables[0, i]) for i in range(3)]
    a.free_slot(0)

    def fetch(blocks):
        # per-block marker rows: leaf [L=2, P, bs, 1] stamped with the
        # physical block id, so restore content is attributable
        return {"k": np.stack(
            [np.full((2, bs, 1), float(b), np.float32)
             for b in blocks], axis=1)}

    for _ in range(8):
        if not a.prefix_entries:
            break
        a.spill_cold(8, fetch=fetch)
    assert a.spilled_blocks == 3
    assert len(a._spilled) == 3
    assert a.blocks_in_use == 0
    assert a.host_spill_bytes > 0
    shared = a.adopt_prefix(1, prompt)
    assert shared == 23                # full chain restored, capped n-1
    assert a.restored_blocks == 3
    recs = a.take_restores()
    assert [r[1] for r in recs] == [0, 8, 16]   # contiguous starts
    for pos, (slot, start, rows, blk) in enumerate(recs):
        assert slot == 1
        # the restored rows carry the marker of the ORIGINAL physical
        # block that held this chain position at spill time
        assert float(rows["k"][0, 0, 0]) == float(chain[pos])
    assert a.host_spill_bytes == 0
    assert not a._spilled
    a.take_restores()                  # drained: second take is empty
    assert a.take_restores() == []


def test_rss_watchdog_releases_oldest_spills_then_evicts(kv_env):
    """``PADDLE_TPU_KV_SPILL_RSS_MB``: over the threshold one watchdog
    round releases host-spilled chains OLDEST-first, then cold index
    leaves through the evict-cold LRU rung — bounded by spill_batch and
    counted in ``kv_pool.rss_spills``; at or under the threshold it is
    a no-op."""
    kv_env(PADDLE_TPU_KV_SPILL_MB="4", PADDLE_TPU_KV_SPILL_RSS_MB="1")
    bs = 8
    a = kv_pool.PagedAllocator(num_blocks=8, block_size=bs, nmax=4,
                               max_batch=2)
    a.ensure_rows(0, 0, 24)
    a.register_prefix(0, list(range(24)))
    a.free_slot(0)

    def fetch(blocks):
        return {"k": np.stack(
            [np.full((2, bs, 1), float(b), np.float32)
             for b in blocks], axis=1)}

    for _ in range(8):
        if not a.prefix_entries:
            break
        a.spill_cold(8, fetch=fetch)
    assert len(a._spilled) == 3 and a.host_spill_bytes > 0
    # at/under threshold (1 MiB): strictly a no-op
    assert a.rss_watchdog(rss_bytes=1 << 20) == 0
    assert len(a._spilled) == 3 and a.rss_spills == 0
    # a fresh cold chain gives the second rung an index leaf to demote
    a.ensure_rows(0, 0, 8)
    a.register_prefix(0, list(range(100, 108)))
    a.free_slot(0)
    freed = a.rss_watchdog(rss_bytes=2 << 20)
    assert freed == 4                  # 3 spilled records + 1 cold leaf
    assert not a._spilled and a.host_spill_bytes == 0
    assert a.prefix_entries == 0
    assert a.rss_spills == 4
    # pressure relieved -> armed but quiet
    assert a.rss_watchdog(rss_bytes=2 << 20) == 0
    assert a.rss_spills == 4


def test_rss_watchdog_rides_the_scheduler_tick(kv_env, markov_gpt):
    """Serving-level: with the RSS flag set to 1 MiB (any real process
    is over it) idle scheduler ticks engage the watchdog every 16th
    tick and drain the retired request's cold prefix chain — no spill
    tier needed (the evict-cold rung alone relieves pressure)."""
    kv_env(PADDLE_TPU_KV_SPILL_RSS_MB="1")
    cfg, params = markov_gpt
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                               layout="paged", block_size=8)
    prompt = [int(x) for x in np.random.default_rng(3).integers(0, 13, 16)]
    rid = srv.submit(prompt, max_new_tokens=4)
    while srv.pending():
        srv.tick()
    assert len(srv.result(rid)) == 4
    assert srv._pool.prefix_entries > 0
    for _ in range(64):                # idle ticks: cadence is 1-in-16
        srv.tick()
    assert srv._pool.prefix_entries == 0
    assert srv._pool.rss_spills > 0
    srv.close()


@pytest.mark.parametrize("kv", ["fp32", "int8"])
@pytest.mark.parametrize("mode", ["tick", "async"])
def test_spill_restore_bit_parity(kv_env, kv, mode, markov_gpt):
    """Serving-level spill->restore cycle: demote a retired prompt's
    whole chain to host RAM, re-serve the prompt — greedy tokens stay
    bit-identical to the cold pass and the contiguous slab, and >= 90%
    of the re-prefill rows come back from host RAM instead of
    recompute.  {fp32, int8 KV} x {tick, async}."""
    kv_env(PADDLE_TPU_KV_DTYPE=None if kv == "fp32" else kv,
           PADDLE_TPU_KV_SPILL_MB="4")
    cfg, params = markov_gpt
    prompt = [int(x) for x in
              np.random.default_rng(9).integers(0, 13, 16)]
    async_ = mode == "async"
    ref, _ = _serve(params, cfg, [prompt], "contiguous", async_=async_)
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                               layout="paged", block_size=8,
                               async_dispatch=async_)
    r0 = srv.submit(prompt, max_new_tokens=6)
    while srv.pending():
        srv.tick()
    cold = srv.result(r0)
    for _ in range(8):                 # demote the whole cold chain
        if not srv._pool.prefix_entries:
            break
        srv._evict_or_spill(8)
    assert srv._pool.spilled_blocks >= 2
    hits0 = srv._pool.prefix_hits
    r1 = srv.submit(prompt, max_new_tokens=6)
    while srv.pending():
        srv.tick()
    warm = srv.result(r1)
    saved = srv._pool.prefix_hits - hits0
    stats = srv._pool.stats()
    srv.close()
    assert warm == cold == ref[0]
    assert stats["restored_blocks"] >= 2
    assert saved >= 0.9 * (len(prompt) - 1)


def test_radix_beats_block_matching_and_spill_cycles_add_no_executable(
        kv_env):
    """A 20-token preamble over 8-token blocks diverges MID-BLOCK: whole
    blocks (``PADDLE_TPU_KV_RADIX=0``) can share 16 tokens, the radix
    split all 20, so its prefix hit rate is strictly higher, with tokens
    equal to the slab's in both arms.  Then two spill -> restore cycles
    of one prompt on one server: the same tokens each time, 90% of the
    re-prefill rows adopted from restored blocks, and the second cycle
    adds no step-cache key (restoring goes through executables the first
    cycle already built)."""
    cfg = _cfg(vocab_size=128, hidden_size=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    pre = [int(x) for x in rng.integers(1, 100, 20)]
    prompts = [pre + [int(x) for x in rng.integers(1, 100, 4)]
               for _ in range(3)]

    def serve(layout, radix):
        kv_env(PADDLE_TPU_KV_RADIX=radix, PADDLE_TPU_KV_SPILL_MB=None)
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=40,
                                   layout=layout, block_size=8)
        toks = []
        for p in prompts:                # one at a time: later ones adopt
            rid = srv.submit(p, max_new_tokens=6)
            while srv.pending():
                srv.tick()
            toks.append(srv.result(rid))
        stats = srv._pool.stats() if srv._pool is not None else None
        srv.close()
        return toks, stats

    def rate(st):
        return st["prefix_hits"] / max(
            1, st["prefix_hits"] + st["prefix_misses"])

    cont, _ = serve("contiguous", "1")
    tok_radix, s_radix = serve("paged", "1")
    tok_block, s_block = serve("paged", "0")
    assert tok_radix == cont and tok_block == cont
    assert s_radix["radix_splits"] >= 1
    assert rate(s_radix) > rate(s_block), (s_radix, s_block)

    kv_env(PADDLE_TPU_KV_RADIX="1", PADDLE_TPU_KV_SPILL_MB="4")
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=40,
                               layout="paged", block_size=8)
    pool, prompt = srv._pool, prompts[0]

    def cycle():
        rid = srv.submit(prompt, max_new_tokens=6)
        while srv.pending():
            srv.tick()
        first = srv.result(rid)
        for _ in range(16):              # demote the whole cold chain
            if not pool._interned:
                break
            srv._evict_or_spill(8)
        hits0 = pool.prefix_hits
        rid = srv.submit(prompt, max_new_tokens=6)
        while srv.pending():
            srv.tick()
        return first, srv.result(rid), pool.prefix_hits - hits0

    first, again, saved = cycle()
    assert first == cont[0] and again == first
    st = pool.stats()
    assert st["spilled_blocks"] >= 1 and st["restored_blocks"] >= 1, st
    assert saved >= 0.9 * (len(prompt) - 1)
    keys0 = set(serving._STEP_CACHE.keys())
    first2, again2, _ = cycle()
    added = set(serving._STEP_CACHE.keys()) - keys0   # before close()
    srv.close()
    assert first2 == first and again2 == first
    assert added == set()


def test_oom_fault_spills_cold_prefix_with_parity(kv_env, markov_gpt):
    """With the spill tier enabled, the OOM chain's first rung DEMOTES
    cold chains instead of dropping them (kv_pool.spilled_blocks
    counted), and the faulted pass still yields bit-identical
    tokens."""
    kv_env(PADDLE_TPU_KV_SPILL_MB="4")
    cfg, params = markov_gpt
    prompt = [int(x) for x in
              np.random.default_rng(5).integers(0, 13, 12)]

    def run(spec):
        faults.reset()
        try:
            srv = serving.DecodeServer(params, cfg, max_batch=2,
                                       max_len=32, layout="paged",
                                       block_size=8)
            r0 = srv.submit(prompt, max_new_tokens=4)
            while srv.pending():
                srv.tick_block(4)
            if spec:
                faults.install(spec)
            r1 = srv.submit([int(x) for x in prompt[::-1][:10]],
                            max_new_tokens=4)
            while srv.pending():
                srv.tick_block(4)
            out = (srv.result(r0), srv.result(r1))
            srv.close()
            return out
        finally:
            faults.reset()

    clean = run("")
    s0 = int(monitor.get_stat("kv_pool.spilled_blocks").get())
    faulted = run("oom:serving.block:1")
    spilled = int(monitor.get_stat("kv_pool.spilled_blocks").get()) - s0
    assert faulted == clean
    assert spilled >= 1


def test_check_instrumented_prefix_rule():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import check_instrumented as ci

    bad = ("class P:\n"
           "    def _split_entry(self, cid, m):\n"
           "        return cid\n")
    assert ci.scan_prefix_cache_source(bad)
    bad2 = ("class R:\n"
            "    def _prefix_route(self, req, cands):\n"
            "        return cands[0]\n")
    assert ci.scan_prefix_cache_source(bad2)
    good = ("class P:\n"
            "    def _split_entry(self, cid, m):\n"
            "        count('kv_pool.radix_splits')\n"
            "        return cid\n"
            "    def spill_cold(self):\n"
            "        self._split_entry(0, 0)\n"
            "    def _restore_spilled(self):\n"
            "        count('kv_pool.restored_blocks')\n")
    assert not ci.scan_prefix_cache_source(good)
