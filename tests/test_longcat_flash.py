"""Latent attention under a shortcut-connected expert layer with
zero-compute experts (LongCat-Flash's layer, one chip's share of it): the
program against ``benchmarks/families/reference_longcat_flash.py`` on
seeded weights, at a small size on the CPU.  Logits, not tokens: with
random weights the largest logit changes on rounding.

The reference is float32 at matmul precision "highest": up-projected
attention over the whole sequence, every held expert on every token.  The
program prefills in chunks (up-projected against the slot's gathered latent
rows), decodes in the absorbed form through the paged latent pool, and
runs the held experts as batched matmuls over all of a step's rows, a
row's score zero for an expert it did not select."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import reference_longcat_flash as ref
from paddle_tpu import telemetry
from paddle_tpu.ops import decode_attention as da
from paddle_tpu.text import engine, fleet, generate, gpt, kv_pool, mla, moe
from paddle_tpu.text import serving

# config.json keys at the small size: 4 heads of 16 + 8 / 16 on a width of
# 128, ranks 32 and 16, 16 routed experts of 64 (4 held) + 8 identity, 4 a token
MODEL = dict(
    hidden_size=128, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, rope_theta=1e7,
    rms_norm_eps=1e-5, routed_scaling_factor=6.0,
    n_routed_experts_published=16, zero_expert_num=8, moe_topk=4,
    held=[0, 4])
ARCH = ref.arch_of(MODEL)
V, T, L, F, FE = 512, 256, 2, 256, 64


def make_cfg(dtype=jnp.float32, held=(0, 4), model=MODEL):
    m = model
    return gpt.GPTConfig(
        vocab_size=V, hidden_size=m["hidden_size"], num_layers=L,
        num_heads=m["num_attention_heads"], intermediate_size=F,
        max_seq_len=T, dtype=dtype, pos_embed="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, bias=False,
        rope_theta=m["rope_theta"],
        mla=mla.MLAConfig(m["q_lora_rank"], m["kv_lora_rank"],
                          m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                          m["v_head_dim"]),
        experts=moe.ExpertShareConfig(
            m["n_routed_experts_published"], m["zero_expert_num"],
            m["moe_topk"], FE, m["routed_scaling_factor"], tuple(held)))


def make_params(cfg, seed=0, router=15.0):
    """``gpt.init_params`` with the router drawn wide (logits of spread 3:
    the selected scores carry weight, so routing counts)."""
    p = gpt.init_params(cfg, jax.random.PRNGKey(seed))
    p["blocks"]["moe"]["router_w"] = p["blocks"]["moe"]["router_w"] * router
    return p


@pytest.fixture(scope="module")
def cfg():
    return make_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return make_params(cfg)


def tokens(seed, n):
    return np.random.default_rng(seed).integers(0, V, (n,)).astype(np.int32)


def cast(params, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


def serve(params, cfg, prompts, max_new=8, max_batch=4, **kw):
    kw.setdefault("layout", "paged")
    kw.setdefault("block_size", 8)
    srv = serving.DecodeServer(params, cfg, max_batch=max_batch, max_len=T,
                               **kw)
    rids = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    while srv.pending():
        srv.tick()
    return srv, [srv.result(r) for r in rids]


def worst_margin(params, prompts, outs, arch=ARCH):
    return max(float(ref.served_margins(params, p, o, arch=arch,
                                        pad_to=T).max())
               for p, o in zip(prompts, outs))


# ---------------------------------------------------------------------------
# the full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_forward_equals_the_reference(cfg, seed):
    params = make_params(cfg, seed)
    toks = np.stack([tokens(seed, 40), tokens(seed + 10, 40)])
    got = gpt.forward(params, jnp.asarray(toks), cfg)
    for b in range(2):
        want = ref.logits(params, toks[b], arch=ARCH)
        np.testing.assert_allclose(got[b], want, atol=5e-6)


def test_forward_bf16_within_the_stated_tolerance(cfg, params):
    """bf16 weights and activations against the float32 reference on the
    same (bf16-rounded) weights: logits of spread 0.25 within 0.03 (a bf16
    step at that size is 0.002; the layer rounds some ten times)."""
    pb = cast(params, jnp.bfloat16)
    toks = tokens(3, 48)
    got = gpt.forward(pb, jnp.asarray(toks)[None],
                      make_cfg(jnp.bfloat16))[0].astype(jnp.float32)
    want = ref.logits(pb, toks, arch=ARCH)
    assert float(jnp.max(jnp.abs(got - want))) < 0.03
    assert float(jnp.std(want)) > 0.1


# ---------------------------------------------------------------------------
# prefill in chunks, then decode, through the paged latent cache
# ---------------------------------------------------------------------------


def pool_with_slot(cfg, slot=1, batch=3, block=8, blocks=40):
    cache = kv_pool.init_paged_cache(cfg, batch, T, block_size=block,
                                     num_blocks=blocks)
    nmax = cache["tables"].shape[1]
    tables = np.full((batch, nmax), -1, np.int32)
    tables[slot, :blocks - 5] = np.arange(5, blocks)[:nmax]
    return dict(cache, tables=jnp.asarray(tables),
                live=jnp.arange(batch) == slot)


def prefill(params, cfg, cache, seq, pos0, n, slot=1, width=16):
    chunk = np.zeros((1, width), np.int32)
    chunk[0, :n] = seq[pos0:pos0 + n]
    return kv_pool.paged_prefill_chunk(
        params, cache, jnp.asarray(chunk), jnp.asarray(pos0),
        jnp.asarray(n), jnp.asarray(slot), cfg)


def decode(params, cfg, cache, tok, pos, slot=1, batch=3):
    t = np.zeros((batch,), np.int32)
    p = np.zeros((batch,), np.int32)
    t[slot], p[slot] = tok, pos
    logits, cache = kv_pool.paged_decode_step_batched(
        params, cache, jnp.asarray(t), jnp.asarray(p), cfg)
    return logits[slot], cache


@pytest.mark.parametrize("n", [7, 8, 9, 15, 16, 17, 31, 33])
def test_prefill_then_decode_equals_the_full_forward(cfg, params, n):
    """Prompts around block (8), chunk and bucket (16) edges, prefilled in
    chunks of 16 (the last one padded), then decoded token by token: every
    position's logits are the reference's full forward's."""
    seq = tokens(n, n + 6)
    want = ref.logits(params, seq, arch=ARCH)
    cache = pool_with_slot(cfg)
    for pos0 in range(0, n, 16):
        logits, cache = prefill(params, cfg, cache, seq, pos0,
                                min(16, n - pos0))
    np.testing.assert_allclose(logits, want[n - 1], atol=5e-6)
    for i in range(n, n + 6):
        logits, cache = decode(params, cfg, cache, seq[i], i)
        np.testing.assert_allclose(logits, want[i], atol=5e-6)


def test_a_buckets_padding_selects_no_expert_and_writes_no_row(cfg, params):
    seq = tokens(5, 11)
    cache = pool_with_slot(cfg)
    _, after = prefill(params, cfg, cache, seq, 0, 11)
    rows = np.asarray(after[kv_pool.LATENT], np.float32)
    # the slot's blocks are 5, 6: rows 0..10 written in every sublayer,
    # 11..15 (the padding) and every other block untouched
    written = np.abs(rows).sum(-1) > 0                     # [2L, N, bs]
    assert written[:, 5].all() and written[:, 6, :3].all()
    assert not written[:, 6, 3:].any()
    written[:, 5:7] = False
    assert not written.any()
    # the lanes past the row's 24 values stay zero
    assert not rows[..., cfg.mla.row_width:].any()
    # what the padding holds changes nothing
    other = seq.copy()
    chunk = np.zeros((1, 16), np.int32)
    chunk[0, :11], chunk[0, 11:] = other, 77
    logits2, _ = kv_pool.paged_prefill_chunk(
        params, cache, jnp.asarray(chunk), jnp.asarray(0), jnp.asarray(11),
        jnp.asarray(1), cfg)
    logits1, _ = prefill(params, cfg, cache, seq, 0, 11)
    np.testing.assert_array_equal(logits1, logits2)


def test_absorbed_decode_equals_up_projected_prefill_on_the_same_rows(
        cfg, params):
    """The last position of a prompt, once as the last query of a prefill
    chunk (keys and values up-projected from the rows) and once as a
    decode step over the rows the earlier positions left (``Wkvb`` absorbed
    into the query and applied to the weighted sum)."""
    seq = tokens(9, 21)
    up, _ = prefill(params, cfg, pool_with_slot(cfg), seq, 0, 21, width=32)
    _, cache = prefill(params, cfg, pool_with_slot(cfg), seq, 0, 20,
                       width=32)
    absorbed, _ = decode(params, cfg, cache, seq[20], 20)
    np.testing.assert_allclose(absorbed, up, atol=5e-6)


def test_absorbed_attention_equals_up_projected_attention(cfg, params):
    """The two forms on one sublayer's weights and one set of rows."""
    p = moe.layer_of(params["blocks"], 0)["attn0"]
    n = jax.random.normal(jax.random.PRNGKey(4), (12, cfg.hidden_size))
    q_nope, q_rope, rows = mla.project(n, p, cfg, jnp.arange(12))
    up = mla.attend_chunk(q_nope, q_rope, rows, 0, p, cfg)[-1]
    pool = jnp.pad(rows, ((0, 4), (0, 128 - rows.shape[1]))).reshape(
        1, 2, 8, 128)
    q_lat = mla.absorb_q(q_nope[-1:], q_rope[-1:], p, cfg)
    lat = da.paged_decode_attention(
        jnp.pad(q_lat, ((0, 0), (0, 0), (0, 128 - q_lat.shape[-1])))[:, None],
        pool, None, jnp.asarray([[0, 1]]), jnp.asarray([11]), 0,
        scale=1.0 / np.sqrt(cfg.mla.qk_head_dim),
        v_width=cfg.mla.kv_lora_rank)
    np.testing.assert_allclose(mla.absorb_out(lat[:, 0], p, cfg)[0], up,
                               atol=2e-6)


def test_paged_kernel_reads_a_shared_row(monkeypatch):
    """The paged kernel (interpret mode) on a pool of one shared row a
    token, its first lanes the value, against the gathered-view reference:
    live pages only, unmapped entries and free slots untouched."""
    B, H, hd, vd, bs, N = 3, 4, 256, 128, 8, 12
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.random.normal(k[0], (2, N, bs, hd), jnp.float32)
    q = jax.random.normal(k[1], (B, 1, H, hd), jnp.float32)
    tables = jnp.asarray([[3, 7, 1, -1], [-1, -1, -1, -1], [9, 2, -1, -1]])
    pos = jnp.asarray([19, 0, 8])
    assert da.paged_supported(q.shape, pool.shape, vd)
    assert not da.paged_supported(q.shape, pool.shape, 16)
    monkeypatch.setattr(da, "_INTERPRET", True)
    got = da.paged_decode_attention(q, pool, None, tables, pos, 1,
                                    scale=0.07, v_width=vd)
    want = da._xla_paged(q, pool, None, tables, pos, jnp.asarray(1), None,
                         None, 0.07, vd)
    assert got.shape == (B, 1, H, vd)
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[2], want[2], atol=2e-5)
    assert not np.asarray(got[1]).any()          # a free slot attends nothing


# ---------------------------------------------------------------------------
# the expert share: no token dropped, static shapes, shares add up
# ---------------------------------------------------------------------------


def steer(params, experts, bias=50.0):
    """``params`` whose selection bias sends every token's top 4 to
    ``experts`` (the bias chooses only: the scores stay the softmax's)."""
    b = np.zeros((L, 24), np.float32)
    b[:, list(experts)] = bias
    out = dict(params, blocks=dict(params["blocks"]))
    out["blocks"]["moe"] = dict(params["blocks"]["moe"],
                                router_b=jnp.asarray(b))
    return out


@pytest.mark.parametrize("where,experts", [
    ("all four selections of every token on the four held experts",
     (0, 1, 2, 3)),
    ("none here: all on other chips' experts", (8, 9, 10, 11)),
    ("none here: all on identity experts", (16, 17, 18, 19)),
    ("one held, one absent, two identity", (2, 12, 17, 23)),
])
def test_no_token_is_dropped_for_any_routing(cfg, params, where, experts):
    steered = steer(params, experts)
    seq = tokens(2, 30)
    want = ref.logits(steered, seq, arch=ARCH)
    got = gpt.forward(steered, jnp.asarray(seq)[None], cfg)[0]
    np.testing.assert_allclose(got, want, atol=5e-6)
    # and through the cache: a chunk, then steps
    cache = pool_with_slot(cfg)
    logits, cache = prefill(params=steered, cfg=cfg, cache=cache, seq=seq,
                            pos0=0, n=16)
    np.testing.assert_allclose(logits, want[15], atol=5e-6)
    for i in range(16, 20):
        logits, cache = decode(steered, cfg, cache, seq[i], i)
        np.testing.assert_allclose(logits, want[i], atol=5e-6)
    held = sum(e < 4 for e in experts)
    zero = sum(e >= 16 for e in experts)
    counts = np.asarray(cache[kv_pool.COUNTS])
    # 4 decode steps x 2 layers x one live slot x 4 selections
    assert list(counts[:3]) == [8 * held, 8 * zero, 8 * (4 - held - zero)]
    assert counts[3] == 8 * held and counts[4] == 8


def test_the_steps_shapes_do_not_depend_on_the_routing(cfg, params):
    step = jax.jit(lambda p, c, t, q: kv_pool.paged_decode_step_batched(
        p, c, t, q, cfg))
    cache = pool_with_slot(cfg)
    tok = jnp.asarray([0, 5, 0])
    for experts in ((0, 1, 2, 3), (8, 9, 10, 11), (16, 17, 18, 19)):
        step(steer(params, experts), cache, tok, jnp.asarray([0, 3, 0]))
    assert step._cache_size() == 1


def test_a_free_or_admitting_slot_changes_no_other_slots_result(cfg, params):
    seq = tokens(6, 12)
    _, cache = prefill(params, cfg, pool_with_slot(cfg), seq, 0, 11)
    alone, _ = decode(params, cfg, cache, seq[11], 11)
    # slot 0 mid-admission and slot 2 free feed other tokens at other
    # positions, steered or not: slot 1 reads the same bits, and they
    # count nowhere
    t = jnp.asarray([400, seq[11], 33])
    p = jnp.asarray([5, 11, 0])
    logits, after = kv_pool.paged_decode_step_batched(params, cache, t, p,
                                                      cfg)
    np.testing.assert_array_equal(logits[1], alone)
    assert int(after[kv_pool.COUNTS][:3].sum()) == 2 * 4


def test_the_shares_add_up_to_the_uncut_layer(cfg):
    """Every share ``held = [4j, 4j + 4)`` of the 16 routed experts: the
    routed parts summed over the shares, plus the identity part and the
    dense path counted once, are the uncut reference's layer."""
    whole_cfg = make_cfg(held=(0, 16))
    whole = make_params(whole_cfg, 3)
    h = jax.random.normal(jax.random.PRNGKey(8), (20, cfg.hidden_size))
    arch = ref.arch_of(dict(MODEL, held=[0, 16]))
    p0 = moe.layer_of(whole["blocks"], 0)
    want = ref.layer(h, p0, arch=arch)

    pos = jnp.arange(20)

    def attend(i, n, p_i):
        q_nope, q_rope, rows = mla.project(n, p_i, whole_cfg, pos)
        return mla.out_proj(mla.attend_chunk(q_nope, q_rope, rows, 0, p_i,
                                             whole_cfg), p_i, whole_cfg)

    def layer_with(moe_p, c):
        return gpt.latent_block(h, dict(p0, moe=moe_p), c, attend)[0]

    def share_of(j, zero_down=False):
        m = dict(p0["moe"])
        for name in ("gate_w", "up_w", "down_w"):
            m[name] = m[name][4 * j:4 * j + 4]
        if zero_down:
            m["down_w"] = jnp.zeros_like(m["down_w"])
        return m

    # what every chip computes alike (the dense path and the identity
    # experts' term): a share whose experts give nothing
    alike = layer_with(share_of(0, zero_down=True), make_cfg(held=(0, 4)))
    total = alike
    for j in range(4):
        c = make_cfg(held=(4 * j, 4 * j + 4))
        total = total + layer_with(share_of(j), c) - alike
    np.testing.assert_allclose(total, want, atol=5e-6)
    # and a share alone is not the layer: the routed part counts
    assert float(jnp.max(jnp.abs(alike - want))) > 1e-2


def test_router_weighs_what_it_selects(cfg, params):
    """At the tests' router a token's selected scores sum to a large share
    of 1 (so leaving the expert layer out cannot pass a margin)."""
    m = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))
    p = moe.layer_of(params["blocks"], 0)["moe"]
    idx, w = moe.route_share(m, p, cfg.experts)
    assert idx.shape == (64, 4) and float(w.sum(-1).mean()) > 0.3
    assert (np.sort(np.asarray(idx), -1)[:, 1:]
            != np.sort(np.asarray(idx), -1)[:, :-1]).all()


# ---------------------------------------------------------------------------
# served: DecodeServer, the plain step kinds
# ---------------------------------------------------------------------------


def test_served_tokens_are_the_references_argmax(cfg, params):
    prompts = [tokens(20 + n, n) for n in (5, 17, 33, 9, 40, 12)]
    srv, outs = serve(params, cfg, prompts, max_new=10, async_dispatch=True)
    assert worst_margin(params, prompts, outs) < 1e-5
    stats = srv.load_stats()
    pairs = (stats["moe_pairs_held"] + stats["moe_pairs_zero"]
             + stats["moe_pairs_absent"])
    assert pairs > 0 and pairs % (4 * L) == 0
    assert 0 < stats["moe_experts_hit"] <= 4
    srv.close()


def test_counts_are_drained_before_an_int32_can_wrap(cfg, params):
    """The device-side counts are int32: tick() fetches and zeroes them
    every ``_share_drain_every`` ticks (a step adds at most max_batch *
    top_k * layers to one), and the totals are those of a server that
    drained once, at the end."""
    prompts = [tokens(70 + n, n) for n in (7, 21, 12)]
    once, want = serve(params, cfg, prompts, async_dispatch=True)
    assert once._share_drain_every == (1 << 28) // (
        4 * cfg.experts.top_k * L)
    srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=T,
                               layout="paged", block_size=8,
                               async_dispatch=True)
    srv._share_drain_every = 3
    rids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    drains = 0
    while srv.pending():
        srv.tick()
        drains += srv._share_ticks == 0
    assert drains >= 2 and [srv.result(r) for r in rids] == want
    a, b = once.load_stats(), srv.load_stats()
    assert a["moe_pairs_zero"] > 0
    assert ({k: v for k, v in a.items() if k.startswith("moe_")}
            == {k: v for k, v in b.items() if k.startswith("moe_")})
    once.close()
    srv.close()


def test_async_and_sync_serve_the_same_tokens(cfg, params):
    prompts = [tokens(40 + n, n) for n in (6, 23, 31, 14, 19)]
    _, a = serve(params, cfg, prompts, max_batch=2, async_dispatch=True)
    _, s = serve(params, cfg, prompts, max_batch=2, async_dispatch=False)
    assert a == s


def test_served_in_bf16_within_the_stated_tolerance(cfg, params):
    """bf16 end to end (weights, activations, latent rows) against the
    float32 reference on the same weights: a served token within 0.05 of
    the reference's best logit (logits of spread 0.25)."""
    pb = cast(params, jnp.bfloat16)
    prompts = [tokens(60 + n, n) for n in (12, 30, 21)]
    srv, outs = serve(pb, make_cfg(jnp.bfloat16), prompts, max_new=12)
    assert srv.cache[kv_pool.LATENT].dtype == jnp.bfloat16
    assert worst_margin(pb, prompts, outs) < 0.05


def test_prefix_adoption_and_copy_on_write_of_latent_blocks(cfg, params):
    """Two prompts share 20 tokens (two whole blocks and half a third): the
    second adopts the first's blocks through its table and copies the
    third on its first write; both are served as if alone."""
    telemetry.reset()
    head = tokens(70, 20)
    prompts = [np.concatenate([head, tokens(71, 9)]),
               np.concatenate([head, tokens(72, 5)])]
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=T,
                               layout="paged", block_size=8)
    outs = []
    for p in prompts:                   # one after the other: the second
        rid = srv.submit(p, max_new_tokens=8)   # finds the first indexed
        while srv.pending():
            srv.tick()
        outs.append(srv.result(rid))
    assert worst_margin(params, prompts, outs) < 1e-5
    counters = telemetry.snapshot()["counters"]
    assert srv._pool.prefix_hits >= 1
    assert counters.get("kv_pool.cow_copies", 0) >= 1
    # 29 + 25 prompt rows, of which the second recomputed its last 5 only
    assert counters["kv_pool.prefill_rows"] < 29 + 25 - 15


def test_counts_and_gauges_reach_telemetry(cfg, params):
    telemetry.reset()
    srv, outs = serve(params, cfg, [tokens(80, 10), tokens(81, 14)],
                      max_new=6)
    srv.close()
    snap = telemetry.snapshot()
    c = snap["counters"]
    pairs = sum(c.get("moe.pairs_" + k, 0)
                for k in ("held", "zero", "absent"))
    # every decode step of a live slot: 4 selections in each of 2 layers
    assert pairs >= 2 * 5 * 4 * L and pairs % (4 * L) == 0
    assert 0 < snap["gauges"]["moe.experts_hit"] <= 4
    assert snap["gauges"]["kv_pool.latent_row_bytes"] == 2 * L * 128 * 4


# ---------------------------------------------------------------------------
# the published shapes
# ---------------------------------------------------------------------------


def published(held=(0, 16), layers=4, vocab=16384):
    return gpt.GPTConfig(
        vocab_size=vocab, hidden_size=6144, num_layers=layers, num_heads=64,
        intermediate_size=12288, max_seq_len=131072, dtype=jnp.bfloat16,
        pos_embed="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=False, bias=False, rope_theta=1e7,
        mla=mla.MLAConfig(1536, 512, 128, 64, 128),
        experts=moe.ExpertShareConfig(512, 256, 12, 2048, 6.0, held))


def test_parameter_counts_at_the_published_shapes():
    cfg = published()
    assert mla.count_params(cfg.mla, 6144, 64) == 90_572_800
    router, expert = moe.count_expert_share(cfg.experts, 6144)
    assert (router, expert) == (4_719_360, 37_748_736)
    outside = (2 * 90_572_800 + 2 * 226_492_416 + 24_576 + router)
    assert outside == 638_874_368
    assert gpt.count_params(cfg) == 5_172_749_312
    shapes = jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes)) == 5_172_749_312
    # the whole model's layer: all 512 experts
    whole = published(held=(0, 512), layers=28, vocab=131072)
    assert gpt.count_params(whole) == 28 * (
        638_874_368 + 512 * 37_748_736) + 2 * 131072 * 6144 + 6144


def test_cache_bytes_a_token_at_the_published_shapes():
    cfg = published()
    cache = jax.eval_shape(lambda: kv_pool.init_paged_cache(
        cfg, 256, 4096, block_size=16, num_blocks=20480))
    leaf = cache[kv_pool.LATENT]
    assert leaf.shape == (8, 20480, 16, 640) and leaf.dtype == jnp.bfloat16
    assert set(cache) == {kv_pool.LATENT, "tables", kv_pool.LIVE,
                          kv_pool.COUNTS}
    # 576 values a sublayer as published, in 640 lanes as stored
    assert 8 * cfg.mla.row_width * 2 == 9_216
    assert leaf.shape[0] * leaf.shape[3] * 2 == 10_240
    assert kv_pool.latent_lanes(cfg) == 640
    assert cache["tables"].shape == (256, 256)


# ---------------------------------------------------------------------------
# what cannot work yet says so at construction
# ---------------------------------------------------------------------------


def server(params, cfg, **kw):
    kw.setdefault("layout", "paged")
    return serving.DecodeServer(params, cfg, max_batch=2, max_len=T,
                                block_size=8, **kw)


@pytest.mark.parametrize("kw,word", [
    ({"layout": "contiguous"}, "contiguous"),
    ({"spec_k": 3}, "speculation"),
    ({"spec_tree": 4}, "speculation"),
    ({"draft_cfg": "cfg", "draft_params": "params"}, "speculation"),
    ({"adapter_pool": object()}, "adapter_pool"),
    ({"mesh": "mesh"}, "no ep exchange"),
])
def test_construction_refuses(cfg, params, kw, word):
    kw = {k: {"cfg": cfg, "params": params}.get(v, v) if isinstance(v, str)
          and k != "layout" else v for k, v in kw.items()}
    if "mesh" in kw:
        kw["mesh"] = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))
    with pytest.raises(NotImplementedError, match=word):
        server(params, cfg, **kw)


@pytest.mark.parametrize("name,value,word", [
    ("PADDLE_TPU_KV_SPILL_MB", "4", "spill"),
    ("PADDLE_TPU_SPEC_K", "3", "speculation"),
    ("PADDLE_TPU_KV_DTYPE", "int8", "int8"),
])
def test_environment_refuses(cfg, params, monkeypatch, name, value, word):
    monkeypatch.setenv(name, value)
    with pytest.raises(NotImplementedError, match=word):
        server(params, cfg)


def test_handoff_and_other_paths_refuse(cfg, params):
    srv = server(params, cfg)
    with pytest.raises(NotImplementedError, match="wire form"):
        srv.submit_prefilled(tokens(1, 8), {}, np.zeros((V,), np.float32))
    with pytest.raises(NotImplementedError, match="wire form"):
        srv.stream_prefilled_begin(tokens(1, 8))
    with pytest.raises(NotImplementedError, match="wire form"):
        fleet.PrefillWorker(params, cfg, max_len=T, layout="paged")
    with pytest.raises(NotImplementedError, match="paged"):
        generate.init_cache(cfg, 2, T)
    with pytest.raises(NotImplementedError, match="ep exchange"):
        gpt.param_shardings(cfg)
    with pytest.raises(NotImplementedError, match="training forward"):
        gpt.forward(params, jnp.zeros((1, 4), jnp.int32), cfg,
                    key=jax.random.PRNGKey(0))


@pytest.mark.parametrize("change,word", [
    ({"experts": None}, "come together"),
    ({"mla": None}, "come together"),
    ({"bias": True}, "latent block is"),
    ({"tie_embeddings": True}, "latent block is"),
    ({"num_kv_heads": 2}, "latent block is"),
    ({"moe": moe.MoEConfig(num_experts=2, top_k=1)}, "latent block is"),
])
def test_config_refuses_what_the_block_is_not(cfg, change, word):
    with pytest.raises(ValueError, match=word):
        dataclasses.replace(cfg, **change)


def test_expert_share_config_holds_a_range_of_the_routed():
    for held in ((4, 4), (-1, 3), (12, 17)):
        with pytest.raises(ValueError, match="held"):
            moe.ExpertShareConfig(16, 8, 4, 64, 6.0, held)
    with pytest.raises(ValueError, match="top_k"):
        moe.ExpertShareConfig(16, 8, 25, 64, 6.0, (0, 4))


def test_no_new_engine_kinds_and_no_new_flags():
    """Served through the step kinds there were (36) and under the flags
    there were (74 ``PADDLE_TPU_*`` names in the package at this PR's
    parent): the steps branch on the configuration and on the leaves the
    cache holds."""
    import pathlib
    import re

    assert len(engine.kinds()) == 36
    assert not [k for k in engine.kinds() if "latent" in k or "mla" in k]
    root = pathlib.Path(serving.__file__).resolve().parents[1]
    names = set()
    for f in root.rglob("*.py"):
        names |= set(re.findall(r"PADDLE_TPU_[A-Z0-9_]+", f.read_text()))
    assert len(names) <= 74, sorted(names)
