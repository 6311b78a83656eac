"""A stated layer pattern (Granite-4.0-H's: Mamba-2 layers and attention
layers without positional encoding, ONE mixer a layer, each followed by
one chip's share of routed experts beside a shared expert): the program
against ``benchmarks/families/reference_granite_moe_hybrid.py`` on seeded
weights, at a small size on the CPU.  Logits, not tokens: with random
weights the largest logit changes on rounding.

The reference is float32 at matmul precision "highest" with the recurrence
as a plain scan, attention over the whole sequence and every held expert
on every token.  The program prefills in chunks (the chunked scan on mamba
layers, rows written to the attention layers' own K/V leaves), decodes
through the paged cache, whose state leaves are as deep as the pattern has
mamba layers and whose K/V leaves as it has attention layers, and runs the
held experts as batched matmuls over all of a step's rows."""
import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import reference_granite_moe_hybrid as ref
from paddle_tpu import telemetry
from paddle_tpu.text import engine, fleet, generate, gpt, kv_pool, moe
from paddle_tpu.text import serving, ssm

# config.json keys at the small size: width 128, the pattern mamba,
# attention, mamba, mamba; 4 query over 2 KV heads of 32; a mixer of 8
# heads of 16, state 16, one group; 8 routed experts of 32 (4 held), 3 a
# token, beside a shared expert of 64; every multiplier off 1
MODEL = dict(
    hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=0.05, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=16.0, rms_norm_eps=1e-5,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, num_experts_per_tok=3, num_local_experts_published=8,
    held=[0, 4], layer_types=["mamba", "attention", "mamba", "mamba"])
ARCH = ref.arch_of(MODEL)
V, T, FE, FS = 512, 256, 32, 64


def make_cfg(dtype=jnp.float32, model=MODEL, chunk=16):
    m = model
    return gpt.GPTConfig(
        vocab_size=V, hidden_size=m["hidden_size"],
        num_layers=len(m["layer_types"]),
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], max_seq_len=T, dtype=dtype,
        pos_embed="none", norm="rmsnorm", activation="swiglu",
        tie_embeddings=True, bias=False,
        layer_types=tuple(m["layer_types"]),
        embedding_multiplier=m["embedding_multiplier"],
        lm_head_multiplier=1.0 / m["logits_scaling"],
        attention_multiplier=m["attention_multiplier"],
        residual_multiplier=m["residual_multiplier"],
        ssm=ssm.SSMConfig(
            n_heads=m["mamba_n_heads"], head_dim=m["mamba_d_head"],
            d_state=m["mamba_d_state"], n_groups=m["mamba_n_groups"],
            d_conv=m["mamba_d_conv"], chunk_size=chunk),
        experts=moe.ExpertShareConfig(
            m["num_local_experts_published"], 0, m["num_experts_per_tok"],
            FE, held=tuple(m["held"]), score="topk_softmax",
            shared_size=FS))


def make_params(cfg, seed=0):
    """``gpt.init_params`` with what the forward scales afterwards drawn
    wider (as the benchmark's family does), so that every branch moves the
    stream, a score has a spread, the router's top scores differ, and the
    stream outgrows the embedding it started from (with a tied head a
    token's own embedding would otherwise make that token the largest
    logit everywhere, and a margin would judge nothing)."""
    p = gpt.init_params(cfg, jax.random.PRNGKey(seed))
    b = p["blocks"]
    p["wte"] = p["wte"] * 5.0
    ex = b["moe"]
    ex["router_w"] = ex["router_w"] * 8.0
    for name, by in (("gate_w", 5.0), ("up_w", 5.0), ("down_w", 560.0)):
        ex[name] = tuple(w * by for w in ex[name])
    ex["shared_gate_w"] = ex["shared_gate_w"] * 5.0
    ex["shared_up_w"] = ex["shared_up_w"] * 5.0
    ex["shared_down_w"] = ex["shared_down_w"] * 180.0
    if "mamba" in b:
        b["mamba"]["ssm_out_w"] = b["mamba"]["ssm_out_w"] * 140.0
    if "attn" in b:
        b["attn"]["q_w"] = b["attn"]["q_w"] * 15.0
        b["attn"]["kv_w"] = b["attn"]["kv_w"] * jnp.asarray(
            [15.0, 5.0])[None, :, None, None]
        b["attn"]["proj_w"] = b["attn"]["proj_w"] * 200.0
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
    for _ in range(3000):
        if not srv.pending():
            break
        srv.tick()
    assert not srv.pending()
    return srv, [srv.result(r) for r in rids]


def want_logits(params, seq, arch=ARCH, pad_to=48):
    """The reference's logits at every position of ``seq``, computed on
    the sequence padded to ``pad_to`` (causal: padding cannot reach back)
    so that every test shares one compiled reference."""
    toks = np.zeros((pad_to,), np.int32)
    toks[:len(seq)] = seq
    return ref.logits(params, toks, arch=arch)[:len(seq)]


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
    toks = np.stack([tokens(seed, 48), tokens(seed + 10, 48)])
    got = jax.jit(lambda p, t: gpt.forward(p, t, cfg))(params,
                                                       jnp.asarray(toks))
    for b in range(2):
        want = want_logits(params, toks[b])
        assert float(jnp.std(want)) > 0.05
        np.testing.assert_allclose(got[b], want, atol=5e-6)


def test_forward_bf16_within_the_stated_tolerance(cfg, params):
    """bf16 weights and activations against the float32 reference on the
    same (bf16-rounded) weights: logits of spread 0.07 within 0.08 (it
    reads 0.045, where leaving a mechanism out reads 0.31-0.39: at this
    size a near-tie at the router's third place that falls the other way
    swaps an expert that weighs a third of the routed sum, which is what a
    whole branch weighs here; a bf16 step at the logits' size is 0.0005)."""
    pb = cast(params, jnp.bfloat16)
    toks = tokens(3, 48)
    got = jax.jit(lambda p, t: gpt.forward(p, t, make_cfg(jnp.bfloat16)))(
        pb, jnp.asarray(toks)[None])[0].astype(jnp.float32)
    want = want_logits(pb, toks)
    assert float(jnp.max(jnp.abs(got - want))) < 0.08
    assert float(jnp.std(want)) > 0.05


def test_every_mechanism_moves_the_logits(cfg, params):
    """At the tests' weights each mixer kind, the routed sum and the shared
    expert carry weight: leaving one out moves the logits by far more than
    the tolerances here (so a program that skipped it would be caught)."""
    toks = tokens(4, 48)
    want = want_logits(params, toks)
    b = params["blocks"]
    for name, changed in (
            ("attention", dict(b, attn=dict(
                b["attn"], proj_w=jnp.zeros_like(b["attn"]["proj_w"])))),
            ("mamba", dict(b, mamba=dict(
                b["mamba"], ssm_out_w=jnp.zeros_like(
                    b["mamba"]["ssm_out_w"])))),
            ("routed", dict(b, moe=dict(b["moe"], down_w=tuple(
                jnp.zeros_like(w) for w in b["moe"]["down_w"])))),
            ("shared", dict(b, moe=dict(
                b["moe"], shared_down_w=jnp.zeros_like(
                    b["moe"]["shared_down_w"]))))):
        got = want_logits(dict(params, blocks=changed), toks)
        assert float(jnp.max(jnp.abs(got - want))) > 0.1, name


def test_a_position_free_plain_block_decodes_as_it_forwards():
    """``pos_embed="none"`` on the plain block: no table in the tree, no
    rotation, and the cached decode step gives the forward's logits."""
    cfg = gpt.GPTConfig(vocab_size=V, hidden_size=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, max_seq_len=64,
                        dtype=jnp.float32, pos_embed="none")
    p = gpt.init_params(cfg, jax.random.PRNGKey(0))
    assert "wpe" not in p
    toks = tokens(1, 12)
    want = jax.jit(lambda p, t: gpt.forward(p, t, cfg))(
        p, jnp.asarray(toks)[None])[0]
    cache = generate.init_cache(cfg, 1, 64)
    step = jax.jit(lambda p, c, t, i: generate.decode_step(p, c, t, i, cfg))
    for i, t in enumerate(toks):
        lg, cache = step(p, cache, jnp.asarray([t]), jnp.asarray(i))
        np.testing.assert_allclose(lg[0], want[i], atol=2e-5)


# ---------------------------------------------------------------------------
# prefill in chunks, then decode, through the paged cache
# ---------------------------------------------------------------------------


def pool_with_slot(cfg, slot=1, batch=3, block=8, blocks=40):
    cache = kv_pool.init_paged_cache(cfg, batch, T, block_size=block,
                                     num_blocks=blocks)
    nmax = cache["tables"].shape[1]
    tables = np.full((batch, nmax), -1, np.int32)
    tables[slot, :blocks - 5] = np.arange(5, blocks)[:nmax]
    return dict(cache, tables=jnp.asarray(tables),
                live=jnp.arange(batch) == slot)


_JITS: dict = {}


def jitted(fn, cfg):
    """``fn(..., cfg)`` jitted once a config value (the tests call the two
    cache paths directly, many times over)."""
    key = (fn.__name__, engine.cfg_key(cfg))
    if key not in _JITS:
        _JITS[key] = jax.jit(lambda *a: fn(*a, cfg))
    return _JITS[key]


def prefill(params, cfg, cache, seq, pos0, n, slot=1, width=16):
    chunk = np.zeros((1, width), np.int32)
    chunk[0, :n] = seq[pos0:pos0 + n]
    return jitted(kv_pool.paged_prefill_chunk, cfg)(
        params, cache, jnp.asarray(chunk), jnp.asarray(pos0),
        jnp.asarray(n), jnp.asarray(slot))


def decode_batch(params, cfg, cache, tok, pos):
    return jitted(kv_pool.paged_decode_step_batched, cfg)(
        params, cache, jnp.asarray(tok, jnp.int32),
        jnp.asarray(pos, jnp.int32))


def decode(params, cfg, cache, tok, pos, slot=1, batch=3):
    t = np.zeros((batch,), np.int32)
    p = np.zeros((batch,), np.int32)
    t[slot], p[slot] = tok, pos
    logits, cache = decode_batch(params, cfg, cache, t, p)
    return logits[slot], cache


@pytest.mark.parametrize("n", [7, 8, 9, 15, 16, 17, 31, 33])
def test_prefill_then_decode_equals_the_full_forward(cfg, params, n):
    """Prompts around block (8), scan-chunk and bucket (16) edges,
    prefilled in chunks of 16 (the last one padded), then decoded token by
    token: every position's logits are the reference's full forward's."""
    seq = tokens(n, n + 6)
    want = want_logits(params, seq)
    cache = pool_with_slot(cfg)
    for pos0 in range(0, n, 16):
        logits, cache = prefill(params, cfg, cache, seq, pos0,
                                min(16, n - pos0))
    np.testing.assert_allclose(logits, want[n - 1], atol=2e-5)
    for i in range(n, n + 6):
        logits, cache = decode(params, cfg, cache, seq[i], i)
        np.testing.assert_allclose(logits, want[i], atol=2e-5)


def test_a_buckets_padding_advances_no_state_writes_no_row_selects_no_expert(
        cfg, params):
    seq = tokens(5, 11)
    cache = pool_with_slot(cfg)
    logits1, after = prefill(params, cfg, cache, seq, 0, 11)
    rows = np.asarray(after["k"], np.float32)
    # the slot's blocks are 5, 6: rows 0..10 written in the ONE K/V layer,
    # 11..15 (the padding) and every other block untouched
    written = np.abs(rows).sum(-1) > 0                     # [1, N, bs]
    assert written[:, 5].all() and written[:, 6, :3].all()
    assert not written[:, 6, 3:].any()
    written[:, 5:7] = False
    assert not written.any()
    # what the padding holds changes nothing: not the logits, not the
    # state the chunk leaves (pads advance none), not a row
    chunk = np.zeros((1, 16), np.int32)
    chunk[0, :11], chunk[0, 11:] = seq, 77
    logits2, after2 = jitted(kv_pool.paged_prefill_chunk, cfg)(
        params, cache, jnp.asarray(chunk), jnp.asarray(0), jnp.asarray(11),
        jnp.asarray(1))
    np.testing.assert_array_equal(logits1, logits2)
    for name in kv_pool.STATE_LEAVES + ("k", "v"):
        np.testing.assert_array_equal(np.asarray(after[name]),
                                      np.asarray(after2[name]))
    # and the state is what eleven positions leave: the next step agrees
    # with the reference
    full = tokens(5, 12)
    np.testing.assert_array_equal(full[:11], seq)
    want = want_logits(params, full)
    lg, _ = decode(params, cfg, after, full[11], 11)
    np.testing.assert_allclose(lg, want[11], atol=2e-5)


# ---------------------------------------------------------------------------
# two kinds of cache whose depths differ
# ---------------------------------------------------------------------------


def published_cfg(layer_types, held=(0, 36), vocab=50176):
    """Granite-4.0-H-Small's config.json shapes (benchmarks/configs)."""
    return gpt.GPTConfig(
        vocab_size=vocab, hidden_size=4096, num_layers=len(layer_types),
        num_heads=32, num_kv_heads=8, max_seq_len=131072,
        dtype=jnp.bfloat16, pos_embed="none", norm="rmsnorm",
        activation="swiglu", tie_embeddings=True, bias=False,
        layer_types=tuple(layer_types), embedding_multiplier=12.0,
        lm_head_multiplier=1 / 16, attention_multiplier=0.0078125,
        residual_multiplier=0.22,
        ssm=ssm.SSMConfig(n_heads=128, head_dim=64, d_state=128, n_groups=1,
                          d_conv=4, chunk_size=256),
        experts=moe.ExpertShareConfig(72, 0, 10, 768, held=tuple(held),
                                      score="topk_softmax",
                                      shared_size=1536))


PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def test_leaf_depths_and_bytes_at_the_published_shapes():
    """The cell's cache: K/V leaves ONE layer deep, state leaves nine deep;
    4,096 B a token and 38,204,928 B a slot."""
    cfg = published_cfg(PERIOD)
    cache = jax.eval_shape(lambda: kv_pool.init_paged_cache(
        cfg, 64, 4096, block_size=16, num_blocks=16384))
    assert cache["k"].shape == cache["v"].shape == (1, 16384, 16, 1024)
    assert cache["ssm"].shape == (9, 64, 128, 64, 128)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["conv"].shape == (9, 64, 3, 8448)
    assert cache["live"].shape == (64,) and cache["moe_counts"].shape == (5,)
    nbytes = lambda x: int(np.prod(x.shape)) * x.dtype.itemsize  # noqa: E731
    a_token = sum(nbytes(cache[n]) for n in ("k", "v")) // (16384 * 16)
    a_slot = sum(nbytes(cache[n]) for n in kv_pool.STATE_LEAVES) // 64
    assert a_token == 4096 and a_slot == 38_204_928
    assert ssm.state_bytes(cfg.ssm, cfg.layers_of("mamba"),
                           cfg.dtype) == 38_204_928
    assert kv_pool._geometry(cache) == (16384, 16, 256)


@pytest.mark.parametrize("kinds,slots", [
    (["mamba", "attention", "mamba", "mamba"],
     [("mamba", 0), ("attention", 0), ("mamba", 1), ("mamba", 2)]),
    (["attention", "mamba", "attention", "mamba"],
     [("attention", 0), ("mamba", 0), ("attention", 1), ("mamba", 1)]),
    (["attention", "attention", "mamba"],
     [("attention", 0), ("attention", 1), ("mamba", 0)]),
])
def test_a_pattern_indexes_its_leaves_rightly(kinds, slots):
    """Two attention layers, and a pattern that starts with attention: the
    leaves are as deep as each kind has layers, a layer reads and writes
    its own, and the cache path equals the reference's full forward."""
    model = dict(MODEL, layer_types=kinds)
    cfg = make_cfg(model=model)
    assert list(cfg.layer_slots) == slots
    p = make_params(cfg, 2)
    n_attn, n_mamba = kinds.count("attention"), kinds.count("mamba")
    assert p["blocks"]["attn"]["q_w"].shape[0] == n_attn
    assert p["blocks"]["mamba"]["ssm_in_w"].shape[0] == n_mamba
    cache = pool_with_slot(cfg)
    assert cache["k"].shape[0] == n_attn
    assert cache["ssm"].shape[0] == cache["conv"].shape[0] == n_mamba
    seq = tokens(7, 26)
    want = want_logits(p, seq, ref.arch_of(model))
    logits, cache = prefill(p, cfg, cache, seq, 0, 16)
    np.testing.assert_allclose(logits, want[15], atol=2e-5)
    logits, cache = prefill(p, cfg, cache, seq, 16, 4)
    np.testing.assert_allclose(logits, want[19], atol=2e-5)
    for i in range(20, 26):
        logits, cache = decode(p, cfg, cache, seq[i], i)
        np.testing.assert_allclose(logits, want[i], atol=2e-5)
    # every leaf of either kind was written, each layer its own rows
    k = np.asarray(cache["k"])
    assert all(np.abs(k[j]).sum() > 0 for j in range(n_attn))
    if n_attn > 1:
        assert (k[0] != k[1]).any()
    s = np.asarray(cache["ssm"])
    assert all(np.abs(s[j, 1]).sum() > 0 for j in range(n_mamba))
    assert not np.abs(s[:, 0]).sum() and not np.abs(s[:, 2]).sum()


# ---------------------------------------------------------------------------
# state: reset at a sequence's first position, kept while idle
# ---------------------------------------------------------------------------


def test_idle_slot_keeps_its_state_and_a_first_position_starts_from_zero(
        cfg, params):
    cache = kv_pool.init_paged_cache(cfg, 4, T, block_size=8)
    nmax = cache["tables"].shape[1]
    cache = dict(cache, tables=jnp.arange(4 * nmax, dtype=jnp.int32).reshape(
        4, nmax))
    junk = {n: jax.random.normal(jax.random.PRNGKey(i), cache[n].shape,
                                 jnp.float32).astype(cache[n].dtype)
            for i, n in enumerate(kv_pool.STATE_LEAVES + ("k", "v"))}
    cache = dict(cache, **junk, live=jnp.asarray([True, False, True, False]))
    tok, pos = [5, 6, 7, 8], [9, 4, 0, 0]
    step = lambda c: decode_batch(params, cfg, c, tok, pos)  # noqa: E731
    lg, new = step(cache)
    for n in kv_pool.STATE_LEAVES:
        for slot in (1, 3):       # free, or between its prefill's chunks
            np.testing.assert_array_equal(np.asarray(new[n][:, slot]),
                                          np.asarray(junk[n][:, slot]))
        assert (np.asarray(new[n][:, 0]) != np.asarray(junk[n][:, 0])).any()
    # idle slots count nowhere: two live slots, 3 selections, 4 layers
    assert int(new[kv_pool.COUNTS][:3].sum()) == 2 * 3 * 4
    # slot 2 at position 0: what a zero state gives, not the junk's
    zero = dict(cache, **{n: jnp.zeros_like(cache[n])
                          for n in kv_pool.STATE_LEAVES})
    lg0, new0 = step(zero)
    np.testing.assert_array_equal(np.asarray(lg[2]), np.asarray(lg0[2]))
    for n in kv_pool.STATE_LEAVES:
        np.testing.assert_array_equal(np.asarray(new[n][:, 2]),
                                      np.asarray(new0[n][:, 2]))


def test_prefill_at_position_zero_starts_from_zero(cfg, params):
    """An admission reads no state: a slot reused after retirement gives
    the logits and leaves the state of a fresh one, to the bit."""
    seq = tokens(8, 14)
    fresh, a = prefill(params, cfg, pool_with_slot(cfg), seq, 0, 14)
    dirty = pool_with_slot(cfg)
    dirty = dict(dirty, **{n: jnp.full(dirty[n].shape, 3.0, dirty[n].dtype)
                           for n in kv_pool.STATE_LEAVES})
    reused, b = prefill(params, cfg, dirty, seq, 0, 14)
    np.testing.assert_array_equal(np.asarray(fresh), np.asarray(reused))
    for n in kv_pool.STATE_LEAVES:
        np.testing.assert_array_equal(np.asarray(a[n][:, 1]),
                                      np.asarray(b[n][:, 1]))
        # the other slots' state is not the chunk's to touch
        assert (np.asarray(b[n][:, 0]) == 3.0).all()


# ---------------------------------------------------------------------------
# the expert share: no token dropped, static shapes, shares add up
# ---------------------------------------------------------------------------

WIDE = dict(MODEL, num_local_experts_published=16, held=[0, 8])


def steer(params, experts, width=16):
    """``params`` whose router sends every token's top 3 to three of the
    six ``experts``, whatever the token: every column a multiple of one
    direction, the six the largest multiples, three of either sign (the
    logits' order turns round with the sign of the token's component)."""
    out = dict(params, blocks=dict(params["blocks"]))
    w = np.asarray(params["blocks"]["moe"]["router_w"])
    alpha = np.zeros((width,), np.float32)
    alpha[list(experts)] = [3.0, 2.5, 2.0, -2.0, -2.5, -3.0]
    rest = [e for e in range(width) if e not in experts]
    alpha[rest] = np.linspace(-0.2, 0.2, len(rest))
    steered = w[:, :, :1] * alpha[None, None, :]
    out["blocks"]["moe"] = dict(params["blocks"]["moe"],
                                router_w=jnp.asarray(steered))
    return out


@pytest.mark.parametrize("where,experts,held", [
    ("all three selections of every token on held experts",
     (0, 1, 2, 5, 6, 7), 3),
    ("none held: all on the other chip's experts",
     (8, 9, 10, 13, 14, 15), 0),
])
def test_no_token_is_dropped_for_any_routing(where, experts, held):
    cfg = make_cfg(model=WIDE)
    arch = ref.arch_of(WIDE)
    steered = steer(make_params(cfg), experts)
    seq = tokens(2, 30)
    want = want_logits(steered, seq, arch)
    # and through the cache: a chunk, then steps
    cache = pool_with_slot(cfg)
    logits, cache = prefill(steered, cfg, cache, seq, 0, 16)
    np.testing.assert_allclose(logits, want[15], atol=2e-5)
    for i in range(16, 20):
        logits, cache = decode(steered, cfg, cache, seq[i], i)
        np.testing.assert_allclose(logits, want[i], atol=2e-5)
    counts = np.asarray(cache[kv_pool.COUNTS])
    # 4 decode steps x 4 layers x one live slot x 3 selections
    assert list(counts[:3]) == [16 * held, 0, 16 * (3 - held)]
    assert counts[3] == 16 * held and counts[4] == 16


def test_the_steps_shapes_do_not_depend_on_the_routing():
    cfg = make_cfg(model=WIDE)
    params = make_params(cfg)
    cache = pool_with_slot(cfg)
    for experts in ((0, 1, 2, 5, 6, 7), (8, 9, 10, 13, 14, 15)):
        decode_batch(steer(params, experts), cfg, cache, [0, 5, 0],
                     [0, 3, 0])
    decode_batch(params, cfg, cache, [0, 5, 0], [0, 3, 0])
    assert jitted(kv_pool.paged_decode_step_batched,
                  cfg)._cache_size() == 1


def test_a_free_or_admitting_slot_changes_no_other_slots_result(cfg, params):
    seq = tokens(6, 12)
    _, cache = prefill(params, cfg, pool_with_slot(cfg), seq, 0, 11)
    alone, _ = decode(params, cfg, cache, seq[11], 11)
    # slot 0 mid-admission and slot 2 free feed other tokens at other
    # positions: slot 1 reads the same bits, and they count nowhere
    logits, after = decode_batch(params, cfg, cache, [400, seq[11], 33],
                                 [5, 11, 0])
    np.testing.assert_array_equal(logits[1], alone)
    assert int(after[kv_pool.COUNTS][:3].sum()) == 4 * 3


def test_the_shares_add_up_to_the_uncut_layer():
    """The two shares ``held = [0, 4)`` and ``[4, 8)`` of the 8 routed
    experts: their routed parts, plus the shared expert and the mixer path
    counted once, are the uncut reference's layer, for a mamba layer and
    for an attention layer."""
    model = dict(MODEL, held=[0, 8])
    whole_cfg = make_cfg(model=model)
    whole = make_params(whole_cfg, 3)
    arch = ref.arch_of(model)
    h = 4.0 * jax.random.normal(jax.random.PRNGKey(8), (20, 128))
    for li in (0, 1):
        kind, p_ref = ref.layer_weights(whole["blocks"], li, arch)
        want = ref.layer(h, kind, p_ref, arch=arch)
        p0 = gpt.pattern_layer(whole["blocks"], whole_cfg, li)

        def mixer(n, kind=kind, p0=p0):
            if kind == "mamba":
                out, _ = ssm.mixer_chunk(
                    n[None], p0, whole_cfg,
                    ssm.zero_state(whole_cfg.ssm, 1, jnp.float32))
                return out[0]
            q, k, v = gpt._project_qkv(n[None], p0, whole_cfg)
            from paddle_tpu.ops.attention import attention_array
            a = attention_array(q, k, v, is_causal=True,
                                scale=whole_cfg.softmax_scale)
            return gpt._attn_out(a.reshape(1, 20, -1), p0, whole_cfg)[0]

        def layer_with(moe_p, c):
            return jax.jit(lambda m: gpt.pattern_block(
                h, dict(p0, moe=m), c, mixer)[0])(moe_p)

        def share_of(j, zero_down=False):
            m = dict(p0["moe"])
            for name in ("gate_w", "up_w", "down_w"):
                m[name] = m[name][4 * j:4 * j + 4]
            if zero_down:
                m["down_w"] = jnp.zeros_like(m["down_w"])
            return m

        # what both chips compute alike (the mixer path and the shared
        # expert): a share whose routed experts give nothing
        alike = layer_with(share_of(0, zero_down=True), make_cfg())
        total = alike
        for j in range(2):
            c = make_cfg(model=dict(MODEL, held=[4 * j, 4 * j + 4]))
            total = total + layer_with(share_of(j), c) - alike
        np.testing.assert_allclose(total, want, atol=2e-5)
        # and a share alone is not the layer: the routed part counts
        assert float(jnp.max(jnp.abs(alike - want))) > 1e-2


def test_the_ten_scores_are_a_softmax_over_the_selected(cfg, params):
    """The second published score form: the ``top_k`` largest logits, a
    softmax over those alone (they sum to 1); the first form stays what it
    was (a softmax over all outputs, not renormalised, a selection bias)."""
    m = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))
    p = moe.layer_of(params["blocks"]["moe"], 0)
    idx, w = moe.route_share(m, p, cfg.experts)
    logits = np.asarray(m @ p["router_w"])
    order = np.argsort(-logits, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(order, -1))
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    top = np.take_along_axis(logits, np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, np.exp(top) / np.exp(top).sum(-1, keepdims=True), atol=1e-6)
    assert "router_b" not in p and float(np.asarray(w).min(-1).mean()) > 0.02
    old = moe.ExpertShareConfig(8, 2, 3, FE, 1.0, (0, 4))
    assert old.selection_bias and not cfg.experts.selection_bias
    assert moe.count_expert_share(old, 128) == (129 * 10, 3 * 128 * FE)
    assert moe.count_expert_share(cfg.experts, 128) == (
        128 * 8 + 3 * 128 * FS, 3 * 128 * FE)
    with pytest.raises(ValueError, match="score form"):
        moe.ExpertShareConfig(8, 0, 3, FE, score="sigmoid", held=(0, 4))


def test_scopes_of_the_pattern_step_and_no_identity_term(cfg, params):
    """The step's ops carry the scopes the per-layer metrics select, the
    shared expert its own (``moe_shared``) inside ``moe``; a config without
    zero-compute experts traces no identity term."""
    cache = pool_with_slot(cfg)
    tok = jnp.asarray([0, 5, 0])
    text = jax.jit(lambda p, c: kv_pool.paged_decode_step_batched(
        p, c, tok, tok, cfg)).lower(params, cache).as_text(debug_info=True)
    for scope in ("moe/moe_shared", "moe/moe_experts", "moe/moe_route",
                  "ssm/ssm_update", "ssm/ssm_conv", "attn", "kv_gather",
                  "ln", "lm_head", "embed"):
        assert scope + "/" in text or scope + '"' in text, scope
    assert "moe_zero" not in text
    chunk = jnp.zeros((1, 16), jnp.int32)
    text = jax.jit(lambda p, c: kv_pool.paged_prefill_chunk(
        p, c, chunk, 0, 16, 1, cfg)).lower(params, cache).as_text(
        debug_info=True)
    for scope in ("moe/moe_shared", "ssm/ssm_scan", "attn", "kv_gather"):
        assert scope + "/" in text or scope + '"' in text, scope


# ---------------------------------------------------------------------------
# served: DecodeServer, the plain step kinds
# ---------------------------------------------------------------------------

PROMPTS = [5, 17, 33, 9, 40, 12]


def test_served_tokens_are_the_references_argmax(cfg, params):
    telemetry.reset()
    prompts = [tokens(20 + n, n) for n in PROMPTS]
    srv, outs = serve(params, cfg, prompts, max_new=10, async_dispatch=True)
    assert worst_margin(params, prompts, outs) < 1e-4
    stats = srv.load_stats()
    pairs = (stats["moe_pairs_held"] + stats["moe_pairs_zero"]
             + stats["moe_pairs_absent"])
    assert pairs > 0 and pairs % (3 * 4) == 0 and stats["moe_pairs_zero"] == 0
    assert 0 < stats["moe_experts_hit"] <= 4
    snap = telemetry.snapshot()
    assert snap["gauges"]["kv_pool.kv_layers"] == 1
    assert snap["gauges"]["kv_pool.state_layers"] == 3
    assert snap["gauges"]["kv_pool.state_bytes"] == sum(
        srv.cache[n].nbytes for n in kv_pool.STATE_LEAVES)
    assert snap["counters"]["moe.pairs_held"] == stats["moe_pairs_held"]
    assert snap["counters"]["kv_pool.state_resets"] == len(prompts)
    assert snap["counters"]["kv_pool.prefix_skipped_recurrent"] == len(prompts)
    assert "moe.pairs_zero" not in snap["counters"]
    srv.close()


def test_async_equals_sync_and_a_reused_slot_equals_a_fresh_one(cfg, params):
    prompts = [tokens(40 + n, n) for n in (6, 23, 31, 14, 19)]
    _, a = serve(params, cfg, prompts, max_batch=2, async_dispatch=True)
    _, s = serve(params, cfg, prompts, max_batch=2, async_dispatch=False)
    assert a == s
    # two slots served five requests: three started in a slot another had
    # left; alone on a fresh server each gives the same tokens
    for p, out in zip(prompts, a):
        assert serve(params, cfg, [p], max_batch=2)[1] == [out]


def test_prefill_chunks_tile_the_prompt(cfg, params):
    """``prefill_chunk`` (the server's cap on an admission's width): the
    chunks of a state's prefill tile the prompt, and the tokens are those
    of whole-bucket admission."""
    prompts = [tokens(50 + n, n) for n in (70, 9, 33)]
    _, whole = serve(params, cfg, prompts)
    _, tiled = serve(params, cfg, prompts, prefill_chunk=32)
    assert whole == tiled
    assert worst_margin(params, prompts, tiled) < 1e-4


def test_served_in_bf16_within_the_stated_tolerance(cfg, params):
    """bf16 end to end (weights, activations, K/V rows, the conv window;
    the state float32) against the float32 reference on the same weights:
    a served token within 0.02 of the reference's best logit (logits of
    spread 0.07; it reads 0.0036, and 0.002 and 0 on two other draws)."""
    pb = cast(params, jnp.bfloat16)
    prompts = [tokens(60 + n, n) for n in (12, 30, 21)]
    srv, outs = serve(pb, make_cfg(jnp.bfloat16), prompts, max_new=12)
    assert srv.cache["k"].dtype == jnp.bfloat16
    assert srv.cache["ssm"].dtype == jnp.float32
    assert worst_margin(pb, prompts, outs) < 0.02


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def test_parameter_counts_at_the_published_shapes():
    """121,464,448 / 61,120,512 a layer outside the experts, 9,437,184 an
    expert, 4,757,211,776 for the cut and 32.2 B for the whole model; the
    tree ``init_params`` would make has exactly the cut's count."""
    cut = published_cfg(PERIOD)
    router_shared, expert = moe.count_expert_share(cut.experts, 4096)
    assert expert == 9_437_184
    assert router_shared == 4096 * 72 + 18_874_368
    assert ssm.count_params(cut.ssm, 4096) == 102_286_976
    one = lambda kinds: gpt.count_params(dataclasses.replace(  # noqa: E731
        published_cfg(kinds, held=(0, 1), vocab=8), num_layers=1))
    assert one(["mamba"]) - expert - 8 * 4096 - 4096 == 121_464_448
    assert one(["attention"]) - expert - 8 * 4096 - 4096 == 61_120_512
    assert gpt.count_params(cut) == 4_757_211_776
    # the published pattern is the period four times over: four attention
    # layers, at 5, 15, 25, 35
    whole = published_cfg(PERIOD * 4, held=(0, 72), vocab=100352)
    assert [i for i, k in enumerate(whole.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert gpt.count_params(whole) == 32_207_337_984
    shapes = jax.eval_shape(lambda k: gpt.init_params(cut, k),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes)) == 4_757_211_776


# ---------------------------------------------------------------------------
# what cannot work yet raises, naming the reason
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
    ("PADDLE_TPU_KV_RADIX", "1", "prefix reuse"),
    ("PADDLE_TPU_SPEC_K", "3", "speculation"),
    ("PADDLE_TPU_KV_DTYPE", "int8", "int8"),
])
def test_environment_refuses(cfg, params, monkeypatch, name, value, word):
    monkeypatch.setenv(name, value)
    with pytest.raises(NotImplementedError, match=word):
        server(params, cfg)


def test_handoff_and_other_paths_refuse(cfg, params):
    srv = server(params, cfg)
    with pytest.raises(NotImplementedError, match="handoff"):
        srv.submit_prefilled(tokens(1, 8), {}, np.zeros((V,), np.float32))
    with pytest.raises(NotImplementedError, match="handoff"):
        srv.stream_prefilled_begin(tokens(1, 8))
    with pytest.raises(NotImplementedError, match="handoff"):
        fleet.PrefillWorker(params, cfg, max_len=T, layout="paged")
    with pytest.raises(NotImplementedError, match="paged"):
        generate.init_cache(cfg, 2, T)
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        gpt.param_shardings(cfg)
    with pytest.raises(NotImplementedError, match="ep exchange"):
        gpt.param_shardings(dataclasses.replace(make_cfg(model=dict(
            MODEL, layer_types=["attention", "attention"])), ssm=None))
    with pytest.raises(NotImplementedError, match="training forward"):
        gpt.forward(params, jnp.zeros((1, 4), jnp.int32), cfg,
                    key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="must divide"):
        server(params, cfg, prefill_chunk=48)


@pytest.mark.parametrize("change,word", [
    ({"layer_types": ("mamba", "attention")}, "one of 'mamba'"),
    ({"layer_types": ("mamba", "window", "mamba", "mamba")},
     "one of 'mamba'"),
    ({"experts": None}, "one mixer a layer"),
    ({"ssm": None}, "one mixer a layer"),
    ({"pos_embed": "rope"}, "pattern block is"),
    ({"bias": True}, "pattern block is"),
    ({"num_kv_heads": None}, "pattern block is"),
    ({"norm": "layernorm"}, "pattern block is"),
    ({"moe": moe.MoEConfig(num_experts=2, top_k=1)}, "pattern block is"),
    ({"layer_types": None}, "come together"),
    ({"layer_types": None, "experts": None}, "pattern block only"),
])
def test_config_refuses_what_the_block_is_not(cfg, change, word):
    with pytest.raises(ValueError, match=word):
        dataclasses.replace(cfg, **change)


def test_no_new_engine_kinds_and_no_new_flags():
    """Served through the step kinds there were (36) and under the flags
    there were (74 ``PADDLE_TPU_*`` names in the package at this PR's
    parent): the steps branch on the configuration and on the leaves the
    cache holds."""
    assert len(engine.kinds()) == 36
    assert not [k for k in engine.kinds()
                if "pattern" in k or "granite" in k or "hybrid" in k]
    root = pathlib.Path(serving.__file__).resolve().parents[1]
    names = set()
    for f in root.rglob("*.py"):
        names |= set(re.findall(r"PADDLE_TPU_[A-Z0-9_]+", f.read_text()))
    assert len(names) <= 74, sorted(names)
