"""A Mamba-2 mixer beside grouped-query attention (Falcon-H1's block):
the program against ``benchmarks/families/reference_falcon_h1.py`` on
seeded weights, at a small size on the CPU.  Logits, not tokens: with
random weights the largest logit changes on rounding.

The reference is float32 at matmul precision "highest" with the recurrence
as a plain scan; the program runs the chunked scan for prefill and the
one-token update for decode, through the paged cache, whose per-slot state
leaves sit beside the pooled KV rows."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import reference_falcon_h1 as ref
from paddle_tpu import telemetry
from paddle_tpu.ops import decode_attention as da
from paddle_tpu.text import engine, fleet, generate, gpt, kv_pool, serving
from paddle_tpu.text import ssm

# config.json keys at the small size: 4 + 2 heads of 64 on a width of 256
# (so num_heads * head_dim is the width only by accident of the toy: the
# kernel-route config below has 2 x 128), 4 mixer heads of 32, state 16,
# 2 groups, an untied head, every multiplier off 1
MODEL = dict(
    num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    rope_theta=1e11, rms_norm_eps=1e-5, attention_in_multiplier=0.9,
    attention_out_multiplier=0.3, key_multiplier=0.5,
    embedding_multiplier=5.65, lm_head_multiplier=0.5,
    mlp_multipliers=[0.7, 0.3], ssm_in_multiplier=1.25,
    ssm_out_multiplier=0.5, ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.35],
    mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_n_groups=2,
    mamba_d_conv=4)
ARCH = ref.arch_of(MODEL)
V, T = 512, 256


def make_cfg(dtype=jnp.float32, model=MODEL, chunk=16, hidden=256):
    m = model
    return gpt.GPTConfig(
        vocab_size=V, hidden_size=hidden, num_layers=2,
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        intermediate_size=640, max_seq_len=T, dtype=dtype, pos_embed="rope",
        norm="rmsnorm", activation="swiglu", tie_embeddings=False,
        bias=False, rope_theta=m["rope_theta"],
        embedding_multiplier=m["embedding_multiplier"],
        lm_head_multiplier=m["lm_head_multiplier"],
        attention_in_multiplier=m["attention_in_multiplier"],
        attention_out_multiplier=m["attention_out_multiplier"],
        key_multiplier=m["key_multiplier"],
        mlp_multipliers=tuple(m["mlp_multipliers"]),
        ssm=ssm.SSMConfig(
            n_heads=m["mamba_n_heads"], head_dim=m["mamba_d_head"],
            d_state=m["mamba_d_state"], n_groups=m["mamba_n_groups"],
            d_conv=m["mamba_d_conv"], chunk_size=chunk,
            in_multiplier=m["ssm_in_multiplier"],
            out_multiplier=m["ssm_out_multiplier"],
            multipliers=tuple(m["ssm_multipliers"])))


@pytest.fixture(scope="module")
def cfg():
    return make_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return gpt.init_params(cfg, jax.random.PRNGKey(0))


def tokens(seed, n):
    return np.random.default_rng(seed).integers(0, V, (n,)).astype(np.int32)


def cast(params, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------


def published_cfg(layers):
    """Falcon-H1-34B's config.json shapes (benchmarks/configs)."""
    return dataclasses.replace(
        make_cfg(jnp.bfloat16, dict(
            MODEL, num_attention_heads=20, num_key_value_heads=4,
            head_dim=128, mamba_n_heads=32, mamba_d_head=128,
            mamba_d_state=256), chunk=128, hidden=5120),
        vocab_size=261120, intermediate_size=21504, num_layers=layers)


def test_count_params_equals_the_published_shapes():
    """430,120,032 a layer: q 5120x2560 + k, v 2 x 5120x512 + o 2560x5120;
    in_proj 5120x9248 + out_proj 4096x5120 + conv 5120x4 + 5120 + dt_bias,
    A_log, D 3x32 + gated norm 4096; MLP 3 x 5120x21504; two norms: no
    bias anywhere.  Six layers, embedding, untied head and final norm:
    5,254,594,112."""
    vocab = 2 * 261120 * 5120 + 5120
    assert gpt.count_params(published_cfg(1)) - vocab == 430_120_032
    six = published_cfg(6)
    assert gpt.count_params(six) == 5_254_594_112
    shapes = jax.eval_shape(lambda k: gpt.init_params(six, k),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes)) == 5_254_594_112
    assert not [k for k in shapes["blocks"] if k.endswith("_b")
                and k != "ssm_conv_b"]
    assert shapes["blocks"]["q_w"].shape == (6, 5120, 2560)
    assert shapes["blocks"]["ssm_in_w"].shape == (6, 5120, 9248)
    assert shapes["lm_head"].shape == (261120, 5120)
    assert ssm.state_bytes(six.ssm, 6, jnp.bfloat16) == 6 * (
        32 * 128 * 256 * 4 + 3 * 5120 * 2)


def test_classic_configs_keep_their_derived_widths():
    c = gpt.GPTConfig(hidden_size=768, num_heads=12)
    assert (c.head_dim, c.ffn_size, c.q_size) == (64, 3072, 768)
    assert "lm_head" not in jax.eval_shape(
        lambda k: gpt.init_params(gpt.GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=2,
            max_seq_len=8), k), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# the full forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.08)])
def test_forward_equals_reference(params, dtype, tol):
    """float32: the chunked scan against the plain one differs by
    summation order only (reads 3e-7 on logits of spread 0.16).  bfloat16
    weights and activations against the float32 reference ON THE SAME
    bf16 weights: the logits' spread is 0.16 and their largest 0.7, one
    bf16 step there is 0.004; 0.08 is half the spread (reads 0.02)."""
    cfg = make_cfg(dtype)
    p = cast(params, dtype)
    toks = np.stack([tokens(1, 50), tokens(2, 50)])
    got = gpt.forward(p, jnp.asarray(toks), cfg).astype(jnp.float32)
    for b in range(2):
        want = ref.logits(p, toks[b], arch=ARCH)
        assert float(jnp.max(jnp.abs(got[b] - want))) < tol


def test_chunked_scan_continues_a_state(cfg, params):
    """Two chunks from the state the first left equal one chunk over
    both; a padded tail neither advances the state nor enters the conv
    window."""
    p = {k: v[0] for k, v in params["blocks"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 256), jnp.float32)
    zero = ssm.zero_state(cfg.ssm, 1, jnp.float32)
    whole, s_whole = ssm.mixer_chunk(x, p, cfg, zero)
    first, s1 = ssm.mixer_chunk(x[:, :24], p, cfg, zero)
    # the second chunk padded to 32 positions with junk past its 16
    tail = jnp.concatenate([x[:, 24:], 7.0 * jnp.ones((1, 16, 256))], 1)
    second, s2 = ssm.mixer_chunk(tail, p, cfg, s1, length=jnp.asarray(16))
    np.testing.assert_allclose(first, whole[:, :24], atol=2e-6)
    np.testing.assert_allclose(second[:, :16], whole[:, 24:], atol=2e-6)
    for n in ssm.STATE_LEAVES:
        np.testing.assert_allclose(s2[n], s_whole[n], atol=2e-6)
    # and token by token from there
    step, s3 = ssm.mixer_step(x[:, :1], p, cfg, zero)
    np.testing.assert_allclose(step, whole[:, :1], atol=2e-6)


# ---------------------------------------------------------------------------
# prefill in chunks, then decode, through the paged cache
# ---------------------------------------------------------------------------


def paged_cache(cfg, batch, block=16):
    """A fully provisioned pool with identity tables."""
    cache = kv_pool.init_paged_cache(cfg, batch, T, block_size=block)
    nmax = cache["tables"].shape[1]
    return dict(cache, tables=jnp.arange(batch * nmax, dtype=jnp.int32)
                .reshape(batch, nmax))


def through_cache(p, cfg, toks, n_prompt, width, slot=1, batch=3,
                  cache=None):
    """Prefill ``toks[:n_prompt]`` in chunks of ``width`` (the last one
    padded), then decode the rest one token a step with only ``slot``
    live: logits at every position from n_prompt - 1 on, and the cache."""
    cache = paged_cache(cfg, batch) if cache is None else cache
    pf = jax.jit(lambda c, t, p0, ln: kv_pool.paged_prefill_chunk(
        p, c, t, p0, ln, jnp.asarray(slot), cfg))
    step = jax.jit(lambda c, t, s: kv_pool.paged_decode_step_batched(
        p, c, t, s, cfg))
    for s0 in range(0, n_prompt, width):
        chunk = toks[s0:min(s0 + width, n_prompt)]
        padded = np.full((1, width), 3, np.int32)     # junk in the padding
        padded[0, :len(chunk)] = chunk
        lg, cache = pf(cache, jnp.asarray(padded), jnp.asarray(s0),
                       jnp.asarray(len(chunk)))
    out = [lg]
    live = np.zeros((batch,), bool)
    live[slot] = True
    cache = dict(cache, live=jnp.asarray(live))
    for i in range(n_prompt, len(toks)):
        tok = np.zeros((batch,), np.int32)
        pos = np.zeros((batch,), np.int32)
        tok[slot], pos[slot] = toks[i], i
        lgs, cache = step(cache, jnp.asarray(tok), jnp.asarray(pos))
        out.append(lgs[slot])
    return jnp.stack(out), cache


@pytest.mark.parametrize("n_prompt,width", [
    (32, 32),       # one whole bucket
    (33, 32),       # a chunk boundary, one position past it
    (21, 32),       # a bucket's padding (not a multiple of the scan chunk)
    (70, 32),       # three chunks, the last short
    (100, 128),     # a bucket larger than the prompt
])
def test_prefill_then_decode_equals_reference(cfg, params, n_prompt, width):
    toks = tokens(n_prompt, n_prompt + 6)
    got, _ = through_cache(params, cfg, toks, n_prompt, width)
    want = ref.logits(params, toks, arch=ARCH)[n_prompt - 1:]
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_prefill_then_decode_bfloat16(params):
    """bf16 weights, activations and KV rows, float32 state: the tolerance
    of the bf16 forward above."""
    cfg = make_cfg(jnp.bfloat16)
    p = cast(params, jnp.bfloat16)
    toks = tokens(5, 60)
    got, cache = through_cache(p, cfg, toks, 50, 32)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["conv"].dtype == jnp.bfloat16
    want = ref.logits(p, toks, arch=ARCH)[49:]
    assert float(jnp.max(jnp.abs(got - want))) < 0.08


KERNEL_MODEL = dict(MODEL, num_attention_heads=2, num_key_value_heads=1,
                    head_dim=128)


def test_kernel_route_equals_reference():
    """The paged Pallas kernel's route (interpret mode; heads of 128, so
    num_heads x head_dim = 256 only because the toy is narrow) carries the
    state in the layer scan's carry: same logits, same idle slots."""
    cfg = make_cfg(model=KERNEL_MODEL)
    p = gpt.init_params(cfg, jax.random.PRNGKey(4))
    toks = tokens(6, 44)
    old = da._INTERPRET
    da._INTERPRET = True
    try:
        assert da.paged_available((3, 1, 2, 128),
                                  (2, T // 16 * 3, 16, 1 * 128))
        calls = []
        real = da._paged_call
        da._paged_call = lambda *a, **k: calls.append(1) or real(*a, **k)
        try:
            got, cache = through_cache(p, cfg, toks, 40, 64)
        finally:
            da._paged_call = real
        assert calls
    finally:
        da._INTERPRET = old
    want = ref.logits(p, toks, arch=ref.arch_of(KERNEL_MODEL))[39:]
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    for n in ssm.STATE_LEAVES:      # slots 0 and 2 never decoded
        assert not np.asarray(cache[n][:, 0]).any()
        assert not np.asarray(cache[n][:, 2]).any()


@pytest.mark.parametrize("interpret", [False, True])
def test_idle_slot_keeps_its_state_bit_for_bit(interpret):
    """A decode step advances the live slots alone: a free slot, and one
    between the chunks of its prefill, read the same state after it, to
    the bit; a slot fed position 0 starts from zero whatever it held.
    The pool beside the state is addressed by (layer, page): each layer's
    K/V leaf takes the step's four rows and keeps every other row, of
    that layer and of the other, to the bit."""
    cfg = make_cfg(model=KERNEL_MODEL)
    p = gpt.init_params(cfg, jax.random.PRNGKey(4))
    cache = paged_cache(cfg, 4)
    junk = {n: jax.random.normal(jax.random.PRNGKey(i), cache[n].shape,
                                 jnp.float32).astype(cache[n].dtype)
            for i, n in enumerate(ssm.STATE_LEAVES + ("k", "v"))}
    cache = dict(cache, **junk, live=jnp.asarray([True, False, True, False]))
    tok = jnp.asarray([5, 6, 7, 8], jnp.int32)
    pos = jnp.asarray([9, 4, 0, 0], jnp.int32)
    old = da._INTERPRET
    da._INTERPRET = interpret
    try:
        lg, new = jax.jit(lambda c: kv_pool.paged_decode_step_batched(
            p, c, tok, pos, cfg))(cache)
    finally:
        da._INTERPRET = old
    for n in ssm.STATE_LEAVES:
        for slot in (1, 3):
            np.testing.assert_array_equal(np.asarray(new[n][:, slot]),
                                          np.asarray(junk[n][:, slot]))
        assert (np.asarray(new[n][:, 0]) != np.asarray(junk[n][:, 0])).any()
    assert cache["k"].shape == (2, 4 * T // 16, 16, 1 * 128)
    written = np.zeros(cache["k"].shape[1:3], bool)
    for slot, at in enumerate(np.asarray(pos)):
        written[np.asarray(cache["tables"])[slot, at // 16], at % 16] = True
    for n in ("k", "v"):
        got, was = np.asarray(new[n]), np.asarray(junk[n])
        np.testing.assert_array_equal(got[:, ~written], was[:, ~written])
        for layer in range(2):
            assert (got[layer][written] != was[layer][written]).any()
        assert (got[0][written] != got[1][written]).any()
    # slot 2 at position 0: what a zero state gives, not the junk's
    zero = dict(cache, **{n: jnp.zeros_like(cache[n])
                          for n in ssm.STATE_LEAVES})
    da._INTERPRET = interpret
    try:
        lg0, new0 = jax.jit(lambda c: kv_pool.paged_decode_step_batched(
            p, c, tok, pos, cfg))(zero)
    finally:
        da._INTERPRET = old
    np.testing.assert_array_equal(np.asarray(lg[2]), np.asarray(lg0[2]))
    for n in ssm.STATE_LEAVES:
        np.testing.assert_array_equal(np.asarray(new[n][:, 2]),
                                      np.asarray(new0[n][:, 2]))


def test_prefill_at_position_zero_starts_from_zero(cfg, params):
    """An admission reads no state: a slot reused after retirement gives
    the logits of a fresh one, to the bit."""
    toks = tokens(8, 30)
    fresh, _ = through_cache(params, cfg, toks, 24, 32)
    dirty = paged_cache(cfg, 3)
    dirty = dict(dirty, **{
        n: jnp.full(dirty[n].shape, 3.0, dirty[n].dtype)
        for n in ssm.STATE_LEAVES})
    reused, _ = through_cache(params, cfg, toks, 24, 32, cache=dirty)
    np.testing.assert_array_equal(np.asarray(fresh), np.asarray(reused))


# ---------------------------------------------------------------------------
# DecodeServer
# ---------------------------------------------------------------------------

PROMPTS = [5, 37, 64, 100, 17, 130]


def serve(params, cfg, prompts, max_new=10, max_batch=4, **kw):
    srv = serving.DecodeServer(params, cfg, max_batch=max_batch, max_len=T,
                               layout="paged", block_size=16, **kw)
    rids = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    for _ in range(2000):
        if not srv.pending():
            break
        srv.tick()
    assert not srv.pending()
    return srv, [srv.result(r) for r in rids]


def worst_margin(params, prompts, outs):
    return max(float(ref.served_margins(params, p, o, arch=ARCH,
                                        pad_to=T).max())
               for p, o in zip(prompts, outs))


@pytest.mark.parametrize("kw", [
    {}, {"async_dispatch": True}, {"prefill_chunk": 32},
    {"prefill_budget": 32, "async_dispatch": True}, {"prefill": False}],
    ids=["bucket", "async", "chunk32", "budget32-async", "no-prefill"])
def test_served_tokens_are_the_reference_argmax(cfg, params, kw):
    """Six requests on four slots (so slots are reused, and under the
    budget a long prompt's chunks interleave with the others' decode
    steps): every served token is the float32 reference's argmax at its
    position (margin 0 but for ties: 1e-5)."""
    prompts = [tokens(10 + n, n) for n in PROMPTS]
    srv, outs = serve(params, cfg, prompts, **kw)
    assert [len(o) for o in outs] == [10] * 6
    assert worst_margin(params, prompts, outs) < 1e-5


def test_async_equals_sync_and_a_reused_slot_equals_a_fresh_one(cfg, params):
    prompts = [tokens(30 + n, n) for n in PROMPTS]
    _, sync = serve(params, cfg, prompts)
    _, asyn = serve(params, cfg, prompts, async_dispatch=True)
    assert sync == asyn
    # one slot, so every request but the first inherits a tenant's state
    _, one = serve(params, cfg, prompts, max_batch=1)
    assert one == sync


def test_idle_slots_of_a_server_keep_their_state(cfg, params):
    """Through ticks that decode one request, the other slots' state
    leaves do not change by a bit; the retired slot's next tenant starts
    from zero (the same tokens as alone)."""
    srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=T,
                               layout="paged", block_size=16)
    srv.submit(tokens(50, 20), max_new_tokens=6)
    (slot,) = srv._slots
    idle = [s for s in range(3) if s != slot]
    before = {n: np.asarray(srv.cache[n][:, idle]) for n in ssm.STATE_LEAVES}
    moved = np.asarray(srv.cache["ssm"][:, slot])
    while srv.pending():
        srv.tick()
    for n in ssm.STATE_LEAVES:
        np.testing.assert_array_equal(np.asarray(srv.cache[n][:, idle]),
                                      before[n])
    assert (np.asarray(srv.cache["ssm"][:, slot]) != moved).any()


def test_admitting_slot_is_not_advanced_by_decode_steps(cfg, params):
    """Between the chunks of a co-scheduled prefill the slot rides decode
    steps at its frontier; its state is the prefill's alone."""
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=T,
                               layout="paged", block_size=16,
                               prefill_budget=32)
    srv.submit(tokens(60, 12), max_new_tokens=40)
    long_rid = srv.submit(tokens(61, 120), max_new_tokens=4)
    seen = 0
    for _ in range(400):
        if not srv.pending():
            break
        adm = [s for s, st in srv._slots.items() if st.get("admitting")]
        before = ({n: np.asarray(srv.cache[n][:, adm[0]])
                   for n in ssm.STATE_LEAVES}, adm[0],
                  srv._slots[adm[0]]["admit_i"]) if adm else None
        srv.tick()
        if before and before[1] in srv._slots \
                and srv._slots[before[1]].get("admit_i") == before[2]:
            # the tick ran a decode step and no chunk of this slot
            seen += 1
            for n in ssm.STATE_LEAVES:
                np.testing.assert_array_equal(
                    np.asarray(srv.cache[n][:, before[1]]), before[0][n])
    assert not srv.pending()
    out = srv.result(long_rid)
    assert float(ref.served_margins(params, tokens(61, 120), out, arch=ARCH,
                                    pad_to=T).max()) < 1e-5


def test_counters_and_gauge(cfg, params):
    telemetry.reset()
    prompts = [tokens(70 + n, n) for n in (9, 40, 33)]
    srv, _ = serve(params, cfg, prompts, max_batch=2)
    counters = telemetry.snapshot()["counters"]
    assert counters["kv_pool.state_resets"] == 3
    assert counters["kv_pool.prefix_skipped_recurrent"] == 3
    assert counters.get("kv_pool.prefix_hits", 0) == 0
    assert srv._pool.prefix_entries == 0
    assert telemetry.gauge("kv_pool.state_bytes").get() == 2 * \
        ssm.state_bytes(cfg.ssm, 2, jnp.float32)


def test_scopes_of_a_closed_server_can_still_be_read(cfg, params):
    """The per-part device metrics ask the program for its op_name paths
    AFTER the serving (the benchmark closes the server first, and the
    reference's work in between lets the cyclic collector run): the
    mixer's scopes are there, on the decode step and on the prefill."""
    import gc
    import time

    t0 = time.perf_counter()
    srv, _ = serve(params, cfg, [tokens(90, 20)], max_new=3,
                   async_dispatch=True)
    srv.close()
    gc.collect()
    scopes = {e["name"]: set(e["ops"].values())
              for e in telemetry.executable_scopes(t0)}
    step = " ".join(scopes["serving.async_step"])
    assert "serving.async_step" in step
    for name in ("ssm", "ssm_conv", "ssm_update", "attn", "mlp", "lm_head",
                 "sample"):
        assert f"{name})" in step or f"{name}/" in step, name
    prefill = " ".join(scopes["serving.paged_prefill@32"])
    assert "ssm_scan" in prefill and "ssm_conv" in prefill


def test_eviction_rebuilds_the_state(cfg, params):
    """The OOM chain evicts a slot and re-admits it from its prompt plus
    what it generated: the re-prefill rebuilds the state, and the tokens
    are those of an undisturbed run."""
    from paddle_tpu import faults

    prompts = [tokens(80 + n, n) for n in (20, 35)]
    _, calm = serve(params, cfg, prompts, max_new=12, max_batch=2)
    telemetry.reset()
    faults.install("oom:tick:3")        # two slots decoding: one is evicted
    try:
        srv, shaken = serve(params, cfg, prompts, max_new=12, max_batch=2)
    finally:
        faults.reset()
    counters = telemetry.snapshot()["counters"]
    assert counters.get("resilience.oom_evictions", 0) >= 1
    assert counters["kv_pool.state_resets"] >= 3    # the re-admission's
    assert shaken == calm
    assert worst_margin(params, prompts, shaken) < 1e-5


# ---------------------------------------------------------------------------
# what cannot work with a recurrent state yet says so at construction
# ---------------------------------------------------------------------------


def server(params, cfg, **kw):
    kw.setdefault("layout", "paged")
    return serving.DecodeServer(params, cfg, max_batch=2, max_len=T,
                                block_size=16, **kw)


@pytest.mark.parametrize("kw,word", [
    ({"layout": "contiguous"}, "contiguous"),
    ({"spec_k": 3}, "speculation"),
    ({"spec_tree": 4}, "speculation"),
    ({"draft_cfg": "cfg", "draft_params": "params"}, "speculation"),
    ({"adapter_pool": object()}, "adapter_pool"),
    ({"mesh": "mesh"}, "mesh"),
])
def test_construction_refuses(cfg, params, kw, word):
    kw = {k: {"cfg": cfg, "params": params}.get(v, v) if isinstance(v, str)
          and k != "layout" else v for k, v in kw.items()}
    if "mesh" in kw:
        kw["mesh"] = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))
    with pytest.raises(NotImplementedError, match=word):
        server(params, cfg, **kw)


def test_environment_and_widths_refuse(cfg, params, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_KV_SPILL_MB", "4")
    with pytest.raises(NotImplementedError, match="spill"):
        server(params, cfg)
    monkeypatch.delenv("PADDLE_TPU_KV_SPILL_MB")
    monkeypatch.setenv("PADDLE_TPU_KV_RADIX", "1")
    with pytest.raises(NotImplementedError, match="prefix reuse"):
        server(params, cfg)
    monkeypatch.delenv("PADDLE_TPU_KV_RADIX")
    monkeypatch.setenv("PADDLE_TPU_SPEC_K", "3")
    with pytest.raises(NotImplementedError, match="speculation"):
        server(params, cfg)
    monkeypatch.delenv("PADDLE_TPU_SPEC_K")
    for kw in ({"prefill_chunk": 48}, {"prefill_budget": 48}):
        with pytest.raises(ValueError, match="must divide"):
            server(params, cfg, **kw)


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
    with pytest.raises(ValueError, match="moe"):
        from paddle_tpu.text.moe import MoEConfig

        dataclasses.replace(cfg, activation="gelu",
                            moe=MoEConfig(num_experts=2, top_k=1))


def test_no_new_engine_kinds():
    """The model is served through the step kinds there were (36 at this
    PR's parent): the steps branch on the configuration and on the leaves
    the cache holds."""
    assert len(engine.kinds()) == 36
    assert not [k for k in engine.kinds() if "ssm" in k or "hybrid" in k]
