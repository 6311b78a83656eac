"""Continuous-batching decode server (text/serving.py).

The correctness property that matters: a request served in a SHARED cache
alongside strangers — admitted mid-flight into a reused slot, batched with
sequences at different positions — must produce exactly the tokens the
model produces for that prompt alone.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.text import generate as G
from paddle_tpu.text import gpt, serving, woq


def _cfg(**over):
    kw = dict(vocab_size=32, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64)
    kw.update(over)
    return gpt.GPTConfig(**kw)


def _greedy_reference(params, cfg, prompt, max_new):
    """Sequential scalar-pos decode_step loop — same kernel, one request."""
    cache = G.init_cache(cfg, 1, cfg.max_seq_len)
    out = []
    tok = None
    for pos in range(len(prompt) + max_new - 1):
        cur = prompt[pos] if pos < len(prompt) else tok
        logits, cache = G.decode_step(params, cache,
                                      jnp.asarray([cur], jnp.int32),
                                      pos, cfg)
        if pos >= len(prompt) - 1:
            tok = int(np.asarray(jnp.argmax(logits, -1))[0])
            out.append(tok)
    return out


def test_batched_step_matches_scalar_step():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    cache_s = G.init_cache(cfg, 3, 16)
    cache_b = G.init_cache(cfg, 3, 16)
    tok = jnp.asarray([1, 2, 3], jnp.int32)
    # equal positions: batched must equal the scalar-pos step exactly
    ls, cache_s = G.decode_step(params, cache_s, tok, 0, cfg)
    lb, cache_b = serving.decode_step_batched(
        params, cache_b, tok, jnp.zeros((3,), jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(lb), np.asarray(ls),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache_b["k"]),
                               np.asarray(cache_s["k"]), rtol=1e-5,
                               atol=1e-5)


def test_server_matches_solo_decode_for_staggered_requests():
    """Three prompts of different lengths, submitted at different times,
    sharing slots — each result equals its solo sequential decode."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (3, 7, 2)]
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                               prefill=False)
    r0 = srv.submit(prompts[0], max_new_tokens=6)
    r1 = srv.submit(prompts[1], max_new_tokens=4)
    # max_batch=2: the third request must WAIT for a freed slot
    r2 = srv.submit(prompts[2], max_new_tokens=5)
    ticks = 0
    while srv.pending():
        srv.tick()
        ticks += 1
        assert ticks < 200
    for rid, prompt, max_new in ((r0, prompts[0], 6), (r1, prompts[1], 4),
                                 (r2, prompts[2], 5)):
        want = _greedy_reference(params, cfg, prompt, max_new)
        assert srv.result(rid) == want, rid


def test_slot_reuse_without_cache_clearing():
    """A slot freed by a finished request serves a new one correctly: the
    causal mask hides the previous tenant's stale cache rows."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(2))
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                               prefill=False)
    rng = np.random.default_rng(1)
    p1 = list(rng.integers(0, cfg.vocab_size, 9))   # long first tenant
    p2 = list(rng.integers(0, cfg.vocab_size, 2))   # short second tenant
    r1 = srv.submit(p1, max_new_tokens=8)
    r2 = srv.submit(p2, max_new_tokens=8)
    while srv.pending():
        srv.tick()
    assert srv.result(r1) == _greedy_reference(params, cfg, p1, 8)
    assert srv.result(r2) == _greedy_reference(params, cfg, p2, 8)


def test_eos_frees_slot_early():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(3))
    # discover the model's first greedy token for a probe prompt, then use
    # it as the eos id so the request terminates on step one
    probe = _greedy_reference(params, cfg, [4, 5], 1)[0]
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                               eos_id=probe, prefill=False)
    rid = srv.submit([4, 5], max_new_tokens=20)
    while srv.pending():
        srv.tick()
    got = srv.result(rid)
    assert got[-1] == probe and len(got) < 20


def test_quantized_params_serve():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(4))
    q = woq.quantize_gpt_int8(params)
    srv = serving.DecodeServer(q, cfg, max_batch=2, max_len=32)
    rid = srv.submit([1, 2, 3], max_new_tokens=4)
    while srv.pending():
        srv.tick()
    assert len(srv.result(rid)) == 4


def test_submit_rejects_overlong():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(5))
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=16)
    with pytest.raises(ValueError, match="exceeds"):
        srv.submit(list(range(10)), max_new_tokens=10)
    with pytest.raises(ValueError, match="empty"):
        srv.submit([], max_new_tokens=1)


def test_post_prompt_feeds_generated_token_not_prompt_tail():
    """Direct wrong-input detector (a stub whose next token = fed + 1):
    after the prompt, each step must be fed the PREVIOUS GENERATED token,
    so outputs climb by one — feeding prompt[-1] forever would return a
    constant.  Random-init models can't catch this (greedy decode
    collapses to an attractor token); the stub can."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(6))
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                               prefill=False)

    def stub_step(p, cache, tok, pos):
        logits = jax.nn.one_hot((tok + 1) % cfg.vocab_size, cfg.vocab_size)
        return logits, cache

    srv._step = stub_step
    rid = srv.submit([5, 3, 9], max_new_tokens=5)
    while srv.pending():
        srv.tick()
    assert srv.result(rid) == [10, 11, 12, 13, 14]


def test_served_markov_model_follows_the_rule(markov_gpt):
    """Trained-model capstone: sequences served in shared slots continue
    the learned rule next = (t*3+1) % 13 — the next token depends on the
    fed token, so the scheduler's feeding is exercised for real."""
    cfg, params = markov_gpt
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=30)
    rids = [srv.submit([s], max_new_tokens=10) for s in (2, 7, 11)]
    while srv.pending():
        srv.tick()
    for rid, start in zip(rids, (2, 7, 11)):
        seq = [start] + srv.result(rid)
        for a, b in zip(seq[:-1], seq[1:]):
            assert b == (a * 3 + 1) % 13, (start, seq)


def test_prefill_logits_match_sequential_feeding():
    """prefill_slot's last-position logits equal the token-by-token
    decode_step logits at the same position (bf16 attention-order
    tolerance), and the cache rows it writes continue decoding exactly."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(7))
    prompt = [3, 9, 1, 7, 4]
    # sequential reference
    cache_r = G.init_cache(cfg, 1, 32)
    for pos in range(len(prompt) - 1):
        _, cache_r = G.decode_step(params, cache_r,
                                   jnp.asarray([prompt[pos]], jnp.int32),
                                   pos, cfg)
    want, cache_r = G.decode_step(
        params, cache_r, jnp.asarray([prompt[-1]], jnp.int32),
        len(prompt) - 1, cfg)
    # prefill: padded to bucket 8, slot 0 of a 2-slot cache
    cache_p = G.init_cache(cfg, 2, 32)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :len(prompt)] = prompt
    got, cache_p = G.prefill_slot(params, cache_p, jnp.asarray(padded),
                                  jnp.asarray(len(prompt)),
                                  jnp.asarray(0), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[0],
                               rtol=2e-2, atol=5e-3)
    # written rows match the sequential cache on the valid prefix...
    np.testing.assert_allclose(
        np.asarray(cache_p["k"][:, 0, :len(prompt)]),
        np.asarray(cache_r["k"][:, 0, :len(prompt)]), rtol=2e-2, atol=5e-3)
    # ...and padded rows beyond the prompt were NOT written
    assert np.asarray(cache_p["k"][:, 0, len(prompt):8]).max() == 0


def test_served_markov_with_prefill_follows_rule(markov_gpt):
    """The default (prefill on) server still continues the learned rule —
    admission prefill + per-tick decode compose correctly."""
    cfg, params = markov_gpt
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=30)
    rids = [srv.submit([s, (s * 3 + 1) % 13], max_new_tokens=8)
            for s in (2, 7, 11)]
    ticks = 0
    while srv.pending():
        srv.tick()
        ticks += 1
    for rid, start in zip(rids, (2, 7, 11)):
        seq = [start, (start * 3 + 1) % 13] + srv.result(rid)
        for a, b in zip(seq[:-1], seq[1:]):
            assert b == (a * 3 + 1) % 13, (start, seq)
    # prompts were consumed by prefill, not ticks: 3 requests x 8 tokens
    # on 2 slots needs at most ~2 waves of 7 post-admission ticks
    assert ticks <= 16, ticks


def test_prefill_default_matches_solo_on_trained(markov_gpt):
    """The DEFAULT configuration (prefill on): served tokens equal the
    solo sequential decode — on the trained model whose margins make the
    equality robust to chunked-vs-stepwise bf16 noise."""
    cfg, params = markov_gpt
    prompts = [[2, 7, 9], [11], [5, 3]]
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=30)
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    while srv.pending():
        srv.tick()
    for rid, p in zip(rids, prompts):
        assert srv.result(rid) == _greedy_reference(params, cfg, p, 6), p


def test_prefill_eos_at_admission_frees_slot(markov_gpt):
    """EOS produced BY the prefill step itself: the request completes at
    admission, the slot is recycled inside the same _admit loop, and the
    next queued request is served."""
    cfg, params = markov_gpt
    # the trained rule: prompt [2] greedily yields (2*3+1)%13 = 7 first
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=30,
                               eos_id=7)
    r1 = srv.submit([2], max_new_tokens=10)   # completes at admission
    r2 = srv.submit([5], max_new_tokens=3)    # must still get the slot
    while srv.pending():
        srv.tick()
    assert srv.result(r1) == [7]
    assert srv.result(r2) == _greedy_reference(params, cfg, [5], 3)


def test_prefill_max_new_one_completes_at_admission(markov_gpt):
    cfg, params = markov_gpt
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=30)
    rid = srv.submit([2, 7], max_new_tokens=1)
    # no ticks needed: prefill already produced the single token
    assert not srv.pending()
    assert srv.result(rid) == _greedy_reference(params, cfg, [2, 7], 1)


def test_prefill_parity_gqa():
    """GQA prefill (unrepeated projection + repeat for attention): written
    cache rows and last-position logits match the sequential feed."""
    cfg = _cfg(num_kv_heads=2)
    params = gpt.init_params(cfg, jax.random.PRNGKey(8))
    prompt = [3, 9, 1, 7]
    cache_r = G.init_cache(cfg, 1, 32)
    want = None
    for pos in range(len(prompt)):
        want, cache_r = G.decode_step(
            params, cache_r, jnp.asarray([prompt[pos]], jnp.int32), pos,
            cfg)
    cache_p = G.init_cache(cfg, 2, 32)
    padded = np.zeros((1, 4), np.int32)
    padded[0, :] = prompt
    got, cache_p = G.prefill_slot(params, cache_p, jnp.asarray(padded),
                                  jnp.asarray(4), jnp.asarray(0), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[0],
                               rtol=2e-2, atol=5e-3)
    np.testing.assert_allclose(np.asarray(cache_p["k"][:, 0, :4]),
                               np.asarray(cache_r["k"][:, 0, :4]),
                               rtol=2e-2, atol=5e-3)


def test_stop_sequences_end_generation(markov_gpt):
    """A multi-token stop sequence ends the request the moment the
    generated tail matches it (sequence included in the result)."""
    cfg, params = markov_gpt
    # the rule from 2: 7, 9, 2, 7, 9, 2 ... -> stop at the [9, 2] tail
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=30)
    rid = srv.submit([2], max_new_tokens=12, stop=[[9, 2]])
    while srv.pending():
        srv.tick()
    got = srv.result(rid)
    assert got[-2:] == [9, 2] and len(got) < 12, got

    # a stop sequence that never occurs: runs to max_new
    rid2 = srv.submit([2], max_new_tokens=6, stop=[[12, 12, 12]])
    while srv.pending():
        srv.tick()
    assert len(srv.result(rid2)) == 6

    import pytest as _pytest
    with _pytest.raises(ValueError, match="empty stop"):
        srv.submit([2], max_new_tokens=3, stop=[[]])


# ---------------------------------------------------------------------------
# device-resident block tick (round-5: one host fetch per `block` tokens)
# ---------------------------------------------------------------------------


def _serve(params, cfg, prompts, max_new, block=None, **kw):
    srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=40, **kw)
    rids = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    ticks = 0
    while srv.pending():
        srv.tick_block(block) if block else srv.tick()
        ticks += 1
        assert ticks < 300
    return [srv.result(r) for r in rids]


def test_tick_block_matches_single_ticks():
    """Block sizes 1/4/8 over 4 requests contending for 3 slots (slot
    reuse + overrun mid-block) must reproduce the per-token tick path
    token-for-token."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 3, 9, 1)]
    ref = _serve(params, cfg, prompts, 11)
    for block in (1, 4, 8):
        assert _serve(params, cfg, prompts, 11, block=block) == ref, block


def test_tick_block_prompt_feeding_falls_back():
    """prefill=False servers still consume prompts token-by-token under
    tick_block (logits-discarded positions can't batch); results match."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (6, 2)]
    assert (_serve(params, cfg, prompts, 8, block=4, prefill=False)
            == _serve(params, cfg, prompts, 8, prefill=False))


def test_tick_block_feeds_generated_token(markov_gpt):
    """Wrong-input detector on the block path: the trained markov model's
    next token depends on the FED token, so any feedback error inside the
    device-side scan would break the rule chain."""
    cfg, params = markov_gpt
    got = _serve(params, cfg, [[2], [5]], 9, block=4)
    for first, out in zip((2, 5), got):
        want, t = [], first
        for _ in range(9):
            t = (t * 3 + 1) % 13
            want.append(t)
        assert out == want


def test_tick_block_eos_and_stop(markov_gpt):
    """EOS and stop sequences end requests mid-block; surplus block tokens
    are discarded."""
    cfg, params = markov_gpt
    # rule from 2: 7, 9, 2, 7, 9, 2 ... -> [9, 2] tail stops it
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=30)
    rid = srv.submit([2], max_new_tokens=12, stop=[[9, 2]])
    while srv.pending():
        srv.tick_block(5)
    got = srv.result(rid)
    assert got[-2:] == [9, 2] and len(got) < 12, got
    srv2 = serving.DecodeServer(params, cfg, max_batch=1, max_len=30,
                                eos_id=9)
    rid2 = srv2.submit([2], max_new_tokens=12)
    while srv2.pending():
        srv2.tick_block(5)
    g2 = srv2.result(rid2)
    assert g2[-1] == 9 and len(g2) < 12, g2


# ---------------------------------------------------------------------------
# MoE chunked prefill (round-5): padding claims no expert capacity
# ---------------------------------------------------------------------------


def _moe_cfg():
    from paddle_tpu.text.moe import MoEConfig

    return _cfg(moe=MoEConfig(num_experts=4, top_k=2, capacity_factor=1.25,
                              router_noise=0.0))


def test_route_padding_claims_zero_capacity():
    """Dropped-token counters, directly on the router: with a valid mask,
    pad rows dispatch NOWHERE (zero capacity slots consumed) and every
    valid token keeps all top_k assignments under the dropless bound —
    and the valid prefix routes exactly as the unpadded prompt would."""
    import jax.numpy as jnp
    from paddle_tpu.text import moe

    cfg = _moe_cfg().moe
    rng = np.random.default_rng(0)
    n, pad = 6, 10          # 6 real tokens in a 16-bucket
    xf = jnp.asarray(rng.standard_normal((n + pad, 32)), jnp.float32)
    params = moe.init_moe_params(jax.random.PRNGKey(0), 32, 64, cfg)
    valid = jnp.arange(n + pad) < n
    C = n + pad             # dropless
    disp, comb, aux = moe._route(params, xf, cfg, None, cfg.num_experts,
                                 C, jnp.float32, valid=valid)
    disp = np.asarray(disp)
    assert disp[n:].sum() == 0            # pads consumed zero capacity
    assert (disp[:n].sum(axis=(1, 2)) == cfg.top_k).all()  # nothing dropped
    # prefix parity: same tokens without padding route to the same slots
    d2, c2, _ = moe._route(params, xf[:n], cfg, None, cfg.num_experts,
                           C, jnp.float32)
    np.testing.assert_array_equal(disp[:n, :, :], np.asarray(d2)[:, :, :])
    np.testing.assert_allclose(np.asarray(comb)[:n], np.asarray(c2),
                               rtol=1e-6, atol=1e-6)


def test_moe_prefill_logits_match_sequential_feeding():
    """prefill_slot on a padded bucket == feeding the prompt stepwise
    through decode_step, for an MoE model (round-4 gap: MoE admission was
    O(prompt_len) device steps because padding would eat capacity)."""
    cfg = _moe_cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(4))
    prompt = [5, 3, 9, 1]
    cache_r = G.init_cache(cfg, 1, 16)
    for pos, tok in enumerate(prompt):
        want, cache_r = G.decode_step(params, cache_r,
                                      jnp.asarray([tok], jnp.int32),
                                      pos, cfg)
    cache_p = G.init_cache(cfg, 1, 16)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :4] = prompt
    got, cache_p = G.prefill_slot(params, cache_p, jnp.asarray(padded),
                                  jnp.asarray(4), jnp.asarray(0), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[0],
                               rtol=2e-2, atol=5e-3)
    np.testing.assert_allclose(np.asarray(cache_p["k"][:, 0, :4]),
                               np.asarray(cache_r["k"][:, 0, :4]),
                               rtol=2e-2, atol=5e-3)


def test_moe_server_prefill_matches_stepwise_serving():
    """End-to-end: an MoE DecodeServer with chunked-prefill admission
    produces the same tokens as the token-by-token path and as solo
    decode (single slot: no batch capacity contention in the ticks)."""
    cfg = _moe_cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(2)
    prompt = list(rng.integers(0, cfg.vocab_size, 5))
    want = _greedy_reference(params, cfg, prompt, 7)

    for prefill in (True, False):
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                                   prefill=prefill)
        rid = srv.submit(prompt, max_new_tokens=7)
        ticks = 0
        while srv.pending():
            srv.tick()
            ticks += 1
            assert ticks < 100
        assert srv.result(rid) == want, prefill
    # prefill admission really is O(1) ticks: after submit, only the
    # 6 generate ticks remain (first token came from admission)
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32)
    rid = srv.submit(prompt, max_new_tokens=7)
    ticks = 0
    while srv.pending():
        srv.tick()
        ticks += 1
    assert ticks == 6, ticks


# ---------------------------------------------------------------------------
# executable-cache hygiene (round-5): bounded growth + explicit release
# ---------------------------------------------------------------------------


def test_step_cache_bounded_and_close_releases():
    """Cycling many model configs through servers must not grow the jit
    cache beyond its LRU bound, and close() eagerly drops a config's
    executables."""
    before = len(serving._STEP_CACHE)
    bound = serving._STEP_CACHE.maxsize
    cfgs = [_cfg(hidden_size=32 + 16 * i) for i in range(4)]
    for cfg in cfgs:
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
        with serving.DecodeServer(params, cfg, max_batch=1,
                                  max_len=16) as srv:
            rid = srv.submit([1, 2], max_new_tokens=2)
            while srv.pending():
                srv.tick()
            assert len(srv.result(rid)) == 2
        # close() dropped this config's prefill/step entries
        ck = G._cfg_key(cfg)
        assert not any(k == ck or (isinstance(k, tuple) and ck in k)
                       for k in serving._STEP_CACHE.keys())
    assert len(serving._STEP_CACHE) <= max(before, bound)
    assert len(serving._STEP_CACHE) <= bound


def test_gen_cache_lru_evicts():
    lru = G._LRU(3)
    for i in range(5):
        lru[("k", i)] = i
    assert len(lru) == 3
    assert lru.get(("k", 0)) is None and lru.get(("k", 4)) == 4
    # touching an entry protects it from the next eviction
    lru.get(("k", 2))
    lru[("k", 9)] = 9
    assert lru.get(("k", 2)) == 2 and lru.get(("k", 3)) is None


def test_tick_block_zero_rejected_and_close_abandons():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(7))
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=16)
    rid = srv.submit([1, 2], max_new_tokens=4)
    import pytest as _pytest
    with _pytest.raises(ValueError, match="block"):
        srv.tick_block(0)
    srv.close()     # rid still mid-flight -> abandoned, not a bare KeyError
    with _pytest.raises(RuntimeError, match="abandoned"):
        srv.result(rid)


# ---------------------------------------------------------------------------
# per-request sampling (round-5): temperature/top-k/top-p per slot
# ---------------------------------------------------------------------------


def _law_after_prompt(params, cfg, prompt, temperature, top_k, top_p):
    cache = G.init_cache(cfg, 1, cfg.max_seq_len)
    for pos, tok in enumerate(prompt):
        l, cache = G.decode_step(params, cache,
                                 jnp.asarray([tok], jnp.int32), pos, cfg)
    return G._filtered_probs(np.asarray(l)[0], temperature, top_k, top_p)


def _chi2_counts(counts, law, n):
    keep = law * n >= 5
    o = np.concatenate([counts[keep], [counts[~keep].sum()]])
    e = np.maximum(np.concatenate([law[keep] * n,
                                   [law[~keep].sum() * n]]), 1e-12)
    return float(((o - e) ** 2 / e).sum()), int(keep.sum())


@pytest.mark.parametrize("asked", [dict(top_p=0.95), dict(top_k=4), {}],
                         ids=["top_p", "top_k", "no_filter"])
def test_sampled_tick_matches_sampled_tick_block(asked):
    """Same seed, same step counters: per-token ticks and block ticks
    draw identical samples (the fold_in(base, step) schedule), through
    the sampler's sorted branch and through its plain draw."""
    cfg = _cfg(vocab_size=12)
    params = gpt.init_params(cfg, jax.random.PRNGKey(8))
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, 12, n)) for n in (4, 2, 6)]

    def run(block):
        srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=32,
                                   seed=11)
        rids = [srv.submit(p, max_new_tokens=9, temperature=1.2,
                           **asked) for p in prompts]
        while srv.pending():
            srv.tick_block(block) if block else srv.tick()
        return [srv.result(r) for r in rids]

    ref = run(None)
    for block in (1, 3, 8):
        assert run(block) == ref, block


def test_mixed_greedy_and_sampled_batch():
    """A greedy request batched with sampled strangers must produce its
    solo greedy tokens exactly (per-slot temp 0 takes raw argmax)."""
    cfg = _cfg(vocab_size=12)
    params = gpt.init_params(cfg, jax.random.PRNGKey(9))
    rng = np.random.default_rng(4)
    gp = list(rng.integers(0, 12, 5))
    want = _greedy_reference(params, cfg, gp, 8)
    srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=32,
                               seed=2)
    rg = srv.submit(gp, max_new_tokens=8)  # greedy
    rs1 = srv.submit(list(rng.integers(0, 12, 3)), max_new_tokens=8,
                     temperature=1.5)
    rs2 = srv.submit(list(rng.integers(0, 12, 2)), max_new_tokens=8,
                     temperature=0.8, top_p=0.9)
    while srv.pending():
        srv.tick_block(4)
    assert srv.result(rg) == want
    for r in (rs1, rs2):
        out = srv.result(r)
        assert len(out) == 8 and all(0 <= t < 12 for t in out)


def test_sampled_serving_follows_target_law_tick_path():
    """Chi-square: with prefill=False and max_new=1 the generated token
    comes from the DEVICE sampler (_sample_batched) — its distribution
    over server seeds must match the exact filtered law."""
    cfg = _cfg(vocab_size=12)
    params = gpt.init_params(cfg, jax.random.PRNGKey(9))
    prompt = [4, 7]
    n = 200
    law = _law_after_prompt(params, cfg, prompt, 1.3, 0, 1.0)
    toks = []
    for i in range(n):
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=16,
                                   prefill=False, seed=100 + i)
        rid = srv.submit(prompt, max_new_tokens=1, temperature=1.3)
        while srv.pending():
            srv.tick()
        toks.append(srv.result(rid)[0])
    counts = np.bincount(toks, minlength=12).astype(float)
    stat, df = _chi2_counts(counts, law, n)
    assert stat < 3 * max(df, 1) + 10, stat


def test_sampled_admission_follows_target_law_prefill_path():
    """Chi-square for the host-side admission draw (prefill first
    token), including nucleus-support respect."""
    cfg = _cfg(vocab_size=12)
    params = gpt.init_params(cfg, jax.random.PRNGKey(9))
    prompt = [4, 7]
    n = 200
    law = _law_after_prompt(params, cfg, prompt, 0.9, 0, 0.7)
    toks = []
    for i in range(n):
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=16,
                                   seed=500 + i)
        rid = srv.submit(prompt, max_new_tokens=1, temperature=0.9,
                         top_p=0.7)
        while srv.pending():
            srv.tick()
        toks.append(srv.result(rid)[0])
    counts = np.bincount(toks, minlength=12).astype(float)
    stat, df = _chi2_counts(counts, law, n)
    assert stat < 3 * max(df, 1) + 10, stat
    assert counts[law == 0].sum() == 0


# ---------------------------------------------------------------------------
# the sampler does what the batch asks for (PR 34): an all-greedy step takes
# an argmax, a sampled step with no filter asked scales and draws, a filtered
# step sorts once.  The reference below is the formula as it stood: two
# sorts, every batch.
# ---------------------------------------------------------------------------


def _two_sort_filter(logits, temperature, top_k, top_p, xp=jnp):
    V = logits.shape[-1]
    lead = logits.shape[:-1]

    def bc(a, dt):
        return xp.broadcast_to(xp.asarray(a, dt), lead)[..., None]

    t = bc(temperature, xp.float32)
    tk = bc(top_k, xp.int32)
    tp = bc(top_p, xp.float32)
    x = xp.where(t > 0, logits / xp.maximum(t, 1e-6), logits)
    srt = xp.sort(x, axis=-1)[..., ::-1]
    kth = xp.take_along_axis(srt, xp.clip(tk - 1, 0, V - 1), axis=-1)
    x = xp.where((tk > 0) & (x < kth), -1e30, x)
    srt2 = xp.sort(x, axis=-1)[..., ::-1]
    e = xp.exp(srt2 - srt2[..., :1])
    probs = e / xp.sum(e, axis=-1, keepdims=True)
    keep = xp.cumsum(probs, axis=-1) - probs < tp
    kth_idx = xp.sum(keep, axis=-1, keepdims=True) - 1
    cutoff = xp.take_along_axis(srt2, kth_idx, axis=-1)
    return xp.where((tp < 1.0) & (x < cutoff), -1e30, x)


def _two_sort_sample(logits, key, temp, topk, topp, mask=None):
    if mask is not None:
        logits = logits + mask
    scaled = _two_sort_filter(logits, temp, topk, topp)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0.0, sampled, greedy)


_SLOTS, _VOCAB = 6, 40
# per-slot (temp, topk, topp), and whether the batch carries a constraint mask
_BATCHES = {
    "all_greedy": ([0, 0, 0, 0, 0, 0], [0, 3, 0, 0, 0, 0],
                   [1, 1, .5, 1, 1, 1], False),
    "mixed": ([0, 1.2, 0, .7, 0, 2.], [0, 5, 3, 0, 0, 0],
              [1, 1, .5, .9, 1, 1], False),
    "no_filter": ([1, 1.2, 0, .7, 0, 2.], [0, 0, 4, 0, 0, 0],
                  [1, 1, 1, 1, .6, 1], False),
    "top_k": ([1, 1.2, .3, .7, 1, 2.], [1, 5, 3, 40, 90, 0],
              [1, 1, 1, 1, 1, 1], False),
    "top_p": ([1, 1.2, .3, .7, 1, 2.], [0, 0, 0, 0, 0, 0],
              [.9, .5, .05, 1, .99, .7], False),
    "both": ([1, 1.2, .3, .7, 1, 2.], [2, 5, 0, 7, 40, 3],
             [.9, .5, .8, 1, .3, .7], False),
    "masked": ([0, 1.2, 0, .7, 1, 2.], [0, 5, 0, 0, 2, 0],
               [1, 1, 1, .9, 1, 1], True),
}


def _batch_arrays(name):
    from paddle_tpu.text import adapters

    temp, topk, topp, masked = _BATCHES[name]
    rng = np.random.default_rng(len(name))
    logits = (rng.normal(size=(_SLOTS, _VOCAB)) * 3).astype(np.float32)
    mask = None
    if masked:
        mask = np.where(rng.random((_SLOTS, _VOCAB)) < 0.3, 0.0,
                        adapters.NEG_INF).astype(np.float32)
        mask[0] = 0.0                         # an unconstrained row
        mask[1, :] = adapters.NEG_INF
        mask[1, [4, 9, 30]] = 0.0             # fewer allowed than top_k asks
    return (jnp.asarray(logits), jnp.asarray(temp, jnp.float32),
            jnp.asarray(topk, jnp.int32), jnp.asarray(topp, jnp.float32),
            None if mask is None else jnp.asarray(mask))


@pytest.mark.parametrize("batch", list(_BATCHES))
def test_sample_batched_draws_the_tokens_of_the_two_sort_formula(batch):
    """Whatever branch the batch selects on the device, a fixed key gives
    exactly the tokens the unconditional two-sort sampler gave."""
    logits, temp, topk, topp, mask = _batch_arrays(batch)
    new = jax.jit(serving._sample_batched)
    old = jax.jit(_two_sort_sample)
    drawn = set()
    for seed in range(24):
        key = jax.random.fold_in(jax.random.PRNGKey(7), seed)
        got = np.asarray(new(logits, key, temp, topk, topp, mask=mask))
        want = np.asarray(old(logits, key, temp, topk, topp, mask=mask))
        np.testing.assert_array_equal(got, want)
        drawn.add(tuple(got))
    greedy = np.asarray(jnp.argmax(
        logits if mask is None else logits + mask, axis=-1))
    np.testing.assert_array_equal(got[np.asarray(temp) == 0],
                                  greedy[np.asarray(temp) == 0])
    assert (len(drawn) > 1) == bool(np.asarray(temp).any())
    if mask is not None:
        assert (np.asarray(mask)[np.arange(_SLOTS), got] == 0).all()


def _filter_rows(case):
    """(logits [R, V], temperature, top_k, top_p), rows and parameters
    chosen to sit on the edges of the one-sort argument."""
    from paddle_tpu.text import adapters

    rng = np.random.default_rng(5)
    x = (rng.normal(size=(5, 24)) * 2).astype(np.float32)
    temp = np.asarray([1.0, 0.5, 2.0, 0.0, 1.3], np.float32)
    topk = np.asarray([4, 4, 4, 4, 4], np.int32)
    topp = np.asarray([1.0, 0.9, 0.5, 0.7, 0.2], np.float32)
    if case == "ties_at_kth":
        x[:, 3:9] = x[:, 3:4]           # six equal values around rank 4
        x[0, :] = 1.25                  # a whole row of one value
    elif case == "top_k_over_unmasked":
        x[:, 3:] = adapters.NEG_INF     # three tokens allowed, four asked
        topk[:] = [4, 7, 24, 100, 3]
    elif case == "neg_inf_rows":
        x[:, ::2] = adapters.NEG_INF    # half the row banned
        x[2, 1:] = adapters.NEG_INF     # one token left
        topk[:] = [0, 4, 2, 12, 13]
    elif case == "filters_off":
        topk[:] = 0
        topp[:] = 1.0
    else:
        assert case == "plain", case
    return x, temp, topk, topp


@pytest.mark.parametrize("case", ["plain", "ties_at_kth",
                                  "top_k_over_unmasked", "neg_inf_rows",
                                  "filters_off"])
@pytest.mark.parametrize("xp", [jnp, np], ids=["jnp", "numpy"])
def test_filter_logits_one_sort_is_the_two_sort_formula_bit_for_bit(xp,
                                                                    case):
    x, temp, topk, topp = _filter_rows(case)
    if xp is np:
        x = x.astype(np.float64)        # the host mirror's precision
    whole = np.asarray(G._filter_logits(xp.asarray(x), temp, topk, topp,
                                        xp=xp))
    want = np.asarray(_two_sort_filter(xp.asarray(x), temp, topk, topp,
                                       xp=xp))
    assert whole.dtype == want.dtype
    np.testing.assert_array_equal(whole, want)
    # scalar parameters, the offline sampler's and the host mirror's form
    got = np.asarray(G._filter_logits(xp.asarray(x[1]), 0.5, 4, 0.9, xp=xp))
    want = np.asarray(_two_sort_filter(xp.asarray(x[1]), 0.5, 4, 0.9,
                                       xp=xp))
    np.testing.assert_array_equal(got, want)
    if case == "filters_off":       # what a plainly sampled step draws from
        np.testing.assert_array_equal(
            np.asarray(G._scale_logits(xp.asarray(x), temp, xp=xp)), whole)


def _sample_step_counts():
    from paddle_tpu.framework import monitor

    return {k: int(monitor.get_stat(f"serving.sample_steps_{k}").get())
            for k in ("greedy", "sampled", "filtered")}


@pytest.mark.parametrize("mode", ["tick", "tick_block", "async",
                                  "async_block"])
def test_sample_step_counters_add_up_to_the_steps_dispatched(mode):
    """Greedy, plainly sampled and filtered requests one after another on
    a one-slot server: every dispatched step is counted once, under the
    branch its arrays select on the device."""
    cfg = _cfg(vocab_size=12)
    params = gpt.init_params(cfg, jax.random.PRNGKey(8))
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                               seed=3, prefill=False,
                               async_dispatch=mode.startswith("async"))
    before, step0 = _sample_step_counts(), srv._step_no
    grew = {}
    for kind, asked in (("greedy", {}), ("sampled", dict(temperature=1.1)),
                        ("filtered", dict(temperature=1.1, top_k=3))):
        was = _sample_step_counts()
        rid = srv.submit([3, 5, 7], max_new_tokens=6, **asked)
        while srv.pending():
            srv.tick_block(2) if mode.endswith("block") else srv.tick()
        assert len(srv.result(rid)) == 6
        now = _sample_step_counts()
        grew[kind] = {k: now[k] - was[k] for k in now}
        # the prompt's feeding steps keep nothing and ask for nothing
        assert grew[kind][kind] >= 4 or kind == "greedy", grew
        assert all(v == 0 for k, v in grew[kind].items()
                   if k not in (kind, "greedy")), grew
    assert grew["greedy"]["greedy"] >= 6
    after = _sample_step_counts()
    assert sum(after.values()) - sum(before.values()) \
        == srv._step_no - step0 > 0
