"""Multi-tenant adapter serving (text/adapters.py + serving plumbing).

The correctness properties that matter: (1) a server carrying an
AdapterPool is BIT-IDENTICAL to the plain server for base-model (adapter
id 0) traffic across every layout and tick mode — attaching the pool
must cost nothing semantically; (2) a batch mixing adapters produces,
per slot, exactly the tokens of that adapter's merged-tree solo decode
(the BGMV gather is the merge); (3) a constrained slot's sampled law is
the renormalized target law over the allowed set, and a JSON-schema
constraint can only ever emit parseable JSON.  Everything else — spec
fallback, warmup no-retrace, jit-key coverage, the ADAPTER lint —
defends those properties under production pressure.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import telemetry as tl
from paddle_tpu.framework import monitor
from paddle_tpu.text import adapters as A
from paddle_tpu.text import generate as G
from paddle_tpu.text import gpt, lora, serving


def _cfg(**over):
    kw = dict(vocab_size=32, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64)
    kw.update(over)
    return gpt.GPTConfig(**kw)


def _count(name):
    return int(monitor.get_stat(name).get())


def _mk_adapter(params, cfg, key, rank=4, scale=0.05):
    """A NON-trivial adapter sub-tree: lora_init's a leaves plus random
    (not zero-init) b leaves, so the delta actually changes tokens."""
    ad = lora.split_lora(lora.lora_init(params, cfg, rank=rank,
                                        key=key))[1]
    out = {}
    for name, v in ad.items():
        if name.endswith("_lora_b"):
            key, sub = jax.random.split(key)
            out[name] = scale * jax.random.normal(sub, v.shape,
                                                  jnp.float32)
        else:
            out[name] = v
    return out


def _greedy_reference(params, cfg, prompt, max_new):
    cache = G.init_cache(cfg, 1, cfg.max_seq_len)
    out, tok = [], None
    for pos in range(len(prompt) + max_new - 1):
        cur = prompt[pos] if pos < len(prompt) else tok
        logits, cache = G.decode_step(params, cache,
                                      jnp.asarray([cur], jnp.int32),
                                      pos, cfg)
        if pos >= len(prompt) - 1:
            tok = int(np.asarray(jnp.argmax(logits, -1))[0])
            out.append(tok)
    return out


def _serve(params, cfg, jobs, max_new=8, block=0, **kw):
    """jobs: list of (prompt, submit_kwargs).  Deliberately NO close():
    close() drops the config's compiled executables from _STEP_CACHE,
    and these tests share them across servers (same idiom as
    test_serving.py — the module teardown clears jax caches)."""
    srv = serving.DecodeServer(params, cfg, **kw)
    rids = [srv.submit(p, max_new_tokens=max_new, **skw)
            for p, skw in jobs]
    ticks = 0
    while srv.pending():
        srv.tick_block(block) if block > 1 else srv.tick()
        ticks += 1
        assert ticks < 500
    return [srv.result(r) for r in rids]


# char-level vocab for the automaton constraints: token i's decoded text
_VOCAB = list('{}":,truefalsokgb0123456789-') + ["?", "!", "#", "~"]
assert len(_VOCAB) == 32 and len(set(_VOCAB)) == 32


# ---------------------------------------------------------------------------
# lora.py satellite: stack/unstack helpers
# ---------------------------------------------------------------------------


def test_stack_unstack_roundtrip():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    ads = [_mk_adapter(params, cfg, jax.random.PRNGKey(i + 1))
           for i in range(3)]
    stacked = lora.stack_adapters(ads)
    for v in stacked.values():
        assert v.shape[0] == 3
    back = lora.unstack_adapters(stacked)
    assert len(back) == 3
    for orig, got in zip(ads, back):
        assert set(orig) == set(got)
        for k in orig:
            np.testing.assert_array_equal(np.asarray(orig[k], np.float32),
                                          np.asarray(got[k]))


def test_stack_adapters_validates_pool_invariant():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    a4 = _mk_adapter(params, cfg, jax.random.PRNGKey(1), rank=4)
    with pytest.raises(ValueError, match="empty"):
        lora.stack_adapters([])
    # mixed rank across the pool
    a8 = _mk_adapter(params, cfg, jax.random.PRNGKey(2), rank=8)
    with pytest.raises(ValueError, match="rank"):
        lora.stack_adapters([a4, a8])
    # mixed target set
    missing = {k: v for k, v in a4.items() if not k.startswith("proj_w")}
    with pytest.raises(ValueError, match="targets"):
        lora.stack_adapters([a4, missing])
    with pytest.raises(ValueError, match="lora leaves"):
        lora.stack_adapters([{"qkv_w": np.zeros((2, 4, 4))}])
    with pytest.raises(ValueError, match="leading axes"):
        lora.unstack_adapters({"a_lora_a": np.zeros((2, 3)),
                               "b_lora_b": np.zeros((3, 3))})


# ---------------------------------------------------------------------------
# AdapterPool registry
# ---------------------------------------------------------------------------


def test_pool_register_resolve_and_validation():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=2)
    ad = _mk_adapter(params, cfg, jax.random.PRNGKey(1))
    assert pool.register("prod-a", ad) == 1
    assert pool.resolve("prod-a") == 1 and pool.resolve(None) == 0
    assert pool.name_of(1) == "prod-a" and pool.name_of(0) == "base"
    with pytest.raises(ValueError, match="unknown adapter"):
        pool.resolve("nope")
    with pytest.raises(ValueError, match="rank"):
        pool.register("bad-rank",
                      _mk_adapter(params, cfg, jax.random.PRNGKey(2),
                                  rank=8))
    # re-register overwrites in place; capacity enforced past that
    assert pool.register("prod-a", ad) == 1
    pool.register("prod-b", _mk_adapter(params, cfg,
                                        jax.random.PRNGKey(3)))
    with pytest.raises(ValueError, match="full"):
        pool.register("prod-c", ad)
    # tenant default: submit(tenant=) resolves weights through the pool
    pool.set_tenant_default("acme", "prod-b")
    assert pool.default_for("acme") == "prod-b"
    assert pool.default_for("other") is None
    with pytest.raises(ValueError, match="unknown adapter"):
        pool.set_tenant_default("acme", "nope")


def test_server_rejects_mismatched_pool():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    other = _cfg(hidden_size=64, num_heads=8)
    pool = A.AdapterPool(gpt.init_params(other, jax.random.PRNGKey(1)),
                         other, rank=4)
    with pytest.raises(ValueError, match="GPTConfig"):
        serving.DecodeServer(params, cfg, max_batch=1, max_len=16,
                             adapter_pool=pool)
    # adapter= without a pool is a submit-time error
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=16)
    with pytest.raises(ValueError, match="adapter"):
        srv.submit([1, 2], max_new_tokens=2, adapter="prod-a")
    srv.close()


# ---------------------------------------------------------------------------
# adapter-0 bit-parity: pool attached, base traffic, every path
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("mode", ["tick", "block", "async"])
def test_adapter_zero_bit_parity(layout, mode):
    """A pool-carrying server serving base-model requests must emit
    tokens bit-identical to the plain server: adapter row 0 is all-zero,
    so the gathered delta is exactly +0.0."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[int(x) for x in r]
               for r in np.random.default_rng(0).integers(1, 30, (3, 5))]
    jobs = [(p, {}) for p in prompts]
    kw = dict(max_batch=2, max_len=48, layout=layout)
    if layout == "paged":
        kw["block_size"] = 8
    if mode == "async":
        kw["async_dispatch"] = True
    block = 4 if mode == "block" else 0
    ref = _serve(params, cfg, jobs, block=block, **kw)
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=2)
    pool.register("prod-a", _mk_adapter(params, cfg,
                                        jax.random.PRNGKey(1)))
    got = _serve(params, cfg, jobs, block=block, adapter_pool=pool, **kw)
    assert got == ref


# ---------------------------------------------------------------------------
# multi-adapter batch parity: the gather IS the merge
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_two_adapter_batch_matches_sequential(layout):
    """One batch mixing {base, adapter-a, adapter-b} slots: each slot's
    tokens equal its adapter's merged-tree (join_lora) solo greedy
    decode, token for token."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    ada = _mk_adapter(params, cfg, jax.random.PRNGKey(1), scale=0.3)
    adb = _mk_adapter(params, cfg, jax.random.PRNGKey(2), scale=0.3)
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=2)
    pool.register("prod-a", ada)
    pool.register("prod-b", adb)
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(1, 30, n)] for n in (5, 4, 6)]
    jobs = [(prompts[0], {}), (prompts[1], {"adapter": "prod-a"}),
            (prompts[2], {"adapter": "prod-b"})]
    kw = dict(max_batch=3, max_len=48, layout=layout, adapter_pool=pool)
    if layout == "paged":
        kw["block_size"] = 8
    got = _serve(params, cfg, jobs, max_new=8, **kw)
    refs = [_greedy_reference(params, cfg, prompts[0], 8),
            _greedy_reference(lora.join_lora(params, ada), cfg,
                              prompts[1], 8),
            _greedy_reference(lora.join_lora(params, adb), cfg,
                              prompts[2], 8)]
    assert got == refs
    # the adapters actually bite: adapted tokens differ from base
    base_b = _greedy_reference(params, cfg, prompts[1], 8)
    assert got[1] != base_b


@pytest.mark.slow
def test_tenant_default_adapter_routes_weights():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    ada = _mk_adapter(params, cfg, jax.random.PRNGKey(1), scale=0.3)
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=2)
    pool.register("prod-a", ada)
    pool.set_tenant_default("acme", "prod-a")
    prompt = [int(x) for x in np.random.default_rng(4).integers(1, 30, 5)]
    got = _serve(params, cfg, [(prompt, {"tenant": "acme"})], max_new=6,
                 max_batch=1, max_len=32, adapter_pool=pool)
    want = _greedy_reference(lora.join_lora(params, ada), cfg, prompt, 6)
    assert got == [want]


# ---------------------------------------------------------------------------
# constrained decoding
# ---------------------------------------------------------------------------


def test_token_set_constraint_greedy_respected():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    allowed = [3, 7, 11, 19]
    got = _serve(params, cfg, [([1, 2, 4], {"constraint": allowed})],
                 max_new=6, max_batch=1, max_len=32)
    assert got[0] and all(t in allowed for t in got[0])


@pytest.mark.slow
def test_constrained_admission_first_token_masked():
    """The admission first-token draw happens ON HOST — the host mask
    (apply_constraint_host) must gate it, not just the device mask."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    allowed = [5, 9]
    for seed in range(8):
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=16,
                                   seed=seed)
        rid = srv.submit([4, 7], max_new_tokens=1, temperature=1.4,
                         constraint=allowed)
        while srv.pending():
            srv.tick()
        (tok,) = srv.result(rid)
        assert tok in allowed, seed


@pytest.mark.slow
def test_constrained_sampled_follows_renormalized_law():
    """Chi-square: a constrained sampled slot's token law is the target
    law renormalized over the allowed set (additive NEG_INF mask before
    the filtered-softmax — Outlines semantics)."""
    cfg = _cfg(vocab_size=12)
    params = gpt.init_params(cfg, jax.random.PRNGKey(9))
    prompt = [4, 7]
    allowed = [1, 3, 4, 8, 10]
    n = 200
    cache = G.init_cache(cfg, 1, cfg.max_seq_len)
    for pos, t in enumerate(prompt):
        l, cache = G.decode_step(params, cache,
                                 jnp.asarray([t], jnp.int32), pos, cfg)
    amask = np.zeros(12, bool)
    amask[allowed] = True
    law = G._filtered_probs(
        np.asarray(l)[0] + np.where(amask, 0.0,
                                    np.float32(A.NEG_INF)), 1.3, 0, 1.0)
    toks = []
    for i in range(n):
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=16,
                                   prefill=False, seed=100 + i)
        rid = srv.submit(prompt, max_new_tokens=1, temperature=1.3,
                         constraint=allowed)
        while srv.pending():
            srv.tick()
        toks.append(srv.result(rid)[0])
    counts = np.bincount(toks, minlength=12).astype(float)
    assert counts[~amask].sum() == 0
    keep = law * n >= 5
    o = np.concatenate([counts[keep], [counts[~keep].sum()]])
    e = np.maximum(np.concatenate([law[keep] * n,
                                   [law[~keep].sum() * n]]), 1e-12)
    stat, df = float(((o - e) ** 2 / e).sum()), int(keep.sum())
    assert stat < 3 * max(df, 1) + 10, stat


@pytest.mark.slow
@pytest.mark.parametrize("temp", [0.0, 1.3])
def test_json_schema_constraint_always_valid_json(temp):
    """Property: every completed request under a (finite) JSON-schema
    constraint decodes to parseable JSON matching the schema shape —
    greedy or sampled, whatever the model wanted to say."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "tag": {"enum": ["a", "b"]}}}
    spec = A.JsonSchemaConstraint(schema, _VOCAB)
    rng = np.random.default_rng(5)
    jobs = [([int(x) for x in rng.integers(1, 30, 4)],
             {"constraint": spec, "temperature": temp})
            for _ in range(3)]
    outs = _serve(params, cfg, jobs, max_new=30, max_batch=3, max_len=48,
                  seed=7)
    for toks in outs:
        text = "".join(_VOCAB[t] for t in toks)
        doc = json.loads(text)                       # parseable, period
        assert set(doc) == {"ok", "tag"}
        assert isinstance(doc["ok"], bool) and doc["tag"] in ("a", "b")


def test_regex_constraint_and_compile_errors():
    rx = A.RegexConstraint("(ab|ba)+", list("ab") + ["~"] * 30)
    st = rx.start(32)
    first = st.allowed_mask()
    assert first[:2].all() and not first[2:].any()
    st.advance(0)                                    # 'a' -> needs 'b'
    assert st.allowed_mask()[1] and not st.allowed_mask()[0]
    with pytest.raises(ValueError, match="vocab"):
        rx.start(16)
    with pytest.raises(ValueError, match="unclosed"):
        A.RegexConstraint("(ab", list("ab"))
    with pytest.raises(ValueError, match="viable"):
        A.RegexConstraint("zz", list("ab") + ["~"] * 30).start(32)
    with pytest.raises(ValueError, match="empty"):
        A.TokenSetConstraint([])
    with pytest.raises(ValueError, match="spec"):
        A.compile_constraint(A.TokenSetConstraint([1]).start(8), 8)
    with pytest.raises(ValueError, match="unsupported schema"):
        A._schema_to_regex({"type": "martian"})


# ---------------------------------------------------------------------------
# composition: speculation fallback, adapters x constraints
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_spec_serving_constrained_falls_back_to_plain_stepping():
    """Draft tokens can't be masked cheaply, so a tick with any
    constrained slot must run plain steps (counted) — and the output
    still honors the constraint exactly."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    allowed = [3, 7, 11]
    f0 = _count("constraint.spec_fallbacks") if tl.enabled() else 0
    got = _serve(params, cfg,
                 [([1, 2, 4], {"constraint": allowed}), ([5, 6], {})],
                 max_new=6, max_batch=2, max_len=48, spec_k=3)
    assert all(t in allowed for t in got[0]) and len(got[1]) == 6
    if tl.enabled():
        assert _count("constraint.spec_fallbacks") > f0


@pytest.mark.slow
def test_adapter_and_constraint_compose():
    """One slot with BOTH an adapter and a constraint: the masked argmax
    of the ADAPTED logits, verified against the merged-tree reference."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    ada = _mk_adapter(params, cfg, jax.random.PRNGKey(1), scale=0.3)
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=1)
    pool.register("prod-a", ada)
    allowed = list(range(16))
    prompt = [2, 9, 4]
    got = _serve(params, cfg,
                 [(prompt, {"adapter": "prod-a", "constraint": allowed})],
                 max_new=5, max_batch=1, max_len=32, adapter_pool=pool)
    # reference: merged tree, argmax restricted to the allowed set
    merged = lora.join_lora(params, ada)
    cache = G.init_cache(cfg, 1, cfg.max_seq_len)
    out, tok = [], None
    for pos in range(len(prompt) + 5 - 1):
        cur = prompt[pos] if pos < len(prompt) else tok
        l, cache = G.decode_step(merged, cache,
                                 jnp.asarray([cur], jnp.int32), pos, cfg)
        if pos >= len(prompt) - 1:
            row = np.asarray(l)[0].copy()
            row[[i for i in range(cfg.vocab_size)
                 if i not in allowed]] = A.NEG_INF
            tok = int(row.argmax())
            out.append(tok)
    assert got == [out]


# ---------------------------------------------------------------------------
# jit discipline: key coverage, warmup no-retrace, telemetry surface
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_adapter_jit_keys_carry_pool_geometry():
    """Every adapter executable's cache key embeds pool_key() — two
    pools with different geometry must never share an executable, and a
    row write (same geometry) must never split one."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=2)
    pool.register("prod-a", _mk_adapter(params, cfg,
                                        jax.random.PRNGKey(1)))
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                               adapter_pool=pool)
    rid = srv.submit([1, 2], max_new_tokens=3, adapter="prod-a")
    while srv.pending():
        srv.tick()
    assert srv.result(rid)
    pk = pool.pool_key()
    # inspect BEFORE close(): close drops this config's executables
    keys = [k for k in serving._STEP_CACHE.keys()
            if isinstance(k, tuple) and k and k[0] == "adapter_step"]
    srv.close()
    assert keys and all(pk in k for k in keys)
    assert pk == ("adapters", 3, 4, pool.targets)
    # registration is a row write, not a geometry change
    pool.register("prod-b", _mk_adapter(params, cfg,
                                        jax.random.PRNGKey(2)))
    assert pool.pool_key() == pk


@pytest.mark.slow
def test_warmup_covers_adapter_and_constraint_paths():
    """warmup() pre-builds the gather/mask executables: serving mixed
    base + adapter + constrained + sampled traffic afterwards must add
    ZERO _STEP_CACHE entries (the zero-mid-serving-retrace guarantee)."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=2)
    pool.register("prod-a", _mk_adapter(params, cfg,
                                        jax.random.PRNGKey(1)))
    srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=48,
                               adapter_pool=pool, seed=3)
    srv.warmup(sample=True, constrained=True, blocks=(4,))
    before = set(serving._STEP_CACHE.keys())
    rng = np.random.default_rng(6)
    rids = [srv.submit([int(x) for x in rng.integers(1, 30, 4)]),
            srv.submit([int(x) for x in rng.integers(1, 30, 5)],
                       adapter="prod-a", temperature=1.1),
            srv.submit([int(x) for x in rng.integers(1, 30, 3)],
                       constraint=[3, 7, 11])]
    while srv.pending():
        srv.tick()
    for r in rids:
        assert srv.result(r)
    rid = srv.submit([1, 2, 3], max_new_tokens=6, adapter="prod-a")
    while srv.pending():
        srv.tick_block(4)
    assert srv.result(rid)
    # snapshot BEFORE close(): close drops this config's executables
    final = set(serving._STEP_CACHE.keys())
    srv.close()
    assert final == before


def test_two_adapters_and_a_json_constraint_on_one_warm_server():
    """The tier-1 round of the three properties above on ONE server (the
    per-property tests are marked slow): after ``warmup(sample=True,
    constrained=True)`` a batch of two adapters and one JSON-schema
    constrained request gives each adapter slot its merged-tree solo
    tokens, parseable JSON of the schema's shape, and no new step-cache
    key."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    ads = {"prod-a": _mk_adapter(params, cfg, jax.random.PRNGKey(1),
                                 scale=0.3),
           "prod-b": _mk_adapter(params, cfg, jax.random.PRNGKey(2),
                                 scale=0.3)}
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=2)
    for name, ad in ads.items():
        pool.register(name, ad)
    rng = np.random.default_rng(7)
    prompts = {name: [int(x) for x in rng.integers(1, 30, 5)]
               for name in ads}
    spec = A.JsonSchemaConstraint(
        {"type": "object", "properties": {"ok": {"type": "boolean"}}},
        _VOCAB)

    srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=64,
                               adapter_pool=pool)
    srv.warmup(sample=True, constrained=True)
    before = set(serving._STEP_CACHE.keys())
    rids = {name: srv.submit(prompts[name], max_new_tokens=10,
                             adapter=name) for name in ads}
    rid_c = srv.submit([int(x) for x in rng.integers(1, 30, 4)],
                       max_new_tokens=20, constraint=spec)
    while srv.pending():
        srv.tick()
    got = {name: srv.result(r) for name, r in rids.items()}
    text = "".join(_VOCAB[t] for t in srv.result(rid_c))
    added = set(serving._STEP_CACHE.keys()) - before   # before close()
    srv.close()
    for name, ad in ads.items():
        assert got[name] == _greedy_reference(
            lora.join_lora(params, ad), cfg, prompts[name], 10), name
    assert isinstance(json.loads(text)["ok"], bool), text
    assert added == set()


def test_load_stats_reports_tenant_shape():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    pool = A.AdapterPool(params, cfg, rank=4, max_adapters=2)
    pool.register("prod-a", _mk_adapter(params, cfg,
                                        jax.random.PRNGKey(1)))
    srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=32,
                               adapter_pool=pool, prefill=False)
    srv.submit([1, 2], max_new_tokens=6, adapter="prod-a")
    srv.submit([3, 4], max_new_tokens=6)
    srv.submit([5, 6], max_new_tokens=6, constraint=[3, 7, 11])
    srv.tick()
    ls = srv.load_stats()
    assert ls["adapters_active"].get("prod-a") == 1
    assert ls["adapters_active"].get("base") == 2
    assert ls["constrained_slots"] == 1
    srv.close()
    # no pool: the adapters_active field is absent, constrained present
    srv2 = serving.DecodeServer(params, cfg, max_batch=1, max_len=16)
    ls2 = srv2.load_stats()
    assert "adapters_active" not in ls2 and ls2["constrained_slots"] == 0
    srv2.close()


@pytest.mark.slow
def test_constraint_telemetry_counters():
    if not tl.enabled():
        pytest.skip("PADDLE_TPU_TELEMETRY=0")
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    m0 = _count("constraint.masked_tokens")
    _serve(params, cfg, [([1, 2], {"constraint": [3, 7]})], max_new=4,
           max_batch=1, max_len=16)
    assert _count("constraint.masked_tokens") > m0


# ---------------------------------------------------------------------------
# ADAPTER lint family (tools/check_instrumented.py)
# ---------------------------------------------------------------------------


def test_adapter_lint_fixtures():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import check_instrumented as ci

    bad = ("class S:\n"
           "    def _gather_adapter_ids(self):\n"
           "        return self.ids\n")
    assert ci.scan_adapter_source(bad)
    bad2 = ("def mask_logits_tick(cons, b, v):\n"
            "    return build(cons, b, v)\n")
    assert ci.scan_adapter_source(bad2)
    good = ("def _gather_adapter_ids(self):\n"
            "    count('adapters.gather_steps')\n"
            "    return self.ids\n")
    assert not ci.scan_adapter_source(good)
    # delegation to a marker-named callee counts (the callee is linted)
    good2 = ("def _mask_array(self):\n"
             "    return mask_logits(self._cons, self.b, self.v)\n"
             "def apply_constraint_row(row, st):\n"
             "    return apply_constraint_host(row, st)\n")
    assert not ci.scan_adapter_source(good2)
    assert ci.scan_repo() == []
