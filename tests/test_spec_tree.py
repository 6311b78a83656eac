"""Draft-tree speculation (round 17): one spec_tree_verify pass scores a
branching token tree per slot under a runtime tree-attention mask.

The correctness bar is the same as linear speculation, sharpened by the
branching: a greedy request served through tree verify rounds must be
bit-identical to the plain server on both KV layouts (off-trunk
acceptance is a row PERMUTE, not a rollback — wrong permutes can't hide
behind tolerance), a sampled request's law must stay exactly the
target's filtered law under SpecInfer-style per-node multi-candidate
rejection, and constrained slots must keep speculating through
DFA-pruned trees with ``constraint.spec_fallbacks`` pinned at zero.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import faults, flags
from paddle_tpu import telemetry as tl
from paddle_tpu.framework import monitor
from paddle_tpu.text import generate as G
from paddle_tpu.text import gpt, serving

from test_speculative import _chi2, _second_token_law
from test_spec_serving import _spec_second_token_counts


def _cfg(**over):
    kw = dict(vocab_size=32, hidden_size=32, num_layers=2, num_heads=4,
              max_seq_len=64)
    kw.update(over)
    return gpt.GPTConfig(**kw)


def _count(name):
    return int(monitor.get_stat(name).get())


def _serve(params, cfg, prompts, max_new=8, block=0, **kw):
    srv = serving.DecodeServer(params, cfg, **kw)
    rids = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
    while srv.pending():
        if block > 1:
            srv.tick_block(block)
        else:
            srv.tick()
    toks = [srv.result(r) for r in rids]
    srv.close()
    return toks


def _biased_draft(params, c=50.0, row=20):
    """A draft whose argmax is a CONSTANT token (final-LN bias pushed
    toward one embedding row): its trunk disagrees with the target
    almost everywhere, so acceptance exercises rejection, off-trunk
    sibling checks, and the fallback machinery."""
    bad = dict(params)
    bad["ln_f_b"] = params["ln_f_b"] + c * params["wte"][row]
    return bad


# ---------------------------------------------------------------------------
# topology units: depths, ancestor mask, chain == linear verify
# ---------------------------------------------------------------------------


def test_tree_depths_and_ancestor_mask_oracle():
    """Hand-checked tree:       0
                              /   \\
                             1     3
                             |    / \\
                             2   4   5   (5 parented at 3? no — at 1)
    parent = [-1, 0, 1, 0, 3, 1]: node 4 under 3, node 5 under 1."""
    parent = [-1, 0, 1, 0, 3, 1]
    assert list(G.tree_depths(parent)) == [0, 1, 2, 1, 2, 2]
    m = G.tree_ancestor_mask(parent)
    want = np.zeros((6, 6), bool)
    for j, path in enumerate([[0], [0, 1], [0, 1, 2], [0, 3],
                              [0, 3, 4], [0, 1, 5]]):
        want[j, path] = True
    np.testing.assert_array_equal(m, want)


def test_tree_verify_chain_equals_linear_verify():
    """A degenerate CHAIN tree (every node's parent is its predecessor)
    is exactly the linear chunk: tree_verify_chunk under the triangular
    ancestor mask must reproduce verify_chunk's logits."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    seq = [5, 3, 9, 1, 7, 4]
    pos0 = 2
    cache_a = G.init_cache(cfg, 1, 16)
    cache_b = G.init_cache(cfg, 1, 16)
    for pos in range(pos0):
        tok = jnp.asarray([seq[pos]], jnp.int32)
        _, cache_a = G.decode_step(params, cache_a, tok, pos, cfg)
        _, cache_b = G.decode_step(params, cache_b, tok, pos, cfg)
    chunk = jnp.asarray([seq[pos0:]], jnp.int32)
    want, _ = G.verify_chunk(params, cache_a, chunk,
                             jnp.asarray(pos0), cfg)
    n = len(seq) - pos0
    parent = [-1] + list(range(n - 1))
    amask = jnp.asarray(G.tree_ancestor_mask(parent)[None])
    depth = jnp.asarray(G.tree_depths(parent)[None])
    got, _ = G.tree_verify_chunk(params, cache_b, chunk, amask, depth,
                                 jnp.asarray(pos0), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=5e-3)


def test_ngram_propose_tree_trunk_plus_branches():
    """Trailing [7, 3] occurred twice with DIFFERENT continuations (5
    then 9): the trie must lay the most-recent continuation as the
    trunk and graft the alternate as a branch off the root — and the
    trunk must leave budget for the branch instead of padding it out."""
    tokens, parent = G.ngram_propose_tree([7, 3, 9, 7, 3, 5, 7, 3], 6,
                                          branch=2)
    assert tokens == [None, 5, 7, 3, 9, 7]
    assert parent == [-1, 0, 1, 2, 0, 4]
    assert G.ngram_propose_tree([1, 2, 3, 4, 5], 4) is None


# ---------------------------------------------------------------------------
# greedy bit-parity: tree server vs plain server
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_tree_self_draft_greedy_parity(layout):
    """N-gram trie trees (no draft model at all) across both KV layouts
    must be bit-identical to the plain server — repetitive prompts make
    the trie fire, branching where history disagrees."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(1))
    prompts = [[5, 9, 5, 9, 5, 9], [7, 3, 9, 7, 3, 5, 7, 3],
               [int(x) for x in
                np.random.default_rng(1).integers(1, 30, 7)]]
    kw = dict(max_batch=2, max_len=48, layout=layout)
    if layout == "paged":
        kw["block_size"] = 8
    ref = _serve(params, cfg, prompts, **kw)
    got = _serve(params, cfg, prompts, spec_tree=5, **kw)
    assert got == ref


@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("block", [0, 4])
def test_tree_draft_model_greedy_parity(layout, block):
    """Draft-model trees (trunk + top-b fanout) across {contiguous,
    paged} x {tick, tick_block}: a BIASED draft makes the trunk wrong
    nearly everywhere, so acceptance lands on sibling branches and the
    off-trunk commit permute runs — wrong permutes break parity."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[int(x) for x in r]
               for r in np.random.default_rng(0).integers(1, 30, (3, 5))]
    kw = dict(max_batch=2, max_len=48, layout=layout)
    if layout == "paged":
        kw["block_size"] = 8
    ref = _serve(params, cfg, prompts, block=block, **kw)
    for dparams in (params, _biased_draft(params)):
        got = _serve(params, cfg, prompts, block=block,
                     draft_cfg=cfg, draft_params=dparams, spec_tree=4,
                     **kw)
        assert got == ref


def test_tree_async_dispatch_parity():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(2))
    prompts = [[int(x) for x in r]
               for r in np.random.default_rng(2).integers(1, 30, (3, 4))]
    ref = _serve(params, cfg, prompts, max_batch=2, max_len=48)
    got = _serve(params, cfg, prompts, max_batch=2, max_len=48,
                 draft_cfg=cfg, draft_params=params, spec_tree=4,
                 async_dispatch=True)
    assert got == ref


def test_tree_small_distinct_draft_parity(markov_gpt):
    """A genuinely different (smaller) draft model proposing the tree:
    the markov target's next token depends on the fed token, so a
    wrong-offset re-feed or a bad commit permute cannot hide."""
    cfg, params = markov_gpt
    dcfg = gpt.GPTConfig(vocab_size=cfg.vocab_size, hidden_size=32,
                         num_layers=1, num_heads=2,
                         max_seq_len=cfg.max_seq_len)
    dparams = gpt.init_params(dcfg, jax.random.PRNGKey(7))
    prompts = [[int(x) for x in r]
               for r in np.random.default_rng(3).integers(1, 13, (3, 5))]
    ref = _serve(params, cfg, prompts, max_batch=2, max_len=32)
    got = _serve(params, cfg, prompts, max_batch=2, max_len=32,
                 draft_cfg=dcfg, draft_params=dparams, spec_tree=4)
    assert got == ref


# ---------------------------------------------------------------------------
# the perf claim: tree beats linear at the same row budget
# ---------------------------------------------------------------------------


def test_tree_fewer_target_passes_than_linear():
    """Under a divergence-heavy draft, tree-N must spend STRICTLY fewer
    target passes than linear-K at the same per-round row budget: when
    the trunk is wrong, a linear chunk wastes the whole round, while a
    tree branch can still land tokens.  Both must stay bit-identical.

    The size and the bias are the ones at which the claim holds: the
    bias has to FLIP the draft's argmax so that linear passes rise
    (vocab 32 / hidden 32 with ``c=50`` ties the two at 10 : 10).  It
    is a count of target passes on the CPU, not a time: whether
    speculation pays on the chip is still open (ROADMAP R-W4, D5)."""
    cfg = _cfg(vocab_size=128, hidden_size=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [[int(x) for x in rng.integers(1, 100, n)] for n in (4, 7)]
    bad = _biased_draft(params, c=30.0, row=42)

    def run(**kw):
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                                   **kw)
        rids = [srv.submit(p, max_new_tokens=12) for p in prompts]
        while srv.pending():
            srv.tick()
        toks = [srv.result(r) for r in rids]
        passes = (srv._spec_rounds + srv._spec_plain_steps
                  if srv._spec_on else srv._step_no)
        srv.close()
        return toks, passes

    ref, _ = run()
    tree, tree_p = run(draft_cfg=cfg, draft_params=bad, spec_tree=4)
    lin, lin_p = run(draft_cfg=cfg, draft_params=bad, spec_k=4)
    assert tree == ref and lin == ref
    assert tree_p < lin_p, (tree_p, lin_p)


# ---------------------------------------------------------------------------
# sampling: SpecInfer per-node rejection keeps the target law exact
# ---------------------------------------------------------------------------


def test_tree_sampled_draft_follows_target_law():
    """Chi-square at batch > 1: sampled through draft-model TREE rounds
    next to a stranger, token #2's law must be exactly the target's
    two-step marginal — per-node accept min(1, p/q) with
    without-replacement sibling draws and the (p - q)+ residual."""
    cfg = _cfg(vocab_size=12)
    params = gpt.init_params(cfg, jax.random.PRNGKey(9))
    prompt = [4, 7]
    n = 200
    law = _second_token_law(params, cfg, prompt, 1.3, 0, 1.0)
    counts = _spec_second_token_counts(
        params, cfg, prompt, n, 1.3, stranger=[2, 9, 1], max_batch=4,
        max_len=16, draft_cfg=cfg, draft_params=params, spec_tree=3)
    stat, df = _chi2(counts, law, n)
    assert stat < 3 * max(df, 1) + 10, stat


def test_tree_sampled_self_draft_follows_target_law():
    """Self-draft trie nodes are point-mass proposals: acceptance is
    min(1, p[x]) per node, rejection zeroes exactly x — valid for ANY
    proposal choice, which is what constraint pruning rides on."""
    cfg = _cfg(vocab_size=12)
    params = gpt.init_params(cfg, jax.random.PRNGKey(9))
    prompt = [4, 7, 4, 7]
    n = 200
    law = _second_token_law(params, cfg, prompt, 1.1, 0, 1.0)
    counts = _spec_second_token_counts(
        params, cfg, prompt, n, 1.1, max_batch=4, max_len=16,
        spec_tree=3)
    stat, df = _chi2(counts, law, n)
    assert stat < 3 * max(df, 1) + 10, stat


# ---------------------------------------------------------------------------
# constrained slots: DFA-pruned trees instead of fallback
# ---------------------------------------------------------------------------


def test_tree_constrained_parity_and_zero_fallbacks():
    """The tentpole's second half: constrained slots SPECULATE in tree
    mode.  Greedy output must match the plain constrained server
    bit-for-bit, tree rounds must actually run, and
    constraint.spec_fallbacks — which counts every linear round that
    punted on a constrained slot — must not move at all."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(3))
    allowed = [2, 5, 9, 11, 17, 23]
    prompts = [[5, 9, 5, 9, 5, 9], [int(x) for x in
                np.random.default_rng(5).integers(1, 30, 6)]]

    def run(**kw):
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=48,
                                   **kw)
        rids = [srv.submit(p, max_new_tokens=8, constraint=allowed)
                for p in prompts]
        while srv.pending():
            srv.tick()
        toks = [srv.result(r) for r in rids]
        srv.close()
        return toks

    ref = run()
    fb0, rounds0 = _count("constraint.spec_fallbacks"), \
        _count("spec.tree_rounds")
    got = run(draft_cfg=cfg, draft_params=params, spec_tree=4)
    assert got == ref
    assert all(t in allowed for toks in got for t in toks)
    assert _count("constraint.spec_fallbacks") - fb0 == 0
    assert _count("spec.tree_rounds") - rounds0 > 0


def test_tree_constrained_prunes_forbidden_branches():
    """A biased draft proposing a FORBIDDEN constant token: the
    lookahead cursor must kill those branches before verify (the
    pruned-branch counter moves), the slot keeps speculating with zero
    fallbacks, and the served tokens still match the plain constrained
    server."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(4))
    allowed = [3, 6, 12, 19, 25]           # token 20 (draft bias) banned
    bad = _biased_draft(params)            # argmaxes to 20 everywhere
    prompts = [[int(x) for x in
                np.random.default_rng(6).integers(1, 30, 5)]]

    def run(**kw):
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                                   **kw)
        rid = srv.submit(prompts[0], max_new_tokens=6,
                         constraint=allowed)
        while srv.pending():
            srv.tick()
        toks = srv.result(rid)
        srv.close()
        return toks

    ref = run()
    p0, fb0 = _count("spec.tree_pruned_constrained"), \
        _count("constraint.spec_fallbacks")
    got = run(draft_cfg=cfg, draft_params=bad, spec_tree=4)
    assert got == ref
    assert _count("spec.tree_pruned_constrained") - p0 > 0
    assert _count("constraint.spec_fallbacks") - fb0 == 0


def test_tree_constrained_sampled_stays_in_language():
    """Sampled constrained tree serving: accept-time rows are masked
    through the lookahead cursor, so every served token must stay in
    the allowed set — and the slot never falls back to linear-mode
    punting."""
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(5))
    allowed = [2, 5, 9, 11, 17]
    fb0 = _count("constraint.spec_fallbacks")
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=48,
                               seed=7, draft_cfg=cfg,
                               draft_params=params, spec_tree=4)
    rids = [srv.submit([4, 7, 4, 7], max_new_tokens=8, temperature=1.2,
                       constraint=allowed) for _ in range(3)]
    while srv.pending():
        srv.tick()
    got = [srv.result(r) for r in rids]
    srv.close()
    assert all(t in allowed for toks in got for t in toks)
    assert _count("constraint.spec_fallbacks") - fb0 == 0


# ---------------------------------------------------------------------------
# production pressure: OOM mid-round, fallback + re-earn, jit key
# ---------------------------------------------------------------------------


def test_tree_oom_evicts_speculating_slot(markov_gpt):
    """Two consecutive tick OOMs on a tree-speculating server: eviction
    requeues mid-round slots (draft cache rows and all) and carried-
    progress re-admission must re-feed exactly — the markov model
    exposes any wrong-offset re-feed."""
    cfg, params = markov_gpt
    prompts = [[int(x) for x in r]
               for r in np.random.default_rng(4).integers(1, 13, (3, 5))]
    clean = _serve(params, cfg, prompts, max_new=6, max_batch=4,
                   max_len=32)
    tl.reset()
    faults.install("oom:tick:2,oom:tick:3")
    try:
        srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=32,
                                   draft_cfg=cfg, draft_params=params,
                                   spec_tree=4)
        rids = [srv.submit(p, max_new_tokens=6, priority=pr)
                for p, pr in zip(prompts, (2, 1, 0))]
        while srv.pending():
            srv.tick()
        assert [srv.result(r) for r in rids] == clean
        srv.close()
    finally:
        faults.reset()
    assert _count("resilience.oom_evictions") >= 1
    assert _count("resilience.oom_retries") >= 1


def test_tree_fallback_then_reearn(monkeypatch):
    """Path-length fallback + the doubling re-earn: a garbage draft
    trips spec.fallbacks (accepted-path-length rate below MIN_ACCEPT),
    the slot reverts to plain rows, and after the cooldown it re-earns
    speculation (spec.reearns counted) — with tokens bit-identical
    throughout."""
    monkeypatch.setenv("PADDLE_TPU_SPEC_MIN_ACCEPT", "0.9")
    cfg = _cfg(max_seq_len=96)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[int(x) for x in r]
               for r in np.random.default_rng(7).integers(1, 30, (2, 5))]
    ref = _serve(params, cfg, prompts, max_new=48, max_batch=2,
                 max_len=96)
    f0, r0 = _count("spec.fallbacks"), _count("spec.reearns")
    got = _serve(params, cfg, prompts, max_new=48, max_batch=2,
                 max_len=96, draft_cfg=cfg,
                 draft_params=_biased_draft(params), spec_tree=4)
    assert got == ref
    assert _count("spec.fallbacks") - f0 >= 1
    assert _count("spec.reearns") - r0 >= 1


def test_spec_tree_in_decode_jit_key(monkeypatch):
    base = flags.decode_jit_key()
    monkeypatch.setenv("PADDLE_TPU_SPEC_TREE", "6")
    assert flags.decode_jit_key() != base
    assert flags.spec_tree() == 6
    monkeypatch.setenv("PADDLE_TPU_SPEC_BRANCH", "3")
    assert flags.spec_branch() == 3
    monkeypatch.setenv("PADDLE_TPU_SPEC_TREE", "1")
    with pytest.raises(ValueError):
        flags.spec_tree()


def test_tree_warmup_then_serve_adds_zero_executables():
    """warmup() on a tree server pre-builds the tree verify (and the
    off-trunk commit permute): serving afterwards compiles NOTHING new
    — node count is the only traced shape, topology is a runtime arg."""
    from paddle_tpu.text import engine

    engine.ENGINE._steps.clear()
    tl.reset()
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(4))
    prompts = [[int(x) for x in r]
               for r in np.random.default_rng(6).integers(1, 30, (2, 5))]
    ref = _serve(params, cfg, prompts, max_batch=2, max_len=48)
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=48,
                               draft_cfg=cfg, draft_params=params,
                               spec_tree=4)
    warmed = srv.warmup()
    assert any("spec_tree_verify" in k for k in warmed)
    keys0 = set(engine.ENGINE._steps.keys())
    compiles0 = len(tl.snapshot()["compiles"])
    rids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    while srv.pending():
        srv.tick()
    got = [srv.result(r) for r in rids]
    assert got == ref
    assert set(engine.ENGINE._steps.keys()) == keys0
    if tl.enabled():
        assert len(tl.snapshot()["compiles"]) == compiles0
    srv.close()


# ---------------------------------------------------------------------------
# telemetry surface + construction validation + lint
# ---------------------------------------------------------------------------


def test_tree_counters_and_accept_len_gauge():
    if not tl.enabled():
        pytest.skip("PADDLE_TPU_TELEMETRY=0")
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    n0 = _count("spec.tree_nodes_proposed")
    a0 = _count("spec.tree_nodes_accepted")
    r0 = _count("spec.tree_rounds")
    srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                               draft_cfg=cfg, draft_params=params,
                               spec_tree=4)
    rid = srv.submit([3, 5, 7, 9], max_new_tokens=8)
    while srv.pending():
        srv.tick()
    assert len(srv.result(rid)) == 8
    stats = srv.load_stats()
    srv.close()
    dn = _count("spec.tree_nodes_proposed") - n0
    da = _count("spec.tree_nodes_accepted") - a0
    assert _count("spec.tree_rounds") - r0 > 0
    assert dn > 0 and 0 < da <= dn
    assert stats["spec_tree_accept_len"] is not None
    assert stats["spec_tree_accept_len"] >= 1.0
    gauges = tl.snapshot()["gauges"]
    assert gauges.get("serving.spec_tree_accept_len", 0) >= 1.0


def test_tree_rejects_bad_construction():
    cfg = _cfg()
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):       # tree and linear K conflict
        serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                             spec_tree=4, spec_k=4)
    with pytest.raises(ValueError):       # degenerate tree (no children)
        serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                             spec_tree=1)
    with pytest.raises(ValueError):       # tree must fit the window
        serving.DecodeServer(params, cfg, max_batch=1, max_len=16,
                             spec_tree=16)
    from paddle_tpu.text.adapters import AdapterPool
    pool = AdapterPool(params, cfg, rank=2)
    with pytest.raises(NotImplementedError):   # adapters x tree: ROADMAP
        serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                             adapter_pool=pool, spec_tree=4)


def test_tree_lint_catches_silent_accept_and_prune():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "tools"))
    import check_instrumented as ci

    bad_accept = ("class S:\n"
                  "    def _spec_tree_accept(self, rows):\n"
                  "        return rows.argmax()\n")
    assert ci.scan_spec_source(bad_accept)
    bad_prune = ("class S:\n"
                 "    def _prune_branches_constrained(self, tp):\n"
                 "        tp['live'][1] = False\n")
    assert ci.scan_spec_source(bad_prune)
    good = ("class S:\n"
            "    def _prune_branches_constrained(self, tp):\n"
            "        count('spec.tree_pruned_constrained')\n"
            "    def _spec_tree_accept(self, rows):\n"
            "        count('spec.tree_nodes_accepted')\n")
    assert not ci.scan_spec_source(good)
    assert ci.scan_repo() == []
