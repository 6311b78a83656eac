"""Copy-free decode hot path: KV-cache buffer donation, async dispatch,
warmup, and the persistent compile cache.

Donation is the load-bearing claim: every jitted decode/prefill/sample
step donates its cache argument, so XLA aliases the K/V buffers in
place instead of copying [L, B, T, Hkv, hd] per token.  The aliasing
tests pin it by buffer pointer; the async tests pin that pipelined
dispatch (one step/block in flight) produces byte-identical tokens to
the sync scheduler.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.text import generate as G, gpt, serving


def _cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                max_seq_len=32)
    base.update(kw)
    return gpt.GPTConfig(**base)


@pytest.fixture()
def small_model():
    cfg = _cfg()
    return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# donation: the jitted steps alias their cache in place
# ---------------------------------------------------------------------------


def test_decode_step_donates_and_aliases_cache(small_model):
    """The serving tick step consumes its input cache (deleted) and the
    output cache reuses the SAME device buffer — the copy-free claim,
    pinned at the buffer-pointer level."""
    cfg, params = small_model
    cache = G.init_cache(cfg, 2, 16)
    kptr = cache["k"].unsafe_buffer_pointer()
    vptr = cache["v"].unsafe_buffer_pointer()
    fn = serving._get_step_fn(cfg)
    _, out = fn(params, cache, jnp.zeros((2,), jnp.int32),
                jnp.zeros((2,), jnp.int32))
    assert cache["k"].is_deleted() and cache["v"].is_deleted()
    assert out["k"].unsafe_buffer_pointer() == kptr
    assert out["v"].unsafe_buffer_pointer() == vptr


def test_prefill_and_sample_steps_donate(small_model):
    cfg, params = small_model
    cache = G.init_cache(cfg, 2, 16)
    pre = serving._get_prefill_fn(cfg, 4)  # bucket = the padded width
    _, cache2 = pre(params, cache, jnp.zeros((1, 4), jnp.int32),
                    jnp.asarray(2), jnp.asarray(0))
    assert cache["k"].is_deleted()
    samp = serving._get_sample_step_fn(cfg)
    _, cache3 = samp(params, cache2, jnp.zeros((2,), jnp.int32),
                     jnp.zeros((2,), jnp.int32), jax.random.PRNGKey(0),
                     jnp.zeros((2,), jnp.float32),
                     jnp.zeros((2,), jnp.int32),
                     jnp.ones((2,), jnp.float32))
    assert cache2["k"].is_deleted()
    assert not cache3["k"].is_deleted()


def test_speculative_verify_step_donates(small_model):
    cfg, params = small_model
    cache = G.init_cache(cfg, 1, 16)
    step = G._jit_by_cfg("decode", G.decode_step, cfg)
    _, cache2 = step(params, cache, jnp.zeros((1,), jnp.int32), 0)
    assert cache["k"].is_deleted()
    verify = G._jit_by_cfg("verify", G.verify_chunk, cfg)
    _, cache3 = verify(params, cache2, jnp.zeros((1, 3), jnp.int32), 1)
    assert cache2["k"].is_deleted() and not cache3["k"].is_deleted()


def test_donate_decode_escape_hatch(monkeypatch, small_model):
    """PADDLE_TPU_DONATE_DECODE=0 turns donation off; the flag is part
    of the jit-cache key so flipping it retraces instead of reusing the
    donating executable."""
    cfg, params = small_model
    monkeypatch.setenv("PADDLE_TPU_DONATE_DECODE", "0")
    cache = G.init_cache(cfg, 2, 16)
    fn = serving._get_step_fn(cfg)
    _, out = fn(params, cache, jnp.zeros((2,), jnp.int32),
                jnp.zeros((2,), jnp.int32))
    assert not cache["k"].is_deleted()
    assert (out["k"].unsafe_buffer_pointer()
            != cache["k"].unsafe_buffer_pointer())


def test_sharded_decode_donates(small_model):
    from jax.sharding import Mesh

    cfg, params = small_model
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    sp, make_cache, decode = G.build_sharded_decode(params, cfg, mesh)
    cache = make_cache(1, 8)
    _, cache2 = decode(sp, cache, jnp.zeros((1,), jnp.int32),
                       jnp.asarray(0))
    assert cache["k"].is_deleted() and not cache2["k"].is_deleted()


def test_server_serves_with_donation_end_to_end(small_model):
    """A full submit/tick/result pass under donation (the default): the
    host scheduler never touches a retired cache generation, so nothing
    here may raise 'buffer deleted'."""
    cfg, params = small_model
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=24)
    rng = np.random.default_rng(0)
    rids = [srv.submit(list(rng.integers(1, 60, 3 + i)), max_new_tokens=5)
            for i in range(3)]
    while srv.pending():
        srv.tick()
    assert all(len(srv.result(r)) == 5 for r in rids)


# ---------------------------------------------------------------------------
# async dispatch: one step in flight, tokens identical to the sync path
# ---------------------------------------------------------------------------


def _serve(params, cfg, reqs, async_dispatch, block=None, warm=False,
           max_batch=2, eos_id=None, **submit_kw):
    srv = serving.DecodeServer(params, cfg, max_batch=max_batch,
                               max_len=24, eos_id=eos_id,
                               async_dispatch=async_dispatch)
    if warm:
        srv.warmup(blocks=(block,) if block else (),
                   sample="temperature" in submit_kw)
    rids = [srv.submit(p, max_new_tokens=n, **submit_kw)
            for p, n in reqs]
    guard = 0
    while srv.pending():
        srv.tick_block(block) if block else srv.tick()
        guard += 1
        assert guard < 300, "server failed to drain"
    return [srv.result(r) for r in rids]


def _staggered_reqs(n=3):
    rng = np.random.default_rng(7)
    # different prompt lengths and budgets: slots sit at different
    # positions every tick, so a wrong-feed bug cannot hide
    return [(list(rng.integers(1, 60, 2 + 2 * i)), 4 + i)
            for i in range(n)]


def test_async_tick_matches_sync_greedy(small_model):
    cfg, params = small_model
    reqs = _staggered_reqs()
    want = _serve(params, cfg, reqs, False)
    assert _serve(params, cfg, reqs, True) == want
    assert _serve(params, cfg, reqs, True, warm=True) == want


def test_async_tick_block_matches_sync(small_model):
    cfg, params = small_model
    reqs = _staggered_reqs()
    want = _serve(params, cfg, reqs, False)  # stepwise reference
    assert _serve(params, cfg, reqs, False, block=4) == want
    assert _serve(params, cfg, reqs, True, block=4) == want
    assert _serve(params, cfg, reqs, True, block=4, warm=True) == want


@pytest.mark.parametrize("asked", [dict(top_k=7), dict(top_p=0.8), {}],
                         ids=["top_k", "top_p", "no_filter"])
def test_async_sampled_matches_sync(small_model, asked):
    """Sampled serving: the async scheduler consumes the same fold_in
    step counters as the sync one, so draws are byte-identical (no
    queueing: admission shifts change WHICH steps a queued slot
    occupies — the documented batched-serving schedule dependence),
    whichever of the sampler's branches the requests select."""
    cfg, params = small_model
    reqs = _staggered_reqs(3)
    kw = dict(temperature=0.8, **asked)
    want = _serve(params, cfg, reqs, False, max_batch=4, **kw)
    assert want != _serve(params, cfg, reqs, False, max_batch=4,
                          temperature=1.3)  # sampling actually engaged
    assert _serve(params, cfg, reqs, True, max_batch=4, **kw) == want
    assert _serve(params, cfg, reqs, True, max_batch=4, warm=True,
                  **kw) == want
    wantb = _serve(params, cfg, reqs, False, block=2, max_batch=4, **kw)
    assert _serve(params, cfg, reqs, True, block=2, max_batch=4,
                  **kw) == wantb


def test_async_eos_retires_and_readmits(small_model):
    """eos mid-flight under async: the in-flight overrun step's tokens
    for the retired slot are discarded, and a queued request admits into
    the freed slot with correct results."""
    cfg, params = small_model
    reqs = [([5, 9], 8), ([11, 3, 7], 8), ([2, 4, 6, 8], 8)]
    for block in (None, 3):
        want = _serve(params, cfg, reqs, False, block=block, eos_id=1)
        got = _serve(params, cfg, reqs, True, block=block, eos_id=1)
        assert got == want


def test_async_markov_follows_rule(markov_gpt):
    """Async serving on the TRAINED markov model: every generated token
    obeys next = (tok * 3 + 1) % 13 — the wrong-input canary (an async
    feed bug would break the chain, where an untrained model's
    attractor tokens could hide it)."""
    cfg, params = markov_gpt
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=20,
                               async_dispatch=True)
    srv.warmup(blocks=(4,))
    rids = [srv.submit([3, 10, 5], max_new_tokens=8),
            srv.submit([7], max_new_tokens=8),
            srv.submit([1, 4], max_new_tokens=8)]
    while srv.pending():
        srv.tick_block(4)
    for rid, first in zip(rids, (5, 7, 4)):
        seq = [first] + srv.result(rid)
        for a, b in zip(seq, seq[1:]):
            assert b == (a * 3 + 1) % 13, (rid, seq)


def test_warmup_reports_compiled_executables(small_model):
    cfg, params = small_model
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=16)
    t = srv.warmup(prompt_lens=[3, 5], blocks=(2,), sample=True)
    assert {"step", "sample_step", "block2", "sample_block2",
            "prefill4", "prefill8"} <= set(t)
    assert all(isinstance(v, float) for v in t.values())
    # warmup leaves the server fully usable
    rid = srv.submit([3, 5, 9], max_new_tokens=4)
    while srv.pending():
        srv.tick()
    assert len(srv.result(rid)) == 4


def test_chunked_prefill_warmup_single_executable(small_model):
    cfg, params = small_model
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=16,
                               prefill_chunk=4)
    t = srv.warmup()
    assert "prefill_chunk4" in t


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------


_CACHE_DIR_SNIPPET = (
    "import jax; from paddle_tpu.framework import platform; "
    "d = platform.init_compile_cache(); "
    "print(d); print(jax.config.jax_compilation_cache_dir)")


def _cache_dir_in_child(env_dir):
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_DIR_SNIPPET],
                         env=env, capture_output=True, text=True,
                         check=True, cwd="/")
    returned, configured = out.stdout.split()[-2:]
    return repo, returned, configured


def test_compile_cache_default_is_inside_checkout_and_stable():
    """Unset env: <checkout>/.jax_cache, the same in two processes (the
    directory is part of the cache key), whatever the cwd or HOME."""
    repo, d1, c1 = _cache_dir_in_child(None)
    _, d2, c2 = _cache_dir_in_child(None)
    assert d1 == d2 == c1 == c2 == os.path.join(repo, ".jax_cache")


def test_compile_cache_env_dir_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory."""
    want = str(tmp_path / "placed")
    _, returned, configured = _cache_dir_in_child(want)
    assert returned == configured == want


def test_compile_cache_has_no_private_knob(monkeypatch):
    from paddle_tpu.framework import platform

    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", "off")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        assert platform.init_compile_cache() == platform._DEFAULT_CACHE_DIR
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


# ---------------------------------------------------------------------------
# inference predictor input donation (Config._donate_inputs wired)
# ---------------------------------------------------------------------------


def test_predictor_buffer_donation(tmp_path):
    from paddle_tpu import inference

    prefix = str(tmp_path / "m")
    inference.save_inference_model(
        prefix, lambda x: x * 2.0 + 1.0,
        [jax.ShapeDtypeStruct((4,), np.float32)])
    cfg = inference.Config(prefix).enable_buffer_donation()
    pred = inference.create_predictor(cfg)
    x = jnp.arange(4, dtype=jnp.float32)
    (out,) = pred.run([x])
    np.testing.assert_allclose(np.asarray(out),
                               np.arange(4, dtype=np.float32) * 2 + 1)
    assert x.is_deleted()  # the input buffer was donated to the call
    # numpy inputs are unaffected (each run transfers afresh)
    (out2,) = pred.run([np.ones(4, np.float32)])
    np.testing.assert_allclose(np.asarray(out2), np.full(4, 3.0))
