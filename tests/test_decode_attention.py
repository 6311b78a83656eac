"""Flash-decode attention + quantized KV cache (ops/decode_attention.py).

The kernel runs in Pallas INTERPRET mode here (JAX_PLATFORMS=cpu — the
conftest pins it), so these tests exercise the real kernel body, not the
XLA fallback: GQA parity against the einsum path across num_kv_heads
{1, H/4, None}, long caches (>= 2k), every cache storage dtype, and
donation.  The on-device certification twin is
chip_smoke.py's kernels phase.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import decode_attention as da
from paddle_tpu.text import generate as G, gpt, serving


@pytest.fixture()
def interpret():
    """Run the decode kernel (and the prefill flash kernel) in interpret
    mode for the duration of a test."""
    from paddle_tpu.ops import flash_attention as fa

    old_da, old_fa = da._INTERPRET, fa._INTERPRET
    da._INTERPRET, fa._INTERPRET = True, True
    # trace-time routing flags are baked into cached executables
    G._GEN_CACHE.clear()
    serving._STEP_CACHE.clear()
    yield
    da._INTERPRET, fa._INTERPRET = old_da, old_fa
    G._GEN_CACHE.clear()
    serving._STEP_CACHE.clear()


@pytest.fixture()
def kv_env(monkeypatch):
    """Setter for the decode-routing env flags that also busts the
    value-keyed jit caches (the flags are part of _cfg_key, but modules
    cache traced fns across tests)."""
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


def _cfg(**kw):
    base = dict(vocab_size=64, hidden_size=512, num_layers=2, num_heads=4,
                max_seq_len=2304)
    base.update(kw)
    return gpt.GPTConfig(**base)


# ---------------------------------------------------------------------------
# op-level parity: kernel vs XLA oracle (GQA sweep, long T, all dtypes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("Hkv,G_", [(1, 8), (2, 4), (8, 1)])
@pytest.mark.parametrize("kv", ["fp32", "bf16", "int8"])
def test_kernel_matches_oracle_long_cache(interpret, Hkv, G_, kv):
    Hq, hd, B, T = Hkv * G_, 128, 2, 2048
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (B, T, Hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, T, Hkv, hd), jnp.float32)
    ksc = vsc = None
    if kv == "int8":
        kc, ksc = da.quantize_kv(kc)
        vc, vsc = da.quantize_kv(vc)
    elif kv == "bf16":
        kc, vc = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
    pos = jnp.asarray([1500, 2047], jnp.int32)
    assert da.supported(q.shape, kc.shape)
    out = da._decode_call(q, kc, vc, pos, ksc, vsc, None)
    ref = da._xla_decode(q, kc, vc, pos, ksc, vsc, None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_kernel_small_tq_chunk(interpret):
    """Tq > 1 (the verify-chunk shape): per-row causal frontier."""
    B, Tq, Hq, Hkv, hd, T = 1, 8, 8, 2, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Tq, Hq, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, T, Hkv, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, T, Hkv, hd), jnp.float32)
    pos = jnp.asarray([100], jnp.int32)
    out = da._decode_call(q, kc, vc, pos, None, None, None)
    ref = da._xla_decode(q, kc, vc, pos, None, None, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_quantize_roundtrip_and_scale_shape():
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 128, 2, 64)) * 4.0
    q, s = da.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (3, 128, 2)
    back = da.dequantize_kv(q, s, jnp.float32)
    # per-head absmax int8: worst-case error is scale/2 = absmax/254
    err = np.abs(np.asarray(back - x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all()


def test_unsupported_shapes_fall_back():
    # hd not in the MXU set -> XLA path (still correct)
    q = jnp.zeros((1, 1, 4, 16))
    k = v = jnp.zeros((1, 24, 4, 16))
    assert not da.supported(q.shape, k.shape)
    out = da.decode_attention(q, k, v, jnp.zeros((1,), jnp.int32))
    assert out.shape == (1, 1, 4, 16)


# ---------------------------------------------------------------------------
# decode-path parity: kernel routing vs the einsum path, full model
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kvh", [None, 1, 2])
def test_decode_step_logits_match_einsum_path(interpret, kv_env, kvh):
    cfg = _cfg(num_kv_heads=kvh)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    cache = G.init_cache(cfg, 2, 2048)
    kk = jax.random.split(jax.random.PRNGKey(3), 2)
    cache = {"k": (jax.random.normal(kk[0], cache["k"].shape) * 0.3
                   ).astype(cache["k"].dtype),
             "v": (jax.random.normal(kk[1], cache["v"].shape) * 0.3
                   ).astype(cache["v"].dtype)}
    tok = jnp.asarray([3, 7], jnp.int32)
    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    lk, ck = G.decode_step(params, dict(cache), tok, 1900, cfg)
    kv_env(PADDLE_TPU_FLASH_DECODE="0")
    lx, cx = G.decode_step(params, dict(cache), tok, 1900, cfg)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lx),
                               atol=3e-2, rtol=3e-2)
    assert (np.asarray(jnp.argmax(lk, -1))
            == np.asarray(jnp.argmax(lx, -1))).all()
    # layer 0's written rows are identical (same projection, same
    # storage); later layers' inputs flow through the differing
    # attention path, so only closeness holds there
    np.testing.assert_allclose(
        np.asarray(ck["k"], np.float32)[0, :, 1900],
        np.asarray(cx["k"], np.float32)[0, :, 1900], atol=1e-6)


def test_greedy_tokens_bit_identical_markov(interpret, kv_env, markov_gpt):
    """Acceptance: greedy decode tokens are bit-identical between the
    kernel and XLA paths for float caches — on the TRAINED markov model
    whose every next token depends on the fed one."""
    cfg, params = markov_gpt
    prompt = [[3, 10, 5]]
    want = np.asarray(G.generate(params, cfg, prompt, max_new_tokens=13))
    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    # markov cfg has hd=16 (< MXU tile): route a supported-hd twin config
    # through the kernel instead of silently testing the fallback
    assert not da.supported((1, 1, cfg.num_heads, cfg.head_dim),
                            (1, 16, cfg.num_heads, cfg.head_dim))
    got = np.asarray(G.generate(params, cfg, prompt, max_new_tokens=13))
    assert (want == got).all()


def test_greedy_tokens_bit_identical_kernel_engaged(interpret, kv_env):
    """The same acceptance on a config the kernel actually covers
    (hd=128, cache length 8-aligned), with engagement asserted."""
    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(1))
    prompt = [[5, 9, 3]]

    calls = {"n": 0}
    orig = da._decode_call

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    kv_env(PADDLE_TPU_FLASH_DECODE="0")
    want = np.asarray(G.generate(params, cfg, prompt, max_new_tokens=13))
    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    da._decode_call = counted
    try:
        got = np.asarray(G.generate(params, cfg, prompt,
                                    max_new_tokens=13))
    finally:
        da._decode_call = orig
    assert calls["n"] >= 1, "kernel path never engaged"
    assert (want == got).all()


# ---------------------------------------------------------------------------
# quantized KV cache: structure, donation, serving end-to-end
# ---------------------------------------------------------------------------


def test_init_cache_rounds_to_tileable_length(kv_env):
    """Cache allocation rounds up to a kernel-tileable row count (extra
    rows stay causally masked) so arbitrary prompt+max_new totals don't
    silently pin decode on the einsum fallback."""
    cfg = _cfg()
    assert G.init_cache(cfg, 1, 10)["k"].shape[2] == 16
    assert G.init_cache(cfg, 1, 16)["k"].shape[2] == 16
    assert G.init_cache(cfg, 1, 513)["k"].shape[2] == 640
    assert G.init_cache(cfg, 1, 1024)["k"].shape[2] == 1024
    # and the rounded lengths actually pass the kernel's shape gate
    for n in (10, 513, 1000):
        T = G.init_cache(cfg, 1, n)["k"].shape[2]
        assert da.supported((1, 1, 4, 128), (1, T, 4, 128)), (n, T)


def test_kernel_engages_on_unaligned_generate_total(interpret, kv_env):
    """generate() with an arbitrary total (prompt 3 + 20 new = 23) still
    runs the kernel — the rounding closes the review's fallback hole."""
    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(1))
    calls = {"n": 0}
    orig = da._decode_call

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    da._decode_call = counted
    try:
        G.generate(params, cfg, [[5, 9, 3]], max_new_tokens=20)
    finally:
        da._decode_call = orig
    assert calls["n"] >= 1


def test_random_filled_cache_matches_format(kv_env):
    key = jax.random.PRNGKey(0)
    cfg = _cfg(num_kv_heads=2)
    filled = da.random_filled_cache(G.init_cache(cfg, 1, 16), key)
    assert filled["k"].dtype == cfg.dtype
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    filled = da.random_filled_cache(G.init_cache(cfg, 1, 16), key)
    assert filled["k"].dtype == jnp.int8
    assert filled["k_s"].shape == filled["k"].shape[:-1]
    assert float(jnp.max(jnp.abs(filled["k_s"]))) > 0


def test_serving_tick_kernel_engaged_matches_einsum(interpret, kv_env):
    """The vmapped serving tick (pallas_call under jax.vmap, SMEM pos
    operand) runs the kernel and serves the same greedy tokens as the
    einsum path — the production path the kernel exists for."""
    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    reqs = [([5, 9, 3], 5), ([7, 1], 6)]

    def serve():
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=24)
        rids = [srv.submit(p, max_new_tokens=n) for p, n in reqs]
        while srv.pending():
            srv.tick()
        return [srv.result(r) for r in rids]

    kv_env(PADDLE_TPU_FLASH_DECODE="0")
    want = serve()
    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    calls = {"n": 0}
    orig = da._decode_call

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    da._decode_call = counted
    try:
        got = serve()
    finally:
        da._decode_call = orig
    assert calls["n"] >= 1, "kernel never engaged under vmap"
    assert got == want


def test_sharded_decode_kernel_engaged_parity(interpret, kv_env):
    """The pjit-sharded decode step (cache head-sharded over mp) runs
    the kernel and matches the unsharded einsum decode."""
    from jax.sharding import Mesh

    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    toks = [5, 9, 3]
    kv_env(PADDLE_TPU_FLASH_DECODE="0")
    cache_r = G.init_cache(cfg, 1, 16)
    want = None
    for pos, t in enumerate(toks):
        want, cache_r = G.decode_step(params, cache_r,
                                      jnp.asarray([t], jnp.int32), pos,
                                      cfg)
    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    calls = {"n": 0}
    orig = da._decode_call

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    sp, make_cache, decode = G.build_sharded_decode(params, cfg, mesh)
    cache = make_cache(1, 16)
    da._decode_call = counted
    try:
        got = None
        for pos, t in enumerate(toks):
            got, cache = decode(sp, cache, jnp.asarray([t], jnp.int32),
                                jnp.asarray(pos))
    finally:
        da._decode_call = orig
    assert calls["n"] >= 1, "kernel never engaged under pjit"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2, rtol=3e-2)
    assert (np.asarray(jnp.argmax(got, -1))
            == np.asarray(jnp.argmax(want, -1))).all()


def test_int8_cache_structure_and_flag_validation(kv_env):
    cfg = _cfg(num_kv_heads=2)
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    cache = G.init_cache(cfg, 3, 16)
    assert set(cache) == {"k", "v", "k_s", "v_s"}
    assert cache["k"].dtype == jnp.int8
    assert cache["k_s"].shape == (2, 3, 16, 2)
    assert cache["k_s"].dtype == jnp.float32
    kv_env(PADDLE_TPU_KV_DTYPE="fp32")
    assert G.init_cache(cfg, 1, 8)["k"].dtype == jnp.float32
    kv_env(PADDLE_TPU_KV_DTYPE="bogus")
    from paddle_tpu import flags
    with pytest.raises(ValueError, match="PADDLE_TPU_KV_DTYPE"):
        flags.kv_cache_dtype()


def test_int8_cache_decode_close_to_float(kv_env):
    """int8-cache greedy decode follows the float path closely on a
    random model (logit-level tolerance; the trained-model token check
    lives in test_int8_markov_rule)."""
    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray([[5, 9, 3, 7]], jnp.int32)
    kv_env()
    cache = G.init_cache(cfg, 1, 8)
    want = []
    for t in range(4):
        l, cache = G.decode_step(params, cache, toks[:, t], t, cfg)
        want.append(np.asarray(l))
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    cache = G.init_cache(cfg, 1, 8)
    for t in range(4):
        l, cache = G.decode_step(params, cache, toks[:, t], t, cfg)
        np.testing.assert_allclose(np.asarray(l), want[t], atol=0.15,
                                   rtol=0.15)


def test_int8_markov_rule(kv_env, markov_gpt):
    """The trained markov chain survives cache quantization: every
    generated token still obeys next = (tok * 3 + 1) % 13."""
    cfg, params = markov_gpt
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    out = np.asarray(G.generate(params, cfg, [[3, 10, 5]],
                                max_new_tokens=10))[0]
    seq = out[2:].tolist()  # from the last prompt token on
    for a, b in zip(seq, seq[1:]):
        assert b == (a * 3 + 1) % 13, seq


def test_int8_cache_donation_and_serving_drain(kv_env):
    """Donation aliases every cache leaf (scale planes included), and a
    DecodeServer drains correctly on an int8 cache."""
    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    cache = G.init_cache(cfg, 2, 16)
    ptrs = {n: cache[n].unsafe_buffer_pointer() for n in cache}
    fn = serving._get_step_fn(cfg)
    _, out = fn(params, cache, jnp.zeros((2,), jnp.int32),
                jnp.zeros((2,), jnp.int32))
    assert all(cache[n].is_deleted() for n in cache)
    assert {n: out[n].unsafe_buffer_pointer() for n in out} == ptrs

    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=24)
    rids = [srv.submit([5, 9, 3], max_new_tokens=5),
            srv.submit([7, 1], max_new_tokens=5)]
    while srv.pending():
        srv.tick()
    assert all(len(srv.result(r)) == 5 for r in rids)


def test_int8_prefill_matches_stepwise_admission(kv_env):
    """Prefill admission and token-by-token feeding write the SAME
    quantized rows — the prefill-parity invariant holds under int8."""
    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    prompt = [5, 9, 3, 7, 2]
    res = {}
    for prefill in (True, False):
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=32,
                                   prefill=prefill)
        rid = srv.submit(prompt, max_new_tokens=6)
        while srv.pending():
            srv.tick()
        res[prefill] = srv.result(rid)
    assert res[True] == res[False]


def test_sharded_decode_int8_cache_specs(kv_env):
    """build_sharded_decode shards the scale planes with the values."""
    from jax.sharding import Mesh

    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    sp, make_cache, decode = G.build_sharded_decode(params, cfg, mesh)
    cache = make_cache(1, 8)
    assert set(cache) == {"k", "v", "k_s", "v_s"}
    k_shard = cache["k"].sharding.shard_shape(cache["k"].shape)
    s_shard = cache["k_s"].sharding.shard_shape(cache["k_s"].shape)
    assert k_shard[3] == 1 and s_shard[3] == 1  # Hkv=2 split over mp=2
    logits, cache = decode(sp, cache, jnp.zeros((1,), jnp.int32),
                           jnp.asarray(0))
    assert logits.shape == (1, cfg.vocab_size)


def test_sharded_decode_kv_flag_flip_fails_loudly(kv_env):
    """make_cache re-reads PADDLE_TPU_KV_DTYPE; a flip since build must
    raise, not hand the baked decode_fn a mismatched pytree."""
    from jax.sharding import Mesh

    cfg = _cfg(num_kv_heads=2, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    kv_env(PADDLE_TPU_KV_DTYPE=None)
    _, make_cache, _ = G.build_sharded_decode(params, cfg, mesh)
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    with pytest.raises(ValueError, match="PADDLE_TPU_KV_DTYPE changed"):
        make_cache(1, 8)


def test_kv_dtype_part_of_jit_key(kv_env):
    cfg = _cfg()
    kv_env(PADDLE_TPU_KV_DTYPE=None)
    k1 = G._cfg_key(cfg)
    kv_env(PADDLE_TPU_KV_DTYPE="int8")
    k2 = G._cfg_key(cfg)
    kv_env(PADDLE_TPU_FLASH_DECODE="0", PADDLE_TPU_KV_DTYPE=None)
    k3 = G._cfg_key(cfg)
    assert len({k1, k2, k3}) == 3


# ---------------------------------------------------------------------------
# prefill flash kernel under the new _INTERPRET hook (satellite)
# ---------------------------------------------------------------------------


def test_prefill_flash_kernel_interpret_parity(interpret):
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops.attention import xla_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 128, 2, 64), jnp.float32)
               for kk in ks)
    out = fa.flash_attention(q, k, v, causal=True)
    ref = xla_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # backward too: the custom_vjp kernels run interpreted
    g = jax.vjp(lambda a, b, c: fa.flash_attention(a, b, c, causal=True),
                q, k, v)[1](ref)
    gr = jax.vjp(lambda a, b, c: xla_attention(a, b, c, is_causal=True),
                 q, k, v)[1](ref)
    for x, y in zip(g, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# flash-verify (round-12): Tq=K batched verify kernel routing
# ---------------------------------------------------------------------------


def test_verify_chunk_batched_kernel_vs_vmapped_einsum(interpret, kv_env):
    """serving.spec_verify_batched's contiguous kernel route
    (generate.verify_chunk_batched — one Tq=K launch per layer) against
    the vmapped per-slot verify_chunk fallback: same logits (within
    kernel tolerance), same argmax verdicts, and layer 0's written chunk
    rows bit-identical (same projection, same storage; later layers flow
    through the differing attention path)."""
    cfg = _cfg(num_kv_heads=2, max_seq_len=256)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    B, K = 2, 4
    cache0 = G.init_cache(cfg, B, 256)
    kk = jax.random.split(jax.random.PRNGKey(7), 2)
    cache0 = {"k": (jax.random.normal(kk[0], cache0["k"].shape) * 0.3
                    ).astype(cache0["k"].dtype),
              "v": (jax.random.normal(kk[1], cache0["v"].shape) * 0.3
                    ).astype(cache0["v"].dtype)}
    tokens = jnp.asarray([[3, 7, 1, 9], [5, 2, 8, 4]], jnp.int32)
    pos = jnp.asarray([19, 42], jnp.int32)        # ragged frontiers

    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    assert da.available((B, K, cfg.num_heads, cfg.head_dim),
                        cache0["k"].shape[1:])
    calls = {"n": 0}
    orig = da._decode_call

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    da._decode_call = counted
    try:
        lk, ck = serving.spec_verify_batched(
            params, dict(cache0), tokens, pos, cfg)
    finally:
        da._decode_call = orig
    # the layer scan traces its body ONCE, so one traced call
    # proves the route regardless of num_layers
    assert calls["n"] >= 1, "verify kernel never engaged"
    kv_env(PADDLE_TPU_FLASH_DECODE="0")
    lx, cx = serving.spec_verify_batched(
        params, dict(cache0), tokens, pos, cfg)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lx),
                               atol=3e-2, rtol=3e-2)
    assert (np.asarray(jnp.argmax(lk, -1))
            == np.asarray(jnp.argmax(lx, -1))).all()
    for b in range(B):
        p0 = int(pos[b])
        np.testing.assert_allclose(
            np.asarray(ck["k"], np.float32)[0, b, p0:p0 + K],
            np.asarray(cx["k"], np.float32)[0, b, p0:p0 + K], atol=1e-6)


def test_paged_verify_kernel_vs_gather_einsum(interpret, kv_env):
    """kv_pool._paged_verify_kernel (Tq=K paged launch, scatter-then-
    attend) against the gather-einsum paged fallback: same logits and
    the chunk's rows land on the same physical pool rows."""
    from paddle_tpu.text import kv_pool

    cfg = _cfg(num_kv_heads=2, max_seq_len=256)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    B, K = 2, 4

    def fresh():
        cache = G.init_cache(cfg, B, 128, layout="paged", block_size=8)
        # identity mapping: slot b's logical block j -> physical
        # b*nmax + j (full provisioning), every needed row mapped
        nmax = cache["tables"].shape[1]
        cache["tables"] = jnp.arange(B * nmax, dtype=jnp.int32
                                     ).reshape(B, nmax)
        kk = jax.random.split(jax.random.PRNGKey(8), 2)
        cache["k"] = (jax.random.normal(kk[0], cache["k"].shape) * 0.3
                      ).astype(cache["k"].dtype)
        cache["v"] = (jax.random.normal(kk[1], cache["v"].shape) * 0.3
                      ).astype(cache["v"].dtype)
        return cache

    tokens = jnp.asarray([[3, 7, 1, 9], [5, 2, 8, 4]], jnp.int32)
    pos = jnp.asarray([19, 42], jnp.int32)

    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    assert da.paged_available((B, K, cfg.num_heads, cfg.head_dim),
                              fresh()["k"].shape)
    calls = {"n": 0}
    orig = da._paged_call

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    da._paged_call = counted
    try:
        lk, ck = kv_pool.paged_verify_chunk_batched(
            params, fresh(), tokens, pos, cfg)
    finally:
        da._paged_call = orig
    assert calls["n"] >= 1, "paged verify kernel never engaged"
    kv_env(PADDLE_TPU_FLASH_DECODE="0")
    lx, cx = kv_pool.paged_verify_chunk_batched(
        params, fresh(), tokens, pos, cfg)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lx),
                               atol=3e-2, rtol=3e-2)
    assert (np.asarray(jnp.argmax(lk, -1))
            == np.asarray(jnp.argmax(lx, -1))).all()
    np.testing.assert_allclose(
        np.asarray(ck["k"], np.float32)[0],
        np.asarray(cx["k"], np.float32)[0], atol=1e-6)


def test_spec_serving_flash_verify_greedy_parity(interpret, kv_env):
    """End-to-end: a speculative DecodeServer on a kernel-eligible
    config serves bit-identical greedy tokens with the flash-verify
    route on vs off, with the kernel demonstrably engaged."""
    cfg = _cfg(num_kv_heads=2, max_seq_len=128)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    reqs = [([5, 9, 3, 11, 2], 6), ([7, 1, 4], 6)]

    def serve():
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=48,
                                   draft_cfg=cfg, draft_params=params,
                                   spec_k=3)
        rids = [srv.submit(p, max_new_tokens=n) for p, n in reqs]
        while srv.pending():
            srv.tick()
        out = [srv.result(r) for r in rids]
        srv.close()
        return out

    kv_env(PADDLE_TPU_FLASH_DECODE="0")
    want = serve()
    kv_env(PADDLE_TPU_FLASH_DECODE="1")
    calls = {"n": 0}
    orig = da._decode_call

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    da._decode_call = counted
    try:
        got = serve()
    finally:
        da._decode_call = orig
    assert calls["n"] >= 1, "flash-verify never engaged in serving"
    assert got == want


# ---------------------------------------------------------------------------
# the paged kernel walks each slot's live blocks: a grid cell a slot, the
# pages of a compute block fetched by their layer and physical number from
# the pool's whole leaf, all KV heads of a page in one copy
# ---------------------------------------------------------------------------


def _leaf(pool):
    """[..., Hkv, hd] rows as the pool stores them: [..., Hkv*hd]."""
    return pool.reshape(pool.shape[:-2] + (-1,))


def _paged_case(Hkv, G_, Tq, bs, kv, hd=128, seed=0, layers=1, layer=0):
    """Operands in which every slot is one way a table can look, the
    K/V leaves of ``layers`` layers to be read at ``layer``.  The pages
    of a compute block are ``P``; ``nmax`` is no multiple of it."""
    P, _ = da._paged_geometry(bs, Hkv, hd, 8, 2)
    nmax = 2 * P + max(1, P // 2)
    T = nmax * bs
    pos = [0,                    # a frontier at row 0
           bs + 5,               # a frontier inside a page
           P * bs - Tq,          # the last row of a compute block
           P * bs,               # the first row of the next one
           7,                    # a free slot: nothing is mapped
           T - Tq]               # the whole window, the tail group short
    B = len(pos)
    N = B * nmax + 3
    rng = np.random.default_rng(seed)
    phys = [int(x) for x in rng.permutation(np.arange(1, N))]  # out of order
    tables = np.full((B, nmax), -1, np.int32)
    for b, p in enumerate(pos):
        if b == 4:
            continue
        live = (p + Tq - 1) // bs + 1
        # some slots map blocks past their frontier, as a slot does that
        # was given room for its next rows
        for j in range(min(nmax, live + b % 2)):
            tables[b, j] = phys.pop()
    tables[5, 0] = tables[1, 0]       # one block shared by two slots
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Tq, Hkv * G_, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (layers, N, bs, Hkv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (layers, N, bs, Hkv, hd), jnp.float32)
    ksc = vsc = None
    if kv == "int8":
        kp, ksc = da.quantize_kv(kp)
        vp, vsc = da.quantize_kv(vp)
    else:
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    return (q, _leaf(kp), _leaf(vp), jnp.asarray(tables),
            jnp.asarray(pos, jnp.int32), jnp.asarray(layer, jnp.int32),
            ksc, vsc)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("bs", [16, 128])
@pytest.mark.parametrize("Tq", [1, 4])
@pytest.mark.parametrize("Hkv,G_", [(16, 1), (4, 5), (1, 2)])
def test_paged_kernel_matches_oracle(interpret, Hkv, G_, Tq, bs, kv):
    args = _paged_case(Hkv, G_, Tq, bs, kv)
    assert da.paged_supported(args[0].shape, args[1].shape)
    out = np.asarray(da._paged_call(*args, None), np.float32)
    ref = np.asarray(da._xla_paged(*args, None), np.float32)
    live = [0, 1, 2, 3, 5]
    np.testing.assert_allclose(out[live], ref[live], atol=2e-2, rtol=2e-2)
    # the oracle reads block 0 where nothing is mapped; the kernel
    # attends nothing there and gives zeros
    assert (out[4] == 0).all()


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("Tq", [1, 4])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_kernel_reads_its_layer_of_the_leaf(interpret, layer, Tq, kv):
    """A leaf of three layers read at each: what the oracle gives on
    that layer alone (handed as a leaf of one layer, read at 0), through
    the public entry and under ``jit`` with the layer a traced value, as
    the step's scan hands it over."""
    q, kp, vp, tables, pos, li, ksc, vsc = _paged_case(
        4, 5, Tq, 16, kv, layers=3, layer=layer)
    assert kp.shape[0] == 3 and da.paged_supported(q.shape, kp.shape)
    sl = slice(layer, layer + 1)
    ref = np.asarray(da._xla_paged(
        q, kp[sl], vp[sl], tables, pos, 0,
        None if ksc is None else ksc[sl], None if vsc is None else vsc[sl],
        None), np.float32)
    out = np.asarray(jax.jit(da.paged_decode_attention)(
        q, kp, vp, tables, pos, li, ksc, vsc), np.float32)
    live = [0, 1, 2, 3, 5]
    np.testing.assert_allclose(out[live], ref[live], atol=2e-2, rtol=2e-2)
    assert (out[4] == 0).all()
    # and the oracle itself reads the leaf by its layer
    np.testing.assert_array_equal(
        np.asarray(da._xla_paged(q, kp, vp, tables, pos, li, ksc, vsc,
                                 None), np.float32), ref)


@pytest.mark.parametrize("Tq", [1, 4])
def test_paged_kernel_reads_no_block_it_need_not(interpret, Tq):
    """Blocks that no table maps, and blocks mapped past their slot's
    frontier, are never copied, and no page of another layer is: filled
    with NaN they change nothing."""
    q, kp, vp, tables, pos, li, _, _ = _paged_case(4, 5, Tq, 16, "bf16",
                                                   layers=2, layer=1)
    bs = kp.shape[2]
    t = np.asarray(tables)
    needed = {int(t[b, j]) for b in range(t.shape[0])
              for j in range(t.shape[1])
              if t[b, j] >= 0 and j * bs <= int(pos[b]) + Tq - 1}
    dead = np.asarray([n for n in range(kp.shape[1]) if n not in needed])
    assert 0 in dead and len(set(t[t >= 0]) & set(dead)) >= 2
    clean = np.asarray(da._paged_call(q, kp, vp, tables, pos, li, None,
                                      None, None), np.float32)
    got = np.asarray(da._paged_call(
        q, kp.at[1, dead].set(jnp.nan).at[0].set(jnp.nan),
        vp.at[1, dead].set(jnp.nan).at[0].set(jnp.nan), tables, pos, li,
        None, None, None), np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


def _pallas_grids(fn, *args):
    """The grid of every pallas_call in ``fn``'s jaxpr."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield tuple(eqn.params["grid_mapping"].grid)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_grid_is_a_cell_a_slot(kv):
    """The cells of a layer hold neither the KV heads nor the table's
    width as a factor (the parent's grid was ``(B * Hkv, nmax)``)."""
    S = jax.ShapeDtypeStruct

    def grid(B, Hkv, G_, nmax, bs):
        pool = S((2, B * nmax, bs, Hkv * 128),
                 jnp.int8 if kv == "int8" else jnp.bfloat16)
        sc = (S((2, B * nmax, bs, Hkv), jnp.float32) if kv == "int8"
              else None)
        (g,) = _pallas_grids(
            lambda q, k, v, t, p, li, a, b: da._paged_call(
                q, k, v, t, p, li, a, b, None),
            S((B, 1, Hkv * G_, 128), jnp.bfloat16), pool, pool,
            S((B, nmax), jnp.int32), S((B,), jnp.int32), S((), jnp.int32),
            sc, sc)
        return g

    cells = {grid(32, Hkv, G_, nmax, bs)
             for Hkv, G_ in ((1, 2), (4, 5), (16, 1))
             for nmax, bs in ((16, 128), (128, 16), (256, 8))}
    assert cells == {(32,)}
    assert grid(64, 4, 5, 128, 16) == (64,)


def test_paged_geometry_follows_the_shapes():
    """Pages a compute block: 128 KV rows at least.  Heads a cell holds
    at once: all of them, until their q rows or their pages outgrow the
    cell."""
    assert da._paged_geometry(16, 16, 128, 8, 2) == (8, 16)
    assert da._paged_geometry(16, 4, 128, 8, 2) == (8, 4)
    assert da._paged_geometry(128, 16, 128, 8, 1) == (1, 16)
    assert da._paged_geometry(8, 2, 256, 32, 4) == (16, 2)
    assert da._paged_geometry(16, 16, 128, 512, 2) == (8, 2)
    assert da._paged_geometry(16, 16, 128, 1024, 2) == (8, 1)
    # 256 float32 rows of 32 heads are 4 MB a buffer: half the heads
    assert da._paged_geometry(256, 32, 128, 8, 4) == (1, 16)


def test_paged_kernel_loops_head_chunks_at_many_q_rows(interpret):
    """Where all heads at once would outgrow the cell, it walks its pages
    once a chunk of heads: the same answers."""
    Hkv, G_, Tq, bs = 4, 8, 64, 16            # 512 q rows a KV head
    assert da._paged_geometry(bs, Hkv, 128, Tq * G_, 4)[1] == 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, Tq, Hkv * G_, 128), jnp.float32)
    kp = jax.random.normal(ks[1], (1, 24, bs, Hkv * 128), jnp.float32)
    vp = jax.random.normal(ks[2], (1, 24, bs, Hkv * 128), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(3).permutation(24)
                         .reshape(2, 12), jnp.int32)
    pos = jnp.asarray([5, 100], jnp.int32)
    li = jnp.asarray(0, jnp.int32)
    assert da.paged_supported(q.shape, kp.shape)
    np.testing.assert_allclose(
        np.asarray(da._paged_call(q, kp, vp, tables, pos, li, None, None,
                                  None)),
        np.asarray(da._xla_paged(q, kp, vp, tables, pos, li, None, None,
                                 None)),
        atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# no quiet fallback: a refusal reaches the caller, a shape gate picks XLA
# ---------------------------------------------------------------------------


def _refuse(*a, **k):
    raise ValueError("Mosaic refused this block shape")


def _decode_operands(hd):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, hd), jnp.float32)
    k = jax.random.normal(ks[1], (2, 64, 4, hd), jnp.float32)
    v = jax.random.normal(ks[2], (2, 64, 4, hd), jnp.float32)
    return q, k, v, jnp.asarray([10, 63], jnp.int32)


@pytest.mark.parametrize("paged", [False, True])
def test_refusal_in_the_kernel_build_propagates(interpret, monkeypatch,
                                                paged):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call", _refuse)
    q, k, v, pos = _decode_operands(128)
    with pytest.raises(ValueError, match="Mosaic refused"):
        if paged:
            tables = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
            da.paged_decode_attention(q, k.reshape(1, 8, 16, 4 * 128),
                                      v.reshape(1, 8, 16, 4 * 128), tables,
                                      pos, 0)
        else:
            da.decode_attention(q, k, v, pos)
    assert not hasattr(da, "_probe") and not hasattr(da, "_paged_probe")
    assert not hasattr(da, "_FALLBACK")


def test_failed_shape_gate_still_picks_xla(interpret, monkeypatch):
    """hd=64 is off the kernel's lane-chunk layout (a cell takes its head
    as an hd-wide chunk of 128-lane rows): the static gate says XLA, for
    the slab and the pool alike, and no kernel is built."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call", _refuse)
    q, k, v, pos = _decode_operands(64)
    assert not da.supported(q.shape, k.shape)
    assert not da.available(q.shape, k.shape)
    np.testing.assert_allclose(
        np.asarray(da.decode_attention(q, k, v, pos)),
        np.asarray(da._xla_decode(q, k, v, pos, None, None, None)),
        atol=1e-6)
    kp, vp = k.reshape(1, 8, 16, 4 * 64), v.reshape(1, 8, 16, 4 * 64)
    tables = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    assert not da.paged_supported(q.shape, kp.shape)
    np.testing.assert_allclose(
        np.asarray(da.paged_decode_attention(q, kp, vp, tables, pos, 0)),
        np.asarray(da._xla_decode(q, k, v, pos, None, None, None)),
        atol=1e-6)


def test_off_a_tpu_the_gate_is_closed_without_interpret():
    """On the CPU, outside interpret mode, routing never reaches Pallas."""
    assert da._INTERPRET is False
    assert da.supported((1, 1, 4, 128), (1, 64, 4, 128))
    assert not da.available((1, 1, 4, 128), (1, 64, 4, 128))
    assert not da.paged_available((1, 1, 4, 128), (1, 8, 16, 4 * 128))
