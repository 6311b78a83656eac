"""Pallas W4A16 dequant-matmul (ops/woq_matmul.py) — interpret-mode
parity, routing, and end-to-end decode identity with the kernel forced.

The kernel's contract: bit-identical dequant math to woq.w's packed
branch (dequant in the activation dtype, per-group scales), so a
trained model must generate IDENTICALLY with the kernel on or off.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import woq_matmul as wm
from paddle_tpu.text import generate, gpt, woq


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setattr(wm, "_INTERPRET", True)


def _pack(q):
    return jnp.asarray(woq.pack_int4_halves(q))


def _case(rng, N, K, M, gs, dtype=jnp.bfloat16):
    x = jnp.asarray(rng.normal(size=(N, K)), dtype)
    q = rng.integers(-7, 8, (K, M))
    scale = jnp.asarray(rng.uniform(0.01, 0.1, (K // gs, 1, M))
                        .astype(np.float32))
    return x, _pack(q), scale


@pytest.mark.parametrize("N,K,M,gs", [
    (3, 128, 256, 32),    # row padding (3 -> 8)
    (8, 256, 128, 64),
    (1, 128, 384, 64),    # M % 256 != 0 -> BM 128
    (16, 512, 256, 64),   # multiple k blocks
])
def test_kernel_matches_xla_dequant(N, K, M, gs):
    rng = np.random.default_rng(N * K + M)
    x, packed, scale = _case(rng, N, K, M, gs)
    out = wm.w4_matmul(x, packed, scale)
    ref = wm._xla_w4(x, packed, scale)
    assert out.dtype == x.dtype and out.shape == (N, M)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_kernel_matches_woq_accessor_exactly():
    """The oracle chain: kernel == _xla_w4 == x @ woq.w(...) on the same
    packed tree — nibble extremes included so sign extension is proven."""
    K, M, gs = 128, 256, 32
    w_ = np.zeros((1, K, M), np.float32)
    rng = np.random.default_rng(0)
    w_[0] = rng.choice([-1.0, -0.5, 0.0, 0.5, 1.0], size=(K, M))
    tree = woq.quantize_gpt_int4({"blocks": {"fc_w": w_},
                                  "wte": rng.normal(size=(8, M))
                                  .astype(np.float32)}, group_size=gs)
    arr, s = tree["blocks"]["fc_w"][0], tree["blocks"]["fc_w_s"][0]
    x = jnp.asarray(rng.normal(size=(4, K)), jnp.bfloat16)
    via_accessor = x @ woq.w({"fc_w": arr, "fc_w_s": s}, "fc_w",
                             jnp.bfloat16)
    via_kernel = wm.w4_matmul(x, arr, s)
    np.testing.assert_array_equal(np.asarray(via_kernel, np.float32),
                                  np.asarray(via_accessor, np.float32))


def test_leading_dims_and_fallbacks():
    rng = np.random.default_rng(1)
    x, packed, scale = _case(rng, 4, 128, 256, 32)
    x3 = x.reshape(2, 2, 128)
    out = wm.w4_matmul(x3, packed, scale)
    assert out.shape == (2, 2, 256)
    # misaligned M -> XLA fallback, same numbers
    xm, pm, sm = _case(rng, 2, 128, 192, 32)
    np.testing.assert_allclose(
        np.asarray(wm.w4_matmul(xm, pm, sm), np.float32),
        np.asarray(wm._xla_w4(xm, pm, sm), np.float32), atol=2e-2,
        rtol=2e-2)
    # shape mismatch raises
    with pytest.raises(ValueError):
        wm.w4_matmul(x, packed[:-1], scale)


def test_mm_routes_only_qualified_weights(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_W4_KERNEL", "1")
    calls = []
    real = wm.w4_matmul

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)
    monkeypatch.setattr(wm, "w4_matmul", spy)
    rng = np.random.default_rng(2)
    K, M = 128, 256
    w_ = rng.normal(size=(1, K, M)).astype(np.float32)
    tree = woq.quantize_gpt_int4({"blocks": {"fc_w": w_},
                                  "wte": rng.normal(size=(8, M))
                                  .astype(np.float32)}, group_size=32)
    p = {"fc_w": tree["blocks"]["fc_w"][0],
         "fc_w_s": tree["blocks"]["fc_w_s"][0]}
    x = jnp.asarray(rng.normal(size=(2, K)), jnp.bfloat16)
    woq.mm(x, p, "fc_w", jnp.bfloat16)
    assert calls == [1]
    # float weights skip the kernel
    woq.mm(x, {"fc_w": jnp.asarray(w_[0])}, "fc_w", jnp.bfloat16)
    assert calls == [1]
    # LoRA-adapted trees skip the kernel
    woq.mm(x, dict(p, fc_w_lora_a=jnp.zeros((K, 2), jnp.float32),
                   fc_w_lora_b=jnp.zeros((2, M), jnp.float32)),
           "fc_w", jnp.bfloat16)
    assert calls == [1]
    # flag off skips the kernel
    monkeypatch.delenv("PADDLE_TPU_W4_KERNEL")
    woq.mm(x, p, "fc_w", jnp.bfloat16)
    assert calls == [1]


def test_mm_stacked_routes_and_matches(monkeypatch):
    """The stacked qkv/kv form: per-slice kernel calls equal the einsum
    over the dequantized stack."""
    monkeypatch.setenv("PADDLE_TPU_W4_KERNEL", "1")
    rng = np.random.default_rng(3)
    K, M = 128, 128
    w_ = rng.normal(size=(1, 3, K, M)).astype(np.float32)  # [L, 3, K, M]
    tree = woq.quantize_gpt_int4({"blocks": {"qkv_w": w_},
                                  "wte": rng.normal(size=(8, M))
                                  .astype(np.float32)}, group_size=32)
    p = {"qkv_w": tree["blocks"]["qkv_w"][0],
         "qkv_w_s": tree["blocks"]["qkv_w_s"][0]}
    x = jnp.asarray(rng.normal(size=(2, 4, K)), jnp.bfloat16)
    calls = []
    real = wm.w4_matmul

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)
    monkeypatch.setattr(wm, "w4_matmul", spy)
    out = woq.mm_stacked(x, p, "qkv_w", jnp.bfloat16)
    assert calls == [1, 1, 1] and out.shape == (3, 2, 4, M)
    ref = jnp.einsum("...d,kde->k...e", x,
                     woq.w(p, "qkv_w", jnp.bfloat16))
    # one-ulp bf16 tolerance: the kernel accumulates its dots in f32
    # (preferred_element_type) while the einsum accumulates in bf16 —
    # same dequant values, occasionally different final rounding
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=4e-3, rtol=4e-3)


def test_decode_identical_with_kernel_forced(markov_gpt, monkeypatch):
    """THE serving guarantee: the trained markov model generates the
    same tokens with the W4 kernel on and off."""
    cfg, params = markov_gpt
    q4 = woq.quantize_gpt_int4(params, group_size=32)
    prompt = jnp.asarray([[1, 4, 0]], jnp.int32)
    off = generate.generate(q4, cfg, prompt, max_new_tokens=16,
                            temperature=0.0)
    monkeypatch.setenv("PADDLE_TPU_W4_KERNEL", "1")
    generate._GEN_CACHE.clear()  # traced with the flag baked in
    on = generate.generate(q4, cfg, prompt, max_new_tokens=16,
                           temperature=0.0)
    generate._GEN_CACHE.clear()
    assert np.array_equal(np.asarray(off), np.asarray(on))


# ---------------------------------------------------------------------------
# no quiet fallback: a refusal reaches the caller, a shape gate picks XLA
# ---------------------------------------------------------------------------


def _refuse(*a, **k):
    raise ValueError("Mosaic refused this block shape")


def _w4_operands(N, K, M, gs=64):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(N, K)), jnp.float32)
    packed = jnp.asarray(woq.pack_int4_halves(rng.integers(-7, 8, (K, M))))
    scale = jnp.asarray(rng.uniform(0.01, 0.1, (K // gs, 1, M))
                        .astype(np.float32))
    return x, packed, scale


def test_refusal_in_the_kernel_build_propagates(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call", _refuse)
    with pytest.raises(ValueError, match="Mosaic refused"):
        wm.w4_matmul(*_w4_operands(8, 256, 256))
    assert not hasattr(wm, "_probe") and not hasattr(wm, "_FALLBACK")


def test_failed_shape_gate_still_picks_xla(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call", _refuse)
    x, packed, scale = _w4_operands(8, 256, 192)  # M off the lane grid
    np.testing.assert_allclose(np.asarray(wm.w4_matmul(x, packed, scale)),
                               np.asarray(wm._xla_w4(x, packed, scale)),
                               rtol=1e-6)
