"""Pallas fused softmax cross-entropy vs the XLA reference, interpret mode.

Same testing stance as tests/test_fused_norm.py: the kernel bodies run
under ``interpret=True`` so the CPU suite exercises the online-softmax
sweep, the label-pick iota compare, and the blockwise backward — the
the Mosaic lowering is checked by tests/test_chip_compile.py, on-device
parity by chip_smoke.py.

Reference parity target: operators/softmax_with_cross_entropy_op.cu.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import fused_ce


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = fused_ce._INTERPRET
    fused_ce._INTERPRET = True
    yield
    fused_ce._INTERPRET = old


def _case(N, V, dtype=jnp.float32, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = jax.random.normal(k1, (N, V), dtype) * 3.0
    labels = jax.random.randint(k2, (N,), 0, V, jnp.int32)
    return logits, labels


class TestForward:
    @pytest.mark.parametrize("N,V", [(32, 256), (64, 512), (16, 384)])
    def test_matches_xla(self, N, V):
        logits, labels = _case(N, V)
        loss = fused_ce._fused_ce(logits, labels)
        ref = fused_ce._xla_ce(logits, labels)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_multi_vocab_block_online_softmax(self):
        # V=1024 with BV<=512 forces >1 vocab block per row: the running
        # max/denominator rescaling and the cross-block label pick are live
        logits, labels = _case(16, 1024)
        # plant extreme values in different blocks to stress the rescale
        logits = logits.at[0, 5].set(40.0).at[0, 900].set(41.0)
        loss = fused_ce._fused_ce(logits, labels)
        ref = fused_ce._xla_ce(logits, labels)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_bf16_logits_f32_loss(self):
        logits, labels = _case(32, 256, jnp.bfloat16)
        loss = fused_ce._fused_ce(logits, labels)
        assert loss.dtype == jnp.float32
        ref = fused_ce._xla_ce(logits.astype(jnp.float32), labels)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   atol=3e-2, rtol=3e-2)


class TestBackward:
    @pytest.mark.parametrize("N,V", [(32, 256), (16, 1024)])
    def test_dlogits_matches_xla(self, N, V):
        logits, labels = _case(N, V)
        dloss = jax.random.normal(jax.random.PRNGKey(3), (N,))
        _, vjp = jax.vjp(lambda a: fused_ce._fused_ce(a, labels), logits)
        _, ref_vjp = jax.vjp(lambda a: fused_ce._xla_ce(a, labels), logits)
        np.testing.assert_allclose(np.asarray(vjp(dloss)[0]),
                                   np.asarray(ref_vjp(dloss)[0]),
                                   atol=1e-5, rtol=1e-5)

    def test_softmax_never_materialized_grad_identity(self):
        # analytic check: sum_j dlogits[i, j] == 0 (softmax rows sum to 1,
        # the one-hot subtracts exactly one unit of probability mass)
        logits, labels = _case(24, 512)
        _, vjp = jax.vjp(lambda a: fused_ce._fused_ce(a, labels), logits)
        dx = np.asarray(vjp(jnp.ones(24))[0])
        np.testing.assert_allclose(dx.sum(axis=1), np.zeros(24), atol=1e-4)
        # and the label column is (p - 1) * dloss < 0
        assert (dx[np.arange(24), np.asarray(labels)] < 0).all()

    def test_mean_loss_grad_through_jit(self):
        logits, labels = _case(16, 256)
        g = jax.grad(lambda a: jnp.mean(fused_ce._fused_ce(a, labels)))(
            logits)
        gr = jax.grad(lambda a: jnp.mean(fused_ce._xla_ce(a, labels)))(
            logits)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-5)


class TestPublicWrapper:
    def test_leading_dims(self):
        B, T, V = 2, 8, 256
        logits = jax.random.normal(jax.random.PRNGKey(0), (B, T, V))
        labels = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, V)
        loss = fused_ce.fused_softmax_ce(logits, labels)
        assert loss.shape == (B, T)
        ref = fused_ce._xla_ce(logits.reshape(-1, V),
                               labels.reshape(-1)).reshape(B, T)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   atol=1e-5)

    def test_gpt_shaped_row_count_padded_not_rejected(self):
        # N = B*(T-1) is odd-ish for power-of-two T: the wrapper must pad
        # rows and still take the kernel (the review finding: without
        # padding the opt-in flag was a silent no-op for such shapes)
        B, Tm1, V = 4, 31, 256
        logits = jax.random.normal(jax.random.PRNGKey(0), (B, Tm1, V))
        labels = jax.random.randint(jax.random.PRNGKey(1), (B, Tm1), 0, V)
        loss, vjp = jax.vjp(
            lambda a: fused_ce.fused_softmax_ce(a, labels), logits)
        ref = fused_ce._xla_ce(logits.reshape(-1, V),
                               labels.reshape(-1)).reshape(B, Tm1)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   atol=1e-5)
        dl = jax.random.normal(jax.random.PRNGKey(2), (B, Tm1))
        _, ref_vjp = jax.vjp(
            lambda a: fused_ce._xla_ce(a.reshape(-1, V),
                                       labels.reshape(-1)).reshape(B, Tm1),
            logits)
        np.testing.assert_allclose(np.asarray(vjp(dl)[0]),
                                   np.asarray(ref_vjp(dl)[0]), atol=1e-5)

    def test_unaligned_vocab_falls_back(self):
        logits, labels = _case(10, 100)  # V % 128 != 0 → XLA path
        loss = fused_ce.fused_softmax_ce(logits, labels)
        np.testing.assert_allclose(np.asarray(loss),
                                   np.asarray(fused_ce._xla_ce(logits,
                                                               labels)),
                                   atol=1e-6)


class TestGPTRoute:
    def test_gpt_loss_parity_with_fused_ce(self, monkeypatch):
        # the opt-in env route must not change GPT's loss numerics
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "1")
        from paddle_tpu.text import gpt

        cfg = gpt.GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=32)
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 256,
                                  jnp.int32)
        with_fused = gpt.loss_fn(params, toks, cfg)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "0")
        without = gpt.loss_fn(params, toks, cfg)
        np.testing.assert_allclose(np.asarray(with_fused),
                                   np.asarray(without), atol=1e-5)


# ---------------------------------------------------------------------------
# no quiet fallback: a refusal reaches the caller, a shape gate picks XLA
# ---------------------------------------------------------------------------


def _refuse(*a, **k):
    raise ValueError("Mosaic refused this block shape")


def _logits_labels(N, V):
    logits = jax.random.normal(jax.random.PRNGKey(0), (N, V))
    labels = jax.random.randint(jax.random.PRNGKey(1), (N,), 0, V)
    return logits, labels


def test_refusal_in_the_kernel_build_propagates(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call", _refuse)
    logits, labels = _logits_labels(64, 512)
    with pytest.raises(ValueError, match="Mosaic refused"):
        fused_ce.fused_softmax_ce(logits, labels)
    assert not hasattr(fused_ce, "_probe")


def test_failed_shape_gate_still_picks_xla(monkeypatch):
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call", _refuse)
    logits, labels = _logits_labels(64, 500)  # vocab off the lane grid
    np.testing.assert_allclose(
        np.asarray(fused_ce.fused_softmax_ce(logits, labels)),
        np.asarray(fused_ce._xla_ce(logits, labels)), atol=1e-6)


def test_vocab_sharded_step_keeps_the_xla_reduction(monkeypatch):
    """Under a tensor-parallel partition the vocab is sharded: the kernel
    needs whole rows, so the entry leaves the reduction to GSPMD."""
    from jax.experimental import pallas as pl
    from jax.sharding import Mesh

    from paddle_tpu.ops import _pallas

    monkeypatch.setattr(pl, "pallas_call", _refuse)
    logits, labels = _logits_labels(64, 512)
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    with _pallas.partitioned(mesh, heads="mp"):
        out = fused_ce.fused_softmax_ce(logits, labels)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(fused_ce._xla_ce(logits, labels)),
                               atol=1e-6)
