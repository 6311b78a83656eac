"""Pallas fused LayerNorm kernel vs the XLA reference, in interpret mode.

Unlike the flash-attention kernel (whose Mosaic lowering can only run
on the chip, checked by chip_smoke.py), the fused LayerNorm kernels
run here under ``interpret=True`` so the CPU suite always exercises the
actual kernel bodies — forward statistics, the custom_vjp plumbing, and the
revisited-block dgamma/dbeta accumulator.

Reference parity target: operators/layer_norm_op.cu (fp32 statistics
accumulation regardless of IO dtype).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import fused_norm


@pytest.fixture(autouse=True)
def _interpret_mode():
    old = fused_norm._INTERPRET
    fused_norm._INTERPRET = True
    yield
    fused_norm._INTERPRET = old


def _rand(shape, dtype=jnp.float32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


class TestForward:
    @pytest.mark.parametrize("N,F", [(64, 256), (32, 128), (256, 512)])
    def test_matches_xla_f32(self, N, F):
        x = _rand((N, F))
        g = _rand((F,), seed=1) + 1.0
        b = _rand((F,), seed=2)
        y = fused_norm._fused_ln(x, g, b, 1e-5)
        ref = fused_norm._xla_ln(x, g, b, 1e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_bf16_io_f32_stats(self):
        # bf16 in/out but fp32 statistics: the kernel must stay within
        # bf16-rounding distance of an all-f32 reference (a bf16-stats
        # implementation would drift far beyond this tolerance)
        x = _rand((64, 256), jnp.bfloat16)
        g = (_rand((256,), seed=1) + 1.0).astype(jnp.bfloat16)
        b = _rand((256,), seed=2).astype(jnp.bfloat16)
        y = fused_norm._fused_ln(x, g, b, 1e-5)
        ref = fused_norm._xla_ln(x.astype(jnp.float32),
                                 g.astype(jnp.float32),
                                 b.astype(jnp.float32), 1e-5)
        assert y.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(ref), atol=3e-2, rtol=3e-2)

    def test_row_stats_are_correct(self):
        x = _rand((32, 128))
        _, mu, rstd = fused_norm._ln_fwd_impl(
            x, jnp.ones(128), jnp.zeros(128), 1e-5)
        np.testing.assert_allclose(mu[:, 0], np.mean(np.asarray(x), axis=1),
                                   atol=1e-6)
        np.testing.assert_allclose(
            rstd[:, 0],
            1.0 / np.sqrt(np.var(np.asarray(x), axis=1) + 1e-5), atol=1e-5)


class TestBackward:
    @pytest.mark.parametrize("N,F", [(64, 256), (48, 128)])
    def test_grads_match_xla(self, N, F):
        x = _rand((N, F))
        g = _rand((F,), seed=1) + 1.0
        b = _rand((F,), seed=2)
        dy = _rand((N, F), seed=3)
        _, vjp = jax.vjp(lambda a, w, c: fused_norm._fused_ln(a, w, c, 1e-5),
                         x, g, b)
        _, ref_vjp = jax.vjp(lambda a, w, c: fused_norm._xla_ln(a, w, c, 1e-5),
                             x, g, b)
        for name, got, want in zip(("dx", "dg", "db"), vjp(dy), ref_vjp(dy)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-4, rtol=2e-4, err_msg=name)

    def test_multi_block_accumulator(self):
        # N=256 with BN<=... forces several grid steps revisiting the same
        # dg/db block — the init-at-step-0 + accumulate pattern under test
        x = _rand((256, 128))
        g = _rand((128,), seed=1) + 1.0
        dy = _rand((256, 128), seed=3)
        _, vjp = jax.vjp(lambda a, w: fused_norm._fused_ln(
            a, w, jnp.zeros(128), 1e-5), x, g)
        dx, dg = vjp(dy)
        xhat = (np.asarray(x) - np.mean(np.asarray(x), 1, keepdims=True)) \
            / np.sqrt(np.var(np.asarray(x), 1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(np.asarray(dg),
                                   np.sum(np.asarray(dy) * xhat, axis=0),
                                   atol=1e-3, rtol=1e-4)

    def test_numeric_grad_spot(self):
        # central differences on a few elements, OpTest-style (f32: a large
        # eps keeps the truncation error above the rounding noise)
        x = _rand((8, 128))
        f = lambda a: float(jnp.sum(  # noqa: E731
            fused_norm._fused_ln(a, jnp.ones(128), jnp.zeros(128), 1e-5)
            ** 2))
        gx = jax.grad(lambda a: jnp.sum(
            fused_norm._fused_ln(a, jnp.ones(128), jnp.zeros(128), 1e-5)
            ** 2))(x)
        eps = 3e-2
        for (i, j) in [(0, 0), (3, 64), (7, 127)]:
            num = (f(x.at[i, j].add(eps)) - f(x.at[i, j].add(-eps))) \
                / (2 * eps)
            np.testing.assert_allclose(float(gx[i, j]), num,
                                       atol=5e-2, rtol=5e-2)


class TestPublicWrapper:
    def test_leading_dims_flattened(self):
        x = _rand((4, 16, 256))
        y = fused_norm.fused_layer_norm(x)
        ref = fused_norm._xla_ln(x, jnp.ones(256), jnp.zeros(256), 1e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
        assert y.shape == x.shape

    def test_unsupported_shape_falls_back(self):
        # F not a multiple of 128: must silently use the XLA expression
        x = _rand((5, 100))
        y = fused_norm.fused_layer_norm(x)
        ref = fused_norm._xla_ln(x, jnp.ones(100), jnp.zeros(100), 1e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)

    def test_affine_optional(self):
        x = _rand((16, 128))
        w = _rand((128,), seed=1)
        y = fused_norm.fused_layer_norm(x, weight=w)
        ref = fused_norm._xla_ln(x, w, jnp.zeros(128), 1e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_row_count_padded_not_rejected(self):
        # N=5 is not a row-block multiple: the wrapper must pad rows and
        # still take the kernel (grads through the pad/slice stay exact)
        x = _rand((5, 128))
        w = _rand((128,), seed=1) + 1.0
        y, vjp = jax.vjp(lambda a: fused_norm.fused_layer_norm(a, weight=w),
                         x)
        ref, ref_vjp = jax.vjp(
            lambda a: fused_norm._xla_ln(a, w, jnp.zeros(128), 1e-5), x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
        dy = _rand((5, 128), seed=3)
        np.testing.assert_allclose(np.asarray(vjp(dy)[0]),
                                   np.asarray(ref_vjp(dy)[0]), atol=2e-4)


class TestFunctionalRoute:
    def test_layer_norm_routes_and_matches(self):
        # functional.layer_norm keeps its numerics whether or not the fused
        # path engages (on CPU the probe rejects it; parity must hold anyway)
        import paddle_tpu as paddle

        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(4, 256).astype(np.float32))
        w = paddle.to_tensor(np.ones(256, np.float32))
        b = paddle.to_tensor(np.zeros(256, np.float32))
        out = paddle.nn.functional.layer_norm(x, 256, weight=w, bias=b)
        ref = fused_norm._xla_ln(jnp.asarray(x.numpy()), jnp.ones(256),
                                 jnp.zeros(256), 1e-5)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref), atol=1e-5)

    def test_layer_norm_bias_without_weight(self):
        # regression: bias-only used to read weight's varargs slot
        # (IndexError) because the unpacking assumed weight was present
        import paddle_tpu as paddle

        x = paddle.to_tensor(np.random.RandomState(1)
                             .randn(4, 256).astype(np.float32))
        b = paddle.to_tensor(np.full(256, 0.5, np.float32))
        out = paddle.nn.functional.layer_norm(x, 256, bias=b)
        ref = fused_norm._xla_ln(jnp.asarray(x.numpy()), jnp.ones(256),
                                 jnp.full(256, 0.5), 1e-5)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# no quiet fallback: a refusal reaches the caller, a shape gate picks XLA
# ---------------------------------------------------------------------------


def _refuse(*a, **k):
    raise ValueError("Mosaic refused this block shape")


class TestNoFallback:
    def test_refusal_in_the_kernel_build_propagates(self, monkeypatch):
        from jax.experimental import pallas as pl

        monkeypatch.setattr(pl, "pallas_call", _refuse)
        with pytest.raises(ValueError, match="Mosaic refused"):
            fused_norm.fused_layer_norm(_rand((64, 256)))
        with pytest.raises(ValueError, match="Mosaic refused"):
            jax.jit(fused_norm.fused_layer_norm)(_rand((64, 256)))

    def test_failed_shape_gate_still_picks_xla(self, monkeypatch):
        from jax.experimental import pallas as pl

        monkeypatch.setattr(pl, "pallas_call", _refuse)
        x = _rand((64, 200))  # feature width off the 128-lane grid
        np.testing.assert_allclose(
            np.asarray(fused_norm.fused_layer_norm(x)),
            np.asarray(fused_norm._xla_ln(x, jnp.ones((200,)),
                                          jnp.zeros((200,)), 1e-5)),
            atol=1e-6)

    def test_off_a_tpu_without_interpret_is_xla_not_a_probe(
            self, monkeypatch):
        from jax.experimental import pallas as pl

        monkeypatch.setattr(fused_norm, "_INTERPRET", False)
        monkeypatch.setattr(pl, "pallas_call", _refuse)
        assert not hasattr(fused_norm, "_probe")
        assert not hasattr(fused_norm, "_FALLBACK")
        fused_norm.fused_layer_norm(_rand((64, 256)))  # the CPU: XLA
