"""Pallas TPU flash attention — forward + backward via custom_vjp.

Blockwise online-softmax attention (FlashAttention-2 style): per
(batch*head, q-block) grid cell the forward streams k/v blocks through VMEM
keeping a running max/denominator, so the [T, T] score matrix never hits
HBM; it also emits the per-row logsumexp.  The backward recomputes blockwise
scores from q/k and the saved logsumexp — two kernels, one accumulating dq
over k-blocks, one accumulating dk/dv over q-blocks.

Under ``jax.checkpoint`` the forward rule's two kernel outputs carry the
names ``SAVED_OUT`` / ``SAVED_LSE``: the kernel's ``out`` is the ``p @ v``
product a matmul-saving policy would have kept on the XLA path, and ``lse``
the few bytes that make it usable.  ``ops/remat_policies`` adds both names
to ``"dots"``, so that policy runs the forward kernel once a layer; a
policy that saves nothing still recomputes it.

This is the TPU-native replacement for the reference's fused attention CUDA
kernels (operators/fused/multihead_matmul_op.cu,
operators/math/bert_encoder_functor).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import _pallas

_INTERPRET = False  # tests flip this to run the kernels on CPU (interpret)

# checkpoint_name tags of the forward rule's residuals (see the header)
SAVED_OUT = "flash_attention_out"
SAVED_LSE = "flash_attention_lse"


def _xla(q, k, v, causal, scale):
    from .attention import xla_attention

    return xla_attention(q, k, v, is_causal=causal, scale=scale)


def _shape_supported(q_shape, s_len) -> bool:
    B, T, H, D = q_shape
    return T % 128 == 0 and s_len % 128 == 0 and D in (64, 128, 256)


def flash_attention(q, k, v, causal: bool = False, scale=None):
    """q,k,v: [B, T, H, D] → [B, T, H, D].  Off a TPU, and for shapes the
    static gate rejects, this is XLA attention; otherwise the kernel is
    compiled with the caller's step and a refusal raises."""
    if not (_INTERPRET or _pallas.on_tpu()):
        return _xla(q, k, v, causal, scale)

    def local(q, k, v):
        if not _shape_supported(q.shape, k.shape[1]):
            return _xla(q, k, v, causal, scale)
        return _flash(q, k, v, causal, scale)

    part = _pallas.partition()
    if part is None:
        return local(q, k, v)
    sp = part.spec(4, batch=0, heads=2)
    return part.shard_map(local, (sp, sp, sp), sp)(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, scale):
    out, _ = _flash_fwd_impl(q, k, v, causal, scale)
    return out


def _flash_fwd(q, k, v, causal, scale):
    out, lse = _flash_fwd_impl(q, k, v, causal, scale)
    out = checkpoint_name(out, SAVED_OUT)
    lse = checkpoint_name(lse, SAVED_LSE)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, res, do):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, do, causal, scale)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _heads_first(x):
    B, T, H, D = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(B * H, T, D)


def _heads_last(x, B, H):
    BH, T, D = x.shape
    return jnp.swapaxes(x.reshape(B, H, T, D), 1, 2)


_NEG = -1e30  # large-negative instead of -inf: keeps lse finite on empty rows


def _block_sizes(T, S):
    BQ = 128 if T % 128 == 0 else T
    BK = 128 if S % 128 == 0 else S
    return BQ, BK


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _flash_fwd_impl(q, k, v, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D**0.5)
    BQ, BK = _block_sizes(T, S)
    qh, kh, vh = _heads_first(q), _heads_first(k), _heads_first(v)
    nq, nk = T // BQ, S // BK

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

        def body():
            qb = q_ref[0].astype(jnp.float32)
            kb = k_ref[0].astype(jnp.float32)
            s = scale * jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if causal:
                rows = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
                cols = ki * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
                s = jnp.where(rows >= cols, s, _NEG)
            m_prev = m_scr[:, 0]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.exp(s - m_cur[:, None])
            alpha = jnp.exp(m_prev - m_cur)
            l_scr[:, 0] = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
            acc_scr[:] = acc_scr[:] * alpha[:, None] + jax.lax.dot_general(
                p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[:, 0] = m_cur

        if causal:
            @pl.when((ki * BK) <= (qi * BQ + BQ - 1))
            def _run():
                body()
        else:
            body()

        @pl.when(ki == nk - 1)
        def _finish():
            l = l_scr[:, 0]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_scr[:] / l_safe[:, None]).astype(o_ref.dtype)
            lse_ref[0] = (m_scr[:, 0] + jnp.log(l_safe))[:, None]

    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BQ, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, D), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="flash_attention_fwd",
    )(qh, kh, vh)
    return _heads_last(out, B, H), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _flash_bwd_impl(q, k, v, out, lse, do, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D**0.5)
    BQ, BK = _block_sizes(T, S)
    nq, nk = T // BQ, S // BK
    qh, kh, vh = _heads_first(q), _heads_first(k), _heads_first(v)
    doh = _heads_first(do)
    # delta_i = sum_d do_i * o_i  (rescaling term of the softmax transpose)
    delta = jnp.sum(doh.astype(jnp.float32) * _heads_first(out).astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, T, 1]

    def scores(q_ref, k_ref, lse_ref, qi, ki):
        qb = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        if causal:
            rows = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
            cols = ki * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
            s = jnp.where(rows >= cols, s, _NEG)
        return jnp.exp(s - lse_ref[0])  # p, normalized (lse block is [BQ, 1])

    # -- dq: grid (BH, nq, nk), accumulate over k blocks --------------------
    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc):
        qi, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)

        def body():
            p = scores(q_ref, k_ref, lse_ref, qi, ki)
            dp = jax.lax.dot_general(
                do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[0])
            acc[:] += scale * jax.lax.dot_general(
                ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if causal:
            @pl.when((ki * BK) <= (qi * BQ + BQ - 1))
            def _run():
                body()
        else:
            body()

        @pl.when(ki == nk - 1)
        def _fin():
            dq_ref[0] = acc[:].astype(dq_ref.dtype)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BQ, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BQ, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((BQ, D), jnp.float32)],
        interpret=_INTERPRET,
        name="flash_attention_bwd_dq",
    )(qh, kh, vh, doh, lse, delta)

    # -- dk/dv: grid (BH, nk, nq), accumulate over q blocks -----------------
    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc):
        ki, qi = pl.program_id(1), pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        def body():
            p = scores(q_ref, k_ref, lse_ref, qi, ki)
            dov = do_ref[0].astype(jnp.float32)
            dv_acc[:] += jax.lax.dot_general(
                p, dov, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                dov, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[0])
            dk_acc[:] += scale * jax.lax.dot_general(
                ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if causal:
            @pl.when((qi * BQ + BQ - 1) >= (ki * BK))
            def _run():
                body()
        else:
            body()

        @pl.when(qi == nq - 1)
        def _fin():
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, BQ, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, BQ, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, BQ, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((BK, D), jnp.float32),
            pltpu.VMEM((BK, D), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="flash_attention_bwd_dkv",
    )(qh, kh, vh, doh, lse, delta)

    return (_heads_last(dq, B, H), _heads_last(dk, B, H), _heads_last(dv, B, H))
