"""Attention ops: XLA reference path + Pallas flash-attention fast path.

Reference capability: operators/fused/multihead_matmul_op.cu and
math/bert_encoder_functor (fused QKV attention for BERT-era serving).
TPU-first: a blockwise flash attention Pallas kernel (paddle_tpu/ops/
flash_attention.py) keeps the softmax running-max online so the full
[T, T] score matrix never materialises in HBM; the XLA path below is the
correctness reference and the fallback for CPU tests.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ..core.dispatch import dispatch
from ..core.tensor import Tensor


def _v(x):
    return x.value if isinstance(x, Tensor) else x


def _use_flash(q_shape) -> bool:
    """Flash kernel on a TPU for shapes its static gate takes."""
    # ablation kill-switch ("0"/"" = flash stays on, matching the
    # PADDLE_TPU_REMAT_PREVENT_CSE flag convention)
    if os.environ.get("PADDLE_TPU_NO_FLASH", "") not in ("", "0"):
        return False
    from . import _pallas

    B, T, H, D = q_shape
    return _pallas.on_tpu() and T % 128 == 0 and D in (64, 128, 256)


def xla_attention(q, k, v, mask=None, is_causal=False, scale=None):
    """Plain XLA attention on [B, T, H, D]; XLA fuses this well for short T."""
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D**0.5)
    qT = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    if is_causal:
        causal = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        qT = jnp.where(causal[None, None], qT, -1e30)
    if mask is not None:
        qT = qT + mask
    p = jax.nn.softmax(qT.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def attention_array(q, k, v, mask=None, is_causal=False, scale=None):
    """Array-level entry used by jitted model code (GPT flagship).  The
    ``attn`` scope of the training and prefill paths is here: the
    attention itself, not its projections."""
    with jax.named_scope("attn"):
        if mask is None and _use_flash(q.shape):
            from . import flash_attention as fa

            return fa.flash_attention(q, k, v, causal=is_causal,
                                      scale=scale)
        return xla_attention(q, k, v, mask=mask, is_causal=is_causal,
                             scale=scale)


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True):
    mask = _v(attn_mask) if attn_mask is not None else None

    def fn(q, k, v):
        out = attention_array(q, k, v, mask=mask, is_causal=is_causal)
        return out

    out = dispatch(fn, query, key, value, op_name="sdpa")
    if dropout_p > 0.0 and training:
        from ..nn import functional as F

        out = F.dropout(out, dropout_p, training=training)
    return out
