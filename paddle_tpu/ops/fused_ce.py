"""Pallas TPU fused softmax cross-entropy — forward + backward, blockwise
over the vocabulary.

The plain XLA path (jax.nn.log_softmax + take_along_axis) materializes a
float32 [N, V] log-probability tensor in HBM plus its cotangent — for a
GPT-class vocab (V ≈ 50k) that is the single largest activation in the
model.  This kernel streams vocab blocks through VMEM instead:

* forward: one online-softmax sweep per row block keeps a running
  max/denominator (exactly flash attention's trick applied to the loss
  head) and picks out the label logit with an in-block iota compare — HBM
  traffic is one read of the logits, and the residuals are two [N] vectors
  (logsumexp and label logit), not an [N, V] softmax;
* backward: dlogits[i, j] = (exp(x[i,j] - lse[i]) - 1{j == label[i]}) *
  dloss[i], recomputed blockwise from the same logits — the softmax is
  never stored.

Statistics and accumulation are float32 regardless of the logits dtype.

Reference parity: this is the loss-head half of the reference's
softmax_with_cross_entropy_op.cu (fused softmax+CE kernel); the
vocab-sharded collective variant (c_softmax_with_cross_entropy, used by
ParallelCrossEntropy) stays on the XLA+psum path in
distributed/megatron.py — there the shard-local max/sum reductions are
tiny and the collectives dominate, so a Pallas body buys nothing.

Routing (platform + static shape gate, no probe) follows ops/_pallas.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _pallas
from ._pallas import pad_rows as _pad_rows
from ._pallas import row_block as _row_block_for

_INTERPRET = False  # tests flip this to run the kernels on CPU (interpret)


def _blocks(N: int, V: int):
    bv = None
    for cand in (2048, 1024, 512, 256, 128):
        if V % cand == 0:
            bv = cand
            break
    if bv is None:
        return None
    bn = _row_block_for(N, bv)
    return None if bn is None else (bn, bv)


def _xla_ce(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def fused_softmax_ce(logits, labels):
    """Per-row cross-entropy: logits [..., V], int labels [...] → loss
    [...] float32.  Rows are padded up to the kernel's row-block multiple
    (pad rows' cotangents are zero by construction, so dlogits stays
    exact — without this, GPT-style row counts like B*(T-1) would
    silently miss the fused path).  Off a TPU, and for an unaligned
    vocab, this is the XLA expression; otherwise the kernel compiles
    with the caller's step and a refusal raises."""
    V = logits.shape[-1]

    def xla(logits, labels):
        return _xla_ce(logits.reshape(-1, V),
                       labels.reshape(-1).astype(jnp.int32)
                       ).reshape(labels.shape)

    def local(logits, labels):
        lead = logits.shape[:-1]
        N = 1
        for d in lead:
            N *= d
        Np = _pad_rows(N)
        if _blocks(Np, V) is None:
            return xla(logits, labels)
        l2 = logits.reshape(N, V)
        lbl = labels.reshape(N).astype(jnp.int32)
        if Np != N:
            l2 = jnp.pad(l2, ((0, Np - N), (0, 0)))
            lbl = jnp.pad(lbl, (0, Np - N))
        return _fused_ce(l2, lbl)[:N].reshape(lead)

    part = _pallas.partition()
    if not (_INTERPRET or _pallas.on_tpu()) \
            or (part is not None and part.heads is not None):
        # a heads (tensor-parallel) axis shards the vocab: the kernel
        # needs whole rows, so GSPMD keeps its own sharded reduction
        return xla(logits, labels)
    if part is None:
        return local(logits, labels)
    ls = part.spec(logits.ndim, batch=0)
    ys = part.spec(labels.ndim, batch=0)
    return part.shard_map(local, (ls, ys), ys)(logits, labels)


@jax.custom_vjp
def _fused_ce(logits, labels):
    loss, _ = _ce_fwd_impl(logits, labels)
    return loss


def _ce_fwd(logits, labels):
    loss, lse = _ce_fwd_impl(logits, labels)
    return loss, (logits, labels, lse)


def _ce_bwd(res, dloss):
    import numpy as np

    logits, labels, lse = res
    # integer primal → float0 cotangent (jax's "no gradient" dtype)
    dlbl = np.zeros(labels.shape, jax.dtypes.float0)
    return _ce_bwd_impl(logits, labels, lse, dloss), dlbl


_fused_ce.defvjp(_ce_fwd, _ce_bwd)


_NEG = -1e30


def _ce_fwd_impl(logits, labels):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, V = logits.shape
    BN, BV = _blocks(N, V)
    nv = V // BV
    lbl2 = labels.reshape(N, 1)

    def kernel(x_ref, lbl_ref, lse_ref, pick_ref, m_scr, l_scr, p_scr):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG)
            l_scr[:] = jnp.zeros_like(l_scr)
            p_scr[:] = jnp.zeros_like(p_scr)

        xb = x_ref[...].astype(jnp.float32)
        cols = j * BV + jax.lax.broadcasted_iota(jnp.int32, (BN, BV), 1)
        hit = cols == lbl_ref[...]  # [BN, 1] broadcasts over the block
        p_scr[:, 0] += jnp.sum(jnp.where(hit, xb, 0.0), axis=1)
        m_prev = m_scr[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(xb, axis=1))
        l_scr[:, 0] = l_scr[:, 0] * jnp.exp(m_prev - m_cur) \
            + jnp.sum(jnp.exp(xb - m_cur[:, None]), axis=1)
        m_scr[:, 0] = m_cur

        @pl.when(j == nv - 1)
        def _finish():
            lse_ref[:, 0] = m_scr[:, 0] + jnp.log(l_scr[:, 0])
            pick_ref[:, 0] = p_scr[:, 0]

    lse, pick = pl.pallas_call(
        kernel,
        grid=(N // BN, nv),
        in_specs=[
            pl.BlockSpec((BN, BV), lambda i, j: (i, j)),
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BN, 1), jnp.float32),
            pltpu.VMEM((BN, 1), jnp.float32),
            pltpu.VMEM((BN, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="fused_softmax_ce_fwd",
    )(logits, lbl2)
    return (lse - pick)[:, 0], lse


def _ce_bwd_impl(logits, labels, lse, dloss):
    from jax.experimental import pallas as pl

    N, V = logits.shape
    BN, BV = _blocks(N, V)
    lbl2 = labels.reshape(N, 1)
    dl2 = dloss.reshape(N, 1).astype(jnp.float32)

    def kernel(x_ref, lbl_ref, lse_ref, dl_ref, dx_ref):
        j = pl.program_id(1)
        xb = x_ref[...].astype(jnp.float32)
        p = jnp.exp(xb - lse_ref[...])
        cols = j * BV + jax.lax.broadcasted_iota(jnp.int32, (BN, BV), 1)
        onehot = (cols == lbl_ref[...]).astype(jnp.float32)
        dx_ref[...] = ((p - onehot) * dl_ref[...]).astype(dx_ref.dtype)

    dx = pl.pallas_call(
        kernel,
        grid=(N // BN, V // BV),
        in_specs=[
            pl.BlockSpec((BN, BV), lambda i, j: (i, j)),
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BN, BV), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, V), logits.dtype),
        interpret=_INTERPRET,
        name="fused_softmax_ce_bwd",
    )(logits, lbl2, lse, dl2)
    return dx
