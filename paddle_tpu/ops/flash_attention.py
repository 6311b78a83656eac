"""Pallas TPU flash attention — forward + backward via custom_vjp.

Blockwise online-softmax attention (FlashAttention-2 style): per
(batch*head, q-block) grid cell the forward streams k/v blocks through VMEM
keeping a running max/denominator, so the [T, T] score matrix never hits
HBM; it also emits the per-row logsumexp.  The backward recomputes blockwise
scores from q/k and the saved logsumexp — two kernels, one accumulating dq
over k-blocks, one accumulating dk/dv over q-blocks.

Tiles come from the shape (``_block_sizes``): a grid step has a fixed
cost of about what a 128 x 128 tile's matmuls take, so each kernel takes
the largest tile that divides the lengths and fits its VMEM budget.  Under
``causal`` a cell above the diagonal computes nothing and fetches nothing:
its index maps name the block the row (or column) already holds, and only
the tiles that straddle the diagonal build the mask.  The dk/dv kernel
scores ``k @ q.T``, so its two products are plain matmuls and it reads the
logsumexp and ``delta`` as lane-dense rows.

Precision: the MXU is fed the operands' own dtype and accumulates in
float32 (``preferred_element_type``); ``p`` and ``ds`` are cast to the
dtype of the operand they multiply, as ``attention.xla_attention`` casts
``p`` to ``q.dtype``.  The scale, the mask, the running max and sum, the
logsumexp, ``delta`` and every accumulator are float32.  So bf16 inputs
compute at the precision the caller's dtype states and the XLA path uses,
and float32 inputs are not cast at all.

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
from typing import NamedTuple

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

# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------

_SIDES = (1024, 512, 256, 128)  # tile sides the rule takes, largest first
# VMEM a kernel's cell may take for its blocks, statistics and accumulators
# by ``_vmem_bytes``'s count: compiled for a v5e at head size 256 in
# float32, cells of up to 11.5 MB by that count were taken and cells of
# 12.6 MB and more refused; every other head size and dtype took 1024 x
# 1024 tiles, so the [BQ, BK] float32 temporaries are not counted
_VMEM_BUDGET = 12 << 20


class _Use(NamedTuple):
    """What one grid cell of a kernel holds in VMEM: [BQ, D] and [BK, D]
    blocks of the inputs' dtype (double-buffered), float32 statistics as
    [BQ, 1] columns (padded to 128 lanes) or [1, BQ] rows (padded to 8
    sublanes), and float32 [BQ, D] / [BK, D] accumulators."""
    q_blocks: int
    k_blocks: int
    columns: int
    rows: int
    q_acc: int
    k_acc: int
    cap: tuple  # the largest (BQ, BK) the chip sweep supports


_USE = {
    # q, o | k, v | lse twice, m, l | - | acc
    "fwd": _Use(2, 2, 4, 0, 1, 0, (1024, 1024)),
    # q, do, dq | k, v | lse, delta twice | - | acc
    "dq": _Use(3, 2, 4, 0, 1, 0, (1024, 1024)),
    # q, do | k, v, dk, dv | - | lse, delta twice | dk, dv acc
    "dkv": _Use(2, 4, 0, 4, 0, 2, (512, 512)),
}


class Tiles(NamedTuple):
    """(BQ, BK) of each kernel."""
    fwd: tuple
    dq: tuple
    dkv: tuple


def _side(n: int, cap: int) -> int:
    if n % 128:
        return n
    return next(s for s in _SIDES if s <= cap and n % s == 0)


def _vmem_bytes(use: _Use, BQ: int, BK: int, D: int, itemsize: int) -> int:
    blocks = 2 * (use.q_blocks * BQ + use.k_blocks * BK) * D * itemsize
    stats = (use.columns * 128 + use.rows * 8) * BQ * 4
    return blocks + stats + (use.q_acc * BQ + use.k_acc * BK) * D * 4


def _block_sizes(T: int, S: int, D: int, dtype) -> Tiles:
    """Each kernel's (BQ, BK) for q length ``T``, k length ``S``, head
    size ``D`` and input ``dtype``: the largest sides up to the kernel's
    cap that divide the lengths, the larger side halved until the cell
    fits ``_VMEM_BUDGET``.  Lengths of 128 keep 128 x 128."""
    itemsize = jnp.dtype(dtype).itemsize
    tiles = {}
    for name, use in _USE.items():
        BQ, BK = _side(T, use.cap[0]), _side(S, use.cap[1])
        while _vmem_bytes(use, BQ, BK, D, itemsize) > _VMEM_BUDGET:
            if BK >= BQ and BK in _SIDES[:-1]:
                BK //= 2
            elif BQ in _SIDES[:-1]:
                BQ //= 2
            else:
                break
        tiles[name] = (BQ, BK)
    return Tiles(**tiles)


def _cells(causal, qi, ki, BQ, BK, body):
    """Run ``body(masked)`` on a live cell: a tile the causal diagonal
    crosses builds the mask, a tile wholly below it does not, a tile
    wholly above it computes nothing."""
    from jax.experimental import pallas as pl

    if not causal:
        body(False)
        return
    first_row, last_row = qi * BQ, qi * BQ + BQ - 1
    first_col, last_col = ki * BK, ki * BK + BK - 1
    live = first_col <= last_row
    crossed = last_col > first_row

    @pl.when(jnp.logical_and(live, crossed))
    def _diagonal():
        body(True)

    @pl.when(jnp.logical_and(live, jnp.logical_not(crossed)))
    def _below():
        body(False)


def _scores(a, b, scale, masked, a0, b0, transposed=False):
    """``scale * a @ b.T`` in float32 for rows at positions ``a0 + i`` and
    columns at ``b0 + j``.  Where ``masked`` the causal mask is applied:
    rows of q (``a`` = q) keep the columns at or before them, rows of k
    (``transposed``, ``a`` = k, ``b`` = q) the columns at or after them."""
    s = scale * jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    if masked:
        diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
        keep = diff <= b0 - a0 if transposed else diff >= b0 - a0
        s = jnp.where(keep, s, _NEG)
    return s


def _row_blocks(causal, BQ, BK, n):
    """Index map of the k-side blocks a (row, column) grid walks along a
    q-block's row: under ``causal`` a cell above the diagonal names the
    row's last live block, which the pipeline holds, so it fetches
    nothing."""
    if not causal:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (
        b, jnp.minimum(j, jnp.minimum((i * BQ + BQ - 1) // BK, n - 1)), 0)


def _column_blocks(causal, BQ, BK, n):
    """Index map of the q-side blocks the dk/dv grid walks down a
    k-block's column: a cell above the diagonal names the column's first
    live block."""
    if not causal:
        return lambda b, j, i: (b, i, 0)
    return lambda b, j, i: (
        b, jnp.maximum(i, jnp.minimum(j * BK // BQ, n - 1)), 0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _flash_fwd_impl(q, k, v, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    S = k.shape[1]
    scale = scale if scale is not None else 1.0 / (D**0.5)
    BQ, BK = _block_sizes(T, S, D, q.dtype).fwd
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

        def body(masked):
            s = _scores(q_ref[0], k_ref[0], scale, masked, qi * BQ, ki * BK)
            m_prev = m_scr[:]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_cur)
            alpha = jnp.exp(m_prev - m_cur)
            l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
            v = v_ref[0]
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[:] = m_cur

        _cells(causal, qi, ki, BQ, BK, body)

        @pl.when(ki == nk - 1)
        def _finish():
            l_safe = jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
            o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
            lse_ref[0] = m_scr[:] + jnp.log(l_safe)

    kv_map = _row_blocks(causal, BQ, BK, nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, D), kv_map),
            pl.BlockSpec((1, BK, D), kv_map),
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
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D**0.5)
    tiles = _block_sizes(T, k.shape[1], D, q.dtype)
    qh, kh, vh = _heads_first(q), _heads_first(k), _heads_first(v)
    doh = _heads_first(do)
    # delta_i = sum_d do_i * o_i  (rescaling term of the softmax transpose)
    delta = jnp.sum(doh.astype(jnp.float32) * _heads_first(out).astype(jnp.float32),
                    axis=-1)  # [BH, T]
    # dq's score tiles have a row per query and read lse / delta as
    # columns; dk/dv's have a row per key and read them as lane-dense rows
    dq = _bwd_dq(qh, kh, vh, doh, lse, delta[..., None], causal, scale,
                 tiles.dq)
    dk, dv = _bwd_dkv(qh, kh, vh, doh, lse.reshape(B * H, 1, T),
                      delta[:, None], causal, scale, tiles.dkv)
    return (_heads_last(dq, B, H), _heads_last(dk, B, H), _heads_last(dv, B, H))


def _bwd_dq(qh, kh, vh, doh, lse, delta, causal, scale, tile):
    """dq over heads-first [BH, T, D] operands: grid (BH, nq, nk),
    accumulating over k blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = qh.shape
    BQ, BK = tile
    nq, nk = T // BQ, kh.shape[1] // BK

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc):
        qi, ki = pl.program_id(1), pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc[:] = jnp.zeros_like(acc)

        def body(masked):
            k = k_ref[0]
            s = _scores(q_ref[0], k, scale, masked, qi * BQ, ki * BK)
            p = jnp.exp(s - lse_ref[0])
            dp = jax.lax.dot_general(
                do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[0])
            acc[:] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _cells(causal, qi, ki, BQ, BK, body)

        @pl.when(ki == nk - 1)
        def _fin():
            dq_ref[0] = (scale * acc[:]).astype(dq_ref.dtype)

    kv_map = _row_blocks(causal, BQ, BK, nk)
    return pl.pallas_call(
        dq_kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BK, D), kv_map),
            pl.BlockSpec((1, BK, D), kv_map),
            pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BQ, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, BQ, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), qh.dtype),
        scratch_shapes=[pltpu.VMEM((BQ, D), jnp.float32)],
        interpret=_INTERPRET,
        name="flash_attention_bwd_dq",
    )(qh, kh, vh, doh, lse, delta)


def _bwd_dkv(qh, kh, vh, doh, lse, delta, causal, scale, tile):
    """dk, dv over heads-first operands: grid (BH, nk, nq), accumulating
    over q blocks.  A cell computes the scores transposed, ``k @ q.T``
    ([BK, BQ]), so that dv = p @ do and dk = ds @ q are plain matmuls and
    ``lse`` / ``delta`` ([BH, 1, T]) are read as lane-dense rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = qh.shape
    S = kh.shape[1]
    BQ, BK = tile
    nq, nk = T // BQ, S // BK

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc):
        ki, qi = pl.program_id(1), pl.program_id(2)

        @pl.when(qi == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

        def body(masked):
            q, do = q_ref[0], do_ref[0]
            s = _scores(k_ref[0], q, scale, masked, ki * BK, qi * BQ,
                        transposed=True)
            p = jnp.exp(s - lse_ref[0])
            dv_acc[:] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                v_ref[0], do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - dl_ref[0])
            dk_acc[:] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _cells(causal, qi, ki, BQ, BK, body)

        @pl.when(qi == nq - 1)
        def _fin():
            dk_ref[0] = (scale * dk_acc[:]).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    q_map = _column_blocks(causal, BQ, BK, nq)

    def row_map(b, j, i):
        return (b, 0, q_map(b, j, i)[1])

    return pl.pallas_call(
        dkv_kernel,
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, BQ, D), q_map),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, BQ, D), q_map),
            pl.BlockSpec((1, 1, BQ), row_map),
            pl.BlockSpec((1, 1, BQ), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, BK, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), kh.dtype),
            jax.ShapeDtypeStruct((BH, S, D), vh.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((BK, D), jnp.float32),
            pltpu.VMEM((BK, D), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="flash_attention_bwd_dkv",
    )(qh, kh, vh, doh, lse, delta)
