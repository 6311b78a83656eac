"""Pallas TPU fused LayerNorm — forward + backward via custom_vjp.

One VMEM-resident pass per row-block: the forward computes mean/rstd and the
normalized-affine output without materializing the centered tensor in HBM;
the backward fuses dx with the dgamma/dbeta row-reductions by revisiting a
single output block across the sequential TPU grid (the accumulator lives in
VMEM for the whole sweep).  Statistics and accumulation are always float32
regardless of the input dtype (bf16-safe, matching the reference kernels'
fp32 mean/variance accumulators).

This is the TPU-native replacement for the reference's fused LayerNorm CUDA
kernels (operators/layer_norm_op.cu, and the inference-side fusions
operators/fused/fused_fc_elementwise_layernorm_op.cu,
operators/fused/skip_layernorm_op.cu) — there the fusion is hand-scheduled
per kernel pair; here XLA already fuses the surrounding elementwise ops and
the Pallas kernel only takes over the row-statistics pattern XLA handles
with an extra HBM round-trip.

Like ops/flash_attention.py, the public entry routes by platform and static
shape (ops/_pallas.py): the plain XLA expression off a TPU and for
unsupported shapes, the kernel otherwise — no probe, no retry on XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _pallas
from ._pallas import pad_rows as _pad_rows
from ._pallas import row_block as _row_block_for

_INTERPRET = False  # tests flip this to run the kernels on CPU (interpret)


def _row_block(N: int, F: int) -> int | None:
    return _row_block_for(N, F)


def _xla_ln(x, g, b, eps):
    # cast back: fp32 affine params promote a bf16 x to fp32, but the
    # public contract is output dtype == x.dtype (what the Pallas path
    # returns) — a shape-gated site must not flip the residual-stream
    # dtype (it breaks the GPT scan carry)
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return ((x - m) * jax.lax.rsqrt(v + eps) * g + b).astype(x.dtype)


def fused_layer_norm(x, weight=None, bias=None, eps: float = 1e-5):
    """LayerNorm over the last axis of ``x`` ([..., F] -> [..., F]).

    ``weight``/``bias`` are optional [F] affine parameters.  Rows are
    padded up to the kernel's row-block multiple (pad rows' cotangents are
    zero by construction, so grads stay exact).  Off a TPU, and for an
    unaligned feature width, this is the XLA expression; otherwise the
    kernel compiles with the caller's step and a refusal raises."""
    F = x.shape[-1]
    g = jnp.ones((F,), x.dtype) if weight is None else weight
    b = jnp.zeros((F,), x.dtype) if bias is None else bias
    if x.ndim < 2 or not (_INTERPRET or _pallas.on_tpu()):
        return _xla_ln(x, g, b, eps)

    def local(x, g, b):
        N = 1
        for d in x.shape[:-1]:
            N *= d
        Np = _pad_rows(N)
        if F % 128 or _row_block(Np, F) is None:
            return _xla_ln(x, g, b, eps)
        x2 = x.reshape(N, F)
        if Np != N:
            x2 = jnp.pad(x2, ((0, Np - N), (0, 0)))
        return _fused_ln(x2, g, b, eps)[:N].reshape(x.shape)

    part = _pallas.partition()
    if part is None:
        return local(x, g, b)
    # rows go with the batch; over a heads axis x is replicated and each
    # shard normalizes its own copy
    xs = part.spec(x.ndim, batch=0)
    return part.shard_map(local, (xs, _pallas.P(), _pallas.P()), xs)(x, g, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ln(x, g, b, eps):
    y, _, _ = _ln_fwd_impl(x, g, b, eps)
    return y


def _ln_fwd(x, g, b, eps):
    y, mu, rstd = _ln_fwd_impl(x, g, b, eps)
    # b rides the residuals only for its dtype: the bias cotangent must
    # match the bias primal (which may differ from the weight's dtype)
    return y, (x, g, b, mu, rstd)


def _ln_bwd(eps, res, dy):
    x, g, b, mu, rstd = res
    dx, dg, db = _ln_bwd_impl(x, g, mu, rstd, dy)
    return dx, dg.astype(g.dtype), db.astype(b.dtype)


_fused_ln.defvjp(_ln_fwd, _ln_bwd)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _ln_fwd_impl(x, g, b, eps):
    from jax.experimental import pallas as pl

    N, F = x.shape
    BN = _row_block(N, F)
    g2, b2 = g.reshape(1, F), b.reshape(1, F)

    def kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rstd_ref):
        xb = x_ref[...].astype(jnp.float32)
        m = jnp.mean(xb, axis=1)
        c = xb - m[:, None]
        v = jnp.mean(c * c, axis=1)
        r = jax.lax.rsqrt(v + eps)
        xhat = c * r[:, None]
        y_ref[...] = (xhat * g_ref[...].astype(jnp.float32)
                      + b_ref[...].astype(jnp.float32)).astype(y_ref.dtype)
        mu_ref[...] = m[:, None]
        rstd_ref[...] = r[:, None]

    y, mu, rstd = pl.pallas_call(
        kernel,
        grid=(N // BN,),
        in_specs=[
            pl.BlockSpec((BN, F), lambda i: (i, 0)),
            pl.BlockSpec((1, F), lambda i: (0, 0)),
            pl.BlockSpec((1, F), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BN, F), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, F), x.dtype),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="fused_layer_norm_fwd",
    )(x, g2, b2)
    return y, mu, rstd


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _ln_bwd_impl(x, g, mu, rstd, dy):
    from jax.experimental import pallas as pl

    N, F = x.shape
    BN = _row_block(N, F)
    nb = N // BN
    g2 = g.reshape(1, F)

    # dgamma/dbeta accumulate into one (1, F) output block revisited by every
    # sequential grid step — the block stays VMEM-resident across the sweep
    def kernel(x_ref, g_ref, mu_ref, rstd_ref, dy_ref,
               dx_ref, dg_ref, db_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            dg_ref[...] = jnp.zeros_like(dg_ref)
            db_ref[...] = jnp.zeros_like(db_ref)

        xb = x_ref[...].astype(jnp.float32)
        dyb = dy_ref[...].astype(jnp.float32)
        r = rstd_ref[...][:, 0]
        xhat = (xb - mu_ref[...]) * r[:, None]
        wdy = dyb * g_ref[...].astype(jnp.float32)
        c1 = jnp.mean(wdy, axis=1)
        c2 = jnp.mean(wdy * xhat, axis=1)
        dx_ref[...] = ((wdy - c1[:, None] - xhat * c2[:, None])
                       * r[:, None]).astype(dx_ref.dtype)
        dg_ref[...] += jnp.sum(dyb * xhat, axis=0)[None, :]
        db_ref[...] += jnp.sum(dyb, axis=0)[None, :]

    dx, dg, db = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((BN, F), lambda i: (i, 0)),
            pl.BlockSpec((1, F), lambda i: (0, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, F), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BN, F), lambda i: (i, 0)),
            pl.BlockSpec((1, F), lambda i: (0, 0)),
            pl.BlockSpec((1, F), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, F), x.dtype),
            jax.ShapeDtypeStruct((1, F), jnp.float32),
            jax.ShapeDtypeStruct((1, F), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="fused_layer_norm_bwd",
    )(x, g2, mu, rstd, dy)
    return dx, dg.reshape(F), db.reshape(F)
