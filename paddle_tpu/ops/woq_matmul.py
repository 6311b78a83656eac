"""Pallas TPU W4A16 dequant-matmul — fused weight-only-int4 decode GEMM.

Weight-only int4 decode params (text/woq.py) store two signed nibbles per
int8 byte, half-split along the input dim (low nibble = rows [0, K/2),
high = rows [K/2, K)) with group-wise scales.  The XLA path must
materialize the dequantized bf16 [K, M] weight before the matmul — the
unpack (shift + concat) and group-scale reshape are producers XLA does
not fuse into a dot — so the HBM traffic is bf16-sized and the entire
point of the 4-bit format (weight-BYTES per decoded token) is lost.
Measured on the v5e through the serving bench, packed int4 decoded at
0.78x the bf16 rate before the half-split relayout.

This kernel streams the PACKED bytes through VMEM instead: each grid
step loads an int8 [BKp, BM] block (4-bit pair rows), sign-extends both
nibbles with two arithmetic shifts, applies the per-group scales in the
activation dtype (bit-identical dequant math to ``woq.w``), and feeds
the MXU with two [N, BKp] x [BKp, BM] dots accumulated in float32 —
HBM reads the int4 bytes ONCE and never writes a dequantized copy.

Forward-only by design: packed int4 weights exist only on the frozen
decode path (training and LoRA fine-tuning keep float masters).

Routing follows ops/_pallas.py (platform + static shape gate, no probe);
the flag lives in ``woq.mm`` (env ``PADDLE_TPU_W4_KERNEL``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _pallas

_INTERPRET = False  # tests flip this to run the kernel on CPU (interpret)

_N_CAP = 256  # decode/serving batches; prefill-sized N stays on XLA


def _blocks(N: int, Kp: int, M: int, gs: int):
    """(BKp, BM) or None when the shapes don't tile.

    BKp is a block of PACKED rows (= BKp original rows per nibble half);
    it must be a multiple of the scale group size so a block's rows use
    whole groups, and divide the packed row count.  M needs lane
    alignment."""
    if M % 128 or Kp % 8 or N > _N_CAP:
        return None
    bm = 256 if M % 256 == 0 else 128
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if Kp % cand == 0 and cand % gs == 0:
            return cand, bm
    return None


def _xla_w4(x, packed, scale):
    """Reference path: dequant exactly like woq.w's packed branch, then
    one matmul.  Also the kernel's parity oracle."""
    dt = x.dtype
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    K = packed.shape[0] * 2
    G = scale.shape[0]
    q = jnp.concatenate([lo, hi], axis=0)
    grouped = q.reshape(G, K // G, -1)
    w = (grouped.astype(dt) * scale.astype(dt)).reshape(K, -1)
    return x @ w


def w4_matmul(x, packed, scale):
    """x [..., K] @ dequant(packed [K/2, M] int8, scale [G, 1, M]) →
    [..., M] in x.dtype.  Rows pad to the sublane multiple.  Off a TPU,
    and for unaligned shapes or a prefill-sized N, this is the XLA
    dequant+matmul; otherwise the kernel compiles with the caller's
    step and a refusal raises."""
    lead = x.shape[:-1]
    K = x.shape[-1]
    Kp, M = packed.shape
    G = scale.shape[0]
    if K != 2 * Kp or K % G:
        raise ValueError(f"shape mismatch: x[..., {K}], packed[{Kp}, {M}],"
                         f" scale[{G}, ...]")
    gs = K // G
    N = 1
    for d in lead:
        N *= d
    x2 = x.reshape(N, K)
    Np = -(-N // 8) * 8
    blk = _blocks(Np, Kp, M, gs)
    # a step partitioned over several chips shards K or M: XLA's matmul
    if blk is None or not (_INTERPRET or _pallas.on_tpu()) \
            or _pallas.partition() is not None:
        return _xla_w4(x2, packed, scale).reshape(*lead, M)
    if Np != N:
        x2 = jnp.pad(x2, ((0, Np - N), (0, 0)))
    return _w4_call(x2, packed, scale, gs)[:N].reshape(*lead, M)


def _w4_call(x, packed, scale, gs):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, K = x.shape
    Kp, M = packed.shape
    BKp, BM = _blocks(N, Kp, M, gs)
    nk, nm = Kp // BKp, M // BM
    G2 = BKp // gs  # scale groups per block (per nibble half)
    dt = x.dtype

    # half-split layout: low nibbles hold original rows [0, K/2), high
    # [K/2, K) — pass each half of x (and of the scale table) as its own
    # contiguous operand so every BlockSpec is a plain strided slice
    x_lo, x_hi = x[:, :Kp], x[:, Kp:]
    s_lo, s_hi = scale[:Kp // gs], scale[Kp // gs:]

    def kernel(xlo_ref, xhi_ref, pk_ref, slo_ref, shi_ref, o_ref, acc):
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        # Mosaic can't legalize arith.shli/shrsi on i8 vectors (v5e cert
        # failure, window 3): widen to i32 and sign-extend the nibbles
        # with 28-bit shift pairs — value-identical to the i8 math in
        # _xla_w4 (shl-28 + ashr-28 == keep low nibble with sign)
        pk = pk_ref[...].astype(jnp.int32)
        lo = jnp.right_shift(jnp.left_shift(pk, 28), 28)
        hi = jnp.right_shift(pk, 4)

        def dq(q, s_ref):
            # dequant in the activation dtype — bit-identical to woq.w
            s = s_ref[...].astype(dt)          # [G2, 1, BM]
            qg = q.astype(dt).reshape(G2, gs, BM)
            return (qg * s).reshape(BKp, BM)

        acc[...] += (
            jnp.dot(xlo_ref[...], dq(lo, slo_ref),
                    preferred_element_type=jnp.float32)
            + jnp.dot(xhi_ref[...], dq(hi, shi_ref),
                      preferred_element_type=jnp.float32))

        @pl.when(k == nk - 1)
        def _finish():
            o_ref[...] = acc[...].astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(nm, nk),  # k innermost: each out tile's reduction completes
        in_specs=[
            pl.BlockSpec((N, BKp), lambda m, k: (0, k)),
            pl.BlockSpec((N, BKp), lambda m, k: (0, k)),
            pl.BlockSpec((BKp, BM), lambda m, k: (k, m)),
            pl.BlockSpec((G2, 1, BM), lambda m, k: (k, 0, m)),
            pl.BlockSpec((G2, 1, BM), lambda m, k: (k, 0, m)),
        ],
        out_specs=pl.BlockSpec((N, BM), lambda m, k: (0, m)),
        out_shape=jax.ShapeDtypeStruct((N, M), dt),
        scratch_shapes=[pltpu.VMEM((N, BM), jnp.float32)],
        interpret=_INTERPRET,
        name="w4_matmul",
    )(x_lo, x_hi, packed, s_lo, s_hi)
