"""Weight-only int8 quantization for GPT decode (W8A16).

Autoregressive decode is HBM-bandwidth-bound: every generated token reads
every weight once, so at batch sizes below the roofline knee the decode
rate is weight-bytes/sec, not FLOPs.  Storing the matmul weights as int8
with per-output-channel fp scales reads half the bytes of bf16 (a quarter
of fp32) — XLA fuses the dequant (convert + channel-scale multiply) into
the matmul's weight read, so no full-precision copy is ever materialized.
Activations stay bf16 (W8A16): decode-time activation tensors are tiny
([B, 1, D]), so activation quantization buys nothing here — this is the
standard weight-only serving recipe, distinct from quantization/int8_infer
(W8A8 with s32 accumulation) which targets compute-bound batch inference.

Usage:
    qparams = woq.quantize_gpt_int8(params)          # same tree keys +
                                                     # "<name>_s" scales
    logits, cache = generate.decode_step(qparams, cache, tok, pos, cfg)
    text.generate.generate(qparams, cfg, prompt, ...)  # transparently

The decode path resolves weights through ``woq.w(p, name, dt)``, which
dequantizes int8 entries and is the identity on float entries — float
params flow through unchanged, so the same decode code serves both.
"""
from __future__ import annotations

import os

import jax

import jax.numpy as jnp
import numpy as np

# block-level matmul weights and the OUTPUT-channel axis to scale over
# (axis indices are for the PER-LAYER slice, i.e. without the leading L)
_BLOCK_WEIGHTS = {
    "qkv_w": 2,   # [3, D, D]   -> out axis 2
    "q_w": 1,     # [D, D]
    "kv_w": 2,    # [2, D, Dkv]
    "proj_w": 1,  # [D, D]
    "fc_w": 1,    # [D, F]
    "gate_w": 1,  # [D, F]  (swiglu third matmul)
    "out_w": 1,   # [F, D]
}

# expert weights inside blocks["moe"]: [E, D, F] / [E, F, D] per layer —
# out axis 2 either way.  The router (router_w) stays float: it is tiny
# and its argmax decides WHICH experts run — routing flips are a far
# larger error than any bandwidth win.
_MOE_WEIGHTS = {"w_in": 2, "w_out": 2}


def _quant(w, axis: int):
    """Symmetric per-channel int8; axis is the output-channel axis of the
    PER-LAYER weight (shift by one for the stacked [L, ...] layout).

    Every weight here is [..., in, out]: reduce ONLY the input-dim axis,
    keeping the layer axis (scan slices it per block), any projection
    stack axis (q/k/v magnitudes diverge after training — sharing one
    scale across the stack would waste v's 8-bit range on q's outliers),
    and the output axis."""
    w = np.asarray(w, np.float32)
    stacked_out = axis + 1   # leading L dim of the stacked blocks
    stacked_in = stacked_out - 1
    scale = np.maximum(np.abs(w).max(axis=stacked_in, keepdims=True), 1e-8)
    q = np.clip(np.round(w / scale * 127.0), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray((scale / 127.0).astype(np.float32))


def _quantize_wte_int8(out: dict, params: dict):
    """wte [V, D]: PER-ROW int8 scales [V, 1] serve both uses — the
    embedding lookup (wte[token] * s[token]) and the tied logits matmul
    (x @ wte.T scaled per OUTPUT vocab column = per wte row)."""
    w = np.asarray(params["wte"], np.float32)
    s = np.maximum(np.abs(w).max(axis=1, keepdims=True), 1e-8)
    out["wte"] = jnp.asarray(
        np.clip(np.round(w / s * 127.0), -127, 127).astype(np.int8))
    out["wte_s"] = jnp.asarray((s / 127.0).astype(np.float32))


def quantize_gpt_int8(params: dict) -> dict:
    """Return a decode-ready param tree: block matmul weights and the tied
    embedding become int8 with per-output-channel scales stored under
    ``<name>_s``.  LayerNorm, biases, and wpe stay float (negligible
    bytes; norm math is fp32 anyway).  MoE expert weights (blocks["moe"]
    w_in/w_out — the bulk of an MoE model) quantize per-output-channel
    like the dense weights; the tiny router stays float (a routing flip
    is a far larger error than its bandwidth is worth)."""
    out = dict(params)
    blocks = dict(params["blocks"])
    for name, axis in _BLOCK_WEIGHTS.items():
        if name in blocks and blocks[name] is not None:
            q, s = _quant(blocks[name], axis)
            blocks[name] = q
            blocks[name + "_s"] = s
    if isinstance(blocks.get("moe"), dict):
        moe = dict(blocks["moe"])
        for name, axis in _MOE_WEIGHTS.items():
            q, s = _quant(moe[name], axis)
            moe[name] = q
            moe[name + "_s"] = s
        blocks["moe"] = moe
    out["blocks"] = blocks
    _quantize_wte_int8(out, params)
    return out


def pack_int4_halves(q):
    """THE int4 byte layout, in one place (consumers: quantize_gpt_int4,
    chip_smoke.py's kernel oracle, tests): signed values in
    [-7, 7] with the input dim at axis -2 pack two-per-byte HALF-SPLIT —
    rows [0, in/2) in the low nibble, rows [in/2, in) in the high — as
    4-bit two's complement assembled in uint8, reinterpreted int8."""
    q = np.asarray(q, np.int32)
    P = q.shape[-2] // 2
    lo, hi = q[..., :P, :], q[..., P:, :]
    return ((lo & 0xF) | ((hi & 0xF) << 4)).astype(np.uint8).view(np.int8)


def quantize_gpt_int4(params: dict, group_size: int = 64) -> dict:
    """4-bit weight-only decode params: block matmul weights become int4
    with GROUP-WISE scales along the input dimension (per-channel alone is
    too coarse at 4 bits — grouping bounds each scale's dynamic range to
    ``group_size`` inputs, the standard W4 recipe).  The embedding stays
    int8 (quantize_gpt_int8's path): lookup tables are small and 4-bit
    token vectors measurably hurt.  HBM reads drop to a quarter of bf16.

    Storage is NIBBLE-PACKED int8 — two signed 4-bit values per byte along
    the input dim ([..., in, out] -> [..., in/2, out]), the GPTQ/AWQ
    layout — not the jnp.int4 dtype: the TPU has no 4-bit compute (XLA
    widens before the matmul either way), PJRT S4 buffers are not
    supported end-to-end on every backend, and a packed byte stream is
    exactly the HBM-read halving the format exists for.  ``w()`` unpacks with two arithmetic
    shifts that XLA fuses into the consuming matmul's weight read."""
    def q4(w_, axis):
        """(packed int4-pair int8, grouped scale) — or per-channel int8
        when the input dim doesn't divide into (even-sized) groups."""
        w_ = np.asarray(w_, np.float32)
        in_axis = axis  # stacked layout: in dim sits just before out
        in_dim = w_.shape[in_axis]
        if in_dim % group_size or in_dim % 2:
            return _quant(w_, axis)
        G = in_dim // group_size
        shp = w_.shape
        grouped = w_.reshape(*shp[:in_axis], G, group_size,
                             *shp[in_axis + 1:])
        scale = np.maximum(np.abs(grouped).max(axis=in_axis + 1,
                                               keepdims=True), 1e-8)
        q = np.clip(np.round(grouped / scale * 7.0), -7, 7)
        # HALF-SPLIT packing (pack_int4_halves): unpack is concat(lo, hi)
        # along the input dim IN ORIGINAL ROW ORDER — two elementwise-
        # derived tensors, no interleave permutation for XLA to
        # materialize (pair-interleaved packing measured 0.78x bf16
        # decode on the chip — the stack+reshape shuffle broke
        # dequant-into-matmul fusion)
        return (jnp.asarray(pack_int4_halves(q.reshape(shp))),
                jnp.asarray((scale / 7.0).astype(np.float32)))

    out = dict(params)
    blocks = dict(params["blocks"])
    for name, axis in _BLOCK_WEIGHTS.items():
        if name not in blocks or blocks[name] is None:
            continue
        blocks[name], blocks[name + "_s"] = q4(blocks[name], axis)
    if isinstance(blocks.get("moe"), dict):
        moe = dict(blocks["moe"])
        for name, axis in _MOE_WEIGHTS.items():
            moe[name], moe[name + "_s"] = q4(moe[name], axis)
        blocks["moe"] = moe
    out["blocks"] = blocks
    _quantize_wte_int8(out, params)
    return out


def w(p: dict, name: str, dt):
    """Resolve a (possibly quantized, possibly LoRA-adapted) weight to
    compute dtype.

    Identity-cost on float params; on int8/int4 params the convert+scale
    is a fusable elementwise producer that XLA folds into the consuming
    matmul's weight read.  Grouped scales' extra axis (scale
    [..., G, 1, out] against weight [..., in/2, out]) marks the
    nibble-packed int4 form (see quantize_gpt_int4): unpack is two
    arithmetic shifts — int8 ``<< 4 >> 4`` sign-extends the low nibble
    (input rows [0, in/2)), ``>> 4`` the high (rows [in/2, in)) —
    concatenated back to [..., in, out] in original row order.  A low-rank
    adapter pair (text/lora.py: ``<name>_lora_a`` [..., in, r] x
    ``<name>_lora_b`` [..., r, out]) adds its delta after dequant — so
    LoRA composes with a frozen float base (classic) or a frozen
    int8/int4 base (QLoRA) through the same accessor."""
    arr = p[name]
    if arr.dtype == jnp.int8:
        s = p[name + "_s"]
        if s.ndim == arr.ndim + 1:  # grouped scales => nibble-packed int4
            # half-split layout: lo = rows [0, in/2), hi = rows [in/2, in)
            # — concat restores original row order with no permutation
            lo = jnp.right_shift(jnp.left_shift(arr, 4), 4)
            hi = jnp.right_shift(arr, 4)
            shp = (*arr.shape[:-2], arr.shape[-2] * 2, arr.shape[-1])
            q = jnp.concatenate([lo, hi], axis=-2)
            G = s.shape[-3]
            grouped = q.reshape(*shp[:-2], G, shp[-2] // G, shp[-1])
            out = (grouped.astype(dt) * s.astype(dt)).reshape(shp)
        else:
            out = arr.astype(dt) * s.astype(dt)
    else:
        out = arr.astype(dt)
    a = p.get(name + "_lora_a")
    if a is not None:
        b = p[name + "_lora_b"]
        out = out + jnp.einsum("...dr,...rf->...df", a.astype(dt),
                               b.astype(dt))
    return out


def _w4_qualifies(p: dict, name: str, ndim: int) -> bool:
    """ONE routing predicate for the Pallas W4 fast path (mm: ndim 2,
    mm_stacked: ndim 3) — env-gated, packed-int4-shaped, unadapted."""
    arr = p[name]
    s = p.get(name + "_s")
    return (os.environ.get("PADDLE_TPU_W4_KERNEL", "") == "1"
            and arr.ndim == ndim and arr.dtype == jnp.int8
            and s is not None and s.ndim == arr.ndim + 1
            and p.get(name + "_lora_a") is None)


def mm(h, p: dict, name: str, dt):
    """``h @ w(p, name, dt)`` with a fused-kernel fast path.

    When ``name`` resolves to a nibble-packed int4 2-D weight, the env
    flag ``PADDLE_TPU_W4_KERNEL=1`` is set (off by default: only
    ``chip_smoke.py``'s kernels phase has held it to its oracle on the
    chip — a compiling-but-wrong kernel must never serve tokens), and no
    LoRA adapter is attached, the
    matmul runs through the Pallas W4 kernel (ops/woq_matmul.py): the
    packed bytes stream through VMEM and no dequantized bf16 copy is
    ever written to HBM.  Every other case — float weights, per-channel
    int8, stacked (3-D+) weights, adapted trees — is exactly
    ``h @ w(...)``, so training and all existing decode paths are
    untouched when the flag is off or the shape doesn't qualify."""
    if _w4_qualifies(p, name, 2):
        from ..ops.woq_matmul import w4_matmul

        return w4_matmul(h.astype(dt), p[name], p[name + "_s"])
    return h @ w(p, name, dt)


def mm_stacked(h, p: dict, name: str, dt):
    """``einsum('...d,kde->k...e', h, w(p, name, dt))`` — the stacked
    qkv/kv projection form — with the same W4 fast path as :func:`mm`:
    a packed 3-D weight [k, in/2, out] runs one Pallas W4 matmul per
    stack slice (k is 2 or 3, a static python loop), covering the
    remaining quarter of dense decode weight bytes the 2-D sites miss."""
    if _w4_qualifies(p, name, 3):
        from ..ops.woq_matmul import w4_matmul

        arr, s = p[name], p[name + "_s"]
        hq = h.astype(dt)
        return jnp.stack([w4_matmul(hq, arr[i], s[i])
                          for i in range(arr.shape[0])])
    return jnp.einsum("...d,kde->k...e", h, w(p, name, dt))


def embed(params: dict, token, dt, mult: float = 1.0):
    """wte[token] in compute dtype, dequantizing per-row scales if int8,
    times the config's ``embedding_multiplier`` (1.0 traces nothing).
    Every path embeds through here, so the ``embed`` scope is here too."""
    with jax.named_scope("embed"):
        e = params["wte"][token].astype(dt)
        if params["wte"].dtype == jnp.int8:
            e = e * params["wte_s"][token].astype(dt)
        if mult != 1.0:
            e = e * jnp.asarray(mult, dt)
        return e


def logits(x, params: dict, dt, mult: float = 1.0):
    """Logits x @ head.T, the head being the tree's own ``lm_head`` leaf
    where it has one (an untied head) and ``wte`` otherwise; per-row
    scales of an int8 head factor out of the contraction and apply on the
    [..., V] output (cheaper than scaling the weight, exactly equal);
    times the config's ``lm_head_multiplier``.  Every path's head, so the
    ``lm_head`` scope is here."""
    name = "lm_head" if "lm_head" in params else "wte"
    with jax.named_scope("lm_head"):
        y = x @ params[name].T.astype(dt)
        if params[name].dtype == jnp.int8:
            y = y * params[name + "_s"].reshape(-1).astype(dt)
        if mult != 1.0:
            y = y * jnp.asarray(mult, dt)
        return y


def is_quantized(params: dict) -> bool:
    return any(k.endswith("_s") for k in params.get("blocks", {}))
