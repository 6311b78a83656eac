"""Pallas TPU split-KV flash-decode attention + the quantized-KV helpers.

The training/prefill flash kernel (ops/flash_attention.py) rejects decode
shapes (T_q = 1), so long-context decode attention ran as plain XLA over
the full [B, T, Hkv, hd] cache — per-token HBM traffic scales with the
context length, which is the serving bottleneck once dispatch and weight
reads are optimized (PR 1/2).  This kernel streams the KV cache through
VMEM in T-blocks with the same online-softmax recurrence as
``_flash_fwd_impl``, specialized for small T_q:

* **split-KV grid** ``(B * Hkv, T // BT)`` (the contiguous kernel): each
  cell owns one (batch, kv-head) pair and walks the KV blocks keeping a
  running max/denominator in VMEM scratch — no [T] score row ever hits
  HBM, and blocks entirely past the causal frontier
  (``base > pos + Tq - 1``) are skipped;
* **GQA-aware**: the q rows for one kv head are its whole query group
  ([Tq * G, hd], G = Hq // Hkv), so the kernel consumes the Hkv-head
  cache DIRECTLY (the ``repeat_kv=False`` layout ``_gqa_qkv`` already
  produces) instead of materializing repeated K/V heads — the HBM read
  is the cache's true size, not G times it;
* **int8 cache**: per-(position, head) scales (``quantize_kv``) dequantize
  inside the kernel right after the VMEM load — HBM reads a quarter of
  the fp32 bytes, and no dequantized copy is ever written back;
* **paged kernel: a grid cell a slot** (``_paged_call``).  Its operand is
  the pool's whole K (or V) leaf ``[L, N, bs, Hkv*hd]`` (every layer's
  pages, the heads of a row side by side in the lanes: a page is one
  contiguous ``[bs, Hkv*hd]`` tile run) and a layer number that rides the
  scalar prefetch beside the tables and the positions.  The leaf stays
  in HBM and is addressed by (layer, page): no layer's slice of it is
  cut out, copied or re-laid-out for the kernel.  A cell loops over its
  slot's compute blocks only as far as the causal frontier and the
  mapped table entries reach, copies each live page by its layer and
  physical number (all KV heads of the page in one copy, the next
  block's copies in flight while this one is attended) and runs the
  same recurrence a head at a time.  A free slot, an unmapped entry and
  every entry past a frontier cost no copy and no loop turn: the cells
  of a layer hold neither ``Hkv`` nor the table's width as a factor.

Forward-only by design (decode is inference).  Routing follows
ops/_pallas.py (platform + static shape gate, no probe); the flag is
``PADDLE_TPU_FLASH_DECODE`` (read by text/generate.py, which keeps its
original einsum math as the flag-off / gate-rejected path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import _pallas

_INTERPRET = False  # tests flip this to run the kernel on CPU (interpret)

_NEG = -1e30  # large-negative instead of -inf (flash_attention's rule)

_R_CAP = 1024  # q rows (Tq * G) per grid cell; verify chunks stay under it


def _kv_block(T: int) -> int | None:
    """KV block length: the largest standard tile dividing T, or T itself
    for short test-sized caches (interpret mode / tiny serving windows)."""
    for cand in (512, 256, 128):
        if T % cand == 0:
            return cand
    if T <= 512 and T % 8 == 0:
        return T
    return None


def supported(q_shape, kv_shape) -> bool:
    """Static shape gate: q [B, Tq, Hq, hd] against cache [B, T, Hkv, hd]."""
    B, Tq, Hq, hd = q_shape
    T, Hkv = kv_shape[1], kv_shape[2]
    return (hd in (128, 256) and Hq % Hkv == 0
            and Tq * (Hq // Hkv) <= _R_CAP
            and _kv_block(T) is not None)


def available(q_shape, kv_shape) -> bool:
    """supported() + a backend that runs the kernel (a TPU, or interpret
    mode when a test flipped ``_INTERPRET``) — the trace-time routing
    check text/generate.py consults before leaving its einsum path."""
    return supported(q_shape, kv_shape) and (_INTERPRET or _pallas.on_tpu())


# ---------------------------------------------------------------------------
# quantized-KV helpers — THE int8 cache format, in one place
# ---------------------------------------------------------------------------


def quantize_kv(x):
    """Symmetric per-(…, head) int8 over the trailing head_dim axis:
    returns (q int8 like x, scale fp32 of x.shape[:-1]).  One K/V row's
    head vector shares one scale — the scale array rides beside the cache
    at hd*... /1 of its size (~1-2%), and dequant inside the kernel is a
    single broadcast multiply."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q, s, dt):
    """Inverse of quantize_kv, in fp32 then cast (matches the kernel's
    internal math) — the XLA-fallback attention path uses this."""
    return (q.astype(jnp.float32) * s[..., None]).astype(dt)


def random_filled_cache(cache: dict, key, amp: float = 1.0) -> dict:
    """A ``generate.init_cache`` tree filled with synthetic normal K/V
    (scaled by ``amp``), quantizing through the real format when the
    cache carries scale planes — THE cache-format-aware fill the tests
    share (one copy; a format change edits
    exactly here).

    Paged caches (``text/kv_pool.py`` trees with a ``tables`` leaf) fill
    the whole [L, N, bs, Hkv*hd] pool (a scale of the int8 format is one
    head's: the leaf is quantized through its per-head view) and, when
    the tables are still
    unmapped (-1), lay slots out identity-style (slot b owns blocks
    [b*nmax, (b+1)*nmax)) so the kernel-parity oracle and the tests
    exercise real block-table gathers without a host allocator."""
    ks = jax.random.split(key, 2)
    kf = jax.random.normal(ks[0], cache["k"].shape) * amp
    vf = jax.random.normal(ks[1], cache["v"].shape) * amp
    if "k_s" in cache:
        per_head = cache["k_s"].shape + (-1,)
        k, k_s = quantize_kv(kf.reshape(per_head))
        v, v_s = quantize_kv(vf.reshape(per_head))
        out = dict(cache, k=k.reshape(kf.shape), v=v.reshape(vf.shape),
                   k_s=k_s, v_s=v_s)
    else:
        out = dict(cache, k=kf.astype(cache["k"].dtype),
                   v=vf.astype(cache["v"].dtype))
    if "tables" in out and bool((out["tables"] < 0).all()):
        B, nmax = out["tables"].shape
        N = out["k"].shape[1]
        out["tables"] = (jnp.arange(B * nmax, dtype=jnp.int32)
                         .reshape(B, nmax) % N)
    return out


# ---------------------------------------------------------------------------
# XLA reference (parity oracle + runtime fallback)
# ---------------------------------------------------------------------------


def _xla_decode(q, k, v, pos, k_scale, v_scale, scale):
    """Grouped-query cached attention in plain XLA: q [B, Tq, Hq, hd],
    cache [B, T, Hkv, hd] (+ scales for int8), mask t <= pos[b] + i for
    q row i.  fp32 softmax like every attention path in this repo."""
    B, Tq, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None]
        vf = vf * v_scale[..., None]
    qg = q.reshape(B, Tq, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bikgd,btkd->bkgit", qg, kf) * scale
    mask = (jnp.arange(T)[None, :]
            <= pos[:, None, None, None, None] + jnp.arange(Tq)[:, None])
    s = jnp.where(mask, s, _NEG)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgit,btkd->bikgd", w, vf)
    return out.reshape(B, Tq, Hq, vf.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------
#
# Layout rules the v5e compiler enforces (tests/test_chip_compile.py keeps
# them checked): the last two dims of every block are (8k, 128k) or the
# array's own.  So the cache is viewed [.., T, Hkv*hd] and a cell takes
# its head as the h-th hd-wide LANE chunk of a T-block (hd % 128 == 0 —
# the shape gate); the int8 scales come in as whole [T-block, Hkv] tiles
# and the cell picks its head's column; q rows pad up to a sublane tile.


def _rows_first(q, Hkv: int):
    """q [B, Tq, Hq, hd] -> [B, Hkv, Rp, hd]: the rows for kv head h are
    its whole query group, causally ordered (row r = tq * G + g; the
    mask recovers tq as r // G), zero-padded to a sublane multiple."""
    B, Tq, Hq, hd = q.shape
    G = Hq // Hkv
    R = Tq * G
    qh = q.reshape(B, Tq, Hkv, G, hd).swapaxes(1, 2).reshape(B, Hkv, R, hd)
    Rp = _pallas.pad_rows(R)
    if Rp != R:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
    return qh


def _rows_last(out, q_shape):
    B, Tq, Hq, _ = q_shape
    Hkv, hd = out.shape[1], out.shape[3]
    G = Hq // Hkv
    return (out[:, :, :Tq * G].reshape(B, Hkv, Tq, G, hd).swapaxes(1, 2)
            .reshape(B, Tq, Hq, hd))


def _scratch(Rp: int, hd: int, heads: tuple = ()):
    """Online-softmax state of one head's Rp rows of ``hd`` value lanes
    (``heads``: a leading dim for a cell that holds several heads at
    once)."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(heads + (Rp, 1), jnp.float32),     # running max
            pltpu.VMEM(heads + (Rp, 1), jnp.float32),     # running denominator
            pltpu.VMEM(heads + (Rp, hd), jnp.float32)]    # running numerator


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finish(l_scr, acc_scr, dtype):
    """The attended rows; zeros where nothing was attended."""
    l = l_scr[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc_scr[...] / l_safe).astype(dtype)


def _head_col(s_blk, h):
    """Column ``h`` of a [BT, Hkv] scale tile as [BT, 1] (a masked
    lane-sum: the head index is a grid value, not a static slice)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, s_blk.shape, 1)
    return jnp.sum(jnp.where(lane == h, s_blk, 0.0), axis=1, keepdims=True)


def _attend_block(p_b, base, G, scale, qb, kb, vb, m_scr, l_scr, acc_scr):
    """One KV block of the online-softmax recurrence, shared by the
    contiguous and the paged kernel: qb [Rp, hd] and kb / vb [BT, hd] in
    float32 (an int8 block already times its scales), the state refs one
    head's; ``base`` is the block's first LOGICAL cache row."""
    s = scale * jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [Rp, BT]
    rows_tq = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
    cols = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols <= p_b + rows_tq, s, _NEG)
    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_cur


def decode_attention(q, k, v, pos, k_scale=None, v_scale=None, scale=None):
    """q [B, Tq, Hq, hd] against a cache [B, T, Hkv, hd] → [B, Tq, Hq, hd]
    (q.dtype).  ``pos`` [B] int32: q row i of batch b attends cache rows
    t <= pos[b] + i (decode passes Tq=1 and the current position; verify/
    chunked-prefill pass the chunk and its first position).  int8 caches
    pass per-row ``k_scale``/``v_scale`` [B, T, Hkv].  Shapes the static
    gate rejects take the XLA expression; a shape it accepts compiles
    the kernel, and a compiler refusal raises to the caller."""
    if not supported(q.shape, k.shape):
        return _xla_decode(q, k, v, pos, k_scale, v_scale, scale)
    return _per_head_shard(
        lambda *a: _decode_call(*a, scale), k.shape[2],
        (q, 2), (k, 2), (v, 2), (pos, None), (k_scale, 2), (v_scale, 2))


def _per_head_shard(call, Hkv: int, *args_and_head_dims):
    """Run ``call`` on the args, per shard of the heads axis when the step
    being traced is partitioned (ops/_pallas.py): each arg comes with
    the dim its heads sit on (None = replicated).  A heads axis that
    does not divide Hkv leaves the cache replicated (generate's
    sharded_cache_specs), and then every shard attends all heads."""
    args = [a for a, _ in args_and_head_dims]
    part = _pallas.partition()
    if part is None:
        return call(*args)
    split = Hkv % part.size(part.heads) == 0
    specs = tuple(
        None if a is None else
        part.spec(a.ndim, heads=d if split else None)
        for a, d in args_and_head_dims)
    return part.shard_map(call, specs, specs[0])(*args)


def _decode_call(q, k, v, pos, k_scale, v_scale, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    BT = _kv_block(T)
    nt = T // BT
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    quant = k_scale is not None

    qh = _rows_first(q, Hkv)
    Rp = qh.shape[2]
    # [B, 1, 1] so the (1, 1, 1) SMEM block's last two dims are the
    # array's own (a [B, 1] operand is refused: block (1, 1) of (B, 1))
    pos3 = pos.reshape(B, 1, 1).astype(jnp.int32)

    def kernel(pos_ref, q_ref, k_ref, v_ref, *rest):
        if quant:
            ks_ref, vs_ref, o_ref, *scr = rest
        else:
            ks_ref = vs_ref = None
            o_ref, *scr = rest
        h = pl.program_id(0) % Hkv
        ti = pl.program_id(1)

        @pl.when(ti == 0)
        def _init():
            _init_scratch(*scr)

        p_b = pos_ref[0, 0, 0]
        base = ti * BT

        # skip KV blocks entirely past the causal frontier
        @pl.when(base <= p_b + Tq - 1)
        def _run():
            kb = k_ref[0].astype(jnp.float32)              # [BT, hd]
            vb = v_ref[0].astype(jnp.float32)
            if quant:
                kb = kb * _head_col(ks_ref[0], h)
                vb = vb * _head_col(vs_ref[0], h)
            _attend_block(p_b, base, G, scale,
                          q_ref[0, 0].astype(jnp.float32), kb, vb, *scr)

        @pl.when(ti == nt - 1)
        def _fin():
            o_ref[0, 0] = _finish(*scr[1:], o_ref.dtype)

    q_spec = pl.BlockSpec((1, 1, Rp, hd),
                          lambda i, t: (i // Hkv, i % Hkv, 0, 0))
    kv_spec = pl.BlockSpec((1, BT, hd), lambda i, t: (i // Hkv, t, i % Hkv))
    in_specs = [
        pl.BlockSpec((1, 1, 1), lambda i, t: (i // Hkv, 0, 0),
                     memory_space=pltpu.SMEM),
        q_spec, kv_spec, kv_spec,
    ]
    # the kernel's view of the slab, heads folded into the lane dimension:
    # on the chip a relayout copy of the cache, so it is counted with the
    # cache's gathers, not with the attention
    with jax.named_scope("kv_gather"):
        args = [pos3, qh, k.reshape(B, T, Hkv * hd),
                v.reshape(B, T, Hkv * hd)]
    if quant:
        s_spec = pl.BlockSpec((1, BT, Hkv), lambda i, t: (i // Hkv, t, 0))
        in_specs += [s_spec, s_spec]
        args += [k_scale, v_scale]

    out = pl.pallas_call(
        kernel,
        grid=(B * Hkv, nt),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=_scratch(Rp, hd),
        interpret=_INTERPRET,
        name="decode_attention",
    )(*args)
    return _rows_last(out, q.shape)


# ---------------------------------------------------------------------------
# paged (block-table) kernel — the pool layout's decode hot path
# ---------------------------------------------------------------------------


def gather_paged_view(leaf, layer, tables):
    """Per-slot contiguous view of one layer of a pooled leaf: leaf
    [L, N, bs, ...] + layer (int32 scalar) + tables [B, nmax] ->
    [B, nmax*bs, ...], gathered from the whole leaf by (layer, page): no
    layer's slice is cut out first.  Unmapped entries (-1) clamp to
    block 0 — their rows sit past every causal frontier (the allocator
    maps blocks through the write position), so the garbage is masked
    exactly like a slab's unwritten rows.  THE oracle/fallback
    materialization; the Pallas path copies the same table's live pages
    inside its grid cell instead."""
    idx = jnp.clip(tables, 0, leaf.shape[1] - 1)             # [B, nmax]
    g = leaf[layer, idx]                                     # [B,nmax,bs,...]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def paged_supported(q_shape, pool_shape, v_width: int | None = None) -> bool:
    """Static shape gate for the paged kernel: q [B, Tq, Hq, hd] against
    a K/V leaf [L, N, bs, Hkv*hd] (the KV block is the pool's own
    block).  ``v_width``: the pool holds one shared row a token (a latent
    row: the leaf's lanes are the score width ``hd``, the row's first
    ``v_width`` lanes the value); whole lane tiles both."""
    B, Tq, Hq, hd = q_shape
    bs, lanes = pool_shape[2], pool_shape[3]
    Hkv = lanes // hd
    if v_width is not None:
        widths = (lanes == hd and hd % 128 == 0 and 0 < v_width <= hd
                  and v_width % 128 == 0)
    else:
        widths = hd in (128, 256)
    return (widths and lanes % hd == 0 and Hq % Hkv == 0
            and Tq * (Hq // Hkv) <= _R_CAP
            and bs >= 8 and bs % 8 == 0)


def paged_available(q_shape, pool_shape, v_width: int | None = None) -> bool:
    """paged_supported + a backend that runs the kernel (a TPU, or
    interpret mode when a test flipped ``_INTERPRET``) — the trace-time
    routing check text/kv_pool.py consults before leaving the
    gather-einsum path."""
    return (paged_supported(q_shape, pool_shape, v_width)
            and (_INTERPRET or _pallas.on_tpu()))


def _xla_paged(q, k_pool, v_pool, tables, pos, layer, k_scale, v_scale,
               scale, v_width=None):
    """Oracle/fallback: gather the per-slot views of the layer through
    the tables and run the contiguous XLA reference — bit-identical
    values to a slab holding the same rows (the gather only relocates
    blocks)."""
    B, hd = q.shape[0], q.shape[3]
    per_head = (B, -1, k_pool.shape[3] // hd, hd)
    k = gather_paged_view(k_pool, layer, tables).reshape(per_head)
    if v_pool is None:                      # the row's first lanes
        v = k[..., :v_width]
    else:
        v = gather_paged_view(v_pool, layer, tables).reshape(per_head)
    ks = vs = None
    if k_scale is not None:
        ks = gather_paged_view(k_scale, layer, tables)
        vs = gather_paged_view(v_scale, layer, tables)
    return _xla_decode(q, k, v, pos, ks, vs, scale)


def paged_decode_attention(q, k_pool, v_pool, tables, pos, layer,
                           k_scale=None, v_scale=None, scale=None,
                           v_width: int | None = None):
    """Block-table decode attention over one layer of the pool: q
    [B, Tq, Hq, hd] against the pool's K/V leaves [L, N, bs, Hkv*hd]
    read at ``layer`` (int32 scalar; a caller that holds one layer's
    pool passes ``pool[None]`` and 0, which copies nothing) and
    addressed through ``tables`` [B, nmax] int32 (physical block per
    logical block; -1 = unmapped) -> [B, Tq, Hq, hd] (q.dtype).  ``pos``
    [B] as in :func:`decode_attention` — logical row t of slot b is
    table[b, t // bs] row t % bs, and rows t <= pos[b] + i are attended.
    int8 pools pass their per-row scale leaves [L, N, bs, Hkv].  Shapes
    the static gate rejects take gather + the XLA reference; a shape it
    accepts compiles the kernel.

    ``v_pool=None`` with ``v_width``: a pool of one shared row a token
    (latent attention, absorbed: ``k_pool`` [L, N, bs, hd] holds the row
    all ``Hq`` heads score against, and its first ``v_width`` lanes are
    the value) -> [B, Tq, Hq, v_width].  A page is then copied once.

    A grid cell is a slot: it walks the table entries up to its causal
    frontier as far as they are mapped and copies those pages from the
    leaf in HBM by (layer, physical number), so the HBM read is each
    slot's live blocks only — never a materialized [B, T] gather, never
    a layer's slice of the pool — and a free slot, or an entry that is
    unmapped or past the frontier, is neither copied nor visited.  A
    slot that attends nothing gives zeros."""
    layer = jnp.asarray(layer, jnp.int32)
    if (v_pool is None) != (v_width is not None):
        raise ValueError("v_pool=None and v_width come together")
    if not paged_supported(q.shape, k_pool.shape, v_width):
        return _xla_paged(q, k_pool, v_pool, tables, pos, layer, k_scale,
                          v_scale, scale, v_width)
    return _per_head_shard(
        lambda *a: _paged_call(*a, scale, v_width),
        k_pool.shape[3] // q.shape[3],
        (q, 2), (k_pool, 3), (v_pool, 3), (tables, None), (pos, None),
        (layer, None), (k_scale, 3), (v_scale, 3))


_KV_ROWS = 128           # KV rows a compute block of the paged kernel holds, at least
_KV_BUF_BYTES = 8 << 20  # of VMEM for a cell's K and V pages, both halves of each


def _paged_geometry(bs: int, Hkv: int, hd: int, Rp: int, itemsize: int):
    """(pages a compute block, KV heads a cell holds at once): enough
    pages for ``_KV_ROWS`` rows, and every head unless their q rows or
    their lanes of a compute block outgrow VMEM (then the cell walks its
    pages once a chunk of heads)."""
    P = -(-_KV_ROWS // bs)

    def fits(h):
        return (h * Rp <= _R_CAP
                and 4 * P * bs * h * hd * itemsize <= _KV_BUF_BYTES)

    Hb = max(h for h in range(1, Hkv + 1)
             if Hkv % h == 0 and (h == 1 or fits(h)))
    return P, Hb


def _paged_call(q, k_pool, v_pool, tables, pos, layer, k_scale, v_scale,
                scale, v_width=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, Hq, hd = q.shape
    bs, Hkv = k_pool.shape[2], k_pool.shape[3] // hd
    G = Hq // Hkv
    nmax = tables.shape[1]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    quant = k_scale is not None
    # one shared row a token: the value is the key page's first lanes,
    # so a page is copied once and there is no V operand or buffer
    shared = v_pool is None
    vd = v_width if shared else hd

    qh = _rows_first(q, Hkv)
    Rp = qh.shape[2]
    P, Hb = _paged_geometry(bs, Hkv, hd, Rp, k_pool.dtype.itemsize)
    KB = P * bs             # KV rows of a compute block
    tab = tables.astype(jnp.int32)
    pos2 = pos.reshape(B).astype(jnp.int32)
    lay = layer.reshape(1).astype(jnp.int32)

    def kernel(tab_ref, pos_ref, lay_ref, q_ref, k_hbm, *rest):
        if shared:
            o_ref, k_buf, sem, *scr = rest
            v_hbm, v_buf = None, k_buf
        elif quant:
            v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, \
                sem, *scr = rest
        else:
            v_hbm, o_ref, k_buf, v_buf, sem, *scr = rest
        b = pl.program_id(0)
        p_b = pos_ref[b]
        li = lay_ref[0]
        # the table entries this slot walks: those up to its causal
        # frontier, as far as they are mapped (the allocator maps every
        # block through the write position; an unmapped entry holds
        # another tenant's rows).  A free slot walks none.
        n_front = jnp.clip(jax.lax.div(p_b + Tq + bs - 1, bs), 0, nmax)
        n_live = jax.lax.fori_loop(
            0, n_front,
            lambda j, n: jnp.where((n == j) & (tab_ref[b, j] >= 0), j + 1, n),
            0)
        n_groups = jax.lax.div(n_live + P - 1, P)

        def copies(g, half, c, go):
            """``go`` on the copy of each live page of group ``g`` (all
            of chunk ``c``'s heads of the page at once) into buffer
            ``half``; a page past the last live one is not copied."""
            lanes = pl.ds(c * Hb * hd, Hb * hd)
            for j in range(P):
                @pl.when(g * P + j < n_live)
                def _page():
                    page = tab_ref[b, g * P + j]
                    pairs = [
                        (k_hbm.at[li, page, :, lanes], k_buf.at[half, j])]
                    if not shared:
                        pairs.append((v_hbm.at[li, page, :, lanes],
                                      v_buf.at[half, j]))
                    if quant:
                        pairs += [(ks_hbm.at[page], ks_buf.at[half, j]),
                                  (vs_hbm.at[page], vs_buf.at[half, j])]
                    for src, dst in pairs:
                        go(pltpu.make_async_copy(src, dst, sem.at[half]))

        for c in range(Hkv // Hb):
            _init_scratch(*scr)

            @pl.when(n_groups > 0)
            def _first():
                copies(0, 0, c, lambda d: d.start())

            def group(g, carry):
                half = jax.lax.rem(g, 2)

                # the next group's copies fly while this one is attended
                @pl.when(g + 1 < n_groups)
                def _next():
                    copies(g + 1, 1 - half, c, lambda d: d.start())

                copies(g, half, c, lambda d: d.wait())
                # the last group's pages past the last live one hold what
                # the buffer held before: their weights are zero, and
                # zero times that must be zero
                for j in range(P):
                    @pl.when(g * P + j >= n_live)
                    def _stale():
                        v_buf[half, j] = jnp.zeros(v_buf.shape[2:],
                                                   v_buf.dtype)
                        if quant:
                            vs_buf[half, j] = jnp.zeros(vs_buf.shape[2:],
                                                        vs_buf.dtype)
                if quant:
                    ks = ks_buf[half].reshape(KB, ks_buf.shape[-1])
                    vs = vs_buf[half].reshape(KB, vs_buf.shape[-1])
                for h in range(Hb):
                    hh = c * Hb + h
                    kb = k_buf[half, :, :, h * hd:(h + 1) * hd].astype(
                        jnp.float32).reshape(KB, hd)
                    vb = v_buf[half, :, :, h * hd:h * hd + vd].astype(
                        jnp.float32).reshape(KB, vd)
                    if quant:
                        kb = kb * ks[:, hh:hh + 1]
                        vb = vb * vs[:, hh:hh + 1]
                    _attend_block(p_b, g * KB, G, scale,
                                  q_ref[0, hh].astype(jnp.float32), kb, vb,
                                  *(r.at[h] for r in scr))
                return carry

            jax.lax.fori_loop(0, n_groups, group, 0)
            for h in range(Hb):
                o_ref[0, c * Hb + h] = _finish(scr[1].at[h], scr[2].at[h],
                                               o_ref.dtype)

    def cell(b, tab_ref, pos_ref, lay_ref):
        return (b, 0, 0, 0)

    q_spec = pl.BlockSpec((1, Hkv, Rp, hd), cell)
    o_spec = pl.BlockSpec((1, Hkv, Rp, vd), cell)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    # the K and V operands are the pool's leaves as they are stored
    pools = [k_pool] if shared else [k_pool, v_pool]
    in_specs = [q_spec] + [in_hbm] * len(pools)
    args = [qh] + pools
    bufs = [pltpu.VMEM((2, P, bs, Hb * hd), x.dtype) for x in pools]
    if quant:
        # a page is copied whole lanes at a time: the scales' head axis
        # is padded up to a lane tile (the chip's compiler refuses to
        # slice a page off an operand whose rows are narrower).  The
        # scale planes alone are still sliced by layer for that: 4 bytes
        # a head of a row beside its hd bytes of int8
        with jax.named_scope("kv_gather"):
            lane_pad = ((0, 0), (0, 0), (0, -Hkv % 128))
            scales = [jnp.pad(x[layer], lane_pad)
                      for x in (k_scale, v_scale)]
        in_specs += [in_hbm, in_hbm]
        args += scales
        bufs += [pltpu.VMEM((2, P) + x.shape[1:], x.dtype) for x in scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=o_spec,
        scratch_shapes=bufs + [pltpu.SemaphoreType.DMA((2,))]
        + _scratch(Rp, vd, (Hb,)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape[:3] + (vd,), q.dtype),
        interpret=_INTERPRET,
        name="paged_decode_attention",
    )(tab, pos2, lay, *args)
    return _rows_last(out, q.shape)
