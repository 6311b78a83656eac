"""Ring attention — context parallelism over a mesh axis.

Capability beyond the reference: xymyeah/Paddle has no sequence/context
parallelism (`grep 'ring.attention|context.parallel|sequence_parallel'` over
python/paddle/distributed is empty — SURVEY.md §2.3); long-context training is
a required capability of the TPU build (BASELINE north star).

Design (RingAttention, Liu et al. — blockwise attention + ring passing):
q/k/v live sharded on the sequence dim over the ``axis`` ring.  Each of the
``ring_size`` steps computes blockwise attention of the LOCAL q chunk against
the k/v chunk currently held, merges it into a running (max, denominator,
accumulator) online-softmax state, then passes k/v to the next ring neighbour
via ``lax.ppermute`` — an ICI neighbour hop that XLA overlaps with the
compute.  The full [T, T] score matrix never exists; per-device memory is
O(T_local * T_local) per step (and the step loop is rematerialized), or
O(T_local * sub_block) — masks included — with ``sub_block`` set (the
flash recurrence over kv sub-chunks; see ``_chunk_attend``).

Causality uses GLOBAL positions: chunk c holds rows [c*Tl, (c+1)*Tl);
diagonal pairs get a triangular mask, off-diagonal pairs an all-or-nothing
one.  Note every ring step still computes its block einsum even when fully
masked — causal runs carry ~2x the minimal FLOPs; masked scores only zero
out through the where.  For balanced causal work use
:func:`ring_attention_zigzag` below (2x less per-device compute).

Differentiable by construction (scan + ppermute both have transposes), so it
composes with jax.grad/pipeline/TP with no custom VJP.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size as _axis_size

_NEG = -1e30


def _block_attend(q, k, v, scale, mask=None):
    """One dense score block: returns (scores-max m, exp-sum l, weighted
    acc) for merging.  q [B,Tq,H,D]; k/v [B,Tk,Hkv,D] where Hkv may be a
    DIVISOR of H (grouped-query attention: q head h shares kv head
    h // (H//Hkv), matching gpt._gqa_qkv's repeat layout) — the group
    dim folds into the einsums so the shared kv heads are never
    materialized H/Hkv times (and never ride the ring repeated)."""
    B, Tq, H, hd = q.shape
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"kv heads {Hkv} must divide q heads {H}")
    g = H // Hkv  # 1 = plain MHA; the grouped form is identical math
    Tk = k.shape[1]
    qg = q.reshape(B, Tq, Hkv, g, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                   k.astype(jnp.float32)) * scale   # [B,Hkv,g,Tq,Tk]
    s = s.reshape(B, H, Tq, Tk)
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m = jnp.max(s, axis=-1)                          # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                          # [B,H,Tq]
    pg = p.reshape(B, Hkv, g, Tq, Tk)
    acc = jnp.einsum("bkgqs,bskd->bkgqd", pg,
                     v.astype(jnp.float32)).reshape(B, H, Tq, hd)
    return m, l, acc


def _chunk_attend(q, k, v, scale, pos=None, sub: int | None = None):
    """Blockwise partial attention with an optional causal mask given as
    POSITIONS, not a dense array: ``pos = (q_pos [Tq], k_pos [Tk])``
    global position ids; rows attend columns with q_pos >= k_pos.

    ``sub`` bounds the score temp: instead of one [B,H,Tq,Tk] block, the
    kv rows are walked in sub-chunks of that many rows with an inner
    online-softmax scan (the flash-attention recurrence in pure XLA), so
    the largest live tensor is [B,H,Tq,sub] — masks included: each
    [Tq, sub] mask slice is built inside the scan body from the linear-
    size position ids, never as one [Tq, Tk] array.  This is what keeps
    per-device memory flat as the LOCAL chunk grows — the ring bounds
    memory in the ring size R, sub-blocking bounds it in Tl."""
    if sub is not None and sub <= 0:
        raise ValueError(f"sub_block must be positive (got {sub})")
    if sub is None or sub >= k.shape[1]:
        mask = (None if pos is None else
                (pos[0][:, None] >= pos[1][None, :])[None, None])
        return _block_attend(q, k, v, scale, mask)
    B, Tk, Hkv, D = k.shape  # Hkv may be a divisor of q's head count
    if Tk % sub:
        raise ValueError(f"sub_block {sub} must divide the kv chunk {Tk}")
    n = Tk // sub
    Tq = q.shape[1]
    ks = jnp.moveaxis(k.reshape(B, n, sub, Hkv, D), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, n, sub, Hkv, D), 1, 0)
    kp = None if pos is None else pos[1].reshape(n, sub)

    def body(carry, xs):
        m_acc, l_acc, o_acc = carry
        if kp is None:
            kk, vv = xs
            mm = None
        else:
            kk, vv, kps = xs
            mm = (pos[0][:, None] >= kps[None, :])[None, None]
        st = _block_attend(q, kk, vv, scale, mm)
        return _merge(m_acc, l_acc, o_acc, *st), None

    Hq = q.shape[2]  # may exceed k's Hkv under grouped-query attention
    m0 = jnp.full((B, Hq, Tq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, Hq, Tq), jnp.float32)
    o0 = jnp.zeros((B, Hq, Tq, D), jnp.float32)
    xs = (ks, vs) if kp is None else (ks, vs, kp)
    # checkpoint the inner body too: without it the inner scan's VJP
    # stacks per-sub-chunk score residuals back up to ~[B,H,Tq,Tk] —
    # defeating the cap exactly where it matters (training).  Recomputing
    # scores per sub-chunk in the backward is the flash-attention trade.
    # prevent_cse=False: the scan structure supplies the CSE protection
    # (text/gpt.py).
    (m, l, acc), _ = lax.scan(jax.checkpoint(body, prevent_cse=False),
                              (m0, l0, o0), xs)
    return m, l, acc


def _merge(m_acc, l_acc, o_acc, m_new, l_new, acc_new):
    """Online-softmax merge of one blockwise partial into the running
    (max, denominator, accumulator) state."""
    m_next = jnp.maximum(m_acc, m_new)
    a_old = jnp.exp(m_acc - m_next)
    a_new = jnp.exp(m_new - m_next)
    return (m_next, l_acc * a_old + l_new * a_new,
            o_acc * a_old[..., None] + acc_new * a_new[..., None])


def ring_attention(q, k, v, axis: str, causal: bool = True, scale=None,
                   sub_block: int | None = None):
    """Sequence-sharded attention inside a ``shard_map`` region.

    q,k,v: LOCAL chunks [B, T_local, H, D], sequence dim sharded over
    ``axis`` (ring of size R; global T = R * T_local).  k/v may carry
    Hkv < H heads (grouped-query attention): the UNREPEATED shared heads
    ride the ring — H/Hkv less KV traffic per hop — and the group dim
    folds into the block einsums.  Returns the local output chunk
    [B, T_local, H, D].  ``sub_block`` caps the live score
    temp at [B,H,Tl,sub_block] (see _chunk_attend) — required for long
    local chunks, where a full [Tl,Tl] block would defeat the point of
    the ring.
    """
    B, Tl, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D**0.5)
    R = _axis_size(axis)
    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % R) for i in range(R)]  # pass kv forward round-robin

    rows = jnp.arange(Tl)

    def step(carry, r):
        k_cur, v_cur, m_acc, l_acc, o_acc = carry
        src = (my - r) % R  # which chunk we hold at ring step r
        if causal:
            # global causal positions of q-chunk `my` and kv-chunk `src`
            # (linear size; the dense mask is built blockwise downstream)
            pos = (my * Tl + rows, src * Tl + rows)
        else:
            pos = None
        m_new, l_new, acc_new = _chunk_attend(q, k_cur, v_cur, scale, pos,
                                              sub=sub_block)
        # online-softmax merge of the partial result into the running state
        m_next, l_next, o_next = _merge(m_acc, l_acc, o_acc,
                                        m_new, l_new, acc_new)
        k_nxt = lax.ppermute(k_cur, axis, perm)
        v_nxt = lax.ppermute(v_cur, axis, perm)
        return (k_nxt, v_nxt, m_next, l_next, o_next), None

    m0 = jnp.full((B, H, Tl), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    o0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    body = jax.checkpoint(step)  # remat each ring step: O(Tl*Tl) live, not R×
    (k_f, v_f, m_f, l_f, o_f), _ = lax.scan(
        body, (k, v, m0, l0, o0), jnp.arange(R))
    l_safe = jnp.where(l_f == 0.0, 1.0, l_f)
    out = (o_f / l_safe[..., None]).astype(q.dtype)   # [B,H,Tl,D]
    return jnp.swapaxes(out, 1, 2)                    # [B,Tl,H,D]


# ---------------------------------------------------------------------------
# zigzag layout: causal load balancing
# ---------------------------------------------------------------------------
# With the contiguous layout above, causality wastes ~half the ring's
# compute: at every step roughly half the devices hold a fully-masked
# (q-chunk, kv-chunk) pair, but the ring is lockstep, so they wait on the
# devices that do have work.  The zigzag layout (as popularized by
# Megatron-LM context parallelism / llama3 training) splits the sequence
# into 2R chunks and gives rank i the PAIR (i, 2R-1-i).  Then at every ring
# step each rank has exactly two unmasked blocks to compute — the high
# chunk 2R-1-i attends every kv chunk it meets, and exactly one of
# {low-vs-low, high-vs-high} is live depending on the ring direction — so
# causal compute is T^2/(2R) scores per device: perfect 1/R scaling, 2x
# better than the contiguous layout's worst-case T^2/R.


def zigzag_permutation(T: int, R: int):
    """Global row order placing chunk pair (i, 2R-1-i) on rank i.

    Returns int32 index array ``perm`` with ``x_zig = x[perm]``; chunks are
    T/(2R) rows each.  Apply to tokens AND anything position-aligned
    (labels, position ids) BEFORE sharding the sequence dim over the ring
    axis; invert with :func:`zigzag_inverse`."""
    import numpy as np

    if T % (2 * R):
        raise ValueError(f"zigzag needs seq len divisible by 2R "
                         f"(T={T}, R={R})")
    Tc = T // (2 * R)
    idx = []
    for i in range(R):
        idx.extend(range(i * Tc, (i + 1) * Tc))            # low chunk i
        idx.extend(range((2 * R - 1 - i) * Tc,
                         (2 * R - i) * Tc))                # high chunk
    return np.asarray(idx, np.int32)


def zigzag_inverse(T: int, R: int):
    """Inverse permutation: ``x == x_zig[zigzag_inverse(T, R)]``."""
    import numpy as np

    perm = zigzag_permutation(T, R)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(T, dtype=np.int32)
    return inv


def ring_attention_zigzag(q, k, v, axis: str, scale=None,
                          sub_block: int | None = None):
    """Causal ring attention over ``axis`` in the zigzag layout.

    q,k,v: LOCAL [B, 2*Tc, H, D] — rows [:Tc] are global chunk ``i`` (the
    rank index), rows [Tc:] global chunk ``2R-1-i``, i.e. the input
    sequence was reordered with :func:`zigzag_permutation` before sharding.
    As with :func:`ring_attention`, k/v may carry Hkv < H grouped-query
    heads and circulate unrepeated.
    Returns the local output in the same layout (undo at the end with
    :func:`zigzag_inverse`).  Causal only — zigzag exists to balance the
    causal mask; use :func:`ring_attention` for the non-causal case.
    """
    B, T2, H, D = q.shape
    if T2 % 2:
        raise ValueError("zigzag local chunk must hold an even row count")
    Tc = T2 // 2
    scale = scale if scale is not None else 1.0 / (D**0.5)
    R = _axis_size(axis)
    my = lax.axis_index(axis)
    perm = [(i, (i + 1) % R) for i in range(R)]

    qa, qb = q[:, :Tc], q[:, Tc:]      # global chunks my, 2R-1-my
    rows = jnp.arange(Tc)
    diag = (rows, rows)  # same-chunk positions → within-chunk tril mask

    def split(kv):
        return kv[:, :Tc], kv[:, Tc:]

    # step 0 (j == my): qa sees its own diagonal; qb sees ka fully
    # (2R-1-my > my for every rank) plus its own diagonal
    ka, kb = split(k)
    va, vb = split(v)
    st_a = _chunk_attend(qa, ka, va, scale, diag, sub=sub_block)
    st_b = _merge(*_chunk_attend(qb, ka, va, scale, sub=sub_block),
                  *_chunk_attend(qb, kb, vb, scale, diag, sub=sub_block))

    def step(carry, r):
        k_cur, v_cur, st_a, st_b = carry
        k_cur = lax.ppermute(k_cur, axis, perm)
        v_cur = lax.ppermute(v_cur, axis, perm)
        j = (my - r) % R                   # rank whose kv we now hold
        ka, kb = split(k_cur)
        va, vb = split(v_cur)
        # always live: high q-chunk vs low kv-chunk (2R-1-my >= R > j)
        st_b2 = _merge(*st_b, *_chunk_attend(qb, ka, va, scale,
                                             sub=sub_block))
        # exactly one of the remaining pairs is causally live:
        #   j < my:  low-vs-low  (my > j)       — update st_a
        #   j > my:  high-vs-high (2R-1-my > 2R-1-j) — update st_b
        st_a2, st_b2 = lax.cond(
            j < my,
            lambda sa, sb: (_merge(*sa, *_chunk_attend(qa, ka, va, scale,
                                                       sub=sub_block)),
                            sb),
            lambda sa, sb: (sa,
                            _merge(*sb, *_chunk_attend(qb, kb, vb, scale,
                                                       sub=sub_block))),
            st_a, st_b2)
        return (k_cur, v_cur, st_a2, st_b2), None

    body = jax.checkpoint(step)
    (k_f, v_f, st_a, st_b), _ = lax.scan(
        body, (k, v, st_a, st_b), jnp.arange(1, R))

    def finish(st):
        m_f, l_f, o_f = st
        l_safe = jnp.where(l_f == 0.0, 1.0, l_f)
        out = (o_f / l_safe[..., None]).astype(q.dtype)  # [B,H,Tc,D]
        return jnp.swapaxes(out, 1, 2)                   # [B,Tc,H,D]

    return jnp.concatenate([finish(st_a), finish(st_b)], axis=1)
