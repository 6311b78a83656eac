"""Latent attention (MLA): low-rank query and key-value projections, one
shared rope key, and a cache that holds the latent row, not per-head keys
and values.

A sublayer on a normed input ``n`` [.., D] (H heads; a head's query and
key are ``[nope | rope]`` wide, its value ``v_head_dim``):

    cq       = RMSNorm(n Wqa)                        -> q_lora_rank
    q        = (cq Wqb) * sqrt(D / q_lora_rank)      -> H x [q_nope | q_rope]
    [c | kr] = n Wkva                                -> kv_lora_rank + rope
    c        = RMSNorm(c) * sqrt(D / kv_lora_rank)   (the latent only)
    kr       = rope(kr);  q_rope = rope(q_rope)      (one rope key, all heads)
    [k_nope | v] = c Wkvb                            -> H x (nope + v)
    p        = softmax(causal((q_nope . k_nope + q_rope . kr)
                              / sqrt(nope + rope)))
    out      = concat_heads(p v) Wo

The cache row of a token is ``[c | kr]`` (``row_width`` values a
sublayer).  Two forms of the same numbers:

* **up-projected** (:func:`attend_chunk`, the prefill chunk): every
  cached row is taken through ``Wkvb`` to per-head keys and values and
  attended as any head is;
* **absorbed** (:func:`absorb_q` / :func:`absorb_out`, the decode step):
  ``Wkvb``'s key half is multiplied into the query, the H heads then
  attend the shared ``row_width``-wide row itself (its first
  ``kv_lora_rank`` lanes are the value), and ``Wkvb``'s value half is
  applied to the weighted sum.  ``tests/test_longcat_flash.py`` holds the
  two equal.

Rope pairs lanes (2i, 2i + 1) (interleaved, the DeepSeek-V3 family's
convention); a score is invariant under any pairing both sides share.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import gpt, woq

# sublayers of the latent block (gpt.GPTConfig.mla): two attention
# sublayers a layer, so the pool's first axis is 2 * num_layers
SUBLAYERS = 2
# query rows a tile of the prefill chunk's score tensor holds: the scores
# of all heads against the whole window are [H, tile, T] float32
Q_TILE = 256


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    scale_q_lora: bool = True      # q times sqrt(D / q_lora_rank)
    scale_kv_lora: bool = True     # the normed latent times sqrt(D / kv rank)

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("rope needs an even qk_rope_head_dim")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """Values a token's cache row holds a sublayer: ``[c | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def key(self) -> tuple:
        return dataclasses.astuple(self)


def count_params(m: MLAConfig, hidden: int, heads: int) -> int:
    """One sublayer: q_a, q_b, kv_a, kv_b, o and the two latent norms."""
    return (hidden * m.q_lora_rank + m.q_lora_rank * heads * m.qk_head_dim
            + hidden * m.row_width
            + m.kv_lora_rank * heads * (m.qk_nope_head_dim + m.v_head_dim)
            + heads * m.v_head_dim * hidden
            + m.q_lora_rank + m.kv_lora_rank)


def init_params(m: MLAConfig, hidden: int, heads: int, layers: int, key,
                std: float = 0.02) -> dict:
    """One attention sublayer's leaves of ``layers`` latent blocks,
    ``[L, ...]``, float32."""
    ks = jax.random.split(key, 5)
    lead = (layers,)

    def nrm(k, shape, s=std):
        return s * jax.random.normal(k, lead + shape, jnp.float32)

    return {
        "q_a_w": nrm(ks[0], (hidden, m.q_lora_rank)),
        "q_a_ln_g": jnp.ones(lead + (m.q_lora_rank,), jnp.float32),
        "q_b_w": nrm(ks[1], (m.q_lora_rank, heads * m.qk_head_dim)),
        "kv_a_w": nrm(ks[2], (hidden, m.row_width)),
        "kv_a_ln_g": jnp.ones(lead + (m.kv_lora_rank,), jnp.float32),
        "kv_b_w": nrm(ks[3], (m.kv_lora_rank,
                              heads * (m.qk_nope_head_dim + m.v_head_dim))),
        "proj_w": nrm(ks[4], (heads * m.v_head_dim, hidden),
                      std / math.sqrt(2 * SUBLAYERS * layers)),
    }


def apply_rope(x, positions, base: float):
    """Rotary embedding on [..., T, r] (r even) at ``positions`` [T],
    pairing lanes (2i, 2i + 1); angles in float32."""
    r = x.shape[-1]
    freqs = float(base) ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * freqs       # [T, r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (r // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _scaled_norm(x, g, scale: float, dt):
    """RMSNorm in float32 times a forward scale factor, one rounding."""
    y = gpt._rms_norm(x.astype(jnp.float32), g)
    return (y * scale if scale != 1.0 else y).astype(dt)


def project(n, p, cfg, positions):
    """The projections of a sublayer on normed rows ``n`` [T, D] at
    ``positions`` [T]: (q_nope [T, H, nope], q_rope [T, H, rope] rotated,
    row [T, row_width] = ``[c | rope(kr)]`` in the compute dtype)."""
    m, dt, H = cfg.mla, cfg.dtype, cfg.num_heads
    D = cfg.hidden_size
    with jax.named_scope("ln"):
        cq = _scaled_norm(woq.mm(n, p, "q_a_w", dt), p["q_a_ln_g"], 1.0, dt)
    q = woq.mm(cq, p, "q_b_w", dt)
    if m.scale_q_lora:
        q = q * jnp.asarray(math.sqrt(D / m.q_lora_rank), dt)
    q = q.reshape(n.shape[0], H, m.qk_head_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    ckr = woq.mm(n, p, "kv_a_w", dt)
    with jax.named_scope("ln"):
        c = _scaled_norm(
            ckr[..., :m.kv_lora_rank], p["kv_a_ln_g"],
            math.sqrt(D / m.kv_lora_rank) if m.scale_kv_lora else 1.0, dt)
    kr = apply_rope(ckr[..., m.kv_lora_rank:], positions, cfg.rope_theta)
    # q_rope [T, H, r]: the head axis rides along (positions index axis 0)
    q_rope = apply_rope(q_rope.swapaxes(0, 1), positions,
                        cfg.rope_theta).swapaxes(0, 1)
    return q_nope, q_rope, jnp.concatenate([c, kr], axis=-1)


def _kv_b(p, cfg):
    """``Wkvb`` as [kv_lora_rank, H, nope + v]."""
    m = cfg.mla
    return woq.w(p, "kv_b_w", cfg.dtype).reshape(
        m.kv_lora_rank, cfg.num_heads, m.qk_nope_head_dim + m.v_head_dim)


def _scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.mla.qk_head_dim)


def attend_chunk(q_nope, q_rope, rows, pos0, p, cfg):
    """Up-projected attention of a chunk's queries [C, H, .] at positions
    [pos0, pos0 + C) over latent rows ``rows`` [T, row_width] (the
    chunk's own already spliced in): row t is attended by query i where
    t <= pos0 + i.  -> [C, H * v].  Queries go a tile at a time so that
    the float32 scores are [H, tile, T]."""
    m, dt = cfg.mla, cfg.dtype
    C, T = q_nope.shape[0], rows.shape[0]
    rows = rows.astype(dt)
    kv = jnp.einsum("tr,rhd->thd", rows[:, :m.kv_lora_rank], _kv_b(p, cfg))
    k_nope, v = kv[..., :m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    kr = rows[:, m.kv_lora_rank:]
    tile = min(C, Q_TILE)
    n = -(-C // tile)
    if n * tile != C:            # a last tile of zero queries, cut off below
        pad = ((0, n * tile - C), (0, 0), (0, 0))
        q_nope, q_rope = jnp.pad(q_nope, pad), jnp.pad(q_rope, pad)

    def one(args):
        qn, qr, i0 = args
        # float32 scores: before the scale they run to a hundred and more,
        # where a bf16 step is 0.5
        s = (jnp.einsum("chd,thd->hct", qn, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("chd,td->hct", qr, kr,
                          preferred_element_type=jnp.float32))
        mask = (jnp.arange(T)[None, :]
                <= pos0 + i0 + jnp.arange(tile)[:, None])
        s = jnp.where(mask[None], s * _scale(cfg), -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("hct,thd->chd", w, v)

    out = jax.lax.map(one, (q_nope.reshape(n, tile, *q_nope.shape[1:]),
                            q_rope.reshape(n, tile, *q_rope.shape[1:]),
                            jnp.arange(n) * tile))
    return out.reshape(n * tile, cfg.num_heads * m.v_head_dim)[:C]


def absorb_q(q_nope, q_rope, p, cfg):
    """The decode step's query against the latent row itself: ``Wkvb``'s
    key half multiplied into q_nope, the rope part beside it.
    [B, H, nope], [B, H, rope] -> [B, H, row_width]."""
    with jax.named_scope("mla_absorb"):
        w_k = _kv_b(p, cfg)[..., :cfg.mla.qk_nope_head_dim]
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_k)
        return jnp.concatenate([q_lat, q_rope], axis=-1)


def absorb_out(attn_lat, p, cfg):
    """``Wkvb``'s value half on the weighted sum of latents:
    [B, H, kv_lora_rank] -> [B, H * v]."""
    with jax.named_scope("mla_absorb"):
        w_v = _kv_b(p, cfg)[..., cfg.mla.qk_nope_head_dim:]
        out = jnp.einsum("bhr,rhd->bhd", attn_lat.astype(cfg.dtype), w_v)
        return out.reshape(out.shape[0], -1)


def out_proj(attn, p, cfg):
    """[.., H * v] -> [.., D]."""
    return woq.mm(attn, p, "proj_w", cfg.dtype)
