"""The plain reference: the forward pass of LongCat-Flash's language model
in ``jax.numpy``, as one chip of its deployment computes it.

Written from the model's public ``config.json``
(https://huggingface.co/meituan-longcat/LongCat-Flash-Omni, the language
model's keys) and the family's published description (shortcut-connected
mixture of experts with zero-compute experts, latent attention): float32,
``jax.default_matmul_precision("highest")``, attention in the up-projected
form over the whole sequence, every held expert evaluated on every token
and weighted by its score where selected: no cache, no batching, no sort,
nothing of ``paddle_tpu``.  It shares only the layout of the parameter
tree, which it has to read.  ``h`` is a layer's input; RMSNorm eps from the
config; no biases:

    -- MLA_i(n), i in {0, 1}: H heads; q/k width nope + rope; v width v
    cq  = RMSNorm(n Wqa)
    q   = (cq Wqb) * sqrt(D / q_lora_rank)          -- mla_scale_q_lora
    [c | kr] = n Wkva
    c   = RMSNorm(c) * sqrt(D / kv_lora_rank)       -- mla_scale_kv_lora
    kr  = rope(kr) ;  q_rope = rope(q_rope)         -- lanes (2i, 2i+1) paired
    [k_nope | v] = c Wkvb
    p   = softmax(causal((q_nope . k_nope + q_rope . kr) / sqrt(nope + rope)))
    MLA = concat_heads(p v) Wo
    -- MoE(m): n_routed SwiGLU experts + zero_expert_num identity experts
    s   = softmax(float32(m) Wr) ;  I = top_k(s + b)
    MoE = routed_scaling_factor * ( sum_{e in I, e held} s_e expert_e(m)
                                    + sum_{e in I, e >= n_routed} s_e m )
    -- the layer
    h1 = h  + MLA_0(RMSNorm_0(h))
    m  = RMSNorm_1(h1) ;  S = MoE(m) ;  h2 = h1 + FFN_0(m)
    h3 = h2 + MLA_1(RMSNorm_2(h2))
    h' = h3 + FFN_1(RMSNorm_3(h3)) + S

    x0 = wte[tokens] ;  logits = RMSNorm(h_L) lm_head^T  (over the rows held)

``held`` = [lo, hi) are the routed experts this chip holds (the tree's
expert leaves are those, in order); a selected routed expert outside it is
another chip's and adds nothing here.

It upcasts a sublayer's weights, a dense FFN's, or one expert's at a time,
and walks the queries in tiles, so it never holds a float32 copy of the
model, or a whole score tensor, beside the system under test.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_TILE = 512

# the keys of ``arch`` (a hashable tuple of pairs, see :func:`arch_of`)
ARCH_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "mla_scale_q_lora",
    "mla_scale_kv_lora", "rope_theta", "rms_norm_eps",
    "routed_scaling_factor", "n_routed_experts_published",
    "zero_expert_num", "moe_topk", "held")


def arch_of(model: dict) -> tuple:
    """The scalars of a ``config.json`` the forward pass reads, hashable
    (a static argument of the jitted pieces)."""
    return tuple((k, tuple(model[k]) if isinstance(model[k], list)
                  else model[k]) for k in ARCH_KEYS)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """Rotary embedding on [T, ..., r] at positions 0..T-1, pairing lanes
    (2i, 2i + 1)."""
    T, r = x.shape[0], x.shape[-1]
    freqs = float(theta) ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs          # [T, r/2]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (r // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _f32(p):
    return jax.tree_util.tree_map(lambda w: w.astype(F32), p)


@functools.partial(jax.jit, static_argnames=("arch",))
def _mla(h, g, p, *, arch):
    """h [T, D] -> MLA(RMSNorm(h; g)) for one sublayer's weights ``p``."""
    a = dict(arch)
    T, D = h.shape
    H, dn, dr, dv = (a["num_attention_heads"], a["qk_nope_head_dim"],
                     a["qk_rope_head_dim"], a["v_head_dim"])
    rq, rkv, eps = a["q_lora_rank"], a["kv_lora_rank"], a["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        n = _rms_norm(h, g.astype(F32), eps)
        q = _rms_norm(n @ p["q_a_w"], p["q_a_ln_g"], eps) @ p["q_b_w"]
        if a["mla_scale_q_lora"]:
            q = q * math.sqrt(D / rq)
        q = q.reshape(T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], a["rope_theta"])
        ckr = n @ p["kv_a_w"]
        c = _rms_norm(ckr[:, :rkv], p["kv_a_ln_g"], eps)
        if a["mla_scale_kv_lora"]:
            c = c * math.sqrt(D / rkv)
        kr = _rope(ckr[:, rkv:], a["rope_theta"])                # [T, dr]
        kv = (c @ p["kv_b_w"]).reshape(T, H, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        outs = []
        for t0 in range(0, T, Q_TILE):           # a tile of queries at a time
            sl = slice(t0, min(t0 + Q_TILE, T))
            s = (jnp.einsum("thd,shd->hts", q_nope[sl], k_nope)
                 + jnp.einsum("thd,sd->hts", q_rope[sl], kr)) \
                / math.sqrt(dn + dr)
            causal = (jnp.arange(T)[None, :]
                      <= jnp.arange(sl.start, sl.stop)[:, None])
            s = jnp.where(causal[None], s, -jnp.inf)
            outs.append(jnp.einsum("hts,shd->thd",
                                   jax.nn.softmax(s, axis=-1), v))
        out = jnp.concatenate(outs, axis=0).reshape(T, H * dv)
        return out @ p["proj_w"]


@jax.jit
def _ffn(m, p):
    """Wdown(silu(Wgate m) * Wup m)."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        return (jax.nn.silu(m @ p["gate_w"]) * (m @ p["fc_w"])) @ p["out_w"]


@functools.partial(jax.jit, static_argnames=("arch",))
def _route(m, router_w, router_b, *, arch):
    """(scores [T, E + Z] with zeros where not selected)."""
    a = dict(arch)
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(m @ router_w.astype(F32), axis=-1)
        _, idx = jax.lax.top_k(s + router_b.astype(F32), a["moe_topk"])
        chosen = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], idx].set(True)
        return jnp.where(chosen, s, 0.0)


@jax.jit
def _expert(m, gate_w, up_w, down_w, w):
    """One routed expert on every token, times its score where selected
    (``w`` [T], zero elsewhere)."""
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(m @ gate_w.astype(F32)) * (m @ up_w.astype(F32))) \
            @ down_w.astype(F32)
        return w[:, None] * y


def _moe(m, p, *, arch):
    a = dict(arch)
    lo, hi = a["held"]
    E = a["n_routed_experts_published"]
    chosen = _route(m, p["router_w"], p["router_b"], arch=arch)
    out = jnp.zeros_like(m)
    for j in range(hi - lo):
        out = out + _expert(m, p["gate_w"][j], p["up_w"][j], p["down_w"][j],
                            chosen[:, lo + j])
    out = out + jnp.sum(chosen[:, E:], axis=-1, keepdims=True) * m
    return a["routed_scaling_factor"] * out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(h, g, *, eps):
    return _rms_norm(h, g.astype(F32), eps)


def layer(h, p, *, arch):
    """One layer on ``h`` [T, D]; ``p`` holds this layer's weights in
    whatever type they are stored."""
    eps = dict(arch)["rms_norm_eps"]
    h1 = h + _mla(h, p["ln_g"][0], p["attn0"], arch=arch)
    m = _norm(h1, p["ln_g"][1], eps=eps)
    S = _moe(m, p["moe"], arch=arch)
    h2 = h1 + _ffn(m, p["ffn0"])
    h3 = h2 + _mla(h2, p["ln_g"][2], p["attn1"], arch=arch)
    return h3 + _ffn(_norm(h3, p["ln_g"][3], eps=eps), p["ffn1"]) + S


def hidden(params, tokens, *, arch):
    """Final-RMSNorm output [T, D] for one sequence ``tokens`` [T]."""
    h = params["wte"][jnp.asarray(tokens)].astype(F32)
    blocks = params["blocks"]
    for li in range(blocks["ln_g"].shape[0]):
        # the experts' leaves are tuples of a leaf a layer, the rest stacked
        p = jax.tree_util.tree_map(
            lambda v: v[li], blocks, is_leaf=lambda v: isinstance(v, tuple))
        h = layer(h, p, arch=arch)
    return _norm(h, params["ln_f_g"], eps=dict(arch)["rms_norm_eps"])


@jax.jit
def _head(x, head):
    with jax.default_matmul_precision("highest"):
        return x @ head.astype(F32).T


def logits(params, tokens, *, arch, rows=None):
    """Logits [R, V] at ``rows`` (all positions by default) over the rows
    of the untied head that are held."""
    x = hidden(params, tokens, arch=arch)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["lm_head"])


def served_margins(params, prompt, served, *, arch, pad_to):
    """Teacher-forced check of one served request.  The whole sequence
    ``prompt + served`` goes through the reference once; entry j is how far
    served token j lies below the reference's best logit at its position
    (0 = it is the argmax).  Sequences are padded to ``pad_to`` positions
    (causal attention, and a token's experts are its own: padding cannot
    reach back) so that every request shares one compiled program."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, m = len(prompt), len(served)
    if n + m > pad_to:
        raise ValueError(f"sequence of {n + m} tokens exceeds {pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:n] = prompt
    toks[n:n + m] = served
    rows = np.zeros((pad_to,), np.int32)          # one program for all
    rows[:m] = np.arange(n - 1, n - 1 + m)
    lg = logits(params, toks, arch=arch, rows=rows)
    tok = np.zeros((pad_to,), np.int32)
    tok[:m] = served
    got = jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(lg, axis=-1) - got)[:m]
