"""The plain reference: the forward pass of Sarvam-105B (``model_type:
sarvam_mla``) in ``jax.numpy``, as one chip of its deployment computes it.

Written from the model's public ``config.json``
(https://huggingface.co/sarvamai/sarvam-105b) and the DeepSeek-V3 form its
keys name (latent attention with DeepSeek's YaRN rope, leading dense
layers, sigmoid-routed experts renormalised over the top k beside a shared
expert): float32, ``jax.default_matmul_precision("highest")``, attention in
the up-projected form over the whole sequence, every held expert evaluated
on every token and weighted by its score where selected: no cache, no
batching, nothing of ``paddle_tpu``.  It shares only the layout of the
parameter tree, which it has to read.  ``h`` is a layer's input; RMSNorm
with the config's eps and a learned gain; no biases:

    n        = RMSNorm(h)
    q        = n Wq                   -> H x [q_nope | q_rope]  (no low rank)
    [c | kr] = n Wkva                 -> kv_lora_rank | rope
    c        = RMSNorm(c)             (the latent norm: use_qk_norm)
    kr, q_rope = yarn_rope(kr), yarn_rope(q_rope)   (one key, all heads)
    [k_nope | v] = c Wkvb             -> H x [nope | v]
    attn     = softmax(causal((q_nope . k_nope + q_rope . kr) * s)) v
    h1       = h + concat_heads(attn) Wo
    m        = RMSNorm(h1)
    layer l < first_k_dense_replace:  h' = h1 + SwiGLU_dense(m)
    otherwise: g = sigmoid(m Wr) ;  I = top_k(g + b)
               w_e = routed_scaling_factor * g_e / sum_{j in I} g_j
               h' = h1 + sum_{e in I, e held} w_e SwiGLU_e(m)
                       + SwiGLU_shared(m)
    logits   = RMSNorm(h_L) lm_head^T   (over the rows held)

with the YaRN table of ``rope_scaling`` (type ``deepseek_yarn``) on the r
rope lanes at base ``rope_theta``, lanes (2i, 2i + 1) paired:

    f_i   = base^(-2i / r)
    low   = floor(r ln(original / (beta_fast 2 pi)) / (2 ln base))
    high  = ceil (r ln(original / (beta_slow 2 pi)) / (2 ln base))
    ramp  = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = f_i / factor * ramp_i + f_i * (1 - ramp_i)
    cos, sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    s = mscale(factor, mscale_all_dim)^2 / sqrt(nope + rope)
    mscale(f, m) = 0.1 m ln f + 1

``held`` = [lo, hi) are the routed experts this chip holds (the tree's
expert leaves are those, in order); a selected routed expert outside it is
another chip's and adds nothing here, though its score is in the sum the
held scores are divided by.

It upcasts one sublayer's, one FFN's or one expert's weights at a time and
walks the queries in tiles, so it never holds a float32 copy of the model,
or a whole score tensor, beside the system under test.
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
    "hidden_size", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_scaling",
    "rms_norm_eps", "routed_scaling_factor", "num_experts_published",
    "num_experts_per_tok", "first_k_dense_replace", "held")


def _hashable(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return tuple(v) if isinstance(v, list) else v


def arch_of(model: dict) -> tuple:
    """The scalars of a ``config.json`` the forward pass reads, hashable
    (a static argument of the jitted pieces)."""
    return tuple((k, _hashable(model[k])) for k in ARCH_KEYS)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(arch) -> tuple:
    """(inv_freq [r / 2] float64, cos / sin factor, softmax factor, low,
    high) of the architecture's ``rope_scaling``."""
    a = dict(arch)
    y, r, base = dict(a["rope_scaling"]), a["qk_rope_head_dim"], \
        float(a["rope_theta"])
    if y["type"] != "deepseek_yarn":
        raise ValueError(f"rope_scaling type {y['type']!r}")
    f = base ** (-np.arange(0, r, 2, dtype=np.float64) / r)

    def dim(rot):
        return (r * math.log(y["original_max_position_embeddings"]
                             / (rot * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(dim(y["beta_fast"])), 0)
    high = min(math.ceil(dim(y["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    inv = f / y["factor"] * ramp + f * (1.0 - ramp)
    cos_f = (_mscale(y["factor"], y["mscale"])
             / _mscale(y["factor"], y["mscale_all_dim"]))
    soft_f = _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return inv, cos_f, soft_f, low, high


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, inv_freq, cos_f):
    """Rotary embedding on [T, ..., r] at positions 0..T-1, pairing lanes
    (2i, 2i + 1), with the table's frequencies."""
    T, r = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(ang) * cos_f, jnp.sin(ang) * cos_f
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape)


def _f32(p):
    return jax.tree_util.tree_map(lambda w: w.astype(F32), p)


@functools.partial(jax.jit, static_argnames=("arch",))
def _mla(n, p, *, arch):
    """n [T, D] (normed) -> the latent sublayer's output for weights
    ``p``."""
    a = dict(arch)
    T = n.shape[0]
    H, dn, dr, dv = (a["num_attention_heads"], a["qk_nope_head_dim"],
                     a["qk_rope_head_dim"], a["v_head_dim"])
    rkv, eps = a["kv_lora_rank"], a["rms_norm_eps"]
    inv, cos_f, soft_f, _, _ = yarn(arch)
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        q = (n @ p["q_w"]).reshape(T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv, cos_f)
        ckr = n @ p["kv_a_w"]
        c = _rms_norm(ckr[:, :rkv], p["kv_a_ln_g"], eps)
        kr = _rope(ckr[:, rkv:], inv, cos_f)                      # [T, dr]
        kv = (c @ p["kv_b_w"]).reshape(T, H, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        scale = soft_f / math.sqrt(dn + dr)
        outs = []
        for t0 in range(0, T, Q_TILE):           # a tile of queries at a time
            sl = slice(t0, min(t0 + Q_TILE, T))
            s = (jnp.einsum("thd,shd->hts", q_nope[sl], k_nope)
                 + jnp.einsum("thd,sd->hts", q_rope[sl], kr)) * scale
            causal = (jnp.arange(T)[None, :]
                      <= jnp.arange(sl.start, sl.stop)[:, None])
            s = jnp.where(causal[None], s, -jnp.inf)
            outs.append(jnp.einsum("hts,shd->thd",
                                   jax.nn.softmax(s, axis=-1), v))
        out = jnp.concatenate(outs, axis=0).reshape(T, H * dv)
        return out @ p["proj_w"]


@jax.jit
def _swiglu(m, gate_w, up_w, down_w, w):
    """Wdown(silu(Wgate m) * Wup m), times ``w`` [T] (an expert's score
    where selected, zero elsewhere; ones for a dense FFN)."""
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(m @ gate_w.astype(F32)) * (m @ up_w.astype(F32))) \
            @ down_w.astype(F32)
        return w[:, None] * y


@functools.partial(jax.jit, static_argnames=("arch",))
def route(m, router_w, router_b, *, arch):
    """Weights [T, E_published]: routed_scaling_factor times the selected
    sigmoids over their sum, zero where not selected (the bias chooses
    only)."""
    a = dict(arch)
    with jax.default_matmul_precision("highest"):
        g = jax.nn.sigmoid(m @ router_w.astype(F32))
        _, idx = jax.lax.top_k(g + router_b.astype(F32),
                               a["num_experts_per_tok"])
        chosen = jnp.zeros(g.shape, bool).at[
            jnp.arange(g.shape[0])[:, None], idx].set(True)
        w = jnp.where(chosen, g, 0.0)
        return a["routed_scaling_factor"] * w / jnp.sum(w, axis=-1,
                                                        keepdims=True)


def _moe(m, p, *, arch, held_mass=None):
    """The held experts' part of the routed sum, and the shared expert.
    ``held_mass``, a list, gains each row's share of its routed weight
    that falls on the held experts."""
    lo, hi = dict(arch)["held"]
    w = route(m, p["router_w"], p["router_b"], arch=arch)
    if held_mass is not None:
        held_mass.append(jnp.sum(w[:, lo:hi], axis=-1) / jnp.sum(w, axis=-1))
    out = _swiglu(m, p["shared_gate_w"], p["shared_up_w"],
                  p["shared_down_w"], jnp.ones((m.shape[0],), F32))
    for j in range(hi - lo):
        out = out + _swiglu(m, p["gate_w"][j], p["up_w"][j], p["down_w"][j],
                            w[:, lo + j])
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(h, g, *, eps):
    return _rms_norm(h, g.astype(F32), eps)


def layer_weights(blocks: dict, li: int, arch) -> dict:
    """The weights of layer ``li``: its norms and latent sublayer by its
    number, and its dense FFN (one of the leading ``first_k_dense_replace``
    layers) or its expert layer by its place among those (the routed
    experts' leaves are tuples of a leaf a layer)."""
    k = dict(arch)["first_k_dense_replace"]
    out = {"ln1_g": blocks["ln1_g"][li], "ln2_g": blocks["ln2_g"][li],
           "mla": {n: v[li] for n, v in blocks["mla"].items()}}
    if li < k:
        out["dense"] = {n: v[li] for n, v in blocks["dense"].items()}
    else:
        out["moe"] = jax.tree_util.tree_map(
            lambda v: v[li - k], blocks["moe"],
            is_leaf=lambda v: isinstance(v, tuple))
    return out


def layer(h, p, *, arch, held_mass=None):
    """One layer on ``h`` [T, D]; ``p`` holds this layer's weights in
    whatever type they are stored (``held_mass``: as ``_moe``'s)."""
    eps = dict(arch)["rms_norm_eps"]
    h1 = h + _mla(_norm(h, p["ln1_g"], eps=eps), p["mla"], arch=arch)
    m = _norm(h1, p["ln2_g"], eps=eps)
    if "dense" in p:
        d = p["dense"]
        return h1 + _swiglu(m, d["gate_w"], d["fc_w"], d["out_w"],
                            jnp.ones((m.shape[0],), F32))
    return h1 + _moe(m, p["moe"], arch=arch, held_mass=held_mass)


def hidden(params, tokens, *, arch, held_mass=None):
    """Final-RMSNorm output [T, D] for one sequence ``tokens`` [T]
    (``held_mass``: as ``_moe``'s, a [T] array an expert layer)."""
    h = params["wte"][jnp.asarray(tokens)].astype(F32)
    blocks = params["blocks"]
    for li in range(blocks["ln1_g"].shape[0]):
        h = layer(h, layer_weights(blocks, li, arch), arch=arch,
                  held_mass=held_mass)
    return _norm(h, params["ln_f_g"], eps=dict(arch)["rms_norm_eps"])


@jax.jit
def _head(x, head):
    with jax.default_matmul_precision("highest"):
        return x @ head.astype(F32).T


def logits(params, tokens, *, arch, rows=None, held_mass=None):
    """Logits [R, V] at ``rows`` (all positions by default) over the rows
    of the untied head that are held."""
    x = hidden(params, tokens, arch=arch, held_mass=held_mass)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return _head(x, params["lm_head"])


def served_margins(params, prompt, served, *, arch, pad_to,
                   held_mass=None):
    """Teacher-forced check of one served request.  The whole sequence
    ``prompt + served`` goes through the reference once; entry j is how far
    served token j lies below the reference's best logit at its position
    (0 = it is the argmax).  Sequences are padded to ``pad_to`` positions
    (causal attention, and a token's experts are its own: padding cannot
    reach back) so that every request shares one compiled program.
    ``held_mass``, a list, gains the mean over the sequence's tokens and
    the expert layers of a token's share of its routed weight that falls
    on the held experts."""
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
    mass = [] if held_mass is not None else None
    lg = logits(params, toks, arch=arch, rows=rows, held_mass=mass)
    if mass:
        held_mass.append(float(jnp.mean(jnp.stack(mass)[:, :n + m])))
    tok = np.zeros((pad_to,), np.int32)
    tok[:m] = served
    got = jnp.take_along_axis(lg, jnp.asarray(tok)[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(lg, axis=-1) - got)[:m]
