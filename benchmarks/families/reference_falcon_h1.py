"""The plain reference: Falcon-H1's forward pass in ``jax.numpy``.

Written from the model's public ``config.json`` (``model_type: falcon_h1``,
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct) and the family's
published description (a Mamba-2 mixer in parallel with grouped-query
attention in every block, muP-style forward multipliers), float32,
``jax.default_matmul_precision("highest")``, the recurrence as a plain
``lax.scan`` over positions: no chunking, no cache, no batching, nothing of
``paddle_tpu``.  It shares only the layout of the parameter tree, which it
has to read.  ``h`` is a block's input; every multiplier is the config's
scalar, applied where written:

    n   = RMSNorm(h)
    q,k,v = (attention_in_multiplier n) Wq, Wk, Wv ;  k = k key_multiplier
    q,k = rope(q,k; theta) ;  a = softmax(q k^T / sqrt(hd), causal) v
    attn = (a Wo) attention_out_multiplier
    p   = ((ssm_in_multiplier n) W_in) mup_vector       -> z | x B C | dt
    xBC = silu(causal_depthwise_conv1d(xBC, width d_conv) + conv_bias)
    dt  = softplus(dt + dt_bias) ;  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t     per head, S in R^{P x N}
    y_t = S_t C_t + D x_t                                head i reads group i // (H / G)
    y   = GroupRMSNorm(y silu(z)) g                      norm after the gate
    ssm = (y W_out) ssm_out_multiplier
    h   = h + attn + ssm
    m   = RMSNorm(h) ;  h = h + Wdown(Wup m silu(Wgate m mlp_multipliers[0])) mlp_multipliers[1]

    x0 = wte[tokens] embedding_multiplier ;  logits = RMSNorm(h_L) lm_head^T lm_head_multiplier

It upcasts one layer's weights at a time and takes the head in slices of
the vocabulary, so it never holds a float32 copy of the model beside the
system under test.  What ``config.json`` does not settle is listed under
``assumed`` in the configuration file (``dt`` unclamped, the recurrent
state in float32, rotate-half rope).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

# the keys of ``arch`` (a hashable tuple of pairs, see :func:`arch_of`)
ARCH_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "rms_norm_eps", "attention_in_multiplier", "attention_out_multiplier",
    "key_multiplier", "embedding_multiplier", "lm_head_multiplier",
    "mlp_multipliers", "ssm_in_multiplier", "ssm_out_multiplier",
    "ssm_multipliers", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
    "mamba_n_groups", "mamba_d_conv")


def arch_of(model: dict) -> tuple:
    """The scalars of a ``config.json`` the forward pass reads, hashable
    (a static argument of the jitted block)."""
    return tuple((k, tuple(model[k]) if isinstance(model[k], list)
                  else model[k]) for k in ARCH_KEYS)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """Rotate-half rotary embedding on [T, H, hd] at positions 0..T-1."""
    T, _, hd = x.shape
    half = hd // 2
    freqs = float(theta) ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(n, p, a):
    T = n.shape[0]
    H, Hkv, hd = (a["num_attention_heads"], a["num_key_value_heads"],
                  a["head_dim"])
    x = n * a["attention_in_multiplier"]
    q = (x @ p["q_w"]).reshape(T, H, hd)
    k = (x @ p["kv_w"][0]).reshape(T, Hkv, hd) * a["key_multiplier"]
    v = (x @ p["kv_w"][1]).reshape(T, Hkv, hd)
    q, k = _rope(q, a["rope_theta"]), _rope(k, a["rope_theta"])
    k = jnp.repeat(k, H // Hkv, axis=1)           # query head i reads kv
    v = jnp.repeat(v, H // Hkv, axis=1)           # head i // (H / Hkv)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return (out.reshape(T, H * hd) @ p["proj_w"]) \
        * a["attention_out_multiplier"]


def _mixer(n, p, a, state_dtype):
    T = n.shape[0]
    H, P, N, G, K = (a["mamba_n_heads"], a["mamba_d_head"],
                     a["mamba_d_state"], a["mamba_n_groups"],
                     a["mamba_d_conv"])
    d_ssm, gn = H * P, G * N
    mz, mx, mb, mc, mdt = a["ssm_multipliers"]
    mup = jnp.concatenate([
        jnp.full((d_ssm,), mz, F32), jnp.full((d_ssm,), mx, F32),
        jnp.full((gn,), mb, F32), jnp.full((gn,), mc, F32),
        jnp.full((H,), mdt, F32)])
    proj = ((n * a["ssm_in_multiplier"]) @ p["ssm_in_w"]) * mup
    z, xBC, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * gn], axis=-1)
    # causal depthwise conv of width K: out_t = sum_k w[:, k] in_{t-K+1+k}
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), F32), xBC])
    conv = sum(padded[k:k + T] * p["ssm_conv_w"][:, k] for k in range(K))
    xBC = jax.nn.silu(conv + p["ssm_conv_b"])
    x, b, c = jnp.split(xBC, [d_ssm, d_ssm + gn], axis=-1)
    x = x.reshape(T, H, P)
    b = jnp.repeat(b.reshape(T, G, N), H // G, axis=1)     # [T, H, N]
    c = jnp.repeat(c.reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["ssm_dt_bias"])            # [T, H]
    A = -jnp.exp(p["ssm_A_log"])                           # [H]

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S.astype(F32)
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        S = S.astype(state_dtype)
        return S, jnp.einsum("hpn,hn->hp", S.astype(F32), c_t)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), state_dtype),
                        (x, b, c, dt))
    y = y + p["ssm_D"][:, None] * x                        # [T, H, P]
    y = y.reshape(T, d_ssm) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(T, G, d_ssm // G), 1.0,
                  a["rms_norm_eps"]).reshape(T, d_ssm) * p["ssm_norm_g"]
    return (y @ p["ssm_out_w"]) * a["ssm_out_multiplier"]


@functools.partial(jax.jit, static_argnames=("arch", "state_dtype"))
def _block(h, p, *, arch, state_dtype):
    """One block on ``h`` [T, D]; ``p`` holds this layer's weights in
    whatever type they are stored, upcast here."""
    a = dict(arch)
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda w: w.astype(F32), p)
        n = _rms_norm(h, p["ln1_g"], a["rms_norm_eps"])
        h = h + _attention(n, p, a) + _mixer(n, p, a, state_dtype)
        m = _rms_norm(h, p["ln2_g"], a["rms_norm_eps"])
        m_gate, m_down = a["mlp_multipliers"]
        up = (m @ p["fc_w"]) * jax.nn.silu((m @ p["gate_w"]) * m_gate)
        return h + (up @ p["out_w"]) * m_down


@functools.partial(jax.jit, static_argnames=("mult",))
def _embed(wte, tokens, *, mult):
    return wte[tokens].astype(F32) * mult


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(h, g, *, eps):
    return _rms_norm(h, g.astype(F32), eps)


def hidden(params, tokens, *, arch, state_dtype=F32):
    """Final-RMSNorm output [T, D] for one sequence ``tokens`` [T]."""
    a = dict(arch)
    h = _embed(params["wte"], jnp.asarray(tokens),
               mult=a["embedding_multiplier"])
    blocks = params["blocks"]
    for layer in range(blocks["q_w"].shape[0]):
        p = {k: v[layer] for k, v in blocks.items()}
        h = _block(h, p, arch=arch, state_dtype=state_dtype)
    return _final_norm(h, params["ln_f_g"], eps=a["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("mult",))
def _slice_logits(x, head_rows, *, mult):
    with jax.default_matmul_precision("highest"):
        return (x @ head_rows.astype(F32).T) * mult


def logits(params, tokens, *, arch, state_dtype=F32, rows=None,
           vocab_slice: int = 32768):
    """Logits [R, V] at ``rows`` (all positions by default), the untied
    head taken ``vocab_slice`` rows at a time."""
    x = hidden(params, tokens, arch=arch, state_dtype=state_dtype)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    head = params["lm_head"]
    mult = dict(arch)["lm_head_multiplier"]
    return jnp.concatenate([
        _slice_logits(x, head[v0:v0 + vocab_slice], mult=mult)
        for v0 in range(0, head.shape[0], vocab_slice)], axis=-1)


def served_margins(params, prompt, served, *, arch, pad_to,
                   state_dtype=F32, vocab_slice: int = 32768):
    """Teacher-forced check of one served request.  The whole sequence
    ``prompt + served`` goes through the reference once; entry j is how far
    served token j lies below the reference's best logit at its position
    (0 = it is the argmax).  Sequences are padded to ``pad_to`` positions
    (causal attention, conv and scan: padding cannot reach back) so that
    every request shares one compiled program."""
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
    tok = np.zeros((pad_to,), np.int32)
    tok[:m] = served
    x = hidden(params, toks, arch=arch, state_dtype=state_dtype)[
        jnp.asarray(rows)]
    head = params["lm_head"]
    mult = dict(arch)["lm_head_multiplier"]
    best = jnp.full((pad_to,), -jnp.inf, F32)
    got = jnp.zeros((pad_to,), F32)
    for v0 in range(0, head.shape[0], vocab_slice):
        best, got = _fold_slice(x, head[v0:v0 + vocab_slice], best, got,
                                jnp.asarray(tok), v0, mult=mult)
    return np.asarray(best - got)[:m]


@functools.partial(jax.jit, static_argnames=("mult",))
def _fold_slice(x, head_rows, best, got, tok, v0, *, mult):
    """One slice of the vocabulary folded into the running best logit and
    the served token's logit (the [R, V] logits are never whole)."""
    lg = _slice_logits(x, head_rows, mult=mult)
    here = (tok >= v0) & (tok < v0 + head_rows.shape[0])
    own = jnp.take_along_axis(
        lg, jnp.clip(tok - v0, 0, head_rows.shape[0] - 1)[:, None],
        axis=-1)[:, 0]
    return (jnp.maximum(best, jnp.max(lg, axis=-1)),
            jnp.where(here, own, got))
