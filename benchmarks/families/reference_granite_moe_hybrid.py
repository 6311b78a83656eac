"""The plain reference: the forward pass of Granite-4.0-H (``model_type:
granitemoehybrid``) in ``jax.numpy``, as one chip of its deployment
computes it.

Written from the model's public ``config.json``
(https://huggingface.co/ibm-granite/granite-4.0-h-small) and the family's
published description (a layer pattern of Mamba-2 layers and attention
layers without positional encoding, each followed by routed experts beside
a shared expert; muP-style forward multipliers): float32,
``jax.default_matmul_precision("highest")``, the recurrence as a plain
``lax.scan`` over positions, attention over the whole sequence, every held
expert evaluated on every token and weighted by its score where selected:
no chunked scan, no cache, no batching, nothing of ``paddle_tpu``.  It
shares only the layout of the parameter tree, which it has to read.  ``h``
is a layer's input; RMSNorm eps from the config; no bias but the conv's:

    x_0 = embedding_multiplier * wte[token]
    layer l of kind t_l = layer_types[l]:
      n  = RMSNorm(h)
      a  = Mamba2(n) if t_l == "mamba" else Attn(n)        -- ONE mixer a layer
      h1 = h  + residual_multiplier * a
      m  = RMSNorm(h1)
      h' = h1 + residual_multiplier * (MoE(m) + Shared(m))
    logits = (RMSNorm(h_L) wte^T) / logits_scaling         -- tied head

    Mamba2(n): [z | xBC | dt] = n W_in
      xBC = silu(causal_depthwise_conv1d(xBC, width d_conv) + b) ; [x | B | C] = xBC
      dt  = softplus(dt + dt_bias) ;  A = -exp(A_log)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t     per head, S in R^{P x N}
      y_t = S_t C_t + D x_t                                head i reads group i // (H / G)
      out = GroupRMSNorm(y silu(z)) g W_out                norm after the gate
    Attn(n): q = n Wq ; k = n Wk ; v = n Wv                -- NO rope, NO position table
      out = concat_heads(softmax(causal(q . k * attention_multiplier)) v) Wo
    MoE(m): l = m Wr ;  I = top_k(l) ;  w = softmax(l[I])  -- over the selected only
      MoE = sum_{e in I, e held} w_e Wdown_e(silu(Wgate_e m) * Wup_e m)
    Shared(m) = Wdown_s(silu(Wgate_s m) * Wup_s m)

``held`` = [lo, hi) are the routed experts this chip holds (the tree's
expert leaves are those, in order); a selected expert outside it is another
chip's and adds nothing here.  The vocabulary is the rows of ``wte`` held.

It upcasts a mixer's, a shared expert's or one routed expert's weights at a
time, walks the queries in tiles and takes the head in slices of the
vocabulary, so it never holds a float32 copy of the model, or a whole score
tensor, beside the system under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_TILE = 512

# the keys of ``arch`` (a hashable tuple of pairs, see :func:`arch_of`)
ARCH_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads",
    "attention_multiplier", "embedding_multiplier", "residual_multiplier",
    "logits_scaling", "rms_norm_eps", "mamba_n_heads", "mamba_d_head",
    "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "num_experts_per_tok",
    "num_local_experts_published", "held", "layer_types")


def arch_of(model: dict) -> tuple:
    """The scalars of a ``config.json`` the forward pass reads, hashable
    (a static argument of the jitted pieces)."""
    return tuple((k, tuple(model[k]) if isinstance(model[k], list)
                  else model[k]) for k in ARCH_KEYS)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _f32(p):
    return jax.tree_util.tree_map(lambda w: w.astype(F32), p)


@functools.partial(jax.jit, static_argnames=("arch",))
def _attention(n, p, *, arch):
    """n [T, D] -> Attn(n): grouped-query, causal, no position."""
    a = dict(arch)
    T = n.shape[0]
    H, Hkv = a["num_attention_heads"], a["num_key_value_heads"]
    hd = a["hidden_size"] // H
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        q = (n @ p["q_w"]).reshape(T, H, hd)
        k = (n @ p["kv_w"][0]).reshape(T, Hkv, hd)
        v = (n @ p["kv_w"][1]).reshape(T, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=1)       # query head i reads kv
        v = jnp.repeat(v, H // Hkv, axis=1)       # head i // (H / Hkv)
        outs = []
        for t0 in range(0, T, Q_TILE):           # a tile of queries at a time
            sl = slice(t0, min(t0 + Q_TILE, T))
            s = jnp.einsum("thd,shd->hts", q[sl], k) \
                * a["attention_multiplier"]
            causal = (jnp.arange(T)[None, :]
                      <= jnp.arange(sl.start, sl.stop)[:, None])
            s = jnp.where(causal[None], s, -jnp.inf)
            outs.append(jnp.einsum("hts,shd->thd",
                                   jax.nn.softmax(s, axis=-1), v))
        return jnp.concatenate(outs, axis=0).reshape(T, H * hd) @ p["proj_w"]


@functools.partial(jax.jit, static_argnames=("arch", "state_dtype"))
def _mixer(n, p, *, arch, state_dtype):
    """n [T, D] -> Mamba2(n), the state carried position by position."""
    a = dict(arch)
    T = n.shape[0]
    H, P, N, G, K = (a["mamba_n_heads"], a["mamba_d_head"],
                     a["mamba_d_state"], a["mamba_n_groups"],
                     a["mamba_d_conv"])
    d_ssm, gn = H * P, G * N
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        proj = n @ p["ssm_in_w"]
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
        return y @ p["ssm_out_w"]


@functools.partial(jax.jit, static_argnames=("arch",))
def _route(m, router_w, *, arch):
    """Scores [T, E_published]: the softmax over a token's top-k logits at
    the selected experts, zero elsewhere."""
    a = dict(arch)
    with jax.default_matmul_precision("highest"):
        logits = m @ router_w.astype(F32)
        top, idx = jax.lax.top_k(logits, a["num_experts_per_tok"])
        w = jax.nn.softmax(top, axis=-1)
        return jnp.zeros(logits.shape, F32).at[
            jnp.arange(logits.shape[0])[:, None], idx].set(w)


@jax.jit
def _expert(m, gate_w, up_w, down_w, w):
    """One SwiGLU expert on every token, times ``w`` [T] (its score where
    selected, zero elsewhere; ones for the shared expert)."""
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(m @ gate_w.astype(F32)) * (m @ up_w.astype(F32))) \
            @ down_w.astype(F32)
        return w[:, None] * y


def _moe(m, p, *, arch):
    """MoE(m) over the held experts + Shared(m)."""
    lo, hi = dict(arch)["held"]
    scores = _route(m, p["router_w"], arch=arch)
    out = _expert(m, p["shared_gate_w"], p["shared_up_w"],
                  p["shared_down_w"], jnp.ones((m.shape[0],), F32))
    for j in range(hi - lo):
        out = out + _expert(m, p["gate_w"][j], p["up_w"][j], p["down_w"][j],
                            scores[:, lo + j])
    return out


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(h, g, *, eps):
    return _rms_norm(h, g.astype(F32), eps)


def layer_weights(blocks: dict, li: int, arch) -> tuple:
    """(kind, the weights of layer ``li``): its norms and expert layer by
    its number, its mixer's leaves by its place among the layers of its
    kind (the tree's ``mamba`` / ``attn`` leaves are as deep as their kind
    has layers; the routed experts' are tuples of a leaf a layer)."""
    kinds = dict(arch)["layer_types"]
    kind = kinds[li]
    i = sum(k == kind for k in kinds[:li])
    moe = jax.tree_util.tree_map(lambda v: v[li], blocks["moe"],
                                 is_leaf=lambda v: isinstance(v, tuple))
    mixer = blocks["mamba" if kind == "mamba" else "attn"]
    return kind, {"ln1_g": blocks["ln1_g"][li], "ln2_g": blocks["ln2_g"][li],
                  "moe": moe, "mixer": {k: v[i] for k, v in mixer.items()}}


def layer(h, kind, p, *, arch, state_dtype=F32):
    """One layer on ``h`` [T, D]; ``p`` holds this layer's weights in
    whatever type they are stored."""
    a = dict(arch)
    eps, rm = a["rms_norm_eps"], a["residual_multiplier"]
    n = _norm(h, p["ln1_g"], eps=eps)
    mix = (_mixer(n, p["mixer"], arch=arch, state_dtype=state_dtype)
           if kind == "mamba" else _attention(n, p["mixer"], arch=arch))
    h1 = h + rm * mix
    return h1 + rm * _moe(_norm(h1, p["ln2_g"], eps=eps), p["moe"],
                          arch=arch)


def hidden(params, tokens, *, arch, state_dtype=F32):
    """Final-RMSNorm output [T, D] for one sequence ``tokens`` [T]."""
    a = dict(arch)
    h = params["wte"][jnp.asarray(tokens)].astype(F32) \
        * a["embedding_multiplier"]
    for li in range(len(a["layer_types"])):
        kind, p = layer_weights(params["blocks"], li, arch)
        h = layer(h, kind, p, arch=arch, state_dtype=state_dtype)
    return _norm(h, params["ln_f_g"], eps=a["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("scaling",))
def _slice_logits(x, head_rows, *, scaling):
    with jax.default_matmul_precision("highest"):
        return (x @ head_rows.astype(F32).T) / scaling


def logits(params, tokens, *, arch, state_dtype=F32, rows=None,
           vocab_slice: int = 32768):
    """Logits [R, V] at ``rows`` (all positions by default) over the rows
    of the tied embedding that are held, ``vocab_slice`` at a time."""
    x = hidden(params, tokens, arch=arch, state_dtype=state_dtype)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    head, scaling = params["wte"], dict(arch)["logits_scaling"]
    return jnp.concatenate([
        _slice_logits(x, head[v0:v0 + vocab_slice], scaling=scaling)
        for v0 in range(0, head.shape[0], vocab_slice)], axis=-1)


@functools.partial(jax.jit, static_argnames=("scaling",))
def _fold_slice(x, head_rows, best, got, tok, v0, *, scaling):
    """One slice of the vocabulary folded into the running best logit and
    the served token's logit (the [R, V] logits are never whole)."""
    lg = _slice_logits(x, head_rows, scaling=scaling)
    here = (tok >= v0) & (tok < v0 + head_rows.shape[0])
    own = jnp.take_along_axis(
        lg, jnp.clip(tok - v0, 0, head_rows.shape[0] - 1)[:, None],
        axis=-1)[:, 0]
    return (jnp.maximum(best, jnp.max(lg, axis=-1)),
            jnp.where(here, own, got))


def served_margins(params, prompt, served, *, arch, pad_to,
                   state_dtype=F32, vocab_slice: int = 32768):
    """Teacher-forced check of one served request.  The whole sequence
    ``prompt + served`` goes through the reference once; entry j is how far
    served token j lies below the reference's best logit at its position
    (0 = it is the argmax).  Sequences are padded to ``pad_to`` positions
    (causal attention, conv and scan, and a token's experts are its own:
    padding cannot reach back) so that every request shares one compiled
    program."""
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
    head, scaling = params["wte"], dict(arch)["logits_scaling"]
    best = jnp.full((pad_to,), -jnp.inf, F32)
    got = jnp.zeros((pad_to,), F32)
    for v0 in range(0, head.shape[0], vocab_slice):
        best, got = _fold_slice(x, head[v0:v0 + vocab_slice], best, got,
                                jnp.asarray(tok), v0, scaling=scaling)
    return np.asarray(best - got)[:m]
