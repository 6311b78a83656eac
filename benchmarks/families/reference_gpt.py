"""The plain reference: GPT-2's forward pass and loss in ``jax.numpy``.

Written from the published description (Radford et al. 2019; the Hugging
Face ``gpt2`` model type that Cerebras-GPT's config.json names), float32,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
batching tricks, and independent of ``paddle_tpu/text/gpt.py``: it shares
only the layout of the parameter tree, which it has to read.

    h_0   = wte[tokens] + wpe[positions]
    a_l   = h_l + proj(softmax(causal(q k^T / sqrt(hd))) v),  q,k,v = qkv(LN1(h_l))
    h_l+1 = a_l + out(gelu(fc(LN2(a_l))))
    logits = LN_f(h_L) wte^T                      (tied head)

It upcasts one layer's weights at a time, so it never holds an fp32 copy of
the model beside the system under test.

One departure from the published model, listed under ``assumed`` in the
configuration files: ``gelu="tanh"`` follows the program
(``gpt._ffn_body`` calls ``jax.nn.gelu`` with its tanh default), while the
published ``activation_function`` is the erf gelu (``gelu="erf"`` here).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu(x, kind):
    if kind == "erf":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    if kind == "tanh":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown gelu {kind!r}")


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "gelu"))
def _block(h, p, *, n_head, eps, gelu):
    """One transformer block on ``h`` [T, D]; ``p`` holds this layer's
    weights in whatever type they are stored, upcast here."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
        T, D = h.shape
        hd = D // n_head
        x = _layer_norm(h, p["ln1_g"], p["ln1_b"], eps)
        q, k, v = ((x @ p["qkv_w"][i] + p["qkv_b"][i])
                   .reshape(T, n_head, hd).transpose(1, 0, 2)
                   for i in range(3))
        scores = q @ k.transpose(0, 2, 1) / math.sqrt(hd)      # [H, T, T]
        causal = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1) @ v              # [H, T, hd]
        attn = attn.transpose(1, 0, 2).reshape(T, D)
        h = h + attn @ p["proj_w"] + p["proj_b"]
        x = _layer_norm(h, p["ln2_g"], p["ln2_b"], eps)
        x = _gelu(x @ p["fc_w"] + p["fc_b"], gelu)
        return h + x @ p["out_w"] + p["out_b"]


@jax.jit
def _embed(wte, wpe, tokens):
    return wte[tokens].astype(F32) + wpe[:tokens.shape[0]].astype(F32)


def hidden(params, tokens, *, n_head, eps=1e-5, gelu="tanh"):
    """Final-LayerNorm output [T, D] for one sequence ``tokens`` [T]."""
    h = _embed(params["wte"], params["wpe"], jnp.asarray(tokens))
    blocks = params["blocks"]
    n_layer = blocks["qkv_w"].shape[0]
    for layer in range(n_layer):
        p = {k: v[layer] for k, v in blocks.items()}
        h = _block(h, p, n_head=n_head, eps=eps, gelu=gelu)
    return _final_norm(h, params["ln_f_g"], params["ln_f_b"], eps=eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(h, g, b, *, eps):
    return _layer_norm(h, g.astype(F32), b.astype(F32), eps)


@jax.jit
def _margins(x, wte, rows, served):
    """For each of ``rows`` (positions in the sequence): the reference's
    best logit there minus its logit for the ``served`` token."""
    with jax.default_matmul_precision("highest"):
        logits = x[rows] @ wte.astype(F32).T                    # [R, V]
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return best - got


def served_margins(params, prompt, served, *, n_head, pad_to, eps=1e-5,
                   gelu="tanh"):
    """Teacher-forced check of one served request.  The whole sequence
    ``prompt + served`` goes through the reference once; entry j is how far
    served token j lies below the reference's best logit at its position
    (0 = it is the argmax).  Sequences are padded to ``pad_to`` positions
    (causal: padding cannot reach back) so that every request shares one
    compiled program."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, m = len(prompt), len(served)
    if n + m > pad_to:
        raise ValueError(f"sequence of {n + m} tokens exceeds {pad_to}")
    toks = np.zeros((pad_to,), np.int32)
    toks[:n] = prompt
    toks[n:n + m] = served
    x = hidden(params, toks, n_head=n_head, eps=eps, gelu=gelu)
    # pad the row list too: one program for every request
    rows = np.zeros((pad_to,), np.int32)
    rows[:m] = np.arange(n - 1, n - 1 + m)
    tok = np.zeros((pad_to,), np.int32)
    tok[:m] = served
    out = _margins(x, params["wte"], jnp.asarray(rows), jnp.asarray(tok))
    return np.asarray(out)[:m]


@jax.jit
def _sequence_nll(x, wte, targets):
    with jax.default_matmul_precision("highest"):
        logits = x @ wte.astype(F32).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss(params, tokens, *, n_head, eps=1e-5, gelu="tanh") -> float:
    """Mean next-token cross-entropy over ``tokens`` [B, T + 1], one
    sequence at a time."""
    tokens = np.asarray(tokens, np.int32)
    total = 0.0
    for row in tokens:
        x = hidden(params, row[:-1], n_head=n_head, eps=eps, gelu=gelu)
        total += float(_sequence_nll(x, params["wte"], jnp.asarray(row[1:])))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
