"""The ``sarvam_mla`` family (``families/__init__.py`` has the contract):
Sarvam-105B's language model (``model_type: sarvam_mla``) as
``paddle_tpu/text/gpt.py``, ``text/mla.py`` and ``text/moe.py`` build it, as
ONE chip of its deployment holds it: a layer pattern of latent-attention
layers (a query with no low-rank stage, a normed latent, DeepSeek's YaRN
rope on one shared rope key), the leading ``first_k_dense_replace`` layers
followed by a dense SwiGLU and the others by sigmoid-routed SwiGLU experts
renormalised over the top k, of which this chip holds ``held``, beside a
shared expert; RMSNorm, an untied head over the vocabulary rows held, no
biases.  From a configuration file (the keys of the model's public
``config.json``, at the file's top level) to the program's ``GPTConfig``,
the weights from the seed, the server, correctness through
``reference_sarvam_mla.py``, and the arithmetic of what a decode step has
to do: the dense weights, the held experts that are hit, the live latent
rows.

A serving family: ``train_step`` / ``reference_loss`` /
``train_flops_per_token`` name the role they lack."""
from __future__ import annotations

import math

import numpy as np

from .. import common
from .falcon_h1 import _make_leaf


def sizes(config: dict) -> dict:
    """The published sizes under short names.  ``T`` is the window the
    server holds a sequence in (``entry_point.args.max_len``); ``E`` the
    routed experts held here of ``E_published``; ``K`` the leading dense
    layers; ``V`` the vocabulary rows held, which the traffic draws
    from."""
    m = config
    y = m["rope_scaling"]
    if (m["model_type"] != "sarvam_mla" or m["hidden_act"] != "silu"
            or m["tie_word_embeddings"] or not m["use_qk_norm"]
            or not m["moe_router_enable_expert_bias"]
            or y["type"] != "deepseek_yarn"
            or m["q_head_dim"] != m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
            or m["head_dim"] != m["kv_lora_rank"] + m["qk_rope_head_dim"]):
        raise SystemExit("benchmark: the sarvam_mla family builds the "
                         "published layer only: latent attention with a "
                         "direct query, a normed latent and DeepSeek's YaRN "
                         "rope, SwiGLU, sigmoid routing with a selection "
                         "bias, an untied head")
    lo, hi = m["held"]
    if hi - lo != m["num_experts"] \
            or not 0 <= lo < hi <= m["num_experts_published"]:
        raise SystemExit("benchmark: held must be a range of num_experts "
                         "of the published experts")
    return {
        "D": m["hidden_size"], "L": m["num_hidden_layers"],
        "K": m["first_k_dense_replace"], "H": m["num_attention_heads"],
        "F": m["intermediate_size"], "Fe": m["moe_intermediate_size"],
        "Fs": m["moe_intermediate_size"] * m["num_shared_experts"],
        "E": m["num_experts"], "E_published": m["num_experts_published"],
        "k": m["num_experts_per_tok"], "rkv": m["kv_lora_rank"],
        "dn": m["qk_nope_head_dim"], "dr": m["qk_rope_head_dim"],
        "dv": m["v_head_dim"],
        "V": m["vocab_size"], "V_published": m["vocab_size"],
        "T": int(config["entry_point"]["args"]["max_len"]),
    }


def gpt_config(config: dict):
    """``gpt.GPTConfig`` with the layer pattern: every width, head count,
    scale and the rope's table from the file."""
    import jax.numpy as jnp

    from paddle_tpu.text import gpt
    try:
        from paddle_tpu.text import mla, moe
        yarn, share = mla.YaRN, moe.ExpertShareConfig
        gpt.GPTConfig.latent_sublayers
    except (ImportError, AttributeError):
        raise SystemExit("benchmark: this program has no latent layer in "
                         "its layer pattern, no YaRN rope in text/mla.py "
                         "and no sigmoid scores in text/moe.py: it cannot "
                         "run the sarvam_mla family") from None

    s, m = sizes(config), config
    if config["dtype"] != "bfloat16":
        raise SystemExit(f"benchmark: dtype {config['dtype']!r} not known")
    y = m["rope_scaling"]
    return gpt.GPTConfig(
        vocab_size=s["V"], hidden_size=s["D"], num_layers=s["L"],
        num_heads=s["H"], intermediate_size=s["F"],
        max_seq_len=m["max_position_embeddings"], dtype=jnp.bfloat16,
        pos_embed="rope", norm="rmsnorm", activation="swiglu",
        rms_norm_eps=float(m["rms_norm_eps"]), tie_embeddings=False,
        bias=False, rope_theta=float(m["rope_theta"]),
        layer_types=("mla",) * s["L"], first_k_dense=s["K"],
        mla=mla.MLAConfig(
            q_lora_rank=None, kv_lora_rank=s["rkv"],
            qk_nope_head_dim=s["dn"], qk_rope_head_dim=s["dr"],
            v_head_dim=s["dv"], scale_q_lora=False, scale_kv_lora=False,
            rope_scaling=yarn(
                factor=float(y["factor"]),
                original_max_position=int(
                    y["original_max_position_embeddings"]),
                beta_fast=float(y["beta_fast"]),
                beta_slow=float(y["beta_slow"]), mscale=float(y["mscale"]),
                mscale_all_dim=float(y["mscale_all_dim"]))),
        experts=share(
            n_routed=s["E_published"], n_zero=0, top_k=s["k"],
            expert_size=s["Fe"], scaling=float(m["routed_scaling_factor"]),
            held=tuple(m["held"]), score="sigmoid", shared_size=s["Fs"]))


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def leaf_recipes(config: dict) -> dict:
    """How each leaf of the tree is drawn: {path: (kind, scale)}.  Matrices
    are N(0, weight_std), the residual-branch outputs (the attention's o,
    the dense FFN's, the experts' and the shared expert's down
    projections) weight_std / sqrt(2 * 2 L); the router N(0, router_std)
    and its selection bias N(0, bias_std), non-zero so that the comparison
    exercises it (``assumed.router_std_why``, ``assumed.bias_why``); gains
    1."""
    a, L = config["assumed"], config["num_hidden_layers"]
    std = float(a["weight_std"])
    out = std / math.sqrt(2 * 2 * L)
    b, at, ex, dn = "blocks/", "blocks/mla/", "blocks/moe/", "blocks/dense/"
    return {
        "wte": ("normal", std), "lm_head": ("normal", std),
        "ln_f_g": ("ones", None),
        b + "ln1_g": ("ones", None), b + "ln2_g": ("ones", None),
        at + "q_w": ("normal", std), at + "kv_a_w": ("normal", std),
        at + "kv_a_ln_g": ("ones", None), at + "kv_b_w": ("normal", std),
        at + "proj_w": ("normal", out),
        dn + "gate_w": ("normal", std), dn + "fc_w": ("normal", std),
        dn + "out_w": ("normal", out),
        ex + "router_w": ("normal", float(a["router_std"])),
        ex + "router_b": ("normal", float(a["bias_std"])),
        ex + "gate_w": ("normal", std), ex + "up_w": ("normal", std),
        ex + "down_w": ("normal", out),
        ex + "shared_gate_w": ("normal", std),
        ex + "shared_up_w": ("normal", std),
        ex + "shared_down_w": ("normal", out),
    }


def weights(config: dict, seed: int):
    """The program's config object and the tree ``gpt.init_params`` would
    make (the same leaves and shapes: checked), drawn leaf by leaf from
    the seed by :func:`leaf_recipes`, in the file's ``dtype``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import gpt

    cfg = gpt_config(config)
    want = jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(want)
    # a leaf's recipe goes by its dict keys (the experts' leaves are tuples
    # of a leaf a layer: every member is drawn alike, from its own key)
    names = ["/".join(str(p.key) for p in path if hasattr(p, "key"))
             for path, _ in leaves]
    recipes = leaf_recipes(config)
    if set(names) != set(recipes):
        raise SystemExit(
            f"benchmark: the program's tree and the family's recipes "
            f"differ: {sorted(set(names) ^ set(recipes))}")
    key = jax.random.PRNGKey(seed)
    made = [_make_leaf(*recipes[name], jax.random.fold_in(key, i),
                       leaf.shape, jnp.bfloat16)
            for i, (name, (_, leaf)) in enumerate(zip(names, leaves))]
    return cfg, jax.tree_util.tree_unflatten(treedef, made)


def server(config: dict, cfg, params):
    return common.entry_point(config)(params, cfg,
                                      **config["entry_point"]["args"])


def served_margins(config: dict, params, prompt, served) -> np.ndarray:
    from . import reference_sarvam_mla as ref

    mass = []
    m = ref.served_margins(
        params, prompt, served, arch=ref.arch_of(config),
        pad_to=sizes(config)["T"], held_mass=mass)
    # the harness compares the largest alone; the p99 and the mean are
    # printed beside it, and the mean share of a token's routed weight
    # that falls on the held experts, from the reference's own route over
    # the request's tokens (about E / E_published in a deployment)
    common.log(f"[margins] {len(prompt)} + {len(served)} tokens: worst "
               f"{float(m.max()):.4f}, p99 {float(np.percentile(m, 99)):.4f}"
               f", mean {float(m.mean()):.5f}; moe.routed_mass_held "
               f"{mass[0]:.4f}")
    return m


def _serving_only(what: str):
    raise SystemExit(f"benchmark: the sarvam_mla family has role serve "
                     f"only; {what} belongs to a role train cell (the "
                     f"expert layer has no training without drops here "
                     f"yet)")


def train_step(config: dict, devices, seed: int):
    _serving_only("train_step")


def reference_loss(config: dict, seed: int, tokens) -> float:
    _serving_only("reference_loss")


def train_flops_per_token(s: dict, seq_len: int) -> float:
    _serving_only("train_flops_per_token")


# --------------------------------------------------------------------------
# required operations and bytes
# --------------------------------------------------------------------------


def mla_params(s: dict) -> int:
    """One latent-attention layer: q, kv_a, the latent norm, kv_b, o."""
    return (s["D"] * s["H"] * (s["dn"] + s["dr"])
            + s["D"] * (s["rkv"] + s["dr"]) + s["rkv"]
            + s["rkv"] * s["H"] * (s["dn"] + s["dv"])
            + s["H"] * s["dv"] * s["D"])


def dense_params(s: dict) -> int:
    """The leading layers' dense SwiGLU."""
    return 3 * s["D"] * s["F"]


def router_params(s: dict) -> int:
    """The router and its selection bias."""
    return (s["D"] + 1) * s["E_published"]


def shared_params(s: dict) -> int:
    return 3 * s["D"] * s["Fs"]


def expert_params(s: dict) -> int:
    return 3 * s["D"] * s["Fe"]


def layer_params(s: dict, dense: bool) -> int:
    """One layer outside its routed experts: the latent sublayer, two
    norms, and the dense FFN or the router with the shared expert."""
    return (mla_params(s) + 2 * s["D"]
            + (dense_params(s) if dense
               else router_params(s) + shared_params(s)))


def total_params(s: dict) -> int:
    """What this chip holds: the layers, their held experts, the
    embedding's and the untied head's rows held, the final norm."""
    K, L = s["K"], s["L"]
    return (K * layer_params(s, True)
            + (L - K) * (layer_params(s, False) + s["E"] * expert_params(s))
            + 2 * s["V"] * s["D"] + s["D"])


def row_values(s: dict) -> int:
    """Values of a token's latent row a layer: ``[c | kr]``."""
    return s["rkv"] + s["dr"]


def experts_hit_even(s: dict, batch_rows: float) -> float:
    """Distinct held experts a layer's step is expected to hit when
    ``batch_rows`` tokens each select ``k`` of the router's outputs
    evenly."""
    p = s["k"] / s["E_published"]
    return s["E"] * (1.0 - (1.0 - p) ** batch_rows)


def mla_attn_cost(s: dict, live_kv_tokens: float, batch_rows: float,
                  bytes_per_el: int = 2) -> dict:
    """What the absorbed attention of one decode step must do (the ops
    under ``attn``): one latent sublayer a layer reads every live latent
    row once and ``Wkvb`` once (both of its halves are multiplied in
    here), scores each token's H heads against its own rows and sums the
    latents."""
    sub = s["L"]
    row = row_values(s)
    kv_b = s["rkv"] * s["H"] * (s["dn"] + s["dv"])
    rows_bytes = sub * row * bytes_per_el * live_kv_tokens
    flops = sub * (2.0 * s["H"] * (row + s["rkv"]) * live_kv_tokens
                   + 2.0 * kv_b * batch_rows)
    return {"bytes": rows_bytes + sub * kv_b * bytes_per_el,
            "flops": flops, "row_bytes": rows_bytes}


def moe_step_cost(s: dict, batch_rows: float, experts_hit: float,
                  pairs_held: float, bytes_per_el: int = 2) -> dict:
    """What the expert layers of one decode step must do (the ops under
    ``moe``, the shared expert among them): each of the L - K expert
    layers reads its router with its selection bias, its shared expert
    and the ``experts_hit`` held experts some token selected, once each;
    scores ``batch_rows`` tokens over the router's 128 outputs, multiplies
    them through the shared expert and ``pairs_held`` token-expert
    selections through a routed one (both a layer a step)."""
    n = s["L"] - s["K"]
    hit_bytes = n * experts_hit * expert_params(s) * bytes_per_el
    flops = n * (2.0 * (s["D"] * s["E_published"] + shared_params(s))
                 * batch_rows + 2.0 * expert_params(s) * pairs_held)
    return {"bytes": n * (router_params(s) + shared_params(s))
            * bytes_per_el + hit_bytes,
            "flops": flops, "expert_bytes": hit_bytes}


def decode_step_cost(s: dict, batch_rows: float, live_kv_tokens: float,
                     bytes_per_el: int = 2) -> dict:
    """What one decode step must do: read the latent sublayers', norms'
    and leading dense FFNs' weights, the final norm and the head once (the
    embedding is a gather of ``batch_rows`` rows), the routers, shared
    experts and the held experts expected to be hit under even routing at
    ``batch_rows``, and every live latent row once a layer (bytes);
    multiply each token through the dense weights, its share of the held
    experts and its rows of the cache (FLOPs)."""
    L, K, D = s["L"], s["K"], s["D"]
    dense = (L * (mla_params(s) + 2 * D) + K * dense_params(s) + D
             + s["V"] * D + batch_rows * D)
    pairs = batch_rows * s["k"] * s["E"] / s["E_published"]
    moe = moe_step_cost(s, batch_rows, experts_hit_even(s, batch_rows),
                        pairs, bytes_per_el)
    attn = mla_attn_cost(s, live_kv_tokens, batch_rows, bytes_per_el)
    kv_b = L * s["rkv"] * s["H"] * (s["dn"] + s["dv"])
    # Wkvb is in ``dense`` already: the rows alone are added to the bytes;
    # its products are the attention's
    flops = (2.0 * (dense - batch_rows * D - D - kv_b) * batch_rows
             + moe["flops"] + attn["flops"])
    return {"bytes": bytes_per_el * dense + moe["bytes"] + attn["row_bytes"],
            "flops": flops, "weight_bytes": bytes_per_el * dense,
            "expert_bytes": moe["expert_bytes"],
            "kv_bytes": attn["row_bytes"]}
