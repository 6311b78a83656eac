"""The ``longcat_flash`` family (``families/__init__.py`` has the contract):
LongCat-Flash's language model as ``paddle_tpu/text/gpt.py``, ``text/mla.py``
and ``text/moe.py`` build it, as ONE chip of its deployment holds it: a
layer of two latent-attention (MLA) sublayers, two dense SwiGLU FFNs and a
shortcut-connected expert layer (routed SwiGLU experts and zero-compute
identity experts behind one router), of which this chip holds the routed
experts ``held``; RMSNorm, rope on a part of each head, an untied head over
the vocabulary rows held, no biases.  From a configuration file (the keys of
the model's public ``config.json``, at the file's top level) to the
program's ``GPTConfig``, the weights from the seed, the server, correctness
through ``reference_longcat_flash.py``, and the arithmetic of what a decode
step has to do: the dense weights, the held experts that are hit, the live
latent rows.

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
    routed experts held here of ``E_published``; ``V`` the vocabulary rows
    held, which the traffic draws from."""
    m = config
    if m["attention_method"] != "MLA" or m["zero_expert_type"] != "identity" \
            or m["attention_bias"]:
        raise SystemExit("benchmark: the longcat_flash family builds the "
                         "published layer only: MLA, identity zero-compute "
                         "experts, no attention bias")
    lo, hi = m["held"]
    if hi - lo != m["n_routed_experts"] \
            or not 0 <= lo < hi <= m["n_routed_experts_published"]:
        raise SystemExit("benchmark: held must be a range of "
                         "n_routed_experts of the published experts")
    return {
        "D": m["hidden_size"], "L": m["num_layers"],
        "H": m["num_attention_heads"], "F": m["ffn_hidden_size"],
        "Fe": m["expert_ffn_hidden_size"], "E": m["n_routed_experts"],
        "E_published": m["n_routed_experts_published"],
        "Z": m["zero_expert_num"], "k": m["moe_topk"],
        "rq": m["q_lora_rank"], "rkv": m["kv_lora_rank"],
        "dn": m["qk_nope_head_dim"], "dr": m["qk_rope_head_dim"],
        "dv": m["v_head_dim"],
        "V": m["vocab_size"], "V_published": m["vocab_size"],
        "T": int(config["entry_point"]["args"]["max_len"]),
    }


def gpt_config(config: dict):
    """``gpt.GPTConfig`` with the latent block: every width, head count and
    scale factor from the file."""
    import jax.numpy as jnp

    from paddle_tpu.text import gpt
    try:
        from paddle_tpu.text import mla, moe
        share = moe.ExpertShareConfig
    except (ImportError, AttributeError):
        raise SystemExit("benchmark: this program has no text/mla.py and "
                         "no expert share in text/moe.py: it cannot run "
                         "the longcat_flash family") from None

    s, m = sizes(config), config
    if config["dtype"] != "bfloat16":
        raise SystemExit(f"benchmark: dtype {config['dtype']!r} not known")
    return gpt.GPTConfig(
        vocab_size=s["V"], hidden_size=s["D"], num_layers=s["L"],
        num_heads=s["H"], intermediate_size=s["F"],
        max_seq_len=m["max_position_embeddings"], dtype=jnp.bfloat16,
        pos_embed="rope", norm="rmsnorm", activation="swiglu",
        tie_embeddings=False, bias=False, rope_theta=float(m["rope_theta"]),
        mla=mla.MLAConfig(
            q_lora_rank=s["rq"], kv_lora_rank=s["rkv"],
            qk_nope_head_dim=s["dn"], qk_rope_head_dim=s["dr"],
            v_head_dim=s["dv"], scale_q_lora=bool(m["mla_scale_q_lora"]),
            scale_kv_lora=bool(m["mla_scale_kv_lora"])),
        experts=share(
            n_routed=s["E_published"], n_zero=s["Z"], top_k=s["k"],
            expert_size=s["Fe"], scaling=float(m["routed_scaling_factor"]),
            held=tuple(m["held"])))


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def leaf_recipes(config: dict) -> dict:
    """How each leaf of the tree is drawn: {path: (kind, scale)}.  Matrices
    are N(0, weight_std), the five residual-branch outputs of a layer
    (two attention, two FFN, the experts') weight_std / sqrt(2 * 4 L); the
    router N(0, router_std), wide enough that a token's top scores carry
    weight (``assumed.router_std_why``); the selection bias 0; gains 1."""
    a, L = config["assumed"], config["num_layers"]
    std = float(a["weight_std"])
    out = std / math.sqrt(2 * 4 * L)
    b = "blocks/"
    sub = {}
    for i in (0, 1):
        a_, f_ = f"{b}attn{i}/", f"{b}ffn{i}/"
        sub.update({
            a_ + "q_a_w": ("normal", std), a_ + "q_a_ln_g": ("ones", None),
            a_ + "q_b_w": ("normal", std), a_ + "kv_a_w": ("normal", std),
            a_ + "kv_a_ln_g": ("ones", None),
            a_ + "kv_b_w": ("normal", std), a_ + "proj_w": ("normal", out),
            f_ + "gate_w": ("normal", std), f_ + "fc_w": ("normal", std),
            f_ + "out_w": ("normal", out)})
    return {
        **sub,
        "wte": ("normal", std), "lm_head": ("normal", std),
        "ln_f_g": ("ones", None), b + "ln_g": ("ones", None),
        b + "moe/router_w": ("normal", float(a["router_std"])),
        b + "moe/router_b": ("zeros", None),
        b + "moe/gate_w": ("normal", std), b + "moe/up_w": ("normal", std),
        b + "moe/down_w": ("normal", out),
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
    from . import reference_longcat_flash as ref

    m = ref.served_margins(
        params, prompt, served, arch=ref.arch_of(config),
        pad_to=sizes(config)["T"])
    # the harness compares the largest alone; the mean and the p99 tell a
    # control apart that the largest does not (the file's
    # ``logit_margin_tol_why``): on an earlier line, for the record
    common.log(f"[margins] {len(prompt)} + {len(served)} tokens: worst "
               f"{float(m.max()):.4f}, p99 {float(np.percentile(m, 99)):.4f}"
               f", mean {float(m.mean()):.5f}")
    return m


def _serving_only(what: str):
    raise SystemExit(f"benchmark: the longcat_flash family has role serve "
                     f"only; {what} belongs to a role train cell (an expert "
                     f"layer trained without drops, and the optimizer's "
                     f"share of 16 bytes a parameter, are not here yet)")


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
    """One latent-attention sublayer: q_a, q_b, kv_a, kv_b, o, two norms."""
    return (s["D"] * s["rq"] + s["rq"] * s["H"] * (s["dn"] + s["dr"])
            + s["D"] * (s["rkv"] + s["dr"])
            + s["rkv"] * s["H"] * (s["dn"] + s["dv"])
            + s["H"] * s["dv"] * s["D"] + s["rq"] + s["rkv"])


def router_params(s: dict) -> int:
    return (s["D"] + 1) * (s["E_published"] + s["Z"])


def expert_params(s: dict) -> int:
    return 3 * s["D"] * s["Fe"]


def dense_layer_params(s: dict) -> int:
    """One layer outside its experts: two attention sublayers, two dense
    FFNs, four norms, the router and its selection bias."""
    return (2 * mla_params(s) + 2 * 3 * s["D"] * s["F"] + 4 * s["D"]
            + router_params(s))


def total_params(s: dict) -> int:
    return (s["L"] * (dense_layer_params(s) + s["E"] * expert_params(s))
            + 2 * s["V"] * s["D"] + s["D"])


def row_values(s: dict) -> int:
    """Values of a token's latent row a sublayer: ``[c | kr]``."""
    return s["rkv"] + s["dr"]


def experts_hit_even(s: dict, batch_rows: float) -> float:
    """Distinct held experts a layer's step is expected to hit when
    ``batch_rows`` tokens each select ``k`` of the router's outputs
    evenly."""
    p = s["k"] / (s["E_published"] + s["Z"])
    return s["E"] * (1.0 - (1.0 - p) ** batch_rows)


def mla_attn_cost(s: dict, live_kv_tokens: float, batch_rows: float,
                  bytes_per_el: int = 2) -> dict:
    """What the absorbed attention of one decode step must do (the ops
    under ``attn``): read every live latent row once a sublayer and
    ``Wkvb`` once (both of its halves are multiplied in here), score each
    token's H heads against its own rows and sum the latents."""
    sub = 2 * s["L"]
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
    ``moe``): a layer reads its router and the ``experts_hit`` held
    experts some token selected, once each, scores ``batch_rows`` tokens
    and multiplies ``pairs_held`` token-expert selections through an
    expert (both a layer a step)."""
    L = s["L"]
    hit_bytes = L * experts_hit * expert_params(s) * bytes_per_el
    flops = L * (2.0 * s["D"] * (s["E_published"] + s["Z"]) * batch_rows
                 + 2.0 * expert_params(s) * pairs_held)
    return {"bytes": L * router_params(s) * bytes_per_el + hit_bytes,
            "flops": flops, "expert_bytes": hit_bytes}


def decode_step_cost(s: dict, batch_rows: float, live_kv_tokens: float,
                     bytes_per_el: int = 2) -> dict:
    """What one decode step must do: read the dense weights, the final
    norm and the head once (the embedding is a gather of ``batch_rows``
    rows), the held experts expected to be hit under even routing at
    ``batch_rows``, and every live latent row once a sublayer (bytes);
    multiply each token through the dense weights, its share of the held
    experts and its rows of the cache (FLOPs)."""
    L, D = s["L"], s["D"]
    dense = (L * (dense_layer_params(s) - router_params(s)) + D
             + s["V"] * D + batch_rows * D)
    pairs = batch_rows * s["k"] * s["E"] / (s["E_published"] + s["Z"])
    moe = moe_step_cost(s, batch_rows, experts_hit_even(s, batch_rows),
                        pairs, bytes_per_el)
    attn = mla_attn_cost(s, live_kv_tokens, batch_rows, bytes_per_el)
    kv_b = 2 * L * s["rkv"] * s["H"] * (s["dn"] + s["dv"])
    # Wkvb is in ``dense`` already: the rows alone are added to the bytes
    flops = (2.0 * (dense - batch_rows * D - D - kv_b) * batch_rows
             + moe["flops"] + attn["flops"])
    return {"bytes": bytes_per_el * dense + moe["bytes"] + attn["row_bytes"],
            "flops": flops, "weight_bytes": bytes_per_el * dense,
            "expert_bytes": moe["expert_bytes"],
            "kv_bytes": attn["row_bytes"]}
