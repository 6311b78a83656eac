"""The ``granite_moe_hybrid`` family (``families/__init__.py`` has the
contract): Granite-4.0-H's language model (``model_type:
granitemoehybrid``) as ``paddle_tpu/text/gpt.py``, ``text/ssm.py`` and
``text/moe.py`` build it, as ONE chip of its deployment holds it: a stated
pattern of layers, each ONE mixer (a Mamba-2 mixer, or grouped-query
attention with no positional encoding) followed by routed SwiGLU experts,
of which this chip holds ``held``, beside a shared expert; RMSNorm, a tied
head over the vocabulary rows held, the family's forward multipliers, no
bias but the conv's.  From a configuration file (the keys of the model's
public ``config.json``, at the file's top level) to the program's
``GPTConfig``, the weights from the seed, the server, correctness through
``reference_granite_moe_hybrid.py``, and the arithmetic of what a decode
step has to do: the mixers', routers' and shared experts' weights, the held
experts that are hit, the occupied slots' recurrent state both ways, the
live KV rows of the layers that attend.

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
    held, which the traffic draws from; ``Lm`` / ``La`` the mamba and the
    attention layers of the pattern."""
    m = config
    kinds = list(m["layer_types"])
    if len(kinds) != m["num_hidden_layers"] \
            or set(kinds) - {"mamba", "attention"}:
        raise SystemExit("benchmark: layer_types states 'mamba' or "
                         "'attention' for each of num_hidden_layers")
    if m["mamba_expand"] * m["hidden_size"] \
            != m["mamba_n_heads"] * m["mamba_d_head"]:
        raise SystemExit("benchmark: mamba_expand x hidden_size must be "
                         "mamba_n_heads x mamba_d_head")
    if m["position_embedding_type"] != "nope" or m["attention_bias"] \
            or m["mamba_proj_bias"] or not m["mamba_conv_bias"] \
            or not m["tie_word_embeddings"] or m["hidden_act"] != "silu" \
            or m["normalization_function"] != "rmsnorm":
        raise SystemExit("benchmark: the granite_moe_hybrid family builds "
                         "the published layer only: no positional "
                         "encoding, no projection bias, a conv bias, a "
                         "tied head, SwiGLU experts, RMSNorm")
    lo, hi = m["held"]
    if hi - lo != m["num_local_experts"] \
            or not 0 <= lo < hi <= m["num_local_experts_published"]:
        raise SystemExit("benchmark: held must be a range of "
                         "num_local_experts of the published experts")
    return {
        "D": m["hidden_size"], "L": len(kinds),
        "Lm": kinds.count("mamba"), "La": kinds.count("attention"),
        "H": m["num_attention_heads"], "Hkv": m["num_key_value_heads"],
        "hd": m["hidden_size"] // m["num_attention_heads"],
        "Fe": m["intermediate_size"], "Fs": m["shared_intermediate_size"],
        "E": m["num_local_experts"],
        "E_published": m["num_local_experts_published"],
        "k": m["num_experts_per_tok"],
        "V": m["vocab_size"], "V_published": m["vocab_size"],
        "T": int(config["entry_point"]["args"]["max_len"]),
        "mixer_heads": m["mamba_n_heads"], "mixer_hd": m["mamba_d_head"],
        "state": m["mamba_d_state"], "groups": m["mamba_n_groups"],
        "conv": m["mamba_d_conv"],
    }


def gpt_config(config: dict):
    """``gpt.GPTConfig`` with the layer pattern: every width, head count
    and multiplier from the file."""
    import jax.numpy as jnp

    from paddle_tpu.text import gpt
    try:
        from paddle_tpu.text import moe, ssm
        gpt.GPTConfig.layer_slots, moe.ExpertShareConfig.selection_bias
    except (ImportError, AttributeError):
        raise SystemExit("benchmark: this program states no layer pattern "
                         "(gpt.GPTConfig.layer_types) and has no shared "
                         "expert in text/moe.py: it cannot run the "
                         "granite_moe_hybrid family") from None

    s, m = sizes(config), config
    if config["dtype"] != "bfloat16":
        raise SystemExit(f"benchmark: dtype {config['dtype']!r} not known")
    return gpt.GPTConfig(
        vocab_size=s["V"], hidden_size=s["D"], num_layers=s["L"],
        num_heads=s["H"], num_kv_heads=s["Hkv"],
        max_seq_len=m["max_position_embeddings"], dtype=jnp.bfloat16,
        pos_embed="none", norm="rmsnorm", activation="swiglu",
        tie_embeddings=True, bias=False,
        layer_types=tuple(m["layer_types"]),
        embedding_multiplier=float(m["embedding_multiplier"]),
        lm_head_multiplier=1.0 / float(m["logits_scaling"]),
        attention_multiplier=float(m["attention_multiplier"]),
        residual_multiplier=float(m["residual_multiplier"]),
        ssm=ssm.SSMConfig(
            n_heads=s["mixer_heads"], head_dim=s["mixer_hd"],
            d_state=s["state"], n_groups=s["groups"], d_conv=s["conv"],
            chunk_size=m["mamba_chunk_size"]),
        experts=moe.ExpertShareConfig(
            n_routed=s["E_published"], n_zero=0, top_k=s["k"],
            expert_size=s["Fe"], held=tuple(m["held"]),
            score="topk_softmax", shared_size=s["Fs"]))


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def leaf_recipes(config: dict) -> dict:
    """How each leaf of the tree is drawn: {path: (kind, scale)}.  Input
    matrices and the tied embedding are N(0, weight_std).  What the
    forward's scalars would flatten is drawn wider, so that a margin on
    the logits judges every mechanism (``assumed.weights_why`` has the
    arithmetic): q and k alike, so wide that a score (q . k x
    ``attention_multiplier``) has the spread ``assumed.score_std`` on
    unit-RMS rows; the four residual-branch outputs (the mixers',
    attention's, the routed and the shared experts' down projections)
    ``assumed.branch_std`` each, sized so that every branch, after
    ``residual_multiplier``, moves the stream by a stated share, and so
    that the stream outgrows the embedding it started from: with a TIED
    head a token's own embedding, still in the stream, would otherwise
    make that token the largest logit at every position by tens of
    spreads, and no margin could tell a wrong step from a right one; the
    router N(0, ``assumed.router_std``), so steep that a token's ten
    scores fall off as a trained router's do and a near-tie at the tenth
    place, which bf16 and float32 settle differently, swaps a small score
    (``assumed.router_std_why``).  The
    mixer's ``A_log``, ``dt_bias`` and ``D`` follow the Mamba-2
    initialisation; gains 1; the conv bias 0."""
    s, m, a = sizes(config), config, config["assumed"]
    std = float(a["weight_std"])
    hd = s["hd"]
    qk = math.sqrt(float(a["score_std"]) / (
        m["attention_multiplier"] * math.sqrt(hd) * s["D"]))
    kv_cols = np.stack([np.full(s["Hkv"] * hd, qk),
                        np.full(s["Hkv"] * hd, std)])[:, None, :]
    br = a["branch_std"]
    b, mm, at, ex = "blocks/", "blocks/mamba/", "blocks/attn/", "blocks/moe/"
    return {
        "wte": ("normal", std),
        "ln_f_g": ("ones", None),
        b + "ln1_g": ("ones", None), b + "ln2_g": ("ones", None),
        at + "q_w": ("normal", qk), at + "kv_w": ("normal", kv_cols),
        at + "proj_w": ("normal", float(br["attention"])),
        mm + "ssm_in_w": ("normal", std),
        mm + "ssm_out_w": ("normal", float(br["mamba"])),
        mm + "ssm_conv_w": ("uniform", 1.0 / math.sqrt(s["conv"])),
        mm + "ssm_conv_b": ("zeros", None),
        mm + "ssm_A_log": ("a_log", (1.0, 16.0)),
        mm + "ssm_dt_bias": ("dt_bias", (1e-3, 1e-1)),
        mm + "ssm_D": ("ones", None),
        mm + "ssm_norm_g": ("ones", None),
        ex + "router_w": ("normal", float(a["router_std"])),
        ex + "gate_w": ("normal", std), ex + "up_w": ("normal", std),
        ex + "down_w": ("normal", float(br["routed"])),
        ex + "shared_gate_w": ("normal", std),
        ex + "shared_up_w": ("normal", std),
        ex + "shared_down_w": ("normal", float(br["shared"])),
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
    from . import reference_granite_moe_hybrid as ref

    m = ref.served_margins(
        params, prompt, served, arch=ref.arch_of(config),
        pad_to=sizes(config)["T"])
    # the harness compares the largest alone; the p99 and the mean are
    # printed beside it on an earlier line, for the record
    common.log(f"[margins] {len(prompt)} + {len(served)} tokens: worst "
               f"{float(m.max()):.4f}, p99 {float(np.percentile(m, 99)):.4f}"
               f", mean {float(m.mean()):.5f}")
    return m


def _serving_only(what: str):
    raise SystemExit(f"benchmark: the granite_moe_hybrid family has role "
                     f"serve only; {what} belongs to a role train cell "
                     f"(the chunked scan has no trained backward and the "
                     f"expert layer no training without drops here yet)")


def train_step(config: dict, devices, seed: int):
    _serving_only("train_step")


def reference_loss(config: dict, seed: int, tokens) -> float:
    _serving_only("reference_loss")


def train_flops_per_token(s: dict, seq_len: int) -> float:
    _serving_only("train_flops_per_token")


# --------------------------------------------------------------------------
# required operations and bytes
# --------------------------------------------------------------------------


def mixer_params(s: dict) -> int:
    """One mamba layer's mixer: in_proj, out_proj, conv weight and bias,
    ``dt_bias``, ``A_log``, ``D``, the gated norm's gain."""
    d_ssm = s["mixer_heads"] * s["mixer_hd"]
    conv_dim = d_ssm + 2 * s["groups"] * s["state"]
    return (s["D"] * (d_ssm + conv_dim + s["mixer_heads"]) + d_ssm * s["D"]
            + conv_dim * s["conv"] + conv_dim + 3 * s["mixer_heads"] + d_ssm)


def attn_params(s: dict) -> int:
    """One attention layer's mixer: q, k, v, o."""
    return 2 * s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["Hkv"] * s["hd"]


def router_params(s: dict) -> int:
    return s["D"] * s["E_published"]


def shared_params(s: dict) -> int:
    return 3 * s["D"] * s["Fs"]


def expert_params(s: dict) -> int:
    return 3 * s["D"] * s["Fe"]


def layer_params(s: dict, kind: str) -> int:
    """One layer outside its routed experts: its mixer, two norms, the
    router, the shared expert."""
    mixer = mixer_params(s) if kind == "mamba" else attn_params(s)
    return mixer + 2 * s["D"] + router_params(s) + shared_params(s)


def total_params(s: dict) -> int:
    """What this chip holds: the layers, their held experts, the tied
    embedding's rows held, the final norm."""
    return (s["Lm"] * layer_params(s, "mamba")
            + s["La"] * layer_params(s, "attention")
            + s["L"] * s["E"] * expert_params(s) + s["V"] * s["D"] + s["D"])


def slot_state_bytes(s: dict, bytes_per_el: int = 2) -> int:
    """One mamba layer of one slot's recurrent state: [heads, head_dim,
    state] float32 and the conv window's d_conv - 1 rows in the compute
    type."""
    d_ssm = s["mixer_heads"] * s["mixer_hd"]
    conv_dim = d_ssm + 2 * s["groups"] * s["state"]
    return (4 * s["mixer_heads"] * s["mixer_hd"] * s["state"]
            + bytes_per_el * (s["conv"] - 1) * conv_dim)


def kv_token_bytes(s: dict, bytes_per_el: int = 2) -> int:
    """K and V rows a token over the layers that attend."""
    return 2 * s["La"] * s["Hkv"] * s["hd"] * bytes_per_el


def experts_hit_even(s: dict, batch_rows: float) -> float:
    """Distinct held experts a layer's step is expected to hit when
    ``batch_rows`` tokens each select ``k`` of the router's outputs
    evenly."""
    p = s["k"] / s["E_published"]
    return s["E"] * (1.0 - (1.0 - p) ** batch_rows)


def ssm_step_cost(s: dict, batch_rows: float, bytes_per_el: int = 2) -> dict:
    """What the mixers of one decode step must do (the ops under ``ssm``):
    the mamba layers read their weights once, and read and write the
    state (the conv window with it) of every occupied slot; two operations
    a weight a token, and the state's decay, update and readout.  Required
    bytes only."""
    Lm = s["Lm"]
    state = 2.0 * Lm * slot_state_bytes(s, bytes_per_el) * batch_rows
    flops = batch_rows * Lm * (
        2.0 * mixer_params(s)
        + 6.0 * s["mixer_heads"] * s["mixer_hd"] * s["state"])
    return {"bytes": bytes_per_el * Lm * mixer_params(s) + state,
            "flops": flops, "state_bytes": state}


def moe_step_cost(s: dict, batch_rows: float, experts_hit: float,
                  pairs_held: float, bytes_per_el: int = 2) -> dict:
    """What the expert layers of one decode step must do (the ops under
    ``moe``, the shared expert among them): a layer reads its router, its
    shared expert and the ``experts_hit`` held experts some token
    selected, once each; scores ``batch_rows`` tokens, multiplies them
    through the shared expert and ``pairs_held`` token-expert selections
    through a routed one (both a layer a step)."""
    L = s["L"]
    hit_bytes = L * experts_hit * expert_params(s) * bytes_per_el
    flops = L * (2.0 * (router_params(s) + shared_params(s)) * batch_rows
                 + 2.0 * expert_params(s) * pairs_held)
    return {"bytes": L * (router_params(s) + shared_params(s))
            * bytes_per_el + hit_bytes,
            "flops": flops, "expert_bytes": hit_bytes}


def decode_step_cost(s: dict, batch_rows: float, live_kv_tokens: float,
                     bytes_per_el: int = 2) -> dict:
    """What one decode step must do: read the mixers', norms', routers'
    and shared experts' weights, the final norm and the tied head's rows
    once (the embedding is a gather of ``batch_rows`` rows), the held
    experts expected to be hit under even routing at ``batch_rows``, every
    live KV row of the attending layers once, and read and write every
    occupied slot's recurrent state (bytes); multiply each token through
    the dense weights, its share of the held experts, its rows of the
    cache and its state (FLOPs)."""
    D = s["D"]
    mixers = s["Lm"] * mixer_params(s) + s["La"] * attn_params(s)
    rest = 2 * s["L"] * D + D + s["V"] * D + batch_rows * D
    pairs = batch_rows * s["k"] * s["E"] / s["E_published"]
    moe = moe_step_cost(s, batch_rows, experts_hit_even(s, batch_rows),
                        pairs, bytes_per_el)
    ssm = ssm_step_cost(s, batch_rows, bytes_per_el)
    kv = float(kv_token_bytes(s, bytes_per_el)) * live_kv_tokens
    dense_bytes = bytes_per_el * (mixers + rest) + moe["bytes"] \
        - moe["expert_bytes"]
    flops = (2.0 * (s["La"] * attn_params(s) + s["V"] * D) * batch_rows
             + ssm["flops"] + moe["flops"]
             + 4.0 * s["La"] * s["H"] * s["hd"] * live_kv_tokens)
    return {"bytes": dense_bytes + moe["expert_bytes"] + kv
            + ssm["state_bytes"],
            "flops": flops, "weight_bytes": dense_bytes,
            "expert_bytes": moe["expert_bytes"], "kv_bytes": kv,
            "state_bytes": ssm["state_bytes"]}
