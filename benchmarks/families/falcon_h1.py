"""The ``falcon_h1`` family (``families/__init__.py`` has the contract):
Falcon-H1's block as ``paddle_tpu/text/gpt.py`` and ``text/ssm.py`` build
it: a Mamba-2 mixer in parallel with grouped-query attention on one
RMS-normed input, a SwiGLU MLP, rope, an untied head, no biases, the
family's forward multipliers.  From a configuration file (the keys of the
model's public ``config.json``, at the file's top level) to the program's ``GPTConfig``, the
weights from the seed, the server, correctness through
``reference_falcon_h1.py``, and the arithmetic of what a decode step has
to do: weights, live KV rows, and the occupied slots' recurrent state read
and written.

A serving family: ``train_step`` / ``reference_loss`` /
``train_flops_per_token`` name the role they lack."""
from __future__ import annotations

import math

import numpy as np

from .. import common

# a leaf is filled this many elements at a time (float32 before the cast)
FILL_ELEMENTS = 1 << 27


def sizes(config: dict) -> dict:
    """The published sizes under short names.  ``T`` is the window the
    server holds a sequence in (``entry_point.args.max_len``: the model's
    own ``max_position_embeddings`` is far past one chip's cache)."""
    m = config
    if m["mamba_d_ssm"] != m["mamba_n_heads"] * m["mamba_d_head"]:
        raise SystemExit("benchmark: mamba_d_ssm must be mamba_n_heads x "
                         "mamba_d_head")
    if m["num_attention_heads"] % m["num_key_value_heads"] \
            or m["mamba_n_heads"] % m["mamba_n_groups"]:
        raise SystemExit("benchmark: key/value heads must divide the query "
                         "heads, and the B/C groups the mixer's heads")
    if not m["mamba_use_mlp"] or not m["mamba_rms_norm"] \
            or m["mamba_norm_before_gate"] or m["tie_word_embeddings"] \
            or any(m[k] for k in ("attention_bias", "mlp_bias",
                                  "mamba_proj_bias", "projectors_bias")) \
            or not m["mamba_conv_bias"] or m["rope_scaling"] is not None:
        raise SystemExit("benchmark: the falcon_h1 family builds the "
                         "published block only: an MLP in every block, a "
                         "gated RMSNorm after the gate, an untied head, no "
                         "projection bias, a conv bias, unscaled rope")
    return {
        "D": m["hidden_size"], "L": m["num_hidden_layers"],
        "H": m["num_attention_heads"], "Hkv": m["num_key_value_heads"],
        "hd": m["head_dim"], "F": m["intermediate_size"],
        "V": m["vocab_size"], "V_published": m["vocab_size"],
        "T": int(config["entry_point"]["args"]["max_len"]),
        "mixer_heads": m["mamba_n_heads"], "mixer_hd": m["mamba_d_head"],
        "state": m["mamba_d_state"], "groups": m["mamba_n_groups"],
        "conv": m["mamba_d_conv"],
    }


def gpt_config(config: dict):
    """``gpt.GPTConfig`` with the mixer beside attention: every width,
    head count and multiplier from the file."""
    import jax.numpy as jnp

    from paddle_tpu.text import gpt
    try:
        from paddle_tpu.text import ssm
    except ImportError:
        raise SystemExit("benchmark: this program has no text/ssm.py (a "
                         "Mamba-2 mixer beside attention): it cannot run "
                         "the falcon_h1 family") from None

    s, m = sizes(config), config
    if config["dtype"] != "bfloat16":
        raise SystemExit(f"benchmark: dtype {config['dtype']!r} not known")
    return gpt.GPTConfig(
        vocab_size=s["V"], hidden_size=s["D"], num_layers=s["L"],
        num_heads=s["H"], num_kv_heads=s["Hkv"], head_dim=s["hd"],
        intermediate_size=s["F"], max_seq_len=m["max_position_embeddings"],
        dtype=jnp.bfloat16, pos_embed="rope", norm="rmsnorm",
        activation="swiglu", tie_embeddings=False, bias=False,
        rope_theta=float(m["rope_theta"]),
        embedding_multiplier=m["embedding_multiplier"],
        lm_head_multiplier=m["lm_head_multiplier"],
        attention_in_multiplier=m["attention_in_multiplier"],
        attention_out_multiplier=m["attention_out_multiplier"],
        key_multiplier=m["key_multiplier"],
        mlp_multipliers=tuple(m["mlp_multipliers"]),
        ssm=ssm.SSMConfig(
            n_heads=s["mixer_heads"], head_dim=s["mixer_hd"],
            d_state=s["state"], n_groups=s["groups"], d_conv=s["conv"],
            chunk_size=m["mamba_chunk_size"],
            in_multiplier=m["ssm_in_multiplier"],
            out_multiplier=m["ssm_out_multiplier"],
            multipliers=tuple(m["ssm_multipliers"])))


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------


def leaf_recipes(config: dict) -> dict:
    """How each leaf of the tree is drawn: {path: (kind, scale)} with
    ``scale`` a number or a per-output-column vector.  Matrices are
    N(0, 0.02) (residual-branch outputs 0.02 / sqrt(2 L), as
    ``gpt.init_params``) divided by the multiplier the forward applies on
    their output, so that every activation has the spread it would have
    without multipliers: a key, a score and a logit of order one.  With
    plain N(0, 0.02) the key multiplier flattens every softmax to a mean
    and the head's puts all 261,120 logits within 0.05 of each other, and
    no tolerance could tell a wrong step from a right one.  The mixer's
    ``A_log``, ``dt_bias`` and ``D`` follow the Mamba-2 initialisation
    (``assumed`` in the configuration file)."""
    s, m = sizes(config), config
    std = float(config["assumed"]["weight_std"])
    out = std / math.sqrt(2 * s["L"])
    d_ssm, gn = s["mixer_heads"] * s["mixer_hd"], s["groups"] * s["state"]
    mz, mx, mb, mc, mdt = m["ssm_multipliers"]
    in_cols = np.concatenate([
        np.full(d_ssm, mz), np.full(d_ssm, mx), np.full(gn, mb),
        np.full(gn, mc), np.full(s["mixer_heads"], mdt)])
    kv_cols = np.stack([np.full(s["Hkv"] * s["hd"], m["key_multiplier"]),
                        np.ones(s["Hkv"] * s["hd"])])[:, None, :]
    b = "blocks/"
    return {
        "wte": ("normal", std / m["embedding_multiplier"]),
        "lm_head": ("normal", std / m["lm_head_multiplier"]),
        "ln_f_g": ("ones", None),
        b + "ln1_g": ("ones", None), b + "ln2_g": ("ones", None),
        b + "q_w": ("normal", std / m["attention_in_multiplier"]),
        b + "kv_w": ("normal",
                     std / m["attention_in_multiplier"] / kv_cols),
        b + "proj_w": ("normal", out / m["attention_out_multiplier"]),
        b + "gate_w": ("normal", std / m["mlp_multipliers"][0]),
        b + "fc_w": ("normal", std),
        b + "out_w": ("normal", out / m["mlp_multipliers"][1]),
        b + "ssm_in_w": ("normal",
                         std / m["ssm_in_multiplier"] / in_cols),
        b + "ssm_out_w": ("normal", out / m["ssm_out_multiplier"]),
        b + "ssm_conv_w": ("uniform", 1.0 / math.sqrt(s["conv"])),
        b + "ssm_conv_b": ("zeros", None),
        b + "ssm_A_log": ("a_log", (1.0, 16.0)),
        b + "ssm_dt_bias": ("dt_bias", (1e-3, 1e-1)),
        b + "ssm_D": ("ones", None),
        b + "ssm_norm_g": ("ones", None),
    }


def _draw(kind, scale, key, shape):
    """One block of a leaf, float32."""
    import jax
    import jax.numpy as jnp

    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * scale
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -scale, scale)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, *scale))
    if kind == "dt_bias":               # inverse softplus of a log-uniform dt
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(scale[0]), math.log(scale[1])))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def _make_leaf(kind, scale, key, shape, dtype):
    """One leaf on the device in ``dtype``, filled along its first axis a
    block at a time into a donated buffer: the float32 draw of a block is
    all that is ever resident beside the leaves themselves (the embedding
    and the head are 5.3 GB each in float32)."""
    import jax
    import jax.numpy as jnp

    rows = shape[0]
    per_row = int(np.prod(shape[1:], dtype=np.int64)) if len(shape) > 1 else 1
    step = max(1, min(rows, FILL_ELEMENTS // max(per_row, 1)))
    if isinstance(scale, np.ndarray):
        scale = jnp.asarray(scale, jnp.float32)

    if step >= rows:
        return jax.jit(lambda k: _draw(kind, scale, k, shape).astype(dtype))(
            key)

    def fill(buf, k, r0):
        block = _draw(kind, scale, k, (step,) + tuple(shape[1:]))
        return jax.lax.dynamic_update_slice_in_dim(
            buf, block.astype(dtype), r0, 0)

    fill = jax.jit(fill, donate_argnums=0)
    buf = jnp.zeros(shape, dtype)
    for i, r0 in enumerate(range(0, rows, step)):
        # the last block backs up to end on the last row (rows need not be
        # a multiple of the step); it draws those rows anew, from its key
        buf = fill(buf, jax.random.fold_in(key, i), min(r0, rows - step))
    return buf


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
    flat = {"/".join(str(p.key) for p in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(want)[0]}
    recipes = leaf_recipes(config)
    if set(flat) != set(recipes):
        raise SystemExit(
            f"benchmark: the program's tree and the family's recipes "
            f"differ: {sorted(set(flat) ^ set(recipes))}")
    key = jax.random.PRNGKey(seed)
    params: dict = {"blocks": {}}
    for i, name in enumerate(sorted(flat)):
        kind, scale = recipes[name]
        leaf = _make_leaf(kind, scale, jax.random.fold_in(key, i),
                          flat[name].shape, jnp.bfloat16)
        where = params["blocks"] if name.startswith("blocks/") else params
        where[name.rpartition("/")[2]] = leaf
    return cfg, params


def server(config: dict, cfg, params):
    return common.entry_point(config)(params, cfg,
                                      **config["entry_point"]["args"])


def served_margins(config: dict, params, prompt, served) -> np.ndarray:
    from . import reference_falcon_h1 as ref

    return ref.served_margins(
        params, prompt, served, arch=ref.arch_of(config),
        pad_to=sizes(config)["T"])


def _serving_only(what: str):
    raise SystemExit(f"benchmark: the falcon_h1 family has role serve "
                     f"only; {what} belongs to a role train cell (the "
                     f"chunked scan has no trained backward here yet)")


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
    """One layer's mixer: in_proj, out_proj, conv weight and bias,
    ``dt_bias``, ``A_log``, ``D``, the gated norm's gain."""
    d_ssm = s["mixer_heads"] * s["mixer_hd"]
    conv_dim = d_ssm + 2 * s["groups"] * s["state"]
    return (s["D"] * (d_ssm + conv_dim + s["mixer_heads"]) + d_ssm * s["D"]
            + conv_dim * s["conv"] + conv_dim + 3 * s["mixer_heads"] + d_ssm)


def layer_params(s: dict) -> int:
    """One layer: attention q, k, v, o; the mixer; the MLP's three
    matrices; two norms."""
    attn = 2 * s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["Hkv"] * s["hd"]
    return attn + mixer_params(s) + 3 * s["D"] * s["F"] + 2 * s["D"]


def slot_state_bytes(s: dict, bytes_per_el: int = 2) -> int:
    """One layer of one slot's recurrent state: [heads, head_dim, state]
    float32 and the conv window's d_conv - 1 rows in the compute type."""
    d_ssm = s["mixer_heads"] * s["mixer_hd"]
    conv_dim = d_ssm + 2 * s["groups"] * s["state"]
    return (4 * s["mixer_heads"] * s["mixer_hd"] * s["state"]
            + bytes_per_el * (s["conv"] - 1) * conv_dim)


def ssm_step_cost(s: dict, batch_rows: float, bytes_per_el: int = 2) -> dict:
    """What the mixers of one decode step must do: read their weights
    once, and read and write the state (the conv window with it) of every
    occupied slot; two operations a weight a token, and the state's decay,
    update and readout."""
    L = s["L"]
    state = 2.0 * L * slot_state_bytes(s, bytes_per_el) * batch_rows
    flops = batch_rows * L * (
        2.0 * mixer_params(s)
        + 6.0 * s["mixer_heads"] * s["mixer_hd"] * s["state"])
    return {"bytes": bytes_per_el * L * mixer_params(s) + state,
            "flops": flops, "state_bytes": state}


def decode_step_cost(s: dict, batch_rows: float, live_kv_tokens: float,
                     bytes_per_el: int = 2) -> dict:
    """What one decode step must do: read the layers' weights, the final
    norm and the head once (the embedding is a gather of ``batch_rows``
    rows), every live KV row once, and read and write every occupied
    slot's recurrent state (bytes); multiply each token through the
    weights, its rows of the cache and its state (FLOPs)."""
    L, D = s["L"], s["D"]
    weights_ = L * layer_params(s) + D + s["V"] * D + batch_rows * D
    kv = 2.0 * L * s["Hkv"] * s["hd"] * bytes_per_el * live_kv_tokens
    ssm = ssm_step_cost(s, batch_rows, bytes_per_el)
    flops = (2.0 * (L * (layer_params(s) - mixer_params(s)) + s["V"] * D)
             * batch_rows
             + 4.0 * L * s["H"] * s["hd"] * live_kv_tokens + ssm["flops"])
    return {"bytes": bytes_per_el * weights_ + kv + ssm["state_bytes"],
            "flops": flops, "weight_bytes": bytes_per_el * weights_,
            "kv_bytes": kv, "state_bytes": ssm["state_bytes"]}
