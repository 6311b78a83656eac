"""The ``gpt`` family (``families/__init__.py`` has the contract): GPT-2's
architecture as ``paddle_tpu/text/gpt.py`` builds it: learned positions,
LayerNorm, gelu, multi-head attention, a tied head.  From a configuration
file to the program's ``GPTConfig``, the weights from the seed, the server
and the train step, correctness through ``reference_gpt.py``, and the
arithmetic of what a step has to do.

The operation and byte counts live here (a copy of ``gpt.flops_per_token``,
plus the decode step's), so that a later PR cannot change what 100%
means."""
from __future__ import annotations

import numpy as np

from .. import common
from . import TrainStep


def sizes(config: dict) -> dict:
    """The published sizes under short names, with the assumed padding."""
    m = config["model"]
    if m["n_inner"] % m["n_embd"] or m["n_embd"] % m["n_head"]:
        raise SystemExit("benchmark: n_inner must be a multiple of n_embd "
                         "and n_embd of n_head (GPTConfig.ffn_ratio is whole)")
    return {"D": m["n_embd"], "L": m["n_layer"], "H": m["n_head"],
            "F": m["n_inner"], "T": m["n_positions"],
            "V": config["assumed"]["vocab_rows"],
            "V_published": m["vocab_size"]}


def gpt_config(config: dict):
    """``gpt.GPTConfig`` as it is: learned positions, LayerNorm, gelu, MHA,
    tied head; widths and depth from the file."""
    import jax.numpy as jnp

    from paddle_tpu.text import gpt

    s = sizes(config)
    if config["dtype"] != "bfloat16":
        raise SystemExit(f"benchmark: dtype {config['dtype']!r} not known")
    return gpt.GPTConfig(
        vocab_size=s["V"], hidden_size=s["D"], num_layers=s["L"],
        num_heads=s["H"], max_seq_len=s["T"], ffn_ratio=s["F"] // s["D"],
        dtype=jnp.bfloat16,
        **config["entry_point"].get("gpt_config", {}))


def bf16_params(cfg, seed: int):
    """``gpt.init_params`` from the seed in one jitted call on the device,
    fp32 leaves cast to bf16 inside it, so the fp32 tree is never resident
    whole (chip_smoke.bf16_params)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import gpt

    cast = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        gpt.init_params(cfg, k)))
    return cast(jax.random.PRNGKey(seed))


def weights(config: dict, seed: int):
    cfg = gpt_config(config)
    return cfg, bf16_params(cfg, seed)


def server(config: dict, cfg, params):
    return common.entry_point(config)(params, cfg,
                                      **config["entry_point"]["args"])


def _reference_args(config: dict) -> dict:
    return {"n_head": sizes(config)["H"], "gelu": "tanh",
            "eps": config["model"]["layer_norm_epsilon"]}


def served_margins(config: dict, params, prompt, served) -> np.ndarray:
    from . import reference_gpt

    return reference_gpt.served_margins(
        params, prompt, served, pad_to=sizes(config)["T"],
        **_reference_args(config))


def train_step(config: dict, devices, seed: int) -> TrainStep:
    """``entry_point.call`` (``build_gpt_train_step``) over a mesh whose
    axes are the file's ``entry_point.mesh`` ({"dp": 1}; a product of the
    cell's chips), with the optimizer it names at a constant rate."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu import optimizer

    ep = config["entry_point"]
    rate = float(config["assumed"]["learning_rate"])
    axes = ep["mesh"]
    mesh = Mesh(np.array(devices).reshape(tuple(axes.values())), tuple(axes))
    opt = getattr(optimizer, ep["optimizer"])(learning_rate=rate)
    init_fn, step_fn, _ = common.entry_point(config)(
        gpt_config(config), mesh, opt, **ep["args"])
    return TrainStep(init_fn, step_fn,
                     (jax.random.PRNGKey(seed), jnp.float32(rate)),
                     int(ep["args"]["accum"]) * int(ep["micro_batch"]))


def reference_loss(config: dict, seed: int, tokens) -> float:
    import jax

    from paddle_tpu.text import gpt

    from . import reference_gpt

    cfg = gpt_config(config)
    params = jax.jit(lambda k: gpt.init_params(cfg, k))(
        jax.random.PRNGKey(seed))
    return reference_gpt.loss(params, tokens, **_reference_args(config))


# --------------------------------------------------------------------------
# required operations and bytes
# --------------------------------------------------------------------------


def matmul_params(s: dict) -> int:
    """Weights that take part in a matmul: qkv, projection, the two FFN
    matrices per layer, and the tied head once (the embedding lookup is a
    gather)."""
    D, F, L, V = s["D"], s["F"], s["L"], s["V"]
    return L * (3 * D * D + D * D + 2 * D * F) + V * D


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """6 x matmul weights + 12 L D T for the attention scores (full,
    non-causal accounting).  Recomputed operations are not counted."""
    return 6.0 * matmul_params(s) + 12.0 * s["L"] * s["D"] * seq_len


def decode_step_cost(s: dict, batch_rows: int, live_kv_tokens: float,
                     bytes_per_el: int = 2) -> dict:
    """What one decode step must do: read every weight once and every live
    KV row once (bytes), and multiply each of ``batch_rows`` tokens through
    the weights plus its own rows of the cache (FLOPs)."""
    D, L = s["D"], s["L"]
    weight_bytes = bytes_per_el * (matmul_params(s) + s["T"] * D)
    kv_bytes = 2.0 * L * D * bytes_per_el * live_kv_tokens
    flops = (2.0 * matmul_params(s) * batch_rows
             + 4.0 * L * D * live_kv_tokens)
    return {"bytes": weight_bytes + kv_bytes, "flops": flops,
            "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}
