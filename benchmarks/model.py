"""From a configuration file to the program's ``GPTConfig``, the weights
from the seed, and the arithmetic of what a step has to do.

The operation and byte counts live here (a copy of ``gpt.flops_per_token``,
plus the decode step's), so that a later PR cannot change what 100%
means."""
from __future__ import annotations

import numpy as np


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


def token_stream(rng, B: int, T: int, vocab: int) -> np.ndarray:
    """[B, T + 1] tokens of the deterministic stream next = (3 tok + 1)
    mod 13, spread over the vocabulary (chip_smoke.token_stream)."""
    t = rng.integers(0, 13, (B, 1))
    rows = [t]
    for _ in range(T):
        t = (t * 3 + 1) % 13
        rows.append(t)
    return (np.concatenate(rows, 1) * (vocab // 13)).astype(np.int32)


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
