"""GPT decoder-only transformer — the flagship model family.

Reference capability: the reference trains ERNIE/GPT-scale transformers via
Fleet (BASELINE configs 4-5); its building blocks are fused attention CUDA
ops + Megatron-style parallel layers (fleet/meta_parallel/parallel_layers/
mp_layers.py).  TPU-first design:

- parameters are a flat pytree; all L transformer blocks are *stacked* along
  a leading axis and the forward scans them with ``lax.scan`` — one compiled
  block body regardless of depth (fast compiles) and a natural pipeline-
  parallel axis (shard the stack on 'pp').
- ``param_shardings`` returns Megatron shardings as PartitionSpecs; under
  pjit XLA inserts the same collectives the reference's ColumnParallel/
  RowParallel layers issue by hand (all_gather / reduce_scatter over 'mp').
- attention routes through the Pallas flash kernel on TPU.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import attention_array
# weight access in _gqa_qkv/_block/forward resolves through woq.w /
# woq.embed / woq.logits: identity on float training params, fused dequant
# on weight-only int8/int4 decode params — forward on quantized params is
# a correct eval (perplexity) path, never silent garbage
from . import woq


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ffn_ratio: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    remat: bool = False
    # selective-checkpoint policy when remat=True (reference recompute
    # lists a subset of ops to keep; jax expresses it as a policy):
    # None = save nothing (full recompute, max memory saving);
    # "dots" = keep matmul outputs (recompute only cheap elementwise —
    #   a middle rung that may also sidestep backends where FULL-remat
    #   programs fail to compile); on the flash-attention path that
    #   includes the kernel's two residuals, flash_attention.SAVED_OUT
    #   (the p @ v product) and SAVED_LSE, so the forward kernel runs
    #   once a layer;
    # "dots_no_batch" = keep only non-batch matmuls (weights-stationary)
    remat_policy: str | None = None
    use_flash: bool = True
    # sequence-parallel ring attention: cap the live score temp at
    # [B, H, Tl, sp_sub_block] by walking kv in sub-chunks (the flash
    # recurrence in XLA — ops/ring_attention.py _chunk_attend).  None =
    # whole-block scores; set for long local chunks.
    sp_sub_block: int | None = None
    # grouped-query attention (beyond the reference — the Llama/Mistral
    # family): ``num_kv_heads`` < num_heads shares each K/V head across a
    # group of query heads, shrinking qkv params and (the real win) the
    # decode KV cache by num_heads/num_kv_heads.  None = MHA; 1 = MQA.
    num_kv_heads: int | None = None
    moe: Any = None  # MoEConfig → every block's FFN becomes expert-parallel
    # Llama-family architecture switches (round-5; independent of each
    # other and of GQA — num_kv_heads + the three below give the
    # Llama/Mistral shape on the same GPT machinery):
    # "learned" = trained wpe table; "rope" = rotary embeddings applied
    # to q/k (no position table; the decode cache stores ROTATED keys);
    # "none" = no position anywhere (neither a table nor a rotation: the
    # causal mask, and any recurrent mixer, carry the order)
    pos_embed: str = "learned"
    norm: str = "layernorm"        # "layernorm" | "rmsnorm" (gain-only)
    rms_norm_eps: float = 1e-5     # RMSNorm's epsilon, as published
    activation: str = "gelu"       # "gelu" | "swiglu" (gated FFN)
    # explicit widths: None keeps the classic derivations (head_dim =
    # hidden_size // num_heads, intermediate_size = ffn_ratio *
    # hidden_size); models whose heads do not tile the width (20 heads of
    # 128 on 5120) or whose FFN is no whole multiple of it state them
    head_dim: int | None = None
    intermediate_size: int | None = None
    tie_embeddings: bool = True    # False: a separate ``lm_head`` [V, D]
    bias: bool = True              # False: projections carry no ``*_b`` leaf
    rope_theta: float = 10000.0
    # forward multipliers (muP-style scalars of the Falcon-H1 family),
    # applied where the published forward applies them, never folded
    # into the weights; 1.0 traces nothing
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)   # gate pre-activation, output
    # ssm.SSMConfig: every block runs a Mamba-2 mixer IN PARALLEL with
    # attention on the same normed input (h + attn + ssm), text/ssm.py
    ssm: Any = None
    # mla.MLAConfig + moe.ExpertShareConfig, together: the layer is the
    # shortcut-connected latent block (:func:`latent_block`): two latent
    # attention sublayers, two dense SwiGLU FFNs, and one chip's share of
    # a routed expert layer whose result joins after the second FFN.
    # ``num_heads`` are the latent heads, ``intermediate_size`` the dense
    # FFNs' width; the cache holds a latent row a token a sublayer
    mla: Any = None
    experts: Any = None
    # a LAYER PATTERN, one entry a layer, "mamba", "attention" or "mla":
    # layer l runs ONE mixer (the ssm mixer, grouped-query attention or
    # one latent-attention sublayer ``mla``) and then the expert layer
    # ``experts``, or, in its ``first_k_dense`` leading layers, the dense
    # SwiGLU of ``intermediate_size`` (:func:`pattern_block`).  Stated
    # once, here: the block, the cache's leaves (state for the mamba
    # layers only, K/V rows for the attention layers only, latent rows for
    # the mla layers only: :attr:`layer_slots`), the decode step and the
    # prefill chunk read it.  The latent layers rotate their rope lanes
    # (pos_embed "rope"); grouped-query layers of a pattern apply no
    # position.  With it two forward scalars of the Granite-4.0-H family:
    # ``attention_multiplier`` is the softmax scale as stated (None:
    # 1 / sqrt(head_dim)); ``residual_multiplier`` scales both branches
    # before they join the residual stream
    layer_types: Any = None
    first_k_dense: int = 0
    attention_multiplier: float | None = None
    residual_multiplier: float = 1.0

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.intermediate_size is None:
            self.intermediate_size = self.ffn_ratio * self.hidden_size
        # the invariant lives on the config, not one entry point: every
        # consumer (count_params/shardings/init_cache/checkpoint-loaded
        # params) inherits the loud failure
        if (self.num_kv_heads is not None
                and self.num_heads % self.num_kv_heads):
            raise ValueError(
                f"num_kv_heads {self.num_kv_heads} must divide num_heads "
                f"{self.num_heads}")
        if self.pos_embed not in ("learned", "rope", "none"):
            raise ValueError(f"unknown pos_embed {self.pos_embed!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(f"unknown activation {self.activation!r}")
        if self.pos_embed == "rope" and self.head_dim % 2:
            raise ValueError("rope needs an even head_dim")
        if self.layer_types is not None:
            self._check_pattern()
        elif (self.mla is None) != (self.experts is None):
            raise ValueError(
                "mla and experts come together (the latent block); a "
                "plain block with latent attention, or with an expert "
                "share and no layer pattern (layer_types), is not "
                "implemented")
        elif (self.attention_multiplier is not None
              or self.residual_multiplier != 1.0 or self.first_k_dense):
            raise ValueError(
                "attention_multiplier, residual_multiplier and "
                "first_k_dense are applied by the pattern block only "
                "(layer_types)")
        if self.mla is not None and self.layer_types is None and (
                self.pos_embed != "rope" or self.norm != "rmsnorm"
                or self.activation != "swiglu" or self.bias
                or self.tie_embeddings or self.moe is not None
                or self.ssm is not None or self.num_kv_heads is not None):
            raise ValueError(
                "the latent block is rope, RMSNorm, SwiGLU, bias-free, an "
                "untied head, and neither cfg.moe (the GShard layer), an "
                "ssm mixer nor num_kv_heads beside it")
        if self.moe is not None and self.activation != "gelu":
            raise ValueError(
                "MoE experts use the gelu FFN; activation='swiglu' with "
                "moe is not implemented")
        if self.moe is not None and (self.ssm is not None or not self.bias):
            raise ValueError(
                "moe with an ssm mixer or bias-free projections is not "
                "implemented (the expert FFN carries its own biases and "
                "the joint-routing step knows no recurrent state)")

    def _check_pattern(self):
        """A stated layer pattern: what the pattern block is."""
        self.layer_types = tuple(self.layer_types)
        kinds = set(self.layer_types)
        if (len(self.layer_types) != self.num_layers
                or kinds - {"mamba", "attention", "mla"}):
            raise ValueError(
                f"layer_types states one of 'mamba' / 'attention' / 'mla' "
                f"a layer ({self.num_layers} layers), got "
                f"{self.layer_types}")
        if (self.experts is None
                or (self.ssm is None and "mamba" in kinds)
                or (self.mla is None) == ("mla" in kinds)
                or not 0 <= self.first_k_dense < self.num_layers):
            raise ValueError(
                "a layer pattern runs one mixer a layer (ssm for its mamba "
                "layers, mla for its mla layers and for them only) and "
                "then an expert layer (experts), or in its first_k_dense "
                "leading layers (fewer than all) the dense SwiGLU")
        if (self.pos_embed != ("rope" if "mla" in kinds else "none")
                or self.norm != "rmsnorm" or self.activation != "swiglu"
                or self.bias or self.moe is not None
                or (self.num_kv_heads is None and "attention" in kinds)):
            raise ValueError(
                "the pattern block is RMSNorm, SwiGLU, bias-free, without "
                "cfg.moe (the GShard layer), grouped-query (num_kv_heads "
                "stated) where it has attention layers, and rotates the "
                "rope lanes of its latent layers only (pos_embed='rope' "
                "with mla layers, 'none' without)")

    @property
    def layer_slots(self) -> tuple:
        """A pattern's static map from a layer to its index among the
        layers of its own kind: ((kind, index), ...).  The index is the
        layer's place in the params' ``mamba`` / ``attn`` leaves and in
        the cache's state / K/V leaves, which are as deep as their kind
        has layers."""
        seen = {"mamba": 0, "attention": 0, "mla": 0}
        out = []
        for kind in self.layer_types:
            out.append((kind, seen[kind]))
            seen[kind] += 1
        return tuple(out)

    def layers_of(self, kind: str) -> int:
        """Layers of ``kind`` ("mamba" / "attention" / "mla"): every layer
        counts as each where no pattern is stated (the parallel hybrid
        block, the latent block)."""
        if self.layer_types is None:
            return self.num_layers
        return sum(t == kind for t in self.layer_types)

    @property
    def latent_sublayers(self) -> int:
        """Latent-attention sublayers a latent layer has: the latent
        block's two, a pattern's mla layer's one.  The pool's latent leaf
        is ``latent_sublayers * layers_of("mla")`` deep."""
        return 2 if self.layer_types is None else 1

    @property
    def softmax_scale(self) -> float:
        return (self.attention_multiplier
                if self.attention_multiplier is not None
                else 1.0 / math.sqrt(self.head_dim))

    @property
    def q_size(self):
        """Width of the query projection: num_heads * head_dim (the
        hidden size unless ``head_dim`` is stated)."""
        return self.num_heads * self.head_dim

    @property
    def kv_heads(self):
        return self.num_kv_heads if self.num_kv_heads is not None \
            else self.num_heads

    @property
    def ffn_size(self):
        return self.intermediate_size


def gpt_1p3b():
    return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
                     max_seq_len=2048)


def gpt_13b():
    return GPTConfig(vocab_size=50304, hidden_size=5120, num_layers=40, num_heads=40,
                     max_seq_len=2048)


_PROJECTION_BIASES = ("qkv_b", "q_b", "kv_b", "proj_b", "fc_b", "gate_b",
                      "out_b")


def init_params(cfg: GPTConfig, key) -> dict:
    """Stacked-block parameter pytree, fp32 master weights."""
    keys = jax.random.split(key, 10)
    D, F, L, V, T = cfg.hidden_size, cfg.ffn_size, cfg.num_layers, cfg.vocab_size, cfg.max_seq_len
    s = 0.02

    def nrm(k, shape, std=s):
        return std * jax.random.normal(k, shape, jnp.float32)

    if cfg.layer_types is not None:
        return _init_pattern_params(cfg, keys, nrm)
    if cfg.mla is not None:
        from . import mla as _mla
        from .moe import init_expert_share

        fk = jax.random.split(keys[9], 6)
        ak = jax.random.split(keys[2], cfg.latent_sublayers)
        blocks = {
            # the four norms of a layer: before each attention sublayer
            # (0, 2) and each dense FFN (1, 3); the expert layer reads
            # norm 1's output
            "ln_g": jnp.ones((L, 4, D), jnp.float32),
            "moe": init_expert_share(keys[3], D, cfg.experts, L, std=s),
        }
        # a sublayer's weights are leaves of their own, [L, ...]: a layer
        # step reads each where it is stored (a leaf [L, 2, ...] would be
        # copied out a layer at a time to be cut in two)
        for i in range(cfg.latent_sublayers):
            blocks[f"attn{i}"] = _mla.init_params(
                cfg.mla, D, cfg.num_heads, L, ak[i], std=s,
                out_std=s / math.sqrt(2 * cfg.latent_sublayers * L))
            blocks[f"ffn{i}"] = {
                "gate_w": nrm(fk[3 * i], (L, D, F)),
                "fc_w": nrm(fk[3 * i + 1], (L, D, F)),
                "out_w": nrm(fk[3 * i + 2], (L, F, D),
                             std=s / math.sqrt(4 * L)),
            }
        return {
            "wte": nrm(keys[0], (V, D)),
            "lm_head": nrm(jax.random.fold_in(keys[0], 1), (V, D)),
            "ln_f_g": jnp.ones((D,), jnp.float32),
            "blocks": blocks,
        }

    Dq = cfg.q_size
    blk_keys = jax.random.split(keys[9], 6)
    # fold_in, NOT split(…, 7): widening the split would silently change
    # blk_keys[0..5] and with them every existing config's initial
    # weights for the same seed (split has no prefix property) — old
    # recorded seeds must keep reproducing their models
    gate_key = jax.random.fold_in(keys[9], 6)
    blocks = {
        "ln1_g": jnp.ones((L, D), jnp.float32),
        "ln2_g": jnp.ones((L, D), jnp.float32),
        "proj_w": nrm(blk_keys[1], (L, Dq, D), std=s / math.sqrt(2 * L)),
        "proj_b": jnp.zeros((L, D), jnp.float32),
    }
    if cfg.norm == "layernorm":   # rmsnorm is gain-only
        blocks["ln1_b"] = jnp.zeros((L, D), jnp.float32)
        blocks["ln2_b"] = jnp.zeros((L, D), jnp.float32)
    if cfg.num_kv_heads is not None:
        Dkv = cfg.kv_heads * cfg.head_dim
        # GQA: q keeps the full width; k/v project to Dkv
        blocks["q_w"] = nrm(blk_keys[4], (L, D, Dq))
        blocks["q_b"] = jnp.zeros((L, Dq), jnp.float32)
        blocks["kv_w"] = nrm(blk_keys[5], (L, 2, D, Dkv))
        blocks["kv_b"] = jnp.zeros((L, 2, Dkv), jnp.float32)
    else:
        # qkv stored as separate [3, D, D] mats (not one [D, 3D]) so the
        # output dim shards cleanly per-projection under tensor parallel
        blocks["qkv_w"] = nrm(blk_keys[0], (L, 3, D, Dq))
        blocks["qkv_b"] = jnp.zeros((L, 3, Dq), jnp.float32)
    if cfg.moe is None:
        blocks.update({
            "fc_w": nrm(blk_keys[2], (L, D, F)),
            "fc_b": jnp.zeros((L, F), jnp.float32),
            "out_w": nrm(blk_keys[3], (L, F, D), std=s / math.sqrt(2 * L)),
            "out_b": jnp.zeros((L, D), jnp.float32),
        })
        if cfg.activation == "swiglu":
            # gated FFN: down(silu(gate(x)) * up(x)) — the third matmul
            blocks["gate_w"] = nrm(gate_key, (L, D, F))
            blocks["gate_b"] = jnp.zeros((L, F), jnp.float32)
    else:
        from .moe import init_moe_params

        per_layer = [init_moe_params(k, D, F, cfg.moe)
                     for k in jax.random.split(blk_keys[2], L)]
        blocks["moe"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per_layer)
    if cfg.ssm is not None:
        from . import ssm as _ssm

        blocks.update(_ssm.init_params(
            cfg.ssm, D, L, jax.random.fold_in(keys[9], 7), std=s))
    if not cfg.bias:   # bias-free projections: the leaves do not exist
        blocks = {k: v for k, v in blocks.items()
                  if k not in _PROJECTION_BIASES}
    params = {
        "wte": nrm(keys[0], (V, D)),
        "ln_f_g": jnp.ones((D,), jnp.float32),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = nrm(jax.random.fold_in(keys[0], 1), (V, D))
    if cfg.pos_embed == "learned":   # rope has no position table
        params["wpe"] = nrm(keys[1], (T, D))
    if cfg.norm == "layernorm":
        params["ln_f_b"] = jnp.zeros((D,), jnp.float32)
    return params


def _init_pattern_params(cfg: GPTConfig, keys, nrm) -> dict:
    """The tree of a stated layer pattern: the two norms of every layer
    ``[L, ...]``, the expert layer of every layer past the
    ``first_k_dense`` leading ones (``moe``, ``[L - K, ...]``; the routed
    experts a tuple of a leaf a layer, ``moe.init_expert_share``) and the
    leading layers' dense SwiGLU (``dense``: ``gate_w``, ``fc_w``,
    ``out_w``, ``[K, ...]``), and each kind's mixer leaves as deep as
    that kind has layers: ``mamba`` (``ssm.init_params``' leaves,
    ``[Lm, ...]``), ``attn`` (``q_w``, ``kv_w``, ``proj_w``, ``[La, ...]``)
    and ``mla`` (``mla.init_params``' leaves, ``[Ll, ...]``).
    :func:`pattern_layer` cuts one layer's out."""
    from . import mla as _mla
    from . import ssm as _ssm
    from .moe import init_expert_share

    D, L, V, K = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
                  cfg.first_k_dense)
    Dq, Dkv = cfg.q_size, cfg.kv_heads * cfg.head_dim
    Lm, La, Ll = (cfg.layers_of("mamba"), cfg.layers_of("attention"),
                  cfg.layers_of("mla"))
    ak = jax.random.split(keys[2], 3)
    blocks = {
        "ln1_g": jnp.ones((L, D), jnp.float32),
        "ln2_g": jnp.ones((L, D), jnp.float32),
        "moe": init_expert_share(keys[3], D, cfg.experts, L - K),
    }
    if K:
        dk = jax.random.split(keys[4], 3)
        F = cfg.ffn_size
        blocks["dense"] = {
            "gate_w": nrm(dk[0], (K, D, F)), "fc_w": nrm(dk[1], (K, D, F)),
            "out_w": nrm(dk[2], (K, F, D), std=0.02 / math.sqrt(2 * L))}
    if Lm:
        blocks["mamba"] = _ssm.init_params(cfg.ssm, D, Lm, keys[9])
    if La:
        blocks["attn"] = {
            "q_w": nrm(ak[0], (La, D, Dq)),
            "kv_w": nrm(ak[1], (La, 2, D, Dkv)),
            "proj_w": nrm(ak[2], (La, Dq, D), std=0.02 / math.sqrt(2 * L)),
        }
    if Ll:
        blocks["mla"] = _mla.init_params(
            cfg.mla, D, cfg.num_heads, Ll, keys[5],
            out_std=0.02 / math.sqrt(2 * L))
    params = {"wte": nrm(keys[0], (V, D)),
              "ln_f_g": jnp.ones((D,), jnp.float32), "blocks": blocks}
    if not cfg.tie_embeddings:
        params["lm_head"] = nrm(jax.random.fold_in(keys[0], 1), (V, D))
    return params


def pattern_layer(blocks: dict, cfg: GPTConfig, li: int) -> dict:
    """Layer ``li``'s weights of a pattern config's ``blocks``: its norms
    by the layer's number, its expert layer (``moe``) or, for one of the
    ``first_k_dense`` leading layers, its dense SwiGLU (``dense``) by its
    index among those, its mixer's leaves by its index among the layers
    of its kind (``cfg.layer_slots``); static slices of the stacked
    leaves, a per-layer tuple's member."""
    from .moe import layer_of

    kind, i = cfg.layer_slots[li]
    mixer = blocks[{"mamba": "mamba", "attention": "attn",
                    "mla": "mla"}[kind]]
    K = cfg.first_k_dense
    return {"ln1_g": blocks["ln1_g"][li], "ln2_g": blocks["ln2_g"][li],
            **({"dense": {k: v[li] for k, v in blocks["dense"].items()}}
               if li < K else {"moe": layer_of(blocks["moe"], li - K)}),
            **{k: v[i] for k, v in mixer.items()}}


def param_shardings(cfg: GPTConfig, dp="dp", mp="mp", pp=None, ep="ep") -> dict:
    """Megatron-style PartitionSpecs (reference mp_layers.py Column/RowParallel
    + VocabParallelEmbedding; ZeRO/pp compose by adding axes).  With MoE the
    expert dim shards over ``ep`` (expert parallelism)."""
    if cfg.ssm is not None:
        raise NotImplementedError(
            "param_shardings: the ssm mixer has no tensor-parallel layout "
            "yet (its heads, groups and conv channels would have to split "
            "together)")
    if cfg.experts is not None:
        raise NotImplementedError(
            "param_shardings: an expert share has no layout across chips "
            "yet (no ep exchange is written: one chip runs its own share "
            "of the experts)")
    l = pp  # leading stacked-layer axis shards over pipeline stages if set
    blocks = {
        "ln1_g": P(l, None),
        "ln2_g": P(l, None),
        "qkv_w": P(l, None, None, mp),  # column parallel (per-projection)
        "qkv_b": P(l, None, mp),
        "proj_w": P(l, mp, None),  # row parallel
        "proj_b": P(l, None),
    }
    if cfg.norm == "layernorm":
        blocks["ln1_b"] = P(l, None)
        blocks["ln2_b"] = P(l, None)
    if cfg.num_kv_heads is not None:
        for k in ("qkv_w", "qkv_b"):
            del blocks[k]
        blocks.update({
            "q_w": P(l, None, mp), "q_b": P(l, mp),
            "kv_w": P(l, None, None, mp), "kv_b": P(l, None, mp),
        })
    if cfg.moe is None:
        blocks.update({
            "fc_w": P(l, None, mp),    # column parallel
            "fc_b": P(l, mp),
            "out_w": P(l, mp, None),   # row parallel
            "out_b": P(l, None),
        })
        if cfg.activation == "swiglu":
            blocks["gate_w"] = P(l, None, mp)   # column parallel like fc
            blocks["gate_b"] = P(l, mp)
    else:
        from .moe import moe_param_shardings

        # per-layer MoE specs with the stacked-layer axis prepended
        blocks["moe"] = {
            k: P(l, *v) for k, v in moe_param_shardings(ep=ep, mp=mp).items()
        }
    if not cfg.bias:
        blocks = {k: v for k, v in blocks.items()
                  if k not in _PROJECTION_BIASES}
    out = {
        "wte": P(mp, None),          # vocab-parallel embedding
        "ln_f_g": P(None),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = P(mp, None)
    if cfg.pos_embed == "learned":
        out["wpe"] = P(None, None)
    if cfg.norm == "layernorm":
        out["ln_f_b"] = P(None)
    return out


def _layer_norm(x, g, b, eps=1e-5):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * g + b


def _rms_norm(x, g, eps=1e-5):
    """Gain-only RMS normalization (Llama family): no mean subtraction,
    no bias — x * rsqrt(mean(x^2)) * g, statistics in the caller's dtype
    (callers upcast to fp32 like _layer_norm's)."""
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * g


def _norm(x, p, prefix: str, cfg):
    """Block-norm dispatch — THE single entry every block path (train,
    cached decode, prefill, verify) normalizes through.  LayerNorm keeps
    the fp32-stats/fused-kernel behavior of _ln; RMSNorm is gain-only
    (params carry no ``<prefix>_b``) and never takes the fused-LN kernel
    (different math)."""
    dt = cfg.dtype
    with jax.named_scope("ln"):
        if cfg.norm == "rmsnorm":
            return _rms_norm(x.astype(jnp.float32), p[prefix + "_g"],
                             cfg.rms_norm_eps).astype(dt)
        return _ln(x, p[prefix + "_g"], p[prefix + "_b"], dt)


def apply_rope(x, positions, base: float = 10000.0):
    """Rotary position embedding on [..., T, H, hd] (hd even): the
    rotate-half convention, angles in fp32.  ``positions`` [T] int —
    decode passes the single cache position, verify/prefill pass
    pos0 + arange(K).  Defining property (tested): inner products depend
    only on POSITION DIFFERENCES, which is what lets the decode cache
    store rotated keys once and never re-rotate them."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = float(base) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs      # [T, half]
    cos = jnp.cos(ang)[:, None, :]                            # [T, 1, half]
    sin = jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _ln(x, g, b, dt):
    """LayerNorm with fp32 statistics, output in the compute dtype.

    The plain path upcasts the whole activation to fp32 (the reference's
    layer_norm_op.cu accumulates fp32 the same way) — but under scan-over-
    layers autodiff those fp32 chains become the largest saved residuals
    (measured on v5e: 6x 288 MB fp32 buffers for GPT-760M at B=1).  The
    Pallas fused kernel (PADDLE_TPU_FUSED_LN=1) keeps x in the compute
    dtype end-to-end and saves only [N,1] statistics."""
    if os.environ.get("PADDLE_TPU_FUSED_LN", "") == "1":
        from ..ops.fused_norm import fused_layer_norm

        # belt-and-braces .astype(dt): the kernel returns x.dtype, which
        # equals dt everywhere in this stack — but the residual-stream
        # dtype is a scan-carry invariant, so enforce it at the call site
        return fused_layer_norm(x, g, b).astype(dt)
    return _layer_norm(x.astype(jnp.float32), g, b).astype(dt)


def _dropout(x, rate, key):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros((), x.dtype))


def _remat_policy(name: str | None):
    """Map GPTConfig.remat_policy to a jax checkpoint policy.  The env
    var PADDLE_TPU_REMAT_POLICY lets a compile check A/B policies
    without rebuilding — but
    only when the config does NOT set one explicitly: an explicit config
    must stay authoritative (and keep raising on invalid values), or
    bench labels and HBM estimates silently desynchronize from the
    program actually compiled."""
    from ..ops.remat_policies import resolve

    if name is None:
        name = os.environ.get("PADDLE_TPU_REMAT_POLICY") or None
    return resolve(name)


def _gqa_qkv(h, p, cfg: GPTConfig, repeat_kv: bool = True,
             H: int | None = None, Hkv: int | None = None):
    """Grouped-query projections.  With ``repeat_kv`` the Hkv k/v heads
    are repeated across their query groups so every attention backend
    (flash included) sees the standard [B, T, H, hd] layout; the decode
    path passes False and keeps the cache at Hkv heads.  ``H``/``Hkv``
    override the config's global head counts with per-rank LOCAL ones
    when the weights are tensor-parallel shards (gpt_hybrid.mp_block).
    The GQA savings live in the params and the decode cache, not the
    training-time attention math."""
    B, T, D = h.shape
    H = H if H is not None else cfg.num_heads
    Hkv = Hkv if Hkv is not None else cfg.kv_heads
    hd = cfg.head_dim
    dt = cfg.dtype
    q = _add_bias(woq.mm(h, p, "q_w", dt), p, "q_b").reshape(B, T, H, hd)
    kv = woq.mm_stacked(h, p, "kv_w", dt)
    if "kv_b" in p:
        kv = kv + p["kv_b"].astype(dt)[:, None, None]
    k = kv[0].reshape(B, T, Hkv, hd)
    if cfg.key_multiplier != 1.0:
        k = k * jnp.asarray(cfg.key_multiplier, dt)
    v = kv[1].reshape(B, T, Hkv, hd)
    rep = H // Hkv
    if repeat_kv and rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return q, k, v


def _project_qkv(h, p, cfg: GPTConfig, repeat_kv: bool = True):
    """qkv projection for BOTH attention families: q [B,T,H,hd], k/v
    [B,T,H,hd] (dense / repeated GQA) or [B,T,Hkv,hd] (repeat_kv=False —
    the cache-row layout).  The single source the train block and every
    decode-path block (generate.py: cached/prefill/verify) project
    through."""
    B, T, _ = h.shape
    dt = cfg.dtype
    if cfg.attention_in_multiplier != 1.0:
        h = h * jnp.asarray(cfg.attention_in_multiplier, dt)
    if cfg.num_kv_heads is not None:
        return _gqa_qkv(h, p, cfg, repeat_kv=repeat_kv)
    H, hd = cfg.num_heads, cfg.head_dim
    qkv = woq.mm_stacked(h, p, "qkv_w", dt)
    if "qkv_b" in p:
        qkv = qkv + p["qkv_b"].astype(dt)[:, None, None]
    q, k, v = (qkv[i].reshape(B, T, H, hd) for i in range(3))
    if cfg.key_multiplier != 1.0:
        k = k * jnp.asarray(cfg.key_multiplier, dt)
    return q, k, v


def _add_bias(y, p, name: str):
    """``y`` plus the bias leaf ``name``, where the tree has one (a
    bias-free config's tree has none)."""
    return y + p[name].astype(y.dtype) if name in p else y


def _attn_out(attn, p, cfg: GPTConfig):
    """The attention output projection on [.., H * hd] -> [.., D]: THE
    one copy the train block and every decode-path block project
    through."""
    a = _add_bias(woq.mm(attn, p, "proj_w", cfg.dtype), p, "proj_b")
    if cfg.attention_out_multiplier != 1.0:
        a = a * jnp.asarray(cfg.attention_out_multiplier, cfg.dtype)
    return a


def _ffn_body(h, p, cfg: GPTConfig):
    """The FFN matmuls on a normalized input — gelu MLP or SwiGLU
    (down(silu(gate) * up)); the single implementation the train block
    and every decode-path block share."""
    dt = cfg.dtype
    with jax.named_scope("mlp"):
        m_gate, m_out = cfg.mlp_multipliers
        if cfg.activation == "swiglu":
            gate = _add_bias(woq.mm(h, p, "gate_w", dt), p, "gate_b")
            if m_gate != 1.0:
                gate = gate * jnp.asarray(m_gate, dt)
            up = _add_bias(woq.mm(h, p, "fc_w", dt), p, "fc_b")
            h = jax.nn.silu(gate) * up
        else:
            h = jax.nn.gelu(_add_bias(woq.mm(h, p, "fc_w", dt), p, "fc_b"))
        out = _add_bias(woq.mm(h, p, "out_w", dt), p, "out_b")
        if m_out != 1.0:
            out = out * jnp.asarray(m_out, dt)
        return out


def _ffn_dense(x, p, cfg: GPTConfig):
    """Residual dense FFN half of a block: x + MLP(norm(x))."""
    return x + _ffn_body(_norm(x, p, "ln2", cfg), p, cfg)


# sentinel for _ffn_tail's legacy capacity rule (``None`` is a MEANINGFUL
# override there: moe_ffn's capacity-factor bound) — module-level so the
# MoE serving step can request cf-based capacity explicitly
_LEGACY = object()


def _ffn_tail(x, p, cfg: GPTConfig, valid=None, capacity=_LEGACY,
              stats=None):
    """Inference FFN half: dense MLP or MoE (aux loss discarded — it only
    matters for the training objective).  MoE capacity is computed from
    the CALL's token count (GShard semantics): at one token nothing can
    drop; a batched call's rows contend for capacity like training
    tokens.  ``valid`` (prefill path): pad mask over x's token dims —
    pads route nowhere, and capacity becomes the dropless bound so a
    padded prompt chunk routes exactly like its unpadded prefix
    (text/moe._route).

    ``capacity`` (round-19, MoE serving): left at the default sentinel it
    keeps the legacy rule — dropless token-count bound when ``valid`` is
    given, moe_ffn's capacity-factor bound otherwise.  An explicit value
    (``None`` included — the cf-based bound) overrides that rule: the
    expert-parallel decode step passes ``valid=act, capacity=None`` so
    occupied slots contend under the CONFIGURED capacity factor while
    free slots claim nothing.
    ``stats``: a ``{"dropped", "load"}`` int32 accumulator tree — when
    given, the call returns ``(x', stats')`` with the routing delta
    added (dense models pass it through unchanged)."""
    if cfg.moe is None:
        out = _ffn_dense(x, p, cfg)
        return (out, stats) if stats is not None else out
    from .moe import moe_ffn

    h = _norm(x, p, "ln2", cfg)
    if capacity is _LEGACY:
        n_tokens = 1
        for d in x.shape[:-1]:
            n_tokens *= d
        capacity = n_tokens if valid is not None else None
    if stats is None:
        with jax.named_scope("mlp"):
            y, _aux = moe_ffn(p["moe"], h, cfg.moe, key=None, valid=valid,
                              capacity=capacity)
        return x + y
    with jax.named_scope("mlp"):
        y, _aux, delta = moe_ffn(p["moe"], h, cfg.moe, key=None,
                                 valid=valid, capacity=capacity,
                                 with_stats=True)
    stats = {"dropped": stats["dropped"] + delta["dropped"],
             "load": stats["load"] + delta["load"]}
    return x + y, stats


def latent_block(x, p, cfg: GPTConfig, attend, valid=None):
    """The shortcut-connected latent layer on rows ``x`` [T, D] (a
    sequence's positions, or a decode step's slots):

        h1 = x  + MLA_0(norm_0(x))
        m  = norm_1(h1);  S = experts(m);  h2 = h1 + FFN_0(m)
        h3 = h2 + MLA_1(norm_2(h2))
        out = h3 + FFN_1(norm_3(h3)) + S

    The residual stream is float32 where the caller hands it in so (the
    serving paths and ``forward`` do): every sublayer reads a normed copy
    in the compute dtype and adds its output to the unrounded stream.
    ``attend(i, n, p_i)`` is sublayer i's attention on the normed rows
    ``n`` with its weights ``p_i``, out-projected: the full forward, the
    prefill chunk and the decode step differ in nothing else.  ``valid``
    [T]: rows that select experts (see ``moe.route_share``).  Returns
    (out, the expert layer's counts)."""
    from .moe import expert_share

    def norm(h, j):
        return _norm(h, {"ln_g": p["ln_g"][j]}, "ln", cfg)

    h1 = x + attend(0, norm(x, 0), p["attn0"])
    m = norm(h1, 1)
    S, counts = expert_share(m, p["moe"], cfg.experts, cfg.dtype, valid)
    h2 = h1 + _ffn_body(m, p["ffn0"], cfg)
    h3 = h2 + attend(1, norm(h2, 2), p["attn1"])
    return h3 + _ffn_body(norm(h3, 3), p["ffn1"], cfg) + S, counts


def _latent_attend_seqs(n, p, cfg: GPTConfig, B: int, T: int):
    """One latent sublayer over whole sequences: normed rows ``n``
    [B * T, D], each sequence's queries attending its own rows,
    up-projected -> [B * T, D]."""
    from . import mla as _mla

    pos = jnp.arange(T)

    def one(nb):
        q_nope, q_rope, rows = _mla.project(nb, p, cfg, pos)
        with jax.named_scope("attn"):
            return _mla.attend_chunk(q_nope, q_rope, rows, 0, p, cfg)

    attn = jax.vmap(one)(n.reshape(B, T, -1))
    return _mla.out_proj(attn.reshape(B * T, -1), p, cfg)


def _latent_forward_block(x, p, cfg: GPTConfig):
    """:func:`latent_block` over whole sequences [B, T, D]: the rows of
    all sequences go through the layer together (the expert layer
    takes rows, no batch axis); a sublayer's queries attend
    their own sequence's rows, up-projected."""
    B, T, D = x.shape
    out, _ = latent_block(
        x.reshape(B * T, D), p, cfg,
        lambda i, n, p_i: _latent_attend_seqs(n, p_i, cfg, B, T))
    return out.reshape(B, T, D)


def pattern_block(x, p, cfg: GPTConfig, mixer, valid=None):
    """One layer of a stated pattern (``cfg.layer_types``) on rows ``x``
    [T, D] (a sequence's positions, or a decode step's slots):

        h1  = x  + residual_multiplier * mixer(norm_1(x))
        out = h1 + residual_multiplier * experts(norm_2(h1))

    with the dense SwiGLU in place of the experts in one of the
    ``first_k_dense`` leading layers (its weights ``p["dense"]``, read
    under ``mlp`` as every dense FFN is).  ``mixer(n)`` is the layer's ONE
    mixer on the normed rows, the ssm mixer, attention or a latent
    sublayer by the layer's kind: the full forward, the prefill chunk and
    the decode step differ in nothing else.  The residual stream is
    float32 where the caller hands it in so (as :func:`latent_block`'s).
    ``valid`` [T]: rows that select experts.  Returns (out, the expert
    layer's counts, None for a dense layer)."""
    from .moe import expert_share

    def scaled(y):
        y = y.astype(x.dtype)
        if cfg.residual_multiplier != 1.0:
            y = y * jnp.asarray(cfg.residual_multiplier, x.dtype)
        return y

    h1 = x + scaled(mixer(_norm(x, p, "ln1", cfg)))
    if "dense" in p:
        return h1 + scaled(_ffn_body(_norm(h1, p, "ln2", cfg), p["dense"],
                                     cfg)), None
    y, counts = expert_share(_norm(h1, p, "ln2", cfg), p["moe"],
                             cfg.experts, cfg.dtype, valid)
    return h1 + scaled(y), counts


def _pattern_forward_block(x, p, cfg: GPTConfig, kind: str):
    """:func:`pattern_block` over whole sequences [B, T, D]: the rows of
    all sequences go through the layer together; a mamba layer scans each
    sequence from the zero state, an attention layer's queries attend
    their own sequence (no position applied to q or k), a latent layer's
    theirs (rope on the rope lanes, up-projected)."""
    B, T, D = x.shape

    def mixer(n):
        if kind == "mla":
            return _latent_attend_seqs(n, p, cfg, B, T)
        n = n.reshape(B, T, D)
        if kind == "mamba":
            from . import ssm as _ssm

            out, _ = _ssm.mixer_chunk(
                n, p, cfg, _ssm.zero_state(cfg.ssm, B, cfg.dtype))
        else:
            q, k, v = _project_qkv(n, p, cfg)
            attn = attention_array(q, k, v, is_causal=True,
                                   scale=cfg.softmax_scale)
            out = _attn_out(attn.reshape(B, T, cfg.q_size), p, cfg)
        return out.reshape(B * T, D)

    out, _ = pattern_block(x.reshape(B * T, D), p, cfg, mixer)
    return out.reshape(B, T, D)


def _block(x, p, cfg: GPTConfig, dropout_key=None):
    """One transformer block on [B, T, D] activations (compute dtype)."""
    B, T, _ = x.shape
    dt = cfg.dtype
    drop = cfg.dropout > 0.0 and dropout_key is not None
    h = _norm(x, p, "ln1", cfg)
    q, k, v = _project_qkv(h, p, cfg)
    if cfg.pos_embed == "rope":
        pos = jnp.arange(T)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    attn = attention_array(q, k, v, is_causal=True)
    attn = attn.reshape(B, T, cfg.q_size)
    a = _attn_out(attn, p, cfg)
    if cfg.ssm is not None:
        # the parallel mixer reads the same normed input, from the zero
        # state a sequence starts in; its final state is not kept here
        from . import ssm as _ssm

        mix, _ = _ssm.mixer_chunk(h, p, cfg,
                                  _ssm.zero_state(cfg.ssm, B, dt))
        a = a + mix
    if drop:
        a = _dropout(a, cfg.dropout, jax.random.fold_in(dropout_key, 0))
    x = x + a
    h = _norm(x, p, "ln2", cfg)
    if cfg.moe is not None:
        from .moe import moe_ffn

        with jax.named_scope("mlp"):
            h, aux = moe_ffn(p["moe"], h, cfg.moe,
                             key=(jax.random.fold_in(dropout_key, 2)
                                  if dropout_key is not None else None))
    else:
        h = _ffn_body(h, p, cfg)
        aux = jnp.zeros((), jnp.float32)
    if drop:
        h = _dropout(h, cfg.dropout, jax.random.fold_in(dropout_key, 1))
    return x + h, aux


def forward_with_aux(params: dict, tokens, cfg: GPTConfig, act_sharding=None,
                     key=None):
    """tokens [B, T] int32 → (logits [B, T, V], aux-loss scalar).

    aux is the summed MoE load-balancing loss (0 for dense models).
    act_sharding: optional NamedSharding constraint applied to the [B, T, D]
    activations — e.g. P('dp', 'sp', None) for sequence parallelism; XLA
    propagates it through the blocks and inserts the sp collectives.
    key: PRNG key enabling dropout (cfg.dropout > 0); None = eval mode."""
    B, T = tokens.shape
    dt = cfg.dtype
    x = woq.embed(params, tokens, dt, cfg.embedding_multiplier)
    if cfg.pos_embed == "learned":
        x = x + params["wpe"][:T].astype(dt)[None]
    if act_sharding is not None:
        x = jax.lax.with_sharding_constraint(x, act_sharding)

    if cfg.experts is not None:
        if key is not None:
            raise NotImplementedError(
                "an expert share has no training forward (dropout, "
                "router noise): pass key=None")
        from .moe import layer_of

        x = x.astype(jnp.float32)   # the residual stream (latent_block)
        for li in range(cfg.num_layers):  # its experts' leaves are a layer's
            if cfg.layer_types is not None:
                x = _pattern_forward_block(
                    x, pattern_layer(params["blocks"], cfg, li), cfg,
                    cfg.layer_types[li])
            else:
                x = _latent_forward_block(
                    x, layer_of(params["blocks"], li), cfg)
        x = _norm(x, params, "ln_f", cfg)
        return woq.logits(x, params, dt, cfg.lm_head_multiplier), \
            jnp.zeros((), jnp.float32)
    blk = functools.partial(_block, cfg=cfg)
    if cfg.remat:  # see _remat_policy for the policy names
        # prevent_cse=False: inside lax.scan the loop structure already
        # prevents the grad-of-checkpoint CSE hazard (jax.checkpoint's
        # own advice for scanned bodies).  Compiled for a described v5e
        # (8 layers at 1.3B widths, B=2, T=2048) both settings compile in
        # under 10 s and differ by 1% in temp memory, so the choice is
        # not about compile time.  PADDLE_TPU_REMAT_PREVENT_CSE=1
        # restores the default barriers for an A/B.
        _cse = os.environ.get("PADDLE_TPU_REMAT_PREVENT_CSE", "") == "1"
        blk = jax.checkpoint(blk, prevent_cse=_cse,
                             policy=_remat_policy(cfg.remat_policy))

    need_keys = key is not None and (cfg.dropout > 0.0 or cfg.moe is not None)
    if need_keys:
        layer_keys = jax.random.split(key, cfg.num_layers)

        def scan_body(x, pk):
            p, k = pk
            return blk(x, p, dropout_key=k)

        x, aux = jax.lax.scan(scan_body, x, (params["blocks"], layer_keys))
    else:
        def scan_body(x, layer_params):
            return blk(x, layer_params)

        x, aux = jax.lax.scan(scan_body, x, params["blocks"])
    x = _norm(x, params, "ln_f", cfg)
    logits = woq.logits(x, params, dt, cfg.lm_head_multiplier)
    return logits, jnp.sum(aux)


def forward(params: dict, tokens, cfg: GPTConfig, act_sharding=None, key=None):
    """tokens [B, T] int32 → logits [B, T, V] (compute dtype)."""
    return forward_with_aux(params, tokens, cfg, act_sharding, key)[0]


def loss_fn(params: dict, tokens, cfg: GPTConfig, act_sharding=None, key=None):
    """Next-token LM loss; softmax-CE in fp32 (reference
    c_softmax_with_cross_entropy keeps the reduction sharded — here XLA
    handles the sharded softmax under pjit).  MoE models add the router
    load-balancing aux loss."""
    logits, aux = forward_with_aux(params, tokens[:, :-1], cfg,
                                   act_sharding=act_sharding, key=key)
    tgt = tokens[:, 1:]
    with jax.named_scope("loss"):
        if os.environ.get("PADDLE_TPU_FUSED_CE", "") == "1":
            # Pallas blockwise loss head: no [B, T, V] fp32 log-softmax in
            # HBM (ops/fused_ce.py; falls back to the expression below
            # off-TPU).  Opt-in until the on-device parity check has
            # passed on hardware.
            from ..ops.fused_ce import fused_softmax_ce

            return jnp.mean(fused_softmax_ce(logits, tgt)) + aux
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll) + aux


def count_params(cfg: GPTConfig) -> int:
    D, F, L, V, T = (cfg.hidden_size, cfg.ffn_size, cfg.num_layers, cfg.vocab_size,
                     cfg.max_seq_len)
    if cfg.layer_types is not None:
        from . import ssm as _ssm
        from .moe import count_expert_share

        from . import mla as _mla

        outside, expert = count_expert_share(cfg.experts, D)
        attn = 2 * D * cfg.q_size + 2 * D * cfg.kv_heads * cfg.head_dim
        mamba = _ssm.count_params(cfg.ssm, D) if cfg.ssm is not None else 0
        latent = (_mla.count_params(cfg.mla, D, cfg.num_heads)
                  if cfg.mla is not None else 0)
        K = cfg.first_k_dense
        return (L * 2 * D + (L - K) * (outside + cfg.experts.n_held * expert)
                + K * 3 * D * F
                + cfg.layers_of("mamba") * mamba
                + cfg.layers_of("attention") * attn
                + cfg.layers_of("mla") * latent
                + V * D * (1 if cfg.tie_embeddings else 2) + D)
    if cfg.mla is not None:
        from . import mla as _mla
        from .moe import count_expert_share

        router, expert = count_expert_share(cfg.experts, D)
        outside = (cfg.latent_sublayers
                   * _mla.count_params(cfg.mla, D, cfg.num_heads)
                   + 2 * 3 * D * F + 4 * D + router)
        return (L * (outside + cfg.experts.n_held * expert)
                + 2 * V * D + D)
    Dq, Dkv = cfg.q_size, cfg.kv_heads * cfg.head_dim
    b = 1 if cfg.bias else 0          # a projection's bias row, or none
    qkv = (D * Dq + b * Dq + 2 * D * Dkv + b * 2 * Dkv
           if cfg.num_kv_heads is not None else 3 * (D * Dq + b * Dq))
    norms = 4 * D if cfg.norm == "layernorm" else 2 * D  # 2 gains (+2 biases)
    ffn = D * F + b * F + F * D + b * D
    if cfg.activation == "swiglu":
        ffn += D * F + b * F                              # gate matmul
    per_block = norms + qkv + Dq * D + b * D + ffn
    if cfg.ssm is not None:
        from . import ssm as _ssm

        per_block += _ssm.count_params(cfg.ssm, D)
    final_norm = 2 * D if cfg.norm == "layernorm" else D
    pos = T * D if cfg.pos_embed == "learned" else 0
    head = 0 if cfg.tie_embeddings else V * D
    return V * D + head + pos + final_norm + L * per_block


def flops_per_token(cfg: GPTConfig, seq_len: int) -> float:
    """Training FLOPs/token = 6 * (matmul-weight params) + attention term.

    Matmul weights: qkv (3 D^2) + attn proj (D^2) + ffn (2 D F) per block,
    plus the tied-embedding head matmul (V D).  The embedding *lookup* is a
    gather (no MXU flops), so with tied weights V*D is counted exactly once;
    wpe, biases and layernorm params contribute no matmul flops.  Attention
    scores: QK^T + AV = 12 L D T training flops/token (full, non-causal
    accounting — the conservative standard for MFU)."""
    D, F, L, V = cfg.hidden_size, cfg.ffn_size, cfg.num_layers, cfg.vocab_size
    Dq, Dkv = cfg.q_size, cfg.kv_heads * cfg.head_dim
    qkv_w = (D * Dq + 2 * D * Dkv if cfg.num_kv_heads is not None
             else 3 * D * Dq)
    ffn_w = (3 if cfg.activation == "swiglu" else 2) * D * F
    n_matmul = L * (qkv_w + Dq * D + ffn_w) + V * D
    attn = 12 * L * Dq * seq_len
    return 6 * n_matmul + attn
