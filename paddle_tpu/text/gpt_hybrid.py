"""Hybrid-parallel GPT training: dp × pp × mp × sp over one device mesh.

Reference capability: Fleet hybrid orchestration — ``HybridCommunicateGroup``
(fleet/base/topology.py:117) + ``PipelineParallel.train_batch``
(meta_parallel/pipeline_parallel.py:109) + Megatron mp_layers + sharding
(ZeRO) — each a separate Program rewrite in the reference.  TPU-first, they
compose into ONE jitted train step:

* pp == 1 → pure GSPMD: ``pjit`` with Megatron PartitionSpecs on params
  (text/gpt.py ``param_shardings``); XLA inserts all_gather / reduce_scatter
  over 'mp', all_reduce over 'dp', and handles 'sp' (sequence-sharded
  activations) automatically.
* pp > 1 → ``shard_map`` pipeline over the 'pp' ICI axis; stage hops ride
  ``ppermute`` (the send_v2/recv_v2 analog) and tensor parallel inside each
  stage uses the manual-collective Megatron primitives
  (distributed/megatron.py) — including the vocab-sharded softmax CE loss
  (c_softmax_with_cross_entropy analog).  Two schedules, matching the
  reference SectionWorker's schedule_mode (section_worker.cc:130-183):
  "1f1b" (default) interleaves one forward and one backward micro-batch step
  per tick with manual per-stage VJP — activation memory is bounded by the
  in-flight window (min(M, 2S-1) stage inputs), flat in the micro-batch
  count; "fthenb" differentiates the forward scan with autodiff (residuals
  for every tick — simple, memory grows with M).

ZeRO optimizer-state sharding (reference sharding_optimizer.py) composes via
``zero_shard_spec`` on the Adam moment specs.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..ops import _pallas

from ..distributed import megatron as mt
from ..ops.ring_attention import ring_attention, ring_attention_zigzag
from . import engine as _engine
from . import gpt


# ---------------------------------------------------------------------------
# tensor-parallel transformer block (manual collectives; used inside shard_map)
# ---------------------------------------------------------------------------

_dropout = gpt._dropout


def mp_block(x, p, cfg: gpt.GPTConfig, mp_axis: str | None, mp_size: int,
             key=None, sp_axis: str | None = None, sp_zigzag: bool = False,
             ep_axis: str | None = None, ep_size: int = 1):
    """One transformer block on [B, T, D]; weight leaves are LOCAL mp shards.

    qkv/fc are column-parallel (heads and ffn split across mp, no comm);
    proj/out are row-parallel (one psum each) — two all-reduces per block,
    exactly the reference Megatron block's comm pattern.  With ``sp_axis``
    set, T is the LOCAL sequence chunk and attention runs as a ring over
    that axis (ops/ring_attention.py) — context parallelism.  With
    ``cfg.moe`` the ffn becomes expert-parallel over ``ep_axis``
    (moe.moe_ffn_manual: explicit all_to_all dispatch).  Returns
    ``(x, aux)`` — the MoE load-balancing loss (0 for dense)."""
    B, T, D = x.shape
    H = cfg.num_heads // mp_size
    hd = cfg.head_dim
    dt = cfg.dtype
    h = gpt._layer_norm(x.astype(jnp.float32), p["ln1_g"], p["ln1_b"]).astype(dt)
    if cfg.num_kv_heads is not None:
        # GQA under tensor parallel: kv heads shard over mp exactly like
        # q heads (column parallel), each rank holding Hkv/mp shared
        # heads.  On the ring paths (sp) the UNREPEATED Hkv heads ride
        # the ppermute ring — the block einsums fold the query-group dim
        # (ops/ring_attention.py _block_attend) — so each hop ships only
        # the shared heads' bytes; the flash/XLA path still repeats to
        # the standard layout.
        q, k, v = gpt._gqa_qkv(h, p, cfg, repeat_kv=(sp_axis is None),
                               H=H, Hkv=cfg.kv_heads // mp_size)
    else:
        qkv = jnp.einsum("btd,kde->kbte", h, p["qkv_w"].astype(dt)) \
            + p["qkv_b"].astype(dt)[:, None, None]
        q = qkv[0].reshape(B, T, H, hd)
        k = qkv[1].reshape(B, T, H, hd)
        v = qkv[2].reshape(B, T, H, hd)
    if sp_axis is not None and sp_zigzag:
        # zigzag layout: rows are the global chunk pair (rank, 2R-1-rank),
        # balancing causal ring work (ops/ring_attention.py)
        attn = ring_attention_zigzag(
            q, k, v, sp_axis,
            sub_block=cfg.sp_sub_block).reshape(B, T, H * hd)
    elif sp_axis is not None:
        attn = ring_attention(
            q, k, v, sp_axis, causal=True,
            sub_block=cfg.sp_sub_block).reshape(B, T, H * hd)
    else:
        attn = gpt.attention_array(q, k, v, is_causal=True).reshape(B, T, H * hd)
    a = mt.row_parallel_linear(attn, p["proj_w"].astype(dt),
                               p["proj_b"].astype(dt), axis=mp_axis)
    if cfg.dropout > 0.0 and key is not None:
        a = _dropout(a, cfg.dropout, jax.random.fold_in(key, 0))
    x = x + a
    h = gpt._layer_norm(x.astype(jnp.float32), p["ln2_g"], p["ln2_b"]).astype(dt)
    if cfg.moe is not None:
        from .moe import moe_ffn_manual

        h, aux = moe_ffn_manual(
            p["moe"], h, cfg.moe, ep_axis, ep_size, mp_axis=mp_axis,
            key=(jax.random.fold_in(key, 2) if key is not None else None))
    else:
        h = jax.nn.gelu(mt.column_parallel_linear(h, p["fc_w"].astype(dt),
                                                  p["fc_b"].astype(dt)))
        h = mt.row_parallel_linear(h, p["out_w"].astype(dt),
                                   p["out_b"].astype(dt), axis=mp_axis)
        aux = jnp.zeros((), jnp.float32)
    if cfg.dropout > 0.0 and key is not None:
        h = _dropout(h, cfg.dropout, jax.random.fold_in(key, 1))
    return x + h, aux


# ---------------------------------------------------------------------------
# pipeline (shard_map) loss
# ---------------------------------------------------------------------------

class _Parts(NamedTuple):
    """Per-rank pipeline closures + axis constants, shared by the F-then-B
    autodiff path and the interleaved-1F1B manual path."""
    S: int
    mp_size: int
    sp_size: int
    ep_size: int
    mp_ax: Any
    sp_ax: Any
    dp_ax: Any
    ep_ax: Any
    vps: int
    perm_fwd: list
    perm_bwd: list
    dt: Any
    embed: Callable
    stage: Callable
    seq_chunk: Callable
    seq_pos: Callable


def _pipeline_parts(cfg: gpt.GPTConfig, mesh: Mesh, dp_axis, pp_axis, mp_axis,
                    sp_axis, ep_axis="ep", sp_zigzag: bool = False) -> _Parts:
    if (cfg.pos_embed != "learned" or cfg.norm != "layernorm"
            or cfg.activation != "gelu"):
        # the manual-collective blocks below hand-build the GPT
        # architecture; this check sits in the SHARED parts builder so
        # every entry point (build_gpt_train_step, make_pipeline_gpt_loss,
        # make_pipeline_1f1b_grads) refuses loudly instead of dying on a
        # missing wpe/ln bias key deep inside shard_map
        raise NotImplementedError(
            "pos_embed/norm/activation variants (rope/rmsnorm/swiglu) "
            "are implemented on the GSPMD path only; use pp == 1, "
            "sp == 1 (dp/mp/ep shard via GSPMD)")
    S = mesh.shape.get(pp_axis, 1)
    mp_size = mesh.shape.get(mp_axis, 1)
    sp_size = mesh.shape.get(sp_axis, 1)
    ep_size = mesh.shape.get(ep_axis, 1)
    mp_ax = mp_axis if mp_size > 1 else None
    sp_ax = sp_axis if sp_size > 1 else None
    dp_ax = dp_axis if mesh.shape.get(dp_axis, 1) > 1 else None
    ep_ax = ep_axis if ep_size > 1 else None
    vps = cfg.vocab_size // mp_size
    dt = cfg.dtype

    zig = bool(sp_zigzag) and sp_ax is not None

    def embed(params, tok, pos):
        # tok [..., Tl] (local chunk); pos = the chunk's global offset
        # (scalar, contiguous layout) or per-row global position ids
        # ([Tl] array, zigzag layout) — see seq_pos
        x = mt.vocab_parallel_embedding(params["wte"], tok, mp_ax, vps)
        if zig:
            # ids are in-bounds by construction (max T-1 < max_seq_len);
            # clip-mode gather skips jnp.take's negative-index wrap pass
            wpe = jnp.take(params["wpe"], pos, axis=0, mode="clip")
        else:
            wpe = lax.dynamic_slice_in_dim(params["wpe"], pos,
                                           tok.shape[-1])
        return (x + wpe).astype(dt)

    def _rank():
        return lax.axis_index(sp_axis) if sp_ax else 0

    def seq_chunk(mb, Tl, shift=0):
        """This rank's local sequence rows from the replicated [..., T].

        Contiguous layout: rows [rank*Tl, (rank+1)*Tl).  Zigzag layout
        (ops/ring_attention.py): the chunk PAIR (rank, 2R-1-rank) of length
        Tl/2 each — causal ring-attention work is then balanced across the
        sp ring.  ``shift`` selects the target slice (inputs vs labels)."""
        if zig:
            if Tl % 2:
                raise ValueError(
                    f"zigzag needs an even local sequence chunk (Tl={Tl}: "
                    f"T-1 must divide by 2*sp)")
            R, Tc = sp_size, Tl // 2
            lo = lax.dynamic_slice_in_dim(mb, _rank() * Tc + shift, Tc,
                                          axis=-1)
            hi = lax.dynamic_slice_in_dim(
                mb, (2 * R - 1 - _rank()) * Tc + shift, Tc, axis=-1)
            return jnp.concatenate([lo, hi], axis=-1)
        return lax.dynamic_slice_in_dim(mb, _rank() * Tl + shift, Tl,
                                        axis=-1)

    def seq_pos(Tl):
        """This rank's global positions: a scalar chunk offset in the
        contiguous layout (embed slices), per-row ids [Tl] under zigzag
        (embed gathers)."""
        if zig:
            R, Tc = sp_size, Tl // 2
            return jnp.concatenate(
                [_rank() * Tc + jnp.arange(Tc),
                 (2 * R - 1 - _rank()) * Tc + jnp.arange(Tc)])
        return _rank() * Tl

    def stage(blocks, x, key):
        """Run this stage's blocks; returns (x, aux) — the summed MoE
        load-balancing loss of the stage's own layers (0 for dense)."""
        n_local = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        if S > 1:
            # decorrelate dropout across stages: the tick key is stage-shared
            key = jax.random.fold_in(key, lax.axis_index(pp_axis))
        if sp_ax is not None:
            # and across sequence chunks, each masking its own positions
            key = jax.random.fold_in(key, lax.axis_index(sp_ax))
        layer_keys = jax.random.split(key, n_local)
        body = functools.partial(mp_block, cfg=cfg, mp_axis=mp_ax,
                                 mp_size=mp_size, sp_axis=sp_ax,
                                 sp_zigzag=zig,
                                 ep_axis=ep_ax, ep_size=ep_size)
        if cfg.remat:
            # prevent_cse=False: scan supplies the CSE protection (gpt.py)
            body = jax.checkpoint(body, prevent_cse=False)

        def scan_body(x, pk):
            p, k = pk
            x, aux = body(x, p, key=k)
            return x, aux

        x, auxs = lax.scan(scan_body, x, (blocks, layer_keys))
        return x, jnp.sum(auxs)

    return _Parts(S, mp_size, sp_size, ep_size, mp_ax, sp_ax, dp_ax, ep_ax,
                  vps,
                  [(i, (i + 1) % S) for i in range(S)],
                  [(i, (i - 1) % S) for i in range(S)], dt, embed, stage,
                  seq_chunk, seq_pos)


def make_pipeline_gpt_loss(cfg: gpt.GPTConfig, mesh: Mesh, n_micro: int,
                           dp_axis="dp", pp_axis="pp", mp_axis="mp",
                           sp_axis="sp", sp_zigzag: bool = False):
    """Full-mesh SPMD loss fn (runs per-device inside shard_map).

    tokens: LOCAL [B_local, T] int32 (dp-sharded by in_specs; the sequence
    dim stays replicated — each sp rank slices its own chunk so the odd
    T+1 LM shift never has to shard).
    params: LOCAL shards per gpt.param_shardings(mp, pp).
    Composes pp (ppermute schedule) × mp (Megatron) × sp (ring attention).

    F-then-B memory profile: autodiff over the tick scan stores residuals
    for every tick — use :func:`make_pipeline_1f1b_grads` for the
    memory-bounded interleaved schedule.
    """
    parts = _pipeline_parts(cfg, mesh, dp_axis, pp_axis, mp_axis, sp_axis,
                            sp_zigzag=sp_zigzag)
    S, mp_ax, sp_ax, dp_ax = parts.S, parts.mp_ax, parts.sp_ax, parts.dp_ax
    sp_size, vps, dt = parts.sp_size, parts.vps, parts.dt
    perm = parts.perm_fwd
    embed, stage = parts.embed, parts.stage
    seq_chunk, seq_pos = parts.seq_chunk, parts.seq_pos

    def loss_fn(params, tokens, key):
        s = lax.axis_index(pp_axis) if S > 1 else 0
        M = n_micro
        B, T = tokens.shape
        if B % M:
            raise ValueError(
                f"per-dp-shard batch {B} must be divisible by n_micro {M}")
        if (T - 1) % sp_size:
            raise ValueError(
                f"sequence length {T - 1} must divide by sp {sp_size}")
        Tl = (T - 1) // sp_size
        mb = tokens.reshape(M, B // M, T)
        # local sequence chunk of inputs/targets (full tokens stay replicated
        # over sp; the shifted slices are taken per-rank, contiguous or
        # zigzag per parts.seq_chunk)
        tok_in = seq_chunk(mb, Tl, 0)
        tok_tgt = seq_chunk(mb, Tl, 1)
        ticks = M + S - 1
        keys = jax.random.split(key, ticks)
        # all micro-batch embeddings up-front, one batched lookup ([M, b, Tl, D])
        x_emb = embed(params, tok_in, seq_pos(Tl))

        def tick(carry, inp):
            x_recv, aux_acc = carry
            t, k_t = inp
            in_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(
                s == 0, lax.dynamic_index_in_dim(x_emb, in_idx, keepdims=False),
                x_recv)
            y, aux = stage(params["blocks"], x_in, k_t)
            # this stage holds real data only at ticks s..s+M-1; fill/drain
            # ticks' aux is garbage and must not enter the loss
            valid = (t >= s) & (t < s + M)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            x_send = lax.ppermute(y, pp_axis, perm) if S > 1 else y
            return (x_send, aux_acc), y

        (_, aux_sum), ys = lax.scan(
            tick, (jnp.zeros_like(x_emb[0]), jnp.zeros((), jnp.float32)),
            (jnp.arange(ticks), keys))
        # ys[t] is this stage's output at tick t; the last stage's final
        # outputs for micro-batch m sit at tick m + S - 1 → static slice.
        # One batched head over all M micro-batches (vs per-tick heads: the
        # vocab matmul is the biggest in the model — do it once).
        y_fin = ys[S - 1:]  # [M, b, Tl, D]
        x = gpt._layer_norm(y_fin.astype(jnp.float32), params["ln_f_g"],
                            params["ln_f_b"]).astype(dt)
        logits = mt.vocab_parallel_logits(x, params["wte"].astype(dt))
        ce = mt.vocab_parallel_softmax_ce(logits, tok_tgt, mp_ax, vps)
        loss = jnp.where(s == S - 1, jnp.mean(ce.astype(jnp.float32)), 0.0)
        # each stage contributes its own layers' MoE aux (mean per micro-
        # batch); summed over pp with the masked head below
        loss = loss + aux_sum / M
        if S > 1:
            loss = lax.psum(loss, pp_axis)  # only last stage's head is real
        if dp_ax is not None:
            loss = lax.pmean(loss, dp_ax)
        if sp_ax is not None:
            loss = lax.pmean(loss, sp_ax)  # equal chunks → mean of means
        # replicate over any remaining axes for a clean P() output
        for ax in mesh.axis_names:
            if ax not in (dp_axis, pp_axis, mp_axis, sp_axis) \
                    and mesh.shape[ax] > 1:
                loss = lax.pmean(loss, ax)
        return loss

    return loss_fn


# ---------------------------------------------------------------------------
# interleaved 1F1B pipeline with manual per-stage VJP (memory-bounded)
# ---------------------------------------------------------------------------

def _spec_axes(spec) -> set:
    axes = set()
    if spec is None:
        return axes
    for el in spec:
        if el is None:
            continue
        if isinstance(el, tuple):
            axes.update(el)
        else:
            axes.add(el)
    return axes


def make_pipeline_1f1b_grads(cfg: gpt.GPTConfig, mesh: Mesh, n_micro: int,
                             dp_axis="dp", pp_axis="pp", mp_axis="mp",
                             sp_axis="sp", sp_zigzag: bool = False):
    """(params, tokens, key) -> (loss, grads) per-rank fn for shard_map.

    The 1F1B-class schedule (reference SectionWorker schedule_mode=1,
    section_worker.cc:130-183): one scan whose every tick runs ONE forward
    micro-batch step and ONE backward micro-batch step per stage.  Micro-batch
    m runs forward on stage s at tick ``m + s`` and backward at tick
    ``m + 2(S-1) - s`` (the backward wave reflects off the last stage, which
    computes its loss-head VJP in the same tick as its forward).  Activations
    live only as a ring buffer of the last ``min(M, 2S-1)`` stage *inputs* —
    flat in M, unlike autodiff over the F-then-B scan which stores residuals
    for all ``M + S - 1`` ticks.  The backward slot recomputes the stage
    forward from the saved input under ``jax.vjp`` (per-block remat applies
    inside when cfg.remat).

    Gradients are accumulated across ticks and explicitly reduced: psum over
    model axes the leaf is NOT sharded over (pp for shared embeddings — the
    reference's allreduce_shared_weight_gradients, pp_layers.py:188 — and mp
    for replicated norms/biases), pmean over the data axes (dp, sp).
    """
    parts = _pipeline_parts(cfg, mesh, dp_axis, pp_axis, mp_axis, sp_axis,
                            sp_zigzag=sp_zigzag)
    S, mp_ax, sp_ax, dp_ax = parts.S, parts.mp_ax, parts.sp_ax, parts.dp_ax
    sp_size, vps, dt = parts.sp_size, parts.vps, parts.dt
    ep_ax, ep_size = parts.ep_ax, parts.ep_size
    embed, stage = parts.embed, parts.stage
    seq_chunk, seq_pos = parts.seq_chunk, parts.seq_pos
    if S < 2:
        raise ValueError("1F1B schedule needs pp >= 2; use the GSPMD path")

    specs = gpt.param_shardings(cfg, mp=mp_ax, pp=pp_axis, ep=ep_ax)
    # the loss is computed redundantly on every mp (and ep) rank; seeding
    # each replica's VJP with 1/replicas keeps the psum'd grads exact
    replicas = parts.mp_size * max(ep_size, 1)

    def sync_grads(grads):
        """Per-rank cotangents follow the partial-sum convention (psum
        transposes to psum under shard_map, and the loss seed is divided by
        the mp*ep replica count), so every leaf's true grad is the SUM over
        the model axes it is not sharded over — pp for shared embeddings
        (the reference's allreduce_shared_weight_gradients), mp for
        replicated leaves, ep for non-expert leaves — and the MEAN over the
        data axes (dp, sp)."""
        def leaf(g, spec):
            owned = _spec_axes(spec)
            sum_axes = tuple(a for a in (pp_axis, mp_axis, ep_ax)
                             if a is not None
                             and mesh.shape.get(a, 1) > 1 and a not in owned)
            if sum_axes:
                g = lax.psum(g, sum_axes)
            mean_axes = tuple(a for a in (dp_axis, sp_axis)
                              if mesh.shape.get(a, 1) > 1)
            if mean_axes:
                g = lax.pmean(g, mean_axes)
            return g

        return jax.tree_util.tree_map(leaf, grads, specs,
                                      is_leaf=lambda x: _spec_leaf(x))

    def loss_and_grads(params, tokens, key):
        s = lax.axis_index(pp_axis)
        M = n_micro
        B, T = tokens.shape
        if B % M:
            raise ValueError(
                f"per-dp-shard batch {B} must be divisible by n_micro {M}")
        if (T - 1) % sp_size:
            raise ValueError(
                f"sequence length {T - 1} must divide by sp {sp_size}")
        b = B // M
        Tl = (T - 1) // sp_size
        pos = seq_pos(Tl)
        mb = tokens.reshape(M, b, T)
        tok_in = seq_chunk(mb, Tl, 0)
        tok_tgt = seq_chunk(mb, Tl, 1)
        D = cfg.hidden_size

        def fwd_only(p, x_in, tok_mb, k):
            x0 = jnp.where(s == 0, embed(p, tok_mb, pos), x_in)
            y, _aux = stage(p["blocks"], x0, k)
            return y

        def full(p, x_in, tok_mb, tgt_mb, k):
            """stage + (masked) loss head — the unit the backward slot VJPs.
            The head term is where-masked off except on the last stage, so
            its cotangents vanish elsewhere; under SPMD every rank still
            executes it (the cost of a uniform program).  The stage's own
            MoE aux loss joins unmasked — every stage owns its layers'
            router gradients."""
            x0 = jnp.where(s == 0, embed(p, tok_mb, pos), x_in)
            y, aux = stage(p["blocks"], x0, k)
            x = gpt._layer_norm(y.astype(jnp.float32), p["ln_f_g"],
                                p["ln_f_b"]).astype(dt)
            logits = mt.vocab_parallel_logits(x, p["wte"].astype(dt))
            ce = mt.vocab_parallel_softmax_ce(logits, tgt_mb, mp_ax, vps)
            loss_mb = jnp.where(s == S - 1,
                                jnp.mean(ce.astype(jnp.float32)), 0.0)
            return y, loss_mb + aux

        BUF = min(M, 2 * S - 1)
        ticks = M + 2 * (S - 1)
        zeros_x = jnp.zeros((b, Tl, D), dt)
        init = (zeros_x, zeros_x, jnp.zeros((BUF, b, Tl, D), dt),
                jax.tree_util.tree_map(jnp.zeros_like, params),
                jnp.zeros((), jnp.float32))

        def tick(carry, t):
            x_fwd, dx_bwd, buf, grads, loss_sum = carry

            # ---- forward slot: micro-batch t - s
            f_m = t - s
            f_valid = (f_m >= 0) & (f_m < M)
            f_idx = jnp.clip(f_m, 0, M - 1)
            tok_f = lax.dynamic_index_in_dim(tok_in, f_idx, keepdims=False)
            y_f = fwd_only(params, x_fwd, tok_f,
                           jax.random.fold_in(key, f_idx))
            # save the stage INPUT for the backward recompute; guard so the
            # drain phase can't clobber a slot whose backward hasn't run
            buf = jnp.where(
                f_valid,
                lax.dynamic_update_index_in_dim(buf, x_fwd, f_idx % BUF, 0),
                buf)
            x_fwd_next = lax.ppermute(y_f, pp_axis, parts.perm_fwd)

            # ---- backward slot: micro-batch t - 2(S-1) + s
            b_m = t - 2 * (S - 1) + s
            b_valid = (b_m >= 0) & (b_m < M)
            b_idx = jnp.clip(b_m, 0, M - 1)
            x_saved = lax.dynamic_index_in_dim(buf, b_idx % BUF,
                                               keepdims=False)
            tok_b = lax.dynamic_index_in_dim(tok_in, b_idx, keepdims=False)
            tgt_b = lax.dynamic_index_in_dim(tok_tgt, b_idx, keepdims=False)
            k_b = jax.random.fold_in(key, b_idx)
            (_, loss_mb), vjp_fn = jax.vjp(
                lambda p, x: full(p, x, tok_b, tgt_b, k_b), params, x_saved)
            # seed: last stage's dy comes from its own head (inside `full`);
            # other stages receive dL/dy from stage s+1's backward slot.
            # The loss seed is split 1/mp_size per rank because cotangents
            # follow the partial-sum convention (psum transposes to psum):
            # every replicated value's true cotangent is the psum of the
            # per-rank pieces, which sync_grads applies at the end.
            valid = b_valid.astype(jnp.float32)
            dy = jnp.where(s == S - 1, jnp.zeros_like(dx_bwd), dx_bwd)
            dy = dy * valid.astype(dt)
            dparams, dx = vjp_fn((dy, valid / (M * replicas)))
            grads = jax.tree_util.tree_map(jnp.add, grads, dparams)
            loss_sum = loss_sum + valid * loss_mb
            dx_next = lax.ppermute(dx, pp_axis, parts.perm_bwd)
            return (x_fwd_next, dx_next, buf, grads, loss_sum), None

        (_, _, _, grads, loss_sum), _ = lax.scan(tick, init,
                                                 jnp.arange(ticks))
        # every stage accumulated: the CE head on the last stage plus each
        # stage's own MoE aux — the psum gathers all of it
        loss = lax.psum(loss_sum, pp_axis) / M
        if dp_ax is not None:
            loss = lax.pmean(loss, dp_ax)
        if sp_ax is not None:
            loss = lax.pmean(loss, sp_ax)
        for ax in mesh.axis_names:
            if ax not in (dp_axis, pp_axis, mp_axis, sp_axis) \
                    and mesh.shape[ax] > 1:
                loss = lax.pmean(loss, ax)
        return loss, sync_grads(grads)

    return loss_and_grads


# ---------------------------------------------------------------------------
# train-step builder
# ---------------------------------------------------------------------------

class GPTTrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: Any


def _spec_leaf(x):
    return isinstance(x, P) or x is None


def build_gpt_train_step(cfg: gpt.GPTConfig, mesh: Mesh, optimizer,
                         n_micro: int = 1, zero: bool | int = False,
                         donate: bool = True, schedule: str = "1f1b",
                         accum: int = 1, sp_zigzag: bool = False):
    """Compile one hybrid-parallel GPT train step over ``mesh``.

    ``schedule`` selects the pipeline schedule when pp > 1: "1f1b"
    (interleaved fwd/bwd, activation memory bounded by the in-flight window
    — reference section_worker.cc schedule_mode 1) or "fthenb" (autodiff
    over the forward scan; residuals for every tick — schedule_mode 0).

    ``accum`` > 1 splits the batch into ``accum`` sequential micro-batches
    with bf16 gradient accumulation (the reference GradientMerge strategy):
    activation memory scales with B/accum at ZERO recompute cost — on a
    single 16 GB chip this is what fits GPT-1.3B without remat.  GSPMD
    path only.

    ``zero`` is the ZeRO stage (reference sharding_optimizer.py stages):
    False/0 = off, True/1 = optimizer state sharded, 2 = + gradients
    (reduce-scatter), 3 = + parameters stored sharded (GSPMD FSDP — XLA
    all-gathers at use).  Stages 2/3 compose with the pure-GSPMD path
    (pp == 1, sp == 1) only.

    Returns (init_fn, step_fn, meta):
      init_fn(seed) -> GPTTrainState  (params/opt-state placed per sharding)
      step_fn(state, tokens, key, lr) -> (state, loss)   [jitted, donating]
      meta: dict of axis sizes + shardings (tok_sharding, param_shardings)
    """
    if schedule not in ("1f1b", "fthenb"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    zero_stage = int(zero)
    axes = dict(mesh.shape)
    pp = axes.get("pp", 1)
    mp = axes.get("mp", 1)
    dp = axes.get("dp", 1)
    sp = axes.get("sp", 1)
    ep = axes.get("ep", 1)
    if cfg.ssm is not None:
        raise NotImplementedError(
            "training a config with an ssm mixer is not supported yet: the "
            "chunked scan's backward has no reference to be held to, and "
            "the mixer no sharded layout (serve it: DecodeServer("
            "layout='paged'))")
    if (pp > 1 or sp > 1) and (cfg.pos_embed != "learned"
                               or cfg.norm != "layernorm"
                               or cfg.activation != "gelu"
                               or not cfg.bias or not cfg.tie_embeddings
                               or cfg.q_size != cfg.hidden_size):
        # early twin of _pipeline_parts' shared guard (which also covers
        # the public make_pipeline_* entry points): refuse before any
        # sharding work rather than silently training a DIFFERENT
        # architecture than the config asks for
        raise NotImplementedError(
            "pos_embed/norm/activation variants (rope/rmsnorm/swiglu) "
            "are implemented on the GSPMD path only; use pp == 1, "
            "sp == 1 (dp/mp/ep shard via GSPMD)")
    if cfg.num_layers % max(pp, 1):
        raise ValueError(f"num_layers {cfg.num_layers} must divide by pp {pp}")
    if cfg.num_heads % max(mp, 1) or cfg.vocab_size % max(mp, 1):
        raise ValueError("num_heads and vocab_size must divide by mp")
    if cfg.moe is not None:
        if cfg.moe.num_experts % max(ep, 1):
            raise ValueError("num_experts must divide by ep")
    if (cfg.num_kv_heads is not None and (pp > 1 or sp > 1)
            and cfg.kv_heads % max(mp, 1)):
        # only the manual-collective path slices kv heads per mp rank;
        # pure GSPMD (pp==1, sp==1) lets XLA lay out any Hkv vs mp
        raise ValueError(
            f"num_kv_heads {cfg.kv_heads} must divide by mp {mp} on the "
            f"pipeline/ring path (kv heads shard over tensor parallel "
            f"like q heads)")

    mp_ax = "mp" if mp > 1 else None
    pp_ax = "pp" if pp > 1 else None
    ep_ax = "ep" if ep > 1 else None
    specs = gpt.param_shardings(cfg, mp=mp_ax, pp=pp_ax, ep=ep_ax)

    # optimizer state: inherit param specs; ZeRO adds dp/sharding axis
    from ..distributed.fleet.base import zero_shard_spec

    zero_axis = "sharding" if axes.get("sharding", 1) > 1 else "dp"
    p_abstract = jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                                jax.ShapeDtypeStruct((2,), jnp.uint32))

    if zero_stage >= 2 and (pp > 1 or sp > 1):
        raise NotImplementedError(
            "ZeRO stage >= 2 composes with the pure-GSPMD path (pp == 1, "
            "sp == 1) only; the manual-collective pipeline computes its own "
            "grad reduction")

    def zero_spec_for(s, leaf):
        s = s if s is not None else P()
        return zero_shard_spec(s, leaf.shape, zero_axis, mesh) or s

    if zero_stage >= 3:
        # params themselves stored sharded over the data axis (FSDP)
        specs = jax.tree_util.tree_map(zero_spec_for, specs, p_abstract,
                                       is_leaf=_spec_leaf)
    p_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        specs, is_leaf=_spec_leaf)

    tok_spec = P("dp") if dp > 1 else P()
    value_and_grad_fn = None
    if pp > 1 and schedule == "1f1b":
        # interleaved 1F1B with manual per-stage VJP (memory-bounded)
        vg_raw = make_pipeline_1f1b_grads(cfg, mesh, n_micro,
                                          sp_zigzag=sp_zigzag)
        value_and_grad_fn = shard_map(
            vg_raw, mesh=mesh, in_specs=(specs, tok_spec, P()),
            out_specs=(P(), specs), check_vma=False)
        loss_fn = None
    elif pp > 1 or sp > 1:
        # manual-collective path: pipeline schedule and/or ring attention
        loss_raw = make_pipeline_gpt_loss(cfg, mesh, n_micro,
                                          sp_zigzag=sp_zigzag)
        loss_fn = shard_map(loss_raw, mesh=mesh,
                            in_specs=(specs, tok_spec, P()), out_specs=P(),
                            check_vma=False)
    else:
        # pure GSPMD: XLA inserts dp/mp collectives from the PartitionSpecs;
        # the Pallas kernels, which it cannot partition, run per shard
        def loss_fn(params, tokens, key):
            with _pallas.partitioned(mesh, batch="dp" if dp > 1 else None,
                                     heads=mp_ax):
                return gpt.loss_fn(params, tokens, cfg, key=key)

    tok_sharding = NamedSharding(mesh, tok_spec)

    def leaf_spec(s, shape):
        s = s if s is not None else P()
        if len(s) > len(shape):
            # reduced-rank optimizer state (Adafactor's factored R/C
            # vectors) can't inherit the full param spec; the vectors
            # are a param's size divided by a matrix dim — replicate
            return P()
        if zero_stage:
            return zero_shard_spec(s, shape, zero_axis, mesh) or s
        return s

    opt_abstract = jax.eval_shape(optimizer.init_state, p_abstract)
    # opt-state tree: same structure as params but leaves are tuples of arrays.
    # Broadcast each param's spec onto its tuple of state arrays.
    opt_specs = jax.tree_util.tree_map(
        lambda s, st: jax.tree_util.tree_map(
            lambda leaf: leaf_spec(s, leaf.shape), st,
            is_leaf=lambda x: hasattr(x, "shape")),
        specs, opt_abstract, is_leaf=_spec_leaf)
    opt_shard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=_spec_leaf)

    def init_fn(seed: int = 0) -> GPTTrainState:
        # cache=False: out_shardings close over THIS mesh — sharing by
        # config value would hand another mesh's placement back
        key = jax.random.PRNGKey(seed)
        params = _engine.ENGINE.jit(
            "hybrid.init_params", None,
            functools.partial(gpt.init_params, cfg), cache=False,
            out_shardings=p_shard)(key)
        opt_state = _engine.ENGINE.jit(
            "hybrid.init_opt_state", None, optimizer.init_state,
            cache=False, out_shardings=opt_shard)(params)
        return GPTTrainState(params, opt_state, jnp.zeros((), jnp.int32))

    # ZeRO-2: gradients reduce-scattered over the zero axis; the optimizer
    # update runs shard-local and XLA gathers updated params back to their
    # stored sharding (a no-op gather under stage 3, where params stay
    # sharded).
    grad_shardings = None
    if zero_stage >= 2:
        grad_shardings = jax.tree_util.tree_map(
            lambda s, leaf: NamedSharding(mesh, zero_spec_for(s, leaf)),
            gpt.param_shardings(cfg, mp=mp_ax, pp=pp_ax, ep=ep_ax),
            p_abstract, is_leaf=_spec_leaf)

    if accum > 1 and (value_and_grad_fn is not None or loss_fn is None
                      or pp > 1 or sp > 1):
        raise ValueError("accum composes with the pure-GSPMD path only "
                         "(pp == 1, sp == 1); the pipeline already "
                         "micro-batches via n_micro")

    def step_fn(state: GPTTrainState, tokens, key, lr):
        if value_and_grad_fn is not None:
            loss, grads = value_and_grad_fn(state.params, tokens, key)
        elif accum > 1:
            B = tokens.shape[0]
            if B % accum:
                raise ValueError(
                    f"batch size {B} must divide by accum {accum}")
            micro = tokens.reshape((accum, B // accum) + tokens.shape[1:])
            keys = jax.random.split(key, accum)
            inv = jnp.float32(1.0 / accum)

            def body(carry, xs):
                t, k = xs
                l, g = jax.value_and_grad(loss_fn)(state.params, t, k)
                cl, cg = carry
                with jax.named_scope("grad_accum"):
                    cg = jax.tree_util.tree_map(
                        lambda a, b: a + (b * inv).astype(a.dtype), cg, g)
                return (cl + l * inv, cg), None

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.bfloat16), state.params)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_g), (micro, keys))
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens,
                                                      key)
        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        with jax.named_scope("optimizer"):
            new_p, new_o = optimizer.apply_gradients(
                grads, state.params, state.opt_state, lr=lr,
                step=state.step + 1)
        return GPTTrainState(new_p, new_o, state.step + 1), loss

    repl = NamedSharding(mesh, P())
    state_shardings = GPTTrainState(p_shard, opt_shard, repl)
    compiled = _engine.ENGINE.jit(
        "hybrid.train_step", None, step_fn, cache=False,
        in_shardings=(state_shardings, tok_sharding, repl, repl),
        out_shardings=(state_shardings, repl),
        donate_argnums=(0,) if donate else (),
    )

    meta = dict(dp=dp, pp=pp, mp=mp, sp=sp, n_micro=n_micro,
                tok_sharding=tok_sharding, param_shardings=p_shard)
    return init_fn, compiled, meta
