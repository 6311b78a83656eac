"""Pipeline parallelism.

Reference: static PipelineOptimizer + SectionWorker 1F1B schedule
(framework/section_worker.cc:130-183: startup fwds, steady-state 1F1B, drain,
micro-batch scopes) and dygraph PipelineParallel.train_batch
(meta_parallel/pipeline_parallel.py:109, p2p send/recv of activations).

TPU-first: the schedule is DATA — a ``lax.scan`` over M + S - 1 ticks inside
``shard_map`` over the 'pp' mesh axis.  Stage s's input each tick arrives by
``ppermute`` from stage s-1 (an ICI neighbour hop, the send_v2/recv_v2
analog).  Because the whole pipeline is one differentiable program, jax.grad
produces the interleaved backward automatically — activation stashing is
XLA's liveness problem, optionally reduced with jax.checkpoint per stage
(the reference's recompute+pipeline combination).

The model contract is the stacked-block layout of text.gpt: params['blocks']
leaves carry a leading layer axis sharded P('pp'), so each stage physically
holds L/S layers.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def make_pipeline_loss(embed_fn, stage_fn, head_loss_fn, n_micro: int, pp_size: int,
                       pp_axis: str = "pp", remat_stage: bool = True):
    """Loss for one shard_map instance with STATIC pipeline size pp_size."""

    S = pp_size
    perm = [(i, (i + 1) % S) for i in range(S)]

    def loss_fn(params, tokens, key):
        s = jax.lax.axis_index(pp_axis)
        M = n_micro
        B, T = tokens.shape
        mb = tokens.reshape(M, B // M, T)

        stage = stage_fn
        if remat_stage:
            stage = jax.checkpoint(stage_fn)

        ticks = M + S - 1
        keys = jax.random.split(key, ticks)
        x0_probe = embed_fn(params, mb[0])

        def tick(carry, inp):
            x_recv, loss_acc = carry
            t, k_t = inp
            in_idx = jnp.clip(t, 0, M - 1)
            tok_in = jax.lax.dynamic_index_in_dim(mb, in_idx, keepdims=False)
            x_in = jnp.where((s == 0), embed_fn(params, tok_in), x_recv)

            y = stage(params["blocks"], x_in, k_t)

            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            tok_out = jax.lax.dynamic_index_in_dim(mb, out_idx, keepdims=False)
            active_out = (s == S - 1) & (t >= S - 1)
            l = head_loss_fn(params, y, tok_out)
            loss_acc = loss_acc + jnp.where(active_out, l, 0.0)

            x_send = jax.lax.ppermute(y, pp_axis, perm)
            return (x_send, loss_acc), None

        init = (jnp.zeros_like(x0_probe), jnp.asarray(0.0, jnp.float32))
        (x_last, loss_sum), _ = jax.lax.scan(
            tick, init, (jnp.arange(ticks), keys))
        # only the last stage accumulated loss; make it visible everywhere
        loss = jax.lax.psum(loss_sum, pp_axis) / n_micro
        return loss

    return loss_fn


def build_pipeline_train_step(mesh: Mesh, embed_fn, stage_fn, head_loss_fn,
                              param_specs, optimizer, n_micro: int,
                              dp_axis="dp", pp_axis="pp", remat_stage=True):
    """pjit-compiled full train step with pp (+optional dp/mp) sharding.

    Returns step(params, opt_state, tokens, key, lr, step) -> (params, opt, loss).
    Gradients of pp-replicated params (embeddings) are psum'd across 'pp' by
    shard_map's AD transpose automatically; dp grads by the outer pmean.
    """
    S = mesh.shape[pp_axis]
    loss_inner = make_pipeline_loss(embed_fn, stage_fn, head_loss_fn, n_micro, S,
                                    pp_axis, remat_stage)

    tok_spec = P(dp_axis) if dp_axis in mesh.shape else P()

    def spmd_loss(params, tokens, key):
        l = loss_inner(params, tokens, key)
        if dp_axis in mesh.shape:
            l = jax.lax.pmean(l, dp_axis)
        # replicate across remaining axes for a fully-replicated scalar
        for ax in mesh.axis_names:
            if ax not in (dp_axis, pp_axis):
                l = jax.lax.pmean(l, ax)
        return l

    sharded_loss = shard_map(
        spmd_loss, mesh=mesh,
        in_specs=(param_specs, tok_spec, P()),
        out_specs=P(),
        check_vma=False,
    )

    def step_fn(params, opt_state, tokens, key, lr, step):
        loss, grads = jax.value_and_grad(sharded_loss)(params, tokens, key)
        new_params, new_opt = optimizer.apply_gradients(grads, params, opt_state,
                                                        lr=lr, step=step + 1)
        return new_params, new_opt, loss

    return jax.jit(step_fn, donate_argnums=(0, 1))
