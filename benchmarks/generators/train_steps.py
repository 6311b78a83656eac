"""Training cell: whole steps of ``build_gpt_train_step`` dispatched back
to back until ``--seconds`` have passed, the loss fetched every few steps
as a trainer's log would.

The rate is taken over all the work and all the time of the window: steps
x sequences x seq_len / elapsed to a final ``block_until_ready``.  With
``--trace 1`` the last ``trace_steps`` steps of the window run under
``jax.profiler``."""
from __future__ import annotations

import os
import time

import numpy as np

from .. import common, model, reference_gpt, trace
from ..common import log


def run(ctx: dict) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu import optimizer
    from paddle_tpu.framework import platform
    from paddle_tpu.text import gpt, gpt_hybrid

    cell, args = ctx["cell"], ctx["args"]
    config, mix = cell["config"], cell["traffic"]
    devs = ctx["devices"]
    log(f"[setup] jax {jax.__version__}, compile cache at "
        f"{platform.init_compile_cache()}")
    s = model.sizes(config)
    ep = config["entry_point"]
    seq = int(mix["seq_len"])
    accum = int(ep["args"]["accum"])
    batch = accum * int(ep["micro_batch"])
    lr = jnp.float32(config["assumed"]["learning_rate"])
    seed = common.jax_seed(args.seed)

    # ---- set-up: state from the seed on the device, warm-up steps --------
    cfg = model.gpt_config(config)
    axes = ep["mesh"]                      # {"dp": 1}; a product of chips
    mesh = Mesh(np.array(devs).reshape(tuple(axes.values())), tuple(axes))
    opt = getattr(optimizer, ep["optimizer"])(
        learning_rate=float(config["assumed"]["learning_rate"]))
    init_fn, step_fn, _ = gpt_hybrid.build_gpt_train_step(
        cfg, mesh, opt, **ep["args"])
    t0 = time.perf_counter()
    state = init_fn(seed)
    jax.block_until_ready(state)
    t_state = time.perf_counter() - t0
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(seed)

    def next_batch():
        return model.token_stream(rng, batch, seq, s["V"])

    first_batch = next_batch()
    losses = []
    t0 = time.perf_counter()
    toks = jnp.asarray(first_batch)
    for _ in range(int(mix["warmup_steps"])):
        state, loss = step_fn(state, toks, key, lr)
        losses.append(float(loss))
        toks = jnp.asarray(next_batch())
    t_warm = time.perf_counter() - t0
    log(f"[setup] state from the seed {t_state:.2f}s; "
        f"{mix['warmup_steps']} warm-up steps (compile or cache load, then "
        f"run) {t_warm:.2f}s; batch {batch} x {seq} tokens, accum {accum}")

    # ---- the window -------------------------------------------------------
    n0 = common.compile_count() + common.jit_entries(step_fn)
    every = int(mix["loss_fetch_every"])
    k_trace = int(mix["trace_steps"]) if args.trace else 0
    seconds = float(args.seconds)
    pending, steps, step_s = [], 0, None
    t_win0 = time.perf_counter()
    setup_s = t_win0 - ctx["t_process_start"]

    def one_step():
        nonlocal state, toks, pending, steps, step_s, losses
        with trace.annotate("bench.dispatch"):
            state, loss = step_fn(state, toks, key, lr)
        pending.append(loss)
        steps += 1
        with trace.annotate("bench.next_batch"):
            toks = jnp.asarray(next_batch())
        if steps % every == 0 or step_s is None:
            with trace.annotate("bench.loss_fetch"):
                losses += [float(x) for x in pending]
            pending = []
            step_s = (time.perf_counter() - t_win0) / steps

    # a traced run ends its window with k_trace steps under the profiler
    while time.perf_counter() - t_win0 < seconds - k_trace * (step_s or 0):
        one_step()
    slice_ = None
    if k_trace:
        jax.block_until_ready(state)
        slice_ = trace.Slice(os.path.join(ctx["scratch"], "trace"))
        slice_.start()
        for _ in range(k_trace):
            one_step()
    with trace.annotate("bench.final_wait"):
        jax.block_until_ready(state)
    t_win1 = time.perf_counter()
    reduced = (trace.reduce(trace.extract(slice_.stop()))
               if slice_ is not None else None)
    losses += [float(x) for x in pending]
    compiles = common.compile_count() + common.jit_entries(step_fn) - n0
    window = t_win1 - t_win0
    tok_s = steps * batch * seq / window / len(devs)
    device = common.device_report(devs)
    log(f"[window] {window:.2f}s; {steps} whole steps of {batch * seq} "
        f"tokens; {window / steps * 1e3:.1f} ms a step; {tok_s:.1f} "
        f"tokens/s per chip; executables compiled in the window: "
        f"{compiles}")
    log(f"[window] losses: first {losses[0]:.4f}, "
        f"{[round(x, 3) for x in losses[1:6]]} ... last {losses[-1]:.4f}")
    log(f"[setup] setup_s {setup_s:.2f}, of which state {t_state:.2f}, "
        f"warm-up steps {t_warm:.2f}")

    # ---- correctness, outside every timing -------------------------------
    del state
    tol = config["correctness"]["first_loss_tol"]
    t0 = time.perf_counter()
    params = jax.jit(lambda k: gpt.init_params(cfg, k))(
        jax.random.PRNGKey(seed))
    ref = reference_gpt.loss(
        params, first_batch, n_head=s["H"],
        eps=config["model"]["layer_norm_epsilon"], gelu="tanh")
    finite = bool(np.isfinite(losses).all())
    correct = (finite and abs(losses[0] - ref) <= tol
               and losses[-1] < losses[0])
    log(f"[correct] first step's loss {losses[0]:.5f} against "
        f"reference_gpt {ref:.5f} on the same batch (tolerance {tol}, "
        f"bf16 compute against float32) in {time.perf_counter() - t0:.1f}s; "
        f"all {len(losses)} losses finite: {finite}; last below first: "
        f"{losses[-1] < losses[0]}")

    common.emit(ctx, {"setup_s": setup_s, "train_tok_s": tok_s,
                      "compiles_in_window": compiles},
                correct, steps, 0 if finite else 1, device,
                {"trace": reduced, "sizes": s, "seq_len": seq})
