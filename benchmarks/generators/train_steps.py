"""Training cell: whole steps of the family's train step
(``families/<family>.train_step``) dispatched back to back until
``--seconds`` have passed, the loss fetched every few steps as a trainer's
log would.

The rate is taken over all the work and all the time of the window: steps
x sequences x seq_len / elapsed to a final ``block_until_ready``.  With
``--trace 1`` the last ``trace_steps`` steps of the window run under
``jax.profiler``."""
from __future__ import annotations

import os
import time

import numpy as np

from .. import common, trace
from ..common import log
from ..traffic_gen import token_stream


def run(ctx: dict) -> None:
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework import platform

    cell, args = ctx["cell"], ctx["args"]
    config, mix = cell["config"], cell["traffic"]
    devs = ctx["devices"]
    log(f"[setup] jax {jax.__version__}, compile cache at "
        f"{platform.init_compile_cache()}")
    fam = common.family(config)
    s = fam.sizes(config)
    seq = int(mix["seq_len"])
    seed = common.jax_seed(args.seed)

    # ---- set-up: state from the seed on the device, warm-up steps --------
    init_fn, step_fn, tail, batch = fam.train_step(config, devs, seed)
    t0 = time.perf_counter()
    state = init_fn(seed)
    jax.block_until_ready(state)
    t_state = time.perf_counter() - t0
    rng = np.random.default_rng(args.seed)

    def next_batch():
        return token_stream(rng, batch, seq, s["V"])

    first_batch = next_batch()
    losses = []
    t0 = time.perf_counter()
    toks = jnp.asarray(first_batch)
    for _ in range(int(mix["warmup_steps"])):
        state, loss = step_fn(state, toks, *tail)
        losses.append(float(loss))
        toks = jnp.asarray(next_batch())
    t_warm = time.perf_counter() - t0
    log(f"[setup] state from the seed {t_state:.2f}s; "
        f"{mix['warmup_steps']} warm-up steps (compile or cache load, then "
        f"run) {t_warm:.2f}s; batch {batch} x {seq} tokens")

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
            state, loss = step_fn(state, toks, *tail)
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
    ref = fam.reference_loss(config, seed, first_batch)
    nonfinite = int((~np.isfinite(losses)).sum())
    log(f"[correct] first step's loss {losses[0]:.5f} against the "
        f"{config['family']} family's reference {ref:.5f} on the same batch "
        f"(tolerance {tol}, bf16 compute against float32) in "
        f"{time.perf_counter() - t0:.1f}s; losses not finite: {nonfinite} "
        f"of {len(losses)}; last less first: {losses[-1] - losses[0]:.5f}")
    compared = {"first_loss_gap": (abs(losses[0] - ref), tol),
                "losses_not_finite": (nonfinite, 0),
                "last_loss_less_first": (losses[-1] - losses[0], 0.0)}

    common.emit(ctx, {"setup_s": setup_s, "train_tok_s": tok_s,
                      "compiles_in_window": compiles},
                compared, steps, 1 if nonfinite else 0, device,
                {"trace": reduced, "sizes": s, "seq_len": seq})
