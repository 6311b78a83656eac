#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process drives the two main entry points at the widths of
``gpt.gpt_1p3b()`` (hidden 2048, 16 heads of 128, vocab 50304, seq 2048),
with random weights from ``--seed``:

  device   a TPU, or exit non-zero (``--rehearse`` asks for the CPU instead)
  kernels  every main-path Pallas kernel, compiled, against its XLA oracle
  train    ``build_gpt_train_step`` on a one-device mesh: loss falls, the
           compiled step holds the flash / fused-LN / fused-CE custom calls
  serve    ``DecodeServer`` (slab and paged KV) over bf16 weights at full
           depth: served tokens hold up against ``gpt.forward``, the compiled
           step holds the decode-attention custom call, nothing compiles
           after ``warmup()``

``--chips 4`` runs instead, and only, what exists across chips: the train
step on a ``{'dp': 2, 'mp': 2}`` mesh against the one-chip step, and
``DecodeServer(mesh=)`` on an ``('mp',)`` mesh against a one-chip server.

Every phase asserts; a failure is an exception and a non-zero exit.  The
last line of stdout is ``{"ok": true, "device": {...}}`` with the device as
jax reports it.  Times and memory are printed as information: they are not
benchmark results.

``--rehearse`` runs the same phases on the CPU at a tiny size with the
kernels in interpret mode (with ``--chips 4``: on four virtual CPU
devices).  It is what tests/test_chip_smoke.py runs; it proves the control
flow, never the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np


def log(msg):
    print(msg, flush=True)


# --------------------------------------------------------------------------
# sizes
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run uses.  On the chip the widths are gpt_1p3b's; depth and
    batch of the train step are cut to what 16 GB hold next to fp32 AdamW
    state (compiled for a described v5e: 8 layers at B=1 take 12.1 GB,
    24 layers do not fit at any batch)."""
    hidden: int
    heads: int
    vocab: int
    seq: int
    train_layers: int
    train_batch: int
    train_steps: int
    train_lr: float
    serve_layers: int
    serve_batch: int
    prompt_lens: tuple
    new_tokens: int
    kernel_rows: int        # rows of the LN / CE / flash checks
    kernel_cache: int       # KV rows of the decode checks

    def cfg(self, layers):
        from paddle_tpu.text import gpt

        return dataclasses.replace(
            gpt.gpt_1p3b(), vocab_size=self.vocab, hidden_size=self.hidden,
            num_heads=self.heads, num_layers=layers, max_seq_len=self.seq)


CHIP = Sizes(hidden=2048, heads=16, vocab=50304, seq=2048,
             train_layers=8, train_batch=1, train_steps=8, train_lr=1e-4,
             serve_layers=24, serve_batch=8,
             prompt_lens=(5, 37, 300, 1100, 5, 37, 300, 64), new_tokens=12,
             kernel_rows=2048, kernel_cache=2048)
# four chips: the one-chip twin of the dp x mp step must fit one chip at
# the mesh's global batch of 2.  The stream uses 13 of 50304 tokens, so
# AdamW's first steps swing the loss wildly (one chip, lr 1e-4: 10.9, 0.6,
# 29.5, ...); a small rate keeps the two trajectories comparable.
CHIP4 = dataclasses.replace(CHIP, train_layers=4, train_batch=2,
                            train_steps=3, train_lr=1e-5,
                            prompt_lens=(5, 37, 300, 1100))
REHEARSAL = Sizes(hidden=256, heads=2, vocab=512, seq=256,
                  train_layers=2, train_batch=2, train_steps=6,
                  train_lr=1e-3, serve_layers=2, serve_batch=4,
                  prompt_lens=(5, 9, 40, 140, 5, 9, 40, 17), new_tokens=6,
                  kernel_rows=256, kernel_cache=256)
# four heads, so that an ('mp',) mesh of four splits them
REHEARSAL4 = dataclasses.replace(REHEARSAL, hidden=512, heads=4,
                                 train_steps=3, prompt_lens=(5, 9, 40, 140))


# --------------------------------------------------------------------------
# phase 1: device
# --------------------------------------------------------------------------


def phase_device(rehearse: bool, chips: int):
    import jax
    import jaxlib

    from paddle_tpu.framework import platform

    devs = jax.devices()
    d = devs[0]
    want = "cpu" if rehearse else "tpu"
    if d.platform != want:
        raise SystemExit(f"chip_smoke: need a {want} device, jax found "
                         f"{d.platform!r} ({d.device_kind}); nothing ran")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but jax found "
                         f"{len(devs)} device(s); nothing ran")
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - informational only
        libtpu = "?"
    cache_dir = platform.init_compile_cache()
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__} jaxlib="
        f"{jaxlib.__version__} libtpu={libtpu} compile_cache={cache_dir}")
    return devs[:chips]


def interpret_kernels():
    """Rehearsal only: the Pallas interpreter stands in for the chip."""
    from paddle_tpu.ops import (decode_attention, flash_attention, fused_ce,
                                fused_norm, ssm_update, woq_matmul)

    for m in (decode_attention, flash_attention, fused_ce, fused_norm,
              ssm_update, woq_matmul):
        m._INTERPRET = True


# --------------------------------------------------------------------------
# phase 2: kernels against their XLA oracles
# --------------------------------------------------------------------------


def _close(name, got, want, tol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite values"
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol, err_msg=name)
    return err


def phase_kernels(sz: Sizes, seed: int):
    """Tolerances are bf16-rounding scale (the MXU's fp32 dots use bf16
    passes and the two paths accumulate in different orders): 2e-2 on
    outputs, 4x that on gradients, 3e-2 for the row-statistics kernels.
    The flash check runs at ``kernel_rows`` (2,048 on the chip), where
    ``_block_sizes`` gives each kernel tiles larger than 128."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import decode_attention as da
    from paddle_tpu.ops import flash_attention as fa
    from paddle_tpu.ops import fused_ce as fce
    from paddle_tpu.ops import fused_norm as fnorm
    from paddle_tpu.ops import ssm_update as su
    from paddle_tpu.ops import woq_matmul as wm
    from paddle_tpu.ops.attention import xla_attention
    from paddle_tpu.text.woq import pack_int4_halves

    bf = jnp.bfloat16
    H, hd, D, V = sz.heads, sz.hidden // sz.heads, sz.hidden, sz.vocab
    N, T = sz.kernel_rows, sz.kernel_cache
    key = jax.random.PRNGKey(seed)
    errs = {}

    # flash attention fwd + bwd, causal
    ks = jax.random.split(key, 4)
    q, k, v, do = (jax.random.normal(kk, (1, N, H, hd), bf) for kk in ks)
    out, vjp = jax.vjp(lambda a, b, c: fa._flash(a, b, c, True, None),
                       q, k, v)
    ref, rvjp = jax.vjp(lambda a, b, c: xla_attention(a, b, c,
                                                      is_causal=True),
                        q, k, v)
    errs["flash fwd"] = _close("flash fwd", out, ref, 2e-2)
    for n, g, r in zip(("dq", "dk", "dv"), vjp(do), rvjp(do)):
        errs[f"flash {n}"] = _close(f"flash {n}", g, r, 8e-2)
    log(f"[kernels] flash tiles at {N} rows: {fa._block_sizes(N, N, hd, bf)}")

    # fused LayerNorm fwd + bwd
    ks = jax.random.split(jax.random.fold_in(key, 1), 4)
    x = jax.random.normal(ks[0], (N, D), bf)
    g = (jax.random.normal(ks[1], (D,)) + 1.0).astype(bf)
    b = jax.random.normal(ks[2], (D,), bf)
    dy = jax.random.normal(ks[3], (N, D), bf)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    y, vjp = jax.vjp(lambda a, w, c: fnorm._fused_ln(a, w, c, 1e-5), x, g, b)
    ref, rvjp = jax.vjp(lambda a, w, c: fnorm._xla_ln(a, w, c, 1e-5),
                        f32(x), f32(g), f32(b))
    errs["ln fwd"] = _close("ln fwd", y, ref, 3e-2)
    for n, got, want in zip(("dx", "dg", "db"), vjp(dy), rvjp(f32(dy))):
        # dg/db sum N rows: the bound grows with the reduction length
        tol = 12e-2 if n == "dx" else 12e-2 * max(1.0, (N / 256) ** 0.5)
        errs[f"ln {n}"] = _close(f"ln {n}", got, want, tol)

    # fused softmax cross-entropy fwd + bwd
    ks = jax.random.split(jax.random.fold_in(key, 2), 3)
    logits = (jax.random.normal(ks[0], (N, V)) * 3.0).astype(bf)
    labels = jax.random.randint(ks[1], (N,), 0, V, jnp.int32)
    dl = jax.random.normal(ks[2], (N,))
    loss, vjp = jax.vjp(lambda a: fce._fused_ce(a, labels), logits)
    ref, rvjp = jax.vjp(lambda a: fce._xla_ce(a, labels), f32(logits))
    errs["ce fwd"] = _close("ce fwd", loss, ref, 3e-2)
    errs["ce dlogits"] = _close("ce dlogits", vjp(dl)[0], rvjp(dl)[0],
                                12e-2)

    # W4 dequant-matmul at decode batch 8 (the MLP up-projection)
    rng = np.random.default_rng(seed)
    gs = 128
    xw = jnp.asarray(rng.normal(size=(8, D)), bf)
    packed = jnp.asarray(pack_int4_halves(rng.integers(-7, 8, (D, 4 * D))))
    scale = jnp.asarray(rng.uniform(0.01, 0.1, (D // gs, 1, 4 * D))
                        .astype(np.float32))
    ref = wm._xla_w4(xw, packed, scale)
    # relative to the output's own scale (|y| ~ sqrt(K) * 7 * 0.05)
    norm = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
    errs["w4"] = _close("w4", wm._w4_call(xw, packed, scale, gs) / norm,
                        ref / norm, 2e-2)

    # split-KV decode attention: bf16 / int8 cache, decode and chunk width
    B = 8
    for kv in ("bf16", "int8"):
        for Tq in (1, 4):
            ks = jax.random.split(jax.random.fold_in(key, 10 + Tq), 3)
            q = jax.random.normal(ks[0], (B, Tq, H, hd), bf)
            kc = jax.random.normal(ks[1], (B, T, H, hd), bf)
            vc = jax.random.normal(ks[2], (B, T, H, hd), bf)
            ksc = vsc = None
            if kv == "int8":
                kc, ksc = da.quantize_kv(kc)
                vc, vsc = da.quantize_kv(vc)
            pos = jnp.asarray(np.linspace(T // 2, T - Tq, B), jnp.int32)
            assert da.supported(q.shape, kc.shape)
            name = f"decode {kv} Tq{Tq}"
            errs[name] = _close(
                name, da._decode_call(q, kc, vc, pos, ksc, vsc, None),
                da._xla_decode(q, kc, vc, pos, ksc, vsc, None), 2e-2)

    # paged decode attention through shuffled block tables: the kernel is
    # handed the pool's whole leaf [L, N, bs, H*hd] and a layer's number
    # (the larger block size reads layer 1), the reference that layer alone
    for kv in ("bf16", "int8"):
        for li, bs in enumerate((16, 128)):
            if T % bs:
                continue
            nmax = T // bs
            nblk = B * nmax
            ks = jax.random.split(jax.random.fold_in(key, 20 + bs), 3)
            q = jax.random.normal(ks[0], (B, 1, H, hd), bf)
            kp = jax.random.normal(ks[1], (2, nblk, bs, H, hd), bf)
            vp = jax.random.normal(ks[2], (2, nblk, bs, H, hd), bf)
            ksc = vsc = ks1 = vs1 = None
            if kv == "int8":
                kp, ksc = da.quantize_kv(kp)
                vp, vsc = da.quantize_kv(vp)
                ks1, vs1 = ksc[li:li + 1], vsc[li:li + 1]
            kp, vp = (x.reshape(2, nblk, bs, H * hd) for x in (kp, vp))
            tables = jnp.asarray(rng.permutation(nblk).reshape(B, nmax),
                                 jnp.int32)
            pos = jnp.asarray(np.linspace(T // 2, T - 1, B), jnp.int32)
            assert da.paged_supported(q.shape, kp.shape)
            name = f"paged {kv} bs{bs}"
            errs[name] = _close(
                name, da._paged_call(q, kp, vp, tables, pos,
                                     jnp.asarray(li, jnp.int32), ksc, vsc,
                                     None),
                da._xla_paged(q, kp[li:li + 1], vp[li:li + 1], tables, pos,
                              0, ks1, vs1, None), 2e-2)

    # the recurrent state advanced where it is stored (float32, the
    # vector unit): 8 slots at the hybrid cell's heads, layer 1 of 2, five
    # slots decoding of which two start from zero; the other slots and the
    # other layer to the bit
    Ls, Hm, P, Ns, G = 2, 32, 128, 256, 2
    ks = jax.random.split(jax.random.fold_in(key, 30), 5)
    leaf = jax.random.normal(ks[0], (Ls, B, Hm, P, Ns), jnp.float32)
    dtx = jax.random.normal(ks[1], (B, Hm, P), jnp.float32)
    decay = jax.random.uniform(ks[2], (B, Hm), jnp.float32)
    bm = jax.random.normal(ks[3], (B, G, Ns), jnp.float32)
    cm = jax.random.normal(ks[4], (B, G, Ns), jnp.float32)
    live = jnp.asarray([1, 0, 1, 1, 0, 0, 1, 1], bool)
    pos = jnp.asarray([4, 4, 0, 9, 0, 2, 0, 1], jnp.int32)
    assert su.supported(leaf.shape, leaf.dtype, G)
    y, new = jax.jit(su.state_update)(leaf, jnp.int32(1), live, pos, dtx,
                                      decay, bm, cm)
    s0 = jnp.where((pos == 0)[:, None, None, None], 0.0, leaf[1])
    per_head = lambda a: jnp.repeat(a, Hm // G, axis=1)  # noqa: E731
    want = (s0 * decay[..., None, None]
            + dtx[..., None] * per_head(bm)[:, :, None])
    on = np.asarray(live)
    errs["ssm state"] = _close("ssm state", new[1][on], want[on], 1e-5)
    errs["ssm y"] = _close(
        "ssm y", y[on], jnp.sum(want * per_head(cm)[:, :, None], -1)[on],
        1e-3)
    assert (np.asarray(new[1])[~on] == np.asarray(leaf[1])[~on]).all()
    assert (np.asarray(new[0]) == np.asarray(leaf[0])).all()

    for name, e in errs.items():
        log(f"[kernels] {name}: max abs err {e:.3g}")
    log(f"[kernels] {len(errs)} checks passed")


# --------------------------------------------------------------------------
# phase 3: train
# --------------------------------------------------------------------------


def token_stream(rng, B, T, vocab):
    """The deterministic stream tests/conftest.py trains its model on,
    next = (tok * 3 + 1) % 13, spread over the vocabulary."""
    t = rng.integers(0, 13, (B, 1))
    rows = [t]
    for _ in range(T):
        t = (t * 3 + 1) % 13
        rows.append(t)
    return (np.concatenate(rows, 1) * (vocab // 13)).astype(np.int32)


def run_train(cfg, mesh, batches, lr, seed, tag):
    """Build the step through the user's entry point, compile it ahead of
    time (its text is checked for the kernels), take ``len(batches)``
    steps.  Returns (losses, state, compiled text)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text import gpt_hybrid

    init_fn, step_fn, _ = gpt_hybrid.build_gpt_train_step(
        cfg, mesh, AdamW(learning_rate=lr))
    state = init_fn(seed)
    key = jax.random.PRNGKey(seed)
    lr = jnp.float32(lr)
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, jnp.asarray(batches[0]), key,
                             lr).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    losses, step_s = [], []
    for toks in batches:
        t0 = time.perf_counter()
        state, loss = compiled(state, jnp.asarray(toks), key, lr)
        jax.block_until_ready((state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    log(f"[{tag}] compile {compile_s:.1f}s; step seconds "
        f"{[round(s, 3) for s in step_s]}; losses "
        f"{[round(l, 4) for l in losses]} (information, not a benchmark)")
    assert all(np.isfinite(losses)), losses
    return losses, state, text


def train_inputs(sz: Sizes, seed: int):
    """(config at the cut depth, one batch per step) for a train phase,
    with the headline training path switched on: flash attention plus the
    fused LayerNorm / cross-entropy kernels (flags read at trace time)."""
    os.environ["PADDLE_TPU_FUSED_LN"] = "1"
    os.environ["PADDLE_TPU_FUSED_CE"] = "1"
    cfg = sz.cfg(sz.train_layers)
    rng = np.random.default_rng(seed)
    return cfg, [token_stream(rng, sz.train_batch, sz.seq, cfg.vocab_size)
                 for _ in range(sz.train_steps)]


def peak_gb(dev):
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


def phase_train(sz: Sizes, dev, seed: int):
    from jax.sharding import Mesh

    cfg, batches = train_inputs(sz, seed)
    log(f"[train] widths of gpt_1p3b; depth cut {24}->{cfg.num_layers}, "
        f"batch {sz.train_batch}, seq {sz.seq}, bf16 compute, fp32 AdamW "
        f"at lr {sz.train_lr}")
    losses, state, text = run_train(cfg, Mesh(np.array([dev]), ("dp",)),
                                    batches, sz.train_lr, seed, "train")
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    calls = text.count("tpu_custom_call")
    log(f"[train] custom calls in the compiled step: {calls}; peak device "
        f"memory {peak_gb(dev)}")
    if dev.platform == "tpu":
        # flash fwd + dq + dkv, LN fwd + bwd (two sites), CE fwd + bwd
        assert calls >= 7, f"kernels missing from the train step: {calls}"
    del state
    gc.collect()


# --------------------------------------------------------------------------
# phase 4: serve
# --------------------------------------------------------------------------


def bf16_params(cfg, seed):
    """gpt.init_params from the seed, fp32 leaves cast to bf16 one layer
    stack at a time, so the fp32 tree is never resident whole."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import gpt

    cast = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        gpt.init_params(cfg, k)))
    return cast(jax.random.PRNGKey(seed))


def make_prompts(sz: Sizes, seed):
    rng = np.random.default_rng(seed + 1)
    return [rng.integers(0, sz.vocab, (n,)).astype(np.int32)
            for n in sz.prompt_lens]


def serve_all(srv, prompts, new_tokens):
    """All requests through submit/tick; returns (tokens per request,
    the server's own time-to-first-token readings in ms as (min, max),
    generated tokens per second)."""
    t0 = time.perf_counter()
    rids = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
    while srv.pending():
        srv.tick()
    wall = time.perf_counter() - t0
    outs = [list(map(int, srv.result(r))) for r in rids]
    ttft = srv.local_snapshot()["histograms"].get("serving.ttft_ms", {})
    return (outs, (ttft.get("min"), ttft.get("max")),
            sum(map(len, outs)) / wall)


def forward_margins(params, cfg, prompts, outs, seq):
    """Teacher-forced check against the whole-sequence program: for every
    served token, how far its logit under ``gpt.forward(prompt + served)``
    lies below that position's best logit.  0 = it IS the argmax."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import gpt

    fwd = jax.jit(lambda p, t: gpt.forward(p, t, cfg).astype(jnp.float32))
    worst = 0.0
    for prompt, out in zip(prompts, outs):
        toks = np.zeros((1, seq), np.int32)
        full = np.concatenate([prompt, np.asarray(out, np.int32)])
        toks[0, :len(full)] = full
        logits = np.asarray(fwd(params, jnp.asarray(toks)))[0]
        for j, tok in enumerate(out):
            row = logits[len(prompt) - 1 + j]
            worst = max(worst, float(row.max() - row[tok]))
    return worst


# A served token must be the whole-sequence program's argmax to within
# bf16 rounding of the logits.  Exact equality with solo
# ``generate.generate`` is printed beside it but is not the criterion:
# random bf16 weights give near-ties that a batched and a solo program
# break differently, and one flip changes every later token.
LOGIT_TOL = 5e-2


def check_served(params, cfg, prompts, outs, sz, tag):
    from paddle_tpu.text import generate

    assert all(len(o) == sz.new_tokens for o in outs), [len(o) for o in outs]
    worst = forward_margins(params, cfg, prompts, outs, sz.seq)
    same = 0
    for prompt, out in zip(prompts, outs):
        solo = np.asarray(generate.generate(
            params, cfg, prompt[None], max_new_tokens=sz.new_tokens))[0]
        same += int(list(map(int, solo[len(prompt):])) == out)
    log(f"[{tag}] worst logit margin vs gpt.forward {worst:.4f} "
        f"(tolerance {LOGIT_TOL}); {same}/{len(outs)} requests equal solo "
        f"generate.generate token for token")
    assert worst <= LOGIT_TOL, (
        f"{tag}: a served token lies {worst:.4f} below the reference "
        f"argmax — more than bf16 rounding")


def compile_count():
    """Executables compiled so far: the builds telemetry logged, plus the
    jit-cache entries under every executable the Engine holds (a call with
    a new argument type compiles again without a new build)."""
    from paddle_tpu import telemetry
    from paddle_tpu.text import engine

    n = len(telemetry.snapshot()["compiles"])
    for cache in (engine.ENGINE._steps, engine.ENGINE._gen):
        for key in cache.keys():
            fn = cache.get(key)
            fn = getattr(fn, "_telemetry_inner", fn)
            if hasattr(fn, "_cache_size"):
                n += fn._cache_size()
    return n


def decode_step_text(srv):
    """Text of the compiled decode step this server ticks with (a
    compile-cache hit: warmup compiled it)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import engine

    fn = engine.ENGINE.get("step", engine.StepSpec(
        cfg=srv.cfg, paged=srv._paged, shard=srv._shard))
    zi = jnp.zeros((srv.max_batch,), jnp.int32)
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding),
        (srv.params, srv.cache, zi, zi))
    return fn.lower(*shapes).compile().as_text()


def run_server(params, cfg, sz, prompts, tag, **kw):
    from paddle_tpu.text.serving import DecodeServer

    srv = DecodeServer(params, cfg, max_batch=sz.serve_batch,
                       max_len=sz.seq, async_dispatch=True, **kw)
    t0 = time.perf_counter()
    srv.warmup()
    warm_s = time.perf_counter() - t0
    text = decode_step_text(srv)
    n0 = compile_count()
    outs, ttft, tok_s = serve_all(srv, prompts, sz.new_tokens)
    new = compile_count() - n0
    log(f"[{tag}] warmup {warm_s:.1f}s; time to first token min/max "
        f"{ttft[0]}/{ttft[1]} ms; "
        f"{tok_s:.1f} generated tokens/s over {len(prompts)} requests "
        f"(information, not a benchmark); executables compiled after "
        f"warm-up: {new}")
    assert new == 0, f"{tag}: {new} executable(s) compiled after warmup()"
    srv.close()
    return outs, text


def phase_serve(sz: Sizes, dev, seed: int,
                layouts=("contiguous", "paged")):
    cfg = sz.cfg(sz.serve_layers)
    params = bf16_params(cfg, seed)
    prompts = make_prompts(sz, seed)
    log(f"[serve] gpt_1p3b at depth {cfg.num_layers}, bf16 weights, "
        f"max_len {sz.seq}, batch {sz.serve_batch}, prompts "
        f"{list(sz.prompt_lens)}, {sz.new_tokens} new tokens each")
    for layout in layouts:
        tag = f"serve {layout}"
        outs, text = run_server(params, cfg, sz, prompts, tag, layout=layout)
        calls = text.count("tpu_custom_call")
        log(f"[{tag}] custom calls in the compiled decode step: {calls}; "
            f"peak device memory {peak_gb(dev)}")
        if dev.platform == "tpu":
            assert calls >= 1, f"{tag}: decode-attention kernel missing"
        check_served(params, cfg, prompts, outs, sz, tag)
        gc.collect()


# --------------------------------------------------------------------------
# --chips 4: what exists only across chips, and what it is compared with
# --------------------------------------------------------------------------


def phase_train_sharded(sz: Sizes, devs, seed: int):
    from jax.sharding import Mesh

    from paddle_tpu import distributed

    cfg, batches = train_inputs(sz, seed)
    log(f"[train x4] widths of gpt_1p3b, depth {cfg.num_layers}, batch "
        f"{sz.train_batch}: one chip, then {{'dp': 2, 'mp': 2}}")
    one, state, _ = run_train(cfg, Mesh(np.array(devs[:1]), ("dp",)),
                              batches, sz.train_lr, seed, "train one chip")
    del state
    gc.collect()
    mesh = distributed.init_parallel_env({"dp": 2, "mp": 2}, devices=devs)
    four, state, text = run_train(cfg, mesh, batches, sz.train_lr, seed,
                                  "train dp2 x mp2")
    tol = 3e-2
    gaps = [abs(a - b) for a, b in zip(one, four)]
    log(f"[train x4] |loss one chip - loss four chips| per step "
        f"{[round(g, 5) for g in gaps]} (tolerance {tol})")
    assert max(gaps) <= tol, (one, four)
    # every mp-sharded parameter: four shards, four devices, half each
    n_sharded = 0
    import jax

    for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
        if "mp" not in jax.tree_util.tree_leaves(tuple(leaf.sharding.spec)):
            continue
        n_sharded += 1
        shards = leaf.addressable_shards
        where = {s.device.id for s in shards}
        assert len(shards) == 4 and len(where) == 4, (path, where)
        for s in shards:
            assert s.data.nbytes * 2 == leaf.nbytes, (path, s.data.shape)
    assert n_sharded >= 4, n_sharded
    log(f"[train x4] {n_sharded} mp-sharded parameters: 4 shards on 4 "
        f"distinct devices, 1/2 of the bytes each; all-reduces in the "
        f"compiled step: {text.count('all-reduce')}; custom calls: "
        f"{text.count('tpu_custom_call')}")
    del state
    gc.collect()


def phase_serve_sharded(sz: Sizes, devs, seed: int):
    from jax.sharding import Mesh

    cfg = sz.cfg(sz.serve_layers)
    params = bf16_params(cfg, seed)
    prompts = make_prompts(sz, seed)
    log(f"[serve x4] gpt_1p3b at depth {cfg.num_layers}: one chip, then "
        f"DecodeServer(mesh=Mesh(4, ('mp',)))")
    one, _ = run_server(params, cfg, sz, prompts, "serve one chip")
    check_served(params, cfg, prompts, one, sz, "serve one chip")
    four, text = run_server(params, cfg, sz, prompts, "serve mp4",
                            mesh=Mesh(np.array(devs), ("mp",)))
    log(f"[serve mp4] custom calls in the compiled decode step: "
        f"{text.count('tpu_custom_call')}; all-reduces: "
        f"{text.count('all-reduce')}")
    if devs[0].platform == "tpu":
        assert text.count("tpu_custom_call") >= 1
    check_served(params, cfg, prompts, four, sz, "serve mp4")
    same = sum(a == b for a, b in zip(one, four))
    log(f"[serve x4] {same}/{len(one)} requests: four chips equal one chip "
        f"token for token")


# --------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny size, interpret-mode kernels")
    ap.add_argument("--phases", default="kernels,train,serve",
                    help="comma list (default: all)")
    args = ap.parse_args(argv)
    phases = set(args.phases.split(","))

    if args.rehearse:
        from paddle_tpu.framework.platform import force_cpu

        force_cpu(args.chips)
    devs = phase_device(args.rehearse, args.chips)
    if args.rehearse:
        interpret_kernels()
    if args.chips == 4:
        sz = REHEARSAL4 if args.rehearse else CHIP4
        if "train" in phases:
            phase_train_sharded(sz, devs, args.seed)
        if "serve" in phases:
            phase_serve_sharded(sz, devs, args.seed)
    else:
        sz = REHEARSAL if args.rehearse else CHIP
        if "kernels" in phases:
            phase_kernels(sz, args.seed)
        if "train" in phases:
            phase_train(sz, devs[0], args.seed)
        if "serve" in phases:
            phase_serve(sz, devs[0], args.seed)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    main()
