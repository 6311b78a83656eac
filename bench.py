#!/usr/bin/env python
"""Benchmark ladder (BASELINE.md configs 1-4) on one chip.

stdout: exactly ONE JSON line — the headline GPT metric:
  {"metric": ..., "value": N, "unit": "tokens/s/chip", "vs_baseline": N}
stderr: per-config progress + diagnostics.
``--all`` additionally measures MNIST-LeNet / ResNet-50 / BERT-base and
writes every config's result to BENCH_DETAILS.json.
``--config NAME`` runs a single config (gpt|mnist|resnet|bert).
``--small`` forces the scaled-down CI configs.

The reference repo publishes no absolute numbers (BASELINE.md), so
``vs_baseline`` is measured MFU relative to the north-star bar of A100-class
MFU (BASELINE.json: ">= A100 MFU"); we take 0.45 MFU — strong published
Megatron-LM A100 efficiency for GPT-scale models — as that bar, i.e.
vs_baseline = our_MFU / 0.45 (>1.0 beats the bar).

Without ``--cpu`` the run needs a TPU: no chip is a non-zero exit before
anything is measured, and a config that raises makes the run exit
non-zero.  ``--cpu --small`` is the rehearsal the tests use.  Everything
runs in THIS process — a chip belongs to one process at a time, so the
ladder's rungs and the decode/serving arms start no children.
"""
from __future__ import annotations

import datetime
import json
import os
import subprocess  # _git_rev only: no child ever needs the chip
import sys
import time

_A100_MFU_BAR = 0.45


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


# Every bench JSON line carries this block (MLPerf-style reporting: a
# number without its measurement conditions is not a result).  The keys
# are the schema — the CI smoke validates them.
_PROVENANCE_KEYS = ("ts", "platform", "device_kind", "jax", "jaxlib",
                    "python", "git_rev", "flags")


def _git_rev():
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None
    except Exception:  # noqa: BLE001 - provenance is evidence, not a gate
        return None


def _provenance(dev) -> dict:
    """The provenance block: where/how THIS bench process measured —
    the device as jax reports it, jax/jaxlib versions, the source git
    rev and the PADDLE_TPU_* flag environment."""
    import jax
    import jaxlib

    return {
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "platform": dev.platform,
        "device_kind": str(getattr(dev, "device_kind", "")),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "python": sys.version.split()[0],
        "git_rev": _git_rev(),
        "flags": {k: v for k, v in sorted(os.environ.items())
                  if k.startswith("PADDLE_TPU_") or k == "JAX_PLATFORMS"},
    }


def _stamp_provenance(rec, dev):
    """Attach the provenance block to a bench record (in place); an
    existing block is kept."""
    if isinstance(rec, dict) and not isinstance(rec.get("provenance"),
                                                dict):
        rec["provenance"] = _provenance(dev)
    return rec


def _peak_flops(dev):
    """bf16 peak FLOPs/s for the chip (None off a TPU, where every MFU
    derived from it reports null) — the table lives in
    paddle_tpu.framework.platform.DEVICE_PEAKS, shared with the
    telemetry device feed's live MFU gauges.  A TPU kind that is not in
    the table raises: no MFU is ever made up from a guessed peak."""
    from paddle_tpu.framework.platform import peak_flops

    return peak_flops(getattr(dev, "device_kind", ""), dev.platform)


def _mfu_fields(mfu) -> dict:
    """The (mfu, vs_baseline) pair, null-safe: unknown peak -> mfu null
    and vs_baseline 0.0 (never a number made up from a guessed peak)."""
    if mfu is None:
        return {"mfu": None, "vs_baseline": 0.0}
    return {"mfu": round(mfu, 4),
            "vs_baseline": round(mfu / _A100_MFU_BAR, 4)}


def _sync_all(trees):
    """Barrier: wait for one scalar data-dependent on EVERY leaf.

    JAX returns before the device finishes, so a timing that does not
    end in ``block_until_ready`` measures the enqueue.  The awaited
    value is a jitted reduction over the first element of every leaf —
    params, optimizer moments, counters, loss — so the wait covers the
    whole last step (its backward and optimizer update too), in one
    compiled program regardless of leaf count."""
    import jax
    import jax.numpy as jnp

    def _reduce(ts):
        acc = jnp.zeros((), jnp.float32)
        for leaf in jax.tree_util.tree_leaves(ts):
            if hasattr(leaf, "ravel") and getattr(leaf, "size", 0):
                acc = acc + leaf.ravel()[:1].astype(jnp.float32)[0]
        return acc
    # jax.jit caches by tree structure: compiled once per bench config
    fn = _sync_all.__dict__.setdefault("_jit", jax.jit(_reduce))
    return jax.block_until_ready(fn(trees))


def _time_steps(run_one, iters, fetch):
    """Steady-state step time: enqueue ``iters`` steps, then synchronize.

    ``fetch()`` must return the updated device state of the LAST step —
    every tensor the step writes (params, optimizer state, loss), so the
    ``_sync_all`` barrier covers the whole step, not just the forward."""
    run_one()  # compile + warmup
    _sync_all(fetch())
    t0 = time.perf_counter()
    for _ in range(iters):
        run_one()
    _sync_all(fetch())
    return (time.perf_counter() - t0) / iters


# ---------------------------------------------------------------------------
# configs
# ---------------------------------------------------------------------------

def _gpt_rungs():
    """Full GPT ladder: (name, cfg_kwargs, B, T, iters, state_dtype, accum,
    fused).

    Ordered by preference: the FIRST rung that fits+runs is the headline.
    bf16 optimizer state (Adam m/v) halves optimizer HBM; gradient
    ACCUMULATION (bf16 carry) lowers the per-micro-batch activation size.

    Measured on the 16 GB v5e (round-4 window 1): the non-fused non-remat
    rungs OOM even at GPT-760M B=1 — the killers are the fp32 LayerNorm
    chains saved as scan residuals (6x 288 MB at 760M/B1), the [B,T,V]
    fp32 log-softmax, and the whole-stack bf16 weight-cast temps.  So the
    ladder now leads with the Pallas fused-LN/CE rungs (which remove the
    first two), then the selective-remat rungs, keeping non-fused rungs
    for larger-HBM chips (v5p fits 1.3B without either).  Full-remat
    rungs stay last (longest compiles)."""
    c13 = dict(vocab_size=50304, hidden_size=2048, num_layers=24,
               num_heads=16, max_seq_len=2048)
    # 760M uses 12 heads (head_dim 128), not Megatron's 16 (head_dim 96):
    # the flash kernel tiles head_dim 64/128/256 onto the MXU, and head_dim
    # 96 silently fell back to XLA attention — a [H,T,T] probability tensor
    # per layer that alone blows the 16 GB budget
    c760 = dict(vocab_size=50304, hidden_size=1536, num_layers=24,
                num_heads=12, max_seq_len=2048)
    c350 = dict(vocab_size=50304, hidden_size=1024, num_layers=24,
                num_heads=16, max_seq_len=2048)
    fused_rungs = [
        ("gpt_1.3b_fused_acc8_b8", dict(c13, remat=False), 8, 2048, 10,
         "bfloat16", 8, True),
        ("gpt_760m_fused_acc16_b16", dict(c760, remat=False), 16, 2048, 10,
         "bfloat16", 16, True),
        ("gpt_760m_fused_acc8_b8", dict(c760, remat=False), 8, 2048, 10,
         "bfloat16", 8, True),
        # v5e-16GB tournament candidates (estimator-enumerated, ~14-15 GB):
        # the no-remat fused 350M has zero recompute overhead (best MFU if
        # it truly fits); the dots-remat pair trades ~mild recompute for a
        # bigger model (760M) or a bigger micro-batch (350M Bm=8)
        ("gpt_350m_fused_acc2_b8", dict(c350, remat=False), 8, 2048, 10,
         "bfloat16", 2, True),
        ("gpt_760m_fused_dots_acc4_b8",
         dict(c760, remat=True, remat_policy="dots"), 8, 2048, 10,
         "bfloat16", 4, True),
        ("gpt_350m_fused_dots_b8",
         dict(c350, remat=True, remat_policy="dots"), 8, 2048, 10,
         "bfloat16", 1, True),
        # round-5 window 2 calibration: est-12.7GB rungs OOM on the real
        # chip (HLO temps the estimate can't see) — mid-footprint fused
        # rungs (~9-10GB est) so the walk has fused rungs that FIT
        ("gpt_350m_fused_acc4_b8", dict(c350, remat=False), 8, 2048, 10,
         "bfloat16", 4, True),
        ("gpt_350m_fused_dots_acc2_b8",
         dict(c350, remat=True, remat_policy="dots"), 8, 2048, 10,
         "bfloat16", 2, True),
        # acc32: UNMEASURED extrapolation of the winner's micro-shape
        # (see _EXTRAPOLATED_FIT) — first so the tournament tests it
        ("gpt_760m_fused_dots_acc32_b32",
         dict(c760, remat=True, remat_policy="dots"), 32, 2048, 5,
         "bfloat16", 32, True),
        # the BASELINE's named model on ONE chip: Adafactor (factored
        # moments) + fused kernels + full remat — inside the tournament's
        # top-3 window so a healthy ladder run actually tries it
        ("gpt_1.3b_fused_remat_af_acc8_b8",
         dict(c13, remat=True), 8, 2048, 5,
         "adafactor", 8, True),
        # THE measured winner (round-5 window 2): MFU 0.476, the first
        # config to beat the A100-class bar — 760M amortizes layer
        # overheads over 2.2x the FLOPs of 350M, and only fits because
        # the fused kernels drop the LN/CE residuals
        ("gpt_760m_fused_dots_acc16_b16",
         dict(c760, remat=True, remat_policy="dots"), 16, 2048, 10,
         "bfloat16", 16, True),
        ("gpt_760m_fused_dots_acc8_b8",
         dict(c760, remat=True, remat_policy="dots"), 8, 2048, 10,
         "bfloat16", 8, True),
        # full-remat twin at Bm=4: the 350M data showed full-remat with a
        # bigger micro-batch edging out dots at Bm=2 (0.2823 vs 0.2776)
        ("gpt_760m_fused_remat_acc2_b8",
         dict(c760, remat=True), 8, 2048, 10,
         "bfloat16", 2, True),
        # dots-remat fused twin of the MEASURED gpt_350m_dots_acc4_b8
        # (MFU 0.276, window 2) — the kernel A/B pair that provably fits:
        # no-remat non-fused twins OOM even at est 9.2GB (whole-weight
        # scan copies the estimate can't see)
        ("gpt_350m_fused_dots_acc4_b8",
         dict(c350, remat=True, remat_policy="dots"), 8, 2048, 10,
         "bfloat16", 4, True),
        ("gpt_1.3b_fused_remat_dots_b2",
         dict(c13, remat=True, remat_policy="dots"), 2, 2048, 10,
         "bfloat16", 1, True),
    ]
    r = fused_rungs + [
        ("gpt_1.3b_acc8_b8", dict(c13, remat=False), 8, 2048, 10,
         "bfloat16", 8, False),
        ("gpt_760m_acc4_b8", dict(c760, remat=False), 8, 2048, 10,
         "bfloat16", 4, False),
        ("gpt_760m_b2", dict(c760, remat=False), 2, 2048, 10,
         "bfloat16", 1, False),
        ("gpt_760m_b1", dict(c760, remat=False), 1, 2048, 10,
         "bfloat16", 1, False),
        ("gpt_350m_acc2_b8", dict(c350, remat=False), 8, 2048, 10,
         "bfloat16", 2, False),
        # round-5: the ungated fast-headline anchor — dots-remat removes
        # the fp32 LN residual chains that push every non-fused no-remat
        # 350M config past 16 GB, without the compile-hang risk of full
        # remat (~12.7 GB estimated)
        ("gpt_350m_dots_acc2_b8",
         dict(c350, remat=True, remat_policy="dots"), 8, 2048, 10,
         "bfloat16", 2, False),
        # round-5 window 2: est-12.7GB OOMed on the chip — higher-accum
        # dots rungs (~9 and ~7GB est) are the new ungated anchors; the
        # non-fused logits term (10 B/elem) shrinks with micro-batch
        ("gpt_350m_dots_acc4_b8",
         dict(c350, remat=True, remat_policy="dots"), 8, 2048, 10,
         "bfloat16", 4, False),
        ("gpt_350m_dots_acc8_b8",
         dict(c350, remat=True, remat_policy="dots"), 8, 2048, 10,
         "bfloat16", 8, False),
        ("gpt_350m_b4", dict(c350, remat=False), 4, 2048, 10,
         "bfloat16", 1, False),
        ("gpt_350m_b2", dict(c350, remat=False), 2, 2048, 10,
         "bfloat16", 1, False),
        # selective-checkpoint middle rungs: keep matmul outputs, recompute
        # elementwise — cheaper recompute than full remat AND a different
        # compile shape, so they may succeed where full-remat programs hang
        ("gpt_1.3b_remat_dots_b2",
         dict(c13, remat=True, remat_policy="dots"), 2, 2048, 10,
         "bfloat16", 1, False),
        ("gpt_1.3b_remat_b4", dict(c13, remat=True), 4, 2048, 10,
         "bfloat16", 1, False),
        ("gpt_350m_remat_b8", dict(c350, remat=True), 8, 2048, 10,
         "bfloat16", 1, False),
    ]
    return r


def _hbm_bytes() -> float:
    env = os.environ.get("BENCH_HBM_GB")
    if env:
        return float(env) * 1e9
    try:
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        if stats.get("bytes_limit"):
            return float(stats["bytes_limit"])
    except Exception:  # noqa: BLE001 - fall through to kind-based default
        pass
    return 16.9e9  # v5e / v5 lite: 15.75 GiB (measured OOM report)


def _gpt_rung_estimate(cfg_kwargs, B, T, state_dtype, accum=1,
                       fused=False) -> float:
    """Static-footprint estimate in bytes: params fp32 + m/v + grads bf16 +
    logits + activations.  With accum, activations/logits scale with
    micro-batch B/accum.  Recorded per rung next to the measured HBM
    high-water so the estimate can be calibrated against reality.

    Round-4 calibration against the first on-device OOMs (v5e window 1):
    three terms the old estimate missed are now counted — the whole-stack
    bf16 weight-cast temps (+2n, observed as bf16[24,3,1536,1536]
    converts), the fp32 LayerNorm residual chains when the fused-LN kernel
    is off (+24 B/token/layer, observed as 6x fp32[24,1,2048,1536]), and
    the fp32 log-softmax + cotangent when the fused-CE kernel is off
    (logits term 10 B/element instead of 4)."""
    from paddle_tpu.text import gpt

    cfg = gpt.GPTConfig(**cfg_kwargs)
    n = gpt.count_params(cfg)
    if state_dtype == "adafactor":
        # factored moments are ~params/dim — negligible; master fp32 +
        # the same grad term as the AdamW branch (grad dtype does not
        # depend on the optimizer choice)
        base = n * (4 + 2)
    else:
        sbytes = 2 if state_dtype == "bfloat16" else 4
        base = n * (4 + 2 * sbytes + 2)
    base += n * 2  # transient bf16 cast of the fp32 master weights
    if accum > 1:
        # the bf16 accumulation carry is live alongside each fresh
        # micro-batch grad tree during the scan
        base += n * 2
    Bm = max(1, B // max(1, accum))
    # logits [Bm*T, V] bytes/element: fused CE = bf16 value + bf16 grad
    # (4); non-fused adds the fp32 log_softmax + its fp32 cotangent, whose
    # bf16 downcast fuses into the softmax buffer (2 + 4 + 4 = 10)
    logits = Bm * T * cfg.vocab_size * (4 if fused else 10)
    from paddle_tpu.ops.remat_policies import canonical

    policy = canonical(_effective_remat_policy(cfg)) if cfg.remat else None
    if cfg.remat and policy in ("dots", "dots_no_batch"):
        # saved matmul outputs per block: qkv (3h) + attn-out (h) + ffn
        # up (4h) + ffn down (h) ≈ 9h per token per layer, bf16.
        # x3.75 on-device calibration (round-5 window 2): fused dots
        # acc2 measured "Used 20.26G of 15.75G" against raw
        # base+logits+acts of 5+1.65+3.62GB — i.e. actual saved mass
        # around the kept dots is ~3.75x the matmul-output count (the
        # checkpoint policy keeps the dots; XLA still saves the tensors
        # BETWEEN them that the recompute path doesn't cover)
        acts = cfg.num_layers * Bm * T * 9 * cfg.hidden_size * 2 * 3.75
        if policy == "dots" and not _flash_active(cfg, T):
            # XLA attention's q@kT scores are batched dots that 'dots'
            # (but not 'dots_no_batch') also saves: H*T floats per token
            acts += cfg.num_layers * Bm * T * T * cfg.num_heads * 2
    elif cfg.remat and policy is None:
        acts = cfg.num_layers * Bm * T * cfg.hidden_size * 2 * 2
    else:  # no remat, or 'everything' (checkpoint is a no-op)
        # x5 on-device calibration (round-5 window 2): fused no-remat
        # 350M at Bm=2 measured "Used 29.05G of 15.75G hbm" against a
        # raw estimate of 9.8GB — the whole-graph residual set (attention
        # internals, gelu/swiglu intermediates, weight-cast twins) is ~5x
        # the headline matmul activations.  No-remat GPT rungs are
        # effectively out of reach on 16GiB-class chips.
        acts = cfg.num_layers * Bm * T * (12 * cfg.hidden_size
                                          + 2 * cfg.ffn_size) * 2 * 5
        if not fused:
            # fp32 LayerNorm chains saved as scan residuals (~6 h-wide
            # fp32 buffers per layer; fused-LN saves [N,1] stats instead)
            acts += cfg.num_layers * Bm * T * cfg.hidden_size * 24
        if not _flash_active(cfg, T):
            # XLA attention saves the [H, T, T] probability tensor
            acts += cfg.num_layers * Bm * cfg.num_heads * T * T * 2
    return float(base + logits + acts)


def _effective_remat_policy(cfg):
    """The policy the program will actually compile with: explicit config
    wins; the PADDLE_TPU_REMAT_POLICY env override only fills a None."""
    return cfg.remat_policy or (
        os.environ.get("PADDLE_TPU_REMAT_POLICY") or None)


def _flash_active(cfg, T) -> bool:
    """Mirrors ops/attention._use_flash for estimation purposes (minus the
    device check — the estimate only matters on TPU)."""
    if os.environ.get("PADDLE_TPU_NO_FLASH", "") not in ("", "0"):
        return False
    head = cfg.hidden_size // cfg.num_heads
    return T % 128 == 0 and head in (64, 128, 256)


# Rungs PROVEN to run on the 15.75GiB v5e (round-5 window 2) — the
# estimate is a pre-filter for rungs never tried, not a veto over
# empirical fact: the 0.476-MFU 760M winner estimates at 16.2GB yet runs.
_PROVEN_FIT = {
    "gpt_760m_fused_dots_acc16_b16",
    "gpt_760m_fused_dots_acc8_b8",
    "gpt_350m_fused_dots_acc4_b8",
    "gpt_350m_dots_acc4_b8",
    "gpt_350m_dots_acc8_b8",
    "gpt_350m_remat_b8",
}
# Same-micro-shape EXTRAPOLATIONS pending an on-device run: admitted to
# the walk (the acc8->acc16 extrapolation measured fine) but NOT claimed
# as ground truth.  An observed OOM costs that rung's ~2-min compile per
# ladder run until a human REMOVES it here (the set is static — there is
# no self-healing); a measured success graduates it to _PROVEN_FIT.
_EXTRAPOLATED_FIT = {
    "gpt_760m_fused_dots_acc32_b32",  # Bm=1 shape of the proven acc8/16
    "gpt_1.3b_fused_remat_af_acc8_b8",  # Adafactor unlock, never tried
}


def _gpt_rung_fits(name, cfg_kwargs, B, T, state_dtype, hbm, accum=1,
                   fused=False) -> bool:
    """Skipping a hopeless rung saves ~2 min of compile-to-OOM each.
    The fit test is ADDITIVE: estimate + headroom <= hbm, headroom
    defaulting to 2GB (the pure-HLO-temp mass observed in window-2 OOM
    dumps; BENCH_HEADROOM_GB overrides) — the larger systematic
    under-counts live in the per-branch calibration factors of
    _gpt_rung_estimate, each anchored to a measured "Used X of Y hbm"
    line.  Rungs in _PROVEN_FIT bypass the estimate, but ONLY on a chip
    at least as large as the 15.75GiB v5e the proof was measured on."""
    # 15.9e9 not 16.9e9: every legacy wrapper exports BENCH_HBM_GB=16
    # (the old default) to MEAN "the v5e" — that spelling must not veto
    # the rungs proven on that exact chip.  The proofs were measured
    # with flash attention ACTIVE: under PADDLE_TPU_NO_FLASH the same
    # rung saves the [H,T,T] score tensors too, so the empirical fact
    # no longer applies and the estimate (with its TT term) decides.
    if (name in (_PROVEN_FIT | _EXTRAPOLATED_FIT) and hbm >= 15.9e9
            and not _no_flash_requested()):
        return True
    headroom = float(os.environ.get("BENCH_HEADROOM_GB", "2")) * 1e9
    return _gpt_rung_estimate(cfg_kwargs, B, T, state_dtype, accum,
                              fused) + headroom <= hbm


def _run_gpt_rung(idx: int):
    """Run one ladder rung in-process and return its result dict."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text import gpt, gpt_hybrid

    if idx < 0:  # CI/CPU smoke rung
        name, cfg_kwargs, B, T, iters, state_dtype, accum, fused = (
            "gpt_small_smoke",
            dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
                 max_seq_len=256), 2, 256, 3, None, 1, False)
    else:
        (name, cfg_kwargs, B, T, iters, state_dtype, accum,
         fused) = _gpt_rungs()[idx]
    if fused:
        # flags are read at trace time by gpt._ln / gpt.loss_fn
        os.environ["PADDLE_TPU_FUSED_LN"] = "1"
        os.environ["PADDLE_TPU_FUSED_CE"] = "1"
    cfg = gpt.GPTConfig(**cfg_kwargs)
    dev = jax.devices()[0]
    mesh = Mesh(np.array([dev]).reshape(1), ("dp",))
    if state_dtype == "adafactor":
        # factored second moments: the state_dtype slot doubles as the
        # optimizer selector for the 1.3B rung (Adam state alone puts
        # 1.3B out of reach on 16GiB; Adafactor's R/C vectors are ~8MB)
        from paddle_tpu.optimizer import Adafactor

        opt = Adafactor(learning_rate=2e-4)
    else:
        opt = AdamW(learning_rate=2e-4, weight_decay=0.01,
                    state_dtype=state_dtype)
    key = jax.random.PRNGKey(0)
    init_fn, step_fn, _ = gpt_hybrid.build_gpt_train_step(cfg, mesh, opt,
                                                          accum=accum)
    state = init_fn(0)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    state, loss = step_fn(state, toks, key, 2e-4)
    jax.device_get(loss)  # forced execution: an OOM must surface HERE

    st = {"state": state, "loss": loss}

    def one():
        st["state"], st["loss"] = step_fn(st["state"], toks, key, 2e-4)

    dt = _time_steps(one, iters, lambda: (st["state"], st["loss"]))
    tok_s = B * T / dt
    peak = _peak_flops(dev)
    achieved = gpt.flops_per_token(cfg, T) * tok_s  # peak-independent
    mfu = (achieved / peak) if peak else None
    _log(f"[bench] {name}: {tok_s:,.0f} tok/s  step={dt * 1e3:.1f}ms  "
         f"loss={float(st['loss']):.4f}  "
         f"MFU={'null (unknown peak)' if mfu is None else f'{mfu:.3f}'}  "
         f"device={dev.device_kind}")
    if mfu is not None and mfu >= 1.0:
        # >=100% of peak is physically impossible: the timing barrier
        # failed to cover execution.  Fail the rung so a broken
        # measurement can never become a headline.
        raise RuntimeError(
            f"implausible MFU {mfu:.1f} for {name} — timing sync is not "
            f"covering device execution; refusing to report")
    out = {"metric": f"tokens_per_sec_per_chip_{name}",
           "value": round(tok_s, 1), "unit": "tokens/s/chip",
           # stamped so downstream joins can refuse to
           # pair measurements from different rounds/revisions
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           # the platform the rung ran on, for downstream joins
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "step_ms": round(dt * 1e3, 2),
           # achieved model FLOPs/s: computable on ANY chip (no peaks
           # table needed) — the tournament orders rungs by this, so an
           # unknown chip kind (every mfu null) still headlines the rung
           # that did the most work, not whichever ran first
           "flops_per_s": round(achieved, 1),
           "remat": bool(cfg.remat),  # configs are NOT comparable across
           "remat_policy": _effective_remat_policy(cfg) if cfg.remat
           else None,
           "state_dtype": state_dtype, "accum": accum,
           "fused_kernels": fused,
           **_mfu_fields(mfu)}
    if idx >= 0:
        out["hbm_est_gb"] = round(_gpt_rung_estimate(
            cfg_kwargs, B, T, state_dtype, accum, fused) / 1e9, 2)
    try:
        stats = dev.memory_stats() or {}
    except Exception:  # noqa: BLE001 - CPU backends may not implement it
        stats = {}
    if stats.get("peak_bytes_in_use"):
        out["hbm_peak_gb"] = round(stats["peak_bytes_in_use"] / 1e9, 2)
    if _no_flash_requested():
        out["flash"] = False
    return _stamp_provenance(out, dev)


def _arm_results(arm_names, measure):
    """``{arm: {"tok_s": N, ...}}`` for bench_decode/bench_serving: every
    arm is measured in this process, one after the other (a chip belongs
    to one process); an arm that raises ends the run."""
    res = {}
    for arm in arm_names:
        r = measure(arm)
        # measurers may return bare tok/s or a dict with diagnostics
        # (first_token_ms, warmup_s)
        res[arm] = dict(r) if isinstance(r, dict) else {"tok_s": r}
        if arm == "int4":
            res[arm]["w4"] = {
                "enabled": os.environ.get("PADDLE_TPU_W4_KERNEL") == "1"}
    return res


def _assemble_arm_record(out, res, arm_names, ratio_ref, headline_arm,
                         log_of):
    """Fold per-arm results into the bench record: ``{arm}_tok_s``
    fields, ``{arm}_vs_{ratio_ref}`` ratios, and the headline arm's
    value."""
    ref = res[ratio_ref]["tok_s"]
    for arm in arm_names:
        r = res[arm]
        out[f"{arm}_tok_s"] = round(r["tok_s"], 1)
        for extra in ("w4", "first_token_ms", "warmup_s"):
            if extra in r:  # kernel flag / post-warmup diagnostics
                out[f"{arm}_{extra}"] = r[extra]
        _log(f"[bench] {log_of} {arm}: {r['tok_s']:,.0f} tok/s")
        if arm != ratio_ref and ref:
            out[f"{arm}_vs_{ratio_ref}"] = round(r["tok_s"] / ref, 3)
    out["value"], out["value_arm"] = (out[f"{headline_arm}_tok_s"],
                                      headline_arm)
    return out


def _fit_lm(vocab, hidden, layers, seq):
    """Small Layer LM for the hapi fit benches: Embedding -> L x
    (Linear+GELU+LayerNorm) -> vocab head, cross-entropy over every
    position — enough matmul per token for tok_s to mean something while
    the loop overheads under test (dispatch, host sync, H2D) stay the
    dominant term at small scale."""
    from paddle_tpu import nn

    mods = [nn.Embedding(vocab, hidden)]
    for _ in range(layers):
        mods += [nn.Linear(hidden, hidden), nn.GELU(),
                 nn.LayerNorm(hidden)]
    mods.append(nn.Linear(hidden, vocab))
    return nn.Sequential(*mods)


def _fit_data(n, seq, vocab, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, (n, seq + 1))
    return (toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int64))


def bench_train(small: bool):
    """hapi ``Model.fit`` training hot path: in-jit gradient accumulation
    (``grad_accum``) + async loss drain + device prefetch, versus the
    fully synchronous ``grad_accum=1`` fit loop at the SAME microbatch
    size and token count.  Reports post-warmup ``steps_s``/``tok_s`` and
    ``accum_speedup`` — accumulation folds ``accum`` dispatch+sync round
    trips into ONE jitted program, async keeps losses on device, prefetch
    overlaps batch assembly + H2D with the running step."""
    import numpy as np
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.optimizer import AdamW

    dev = jax.devices()[0]
    if small:
        vocab, hidden, layers, T, Bm, accum, steps = 256, 64, 2, 32, 4, 4, 8
    else:
        vocab, hidden, layers, T, Bm, accum, steps = 8192, 512, 4, 256, 8, 8, 16
    n = Bm * accum * steps  # same sample count for both arms
    X, Y = _fit_data(n, T, vocab)

    def arm(grad_accum, async_, prefetch):
        from paddle_tpu import telemetry as _tl

        paddle.seed(0)
        net = _fit_lm(vocab, hidden, layers, T)
        m = Model(net)
        m.prepare(AdamW(learning_rate=1e-3, parameters=net.parameters()),
                  nn.functional.cross_entropy, grad_accum=grad_accum,
                  async_metrics=async_)
        bs = Bm * grad_accum
        pf = 4 if prefetch else 0
        fit = lambda: m.fit((X, Y), batch_size=bs, epochs=1, verbose=0,
                            shuffle=False, log_freq=10 ** 9,
                            prefetch_factor=pf)
        fit()  # compile + warmup epoch
        step = m._train_step
        _sync_all((step._params, step._opt_state))
        _tl.reset()  # telemetry window = the warm timed epoch only
        t0 = time.perf_counter()
        fit()
        _sync_all((step._params, step._opt_state))
        dt = time.perf_counter() - t0
        opt_steps = n // bs
        return {"tok_s": n * T / dt, "steps_s": opt_steps / dt,
                "epoch_s": round(dt, 4),
                "telemetry": (_tl.latency_summary("train.")
                              if _tl.enabled() else {"enabled": False})}

    base = arm(1, async_=False, prefetch=False)
    over = arm(accum, async_=True, prefetch=True)
    _log(f"[bench] train fit: overlapped {over['tok_s']:,.0f} tok/s "
         f"(accum={accum}) vs sync baseline {base['tok_s']:,.0f} tok/s "
         f"-> accum_speedup {over['tok_s'] / base['tok_s']:.2f}x")
    return {"metric": "tokens_per_sec_train_fit"
                      + ("_small" if small else ""),
            "value": round(over["tok_s"], 1), "unit": "tokens/s/chip",
            "device": dev.platform,
            "device_kind": str(getattr(dev, "device_kind", "")),
            "steps_s": round(over["steps_s"], 2),
            "tok_s": round(over["tok_s"], 1),
            "baseline_tok_s": round(base["tok_s"], 1),
            "baseline_steps_s": round(base["steps_s"], 2),
            "accum_speedup": round(over["tok_s"] / base["tok_s"], 3),
            "grad_accum": accum, "async": True, "prefetch": True,
            "telemetry": over.get("telemetry", {}),
            "vs_baseline": 0.0}


def _train_smoke():
    """Accumulated + async + prefetched fit smoke, run by ``--config gpt
    --small`` (CI): exercises the exact training hot path the train bench
    measures — in-jit grad accumulation, device-resident losses, prefetch
    — on a tiny config and RAISES on parity loss vs the sync grad_accum=1
    loop, so a hot-path regression fails CI before it burns a TPU
    window."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import flags, nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.optimizer import AdamW

    vocab, hidden, T, B = 64, 32, 16, 8
    X, Y = _fit_data(24, T, vocab)

    def run(grad_accum, async_, prefetch):
        paddle.seed(0)
        net = _fit_lm(vocab, hidden, 1, T)
        m = Model(net)
        m.prepare(AdamW(learning_rate=1e-3, parameters=net.parameters()),
                  nn.functional.cross_entropy, grad_accum=grad_accum,
                  async_metrics=async_)
        hist = m.fit((X, Y), batch_size=B, epochs=2, verbose=0,
                     shuffle=False, prefetch_factor=4 if prefetch else 0)
        return hist, {k: np.asarray(p.value)
                      for k, p in net.named_parameters()}

    sync_hist, sync_p = run(1, async_=False, prefetch=False)
    over_hist, over_p = run(2, async_=True, prefetch=True)
    for k in sync_p:
        if not np.allclose(sync_p[k], over_p[k], rtol=1e-4, atol=1e-5):
            raise AssertionError(
                f"accumulated/async fit diverged from the sync loop at "
                f"{k}: max |d|="
                f"{np.abs(sync_p[k] - over_p[k]).max():.2e}")
    if not all(np.isfinite(h["loss"]) for h in over_hist):
        raise AssertionError(f"non-finite fit loss: {over_hist}")
    return {"ok": True, "epochs": len(over_hist),
            "loss": round(float(over_hist[-1]["loss"]), 4),
            "grad_accum": 2, "async": flags.async_train(),
            "prefetch": flags.fit_prefetch()}


def _decode_smoke():
    """Warmup + donated + async decode smoke, run by ``--config gpt
    --small`` (CI): exercises the exact serving hot path the TPU bench
    uses — KV-cache donation, async dispatch, warmup — on a tiny config
    and RAISES on any shape/aliasing/parity error, so a donation
    regression fails CI before it burns a TPU window."""
    import numpy as np
    import jax

    from paddle_tpu import flags, telemetry as _tl
    from paddle_tpu.text import gpt, serving

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(1, 100, (3, 5))

    _tl.reset()

    def pass_(async_):
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32,
                                   async_dispatch=async_)
        wt = srv.warmup(prompt_lens=[5], blocks=(4,)) if async_ else {}
        rids = [srv.submit(prompts[i], max_new_tokens=6) for i in range(3)]
        while srv.pending():
            srv.tick_block(4)
        return [srv.result(r) for r in rids], wt

    sync_toks, _ = pass_(False)
    async_toks, wt = pass_(True)
    if sync_toks != async_toks:
        raise AssertionError(
            f"async/sync decode divergence: {async_toks} vs {sync_toks}")
    rec = {"ok": True, "tokens": sum(len(t) for t in async_toks),
           "donate": flags.donate_decode(), "warmed": sorted(wt)}
    if _tl.enabled():
        # tier-1-safe telemetry smoke: the serving pass above must leave
        # TTFT/per-token/e2e records and a drained queue — a silent
        # telemetry regression fails CI here, not on a TPU window
        snap = _tl.snapshot()
        h = snap["histograms"]
        for name in ("serving.ttft_ms", "serving.tpot_ms",
                     "serving.e2e_ms"):
            if h.get(name, {}).get("count", 0) <= 0:
                raise AssertionError(
                    f"telemetry smoke: no {name} records after a serving "
                    f"pass (histograms: {sorted(h)})")
        if snap["gauges"].get("serving.queue_depth") != 0:
            raise AssertionError(
                f"telemetry smoke: queue_depth gauge did not return to 0 "
                f"({snap['gauges']})")
        rec["telemetry"] = _tl.latency_summary("serving.")
        if flags.device_feed_enabled():
            # the device feed must be NON-NULL after a serving pass:
            # per-compiled-step FLOPs captured at instrument_compile
            # time (cost analysis works on the CPU jit too) — a feed
            # regression fails CI here, not on a TPU window
            feed = snap.get("device", {})
            with_flops = sorted(n for n, s in feed.get("steps", {}).items()
                                if s.get("flops"))
            if not with_flops:
                raise AssertionError(
                    f"device feed is dark after a serving pass: no "
                    f"compiled step carries FLOPs "
                    f"(steps: {sorted(feed.get('steps', {}))})")
            rec["device_feed"] = {"steps": with_flops,
                                  "platform": feed.get("platform")}
    return rec


def _resilience_smoke():
    """Injected-fault round, run by ``--config gpt --small`` (CI): one
    OOM injected on a serving tick (the resilience retry chain must
    engage AND the requests still finish with tokens bit-identical to a
    fault-free pass) plus one expired deadline (shed with the timeout
    status), with the engaged ``resilience.*`` counters asserted in the
    returned record — a silent regression of the recovery paths fails CI
    before it pages an operator."""
    import time as _time

    import numpy as np
    import jax

    from paddle_tpu import faults, resilience, telemetry as _tl
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import gpt, serving

    if not resilience.enabled():
        return {"ok": True, "skipped": "PADDLE_TPU_RESILIENCE=0"}
    if not _tl.enabled():
        # the smoke ASSERTS the engaged counters, which only record with
        # telemetry on — without it the chain still engages but the
        # assertion would fail for the wrong reason
        return {"ok": True, "skipped": "PADDLE_TPU_TELEMETRY=0"}
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(1, 100, (2, 5))

    def serve(spec):
        faults.reset()
        if spec:
            faults.install(spec)
        try:
            srv = serving.DecodeServer(params, cfg, max_batch=2,
                                       max_len=32)
            rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
            while srv.pending():
                srv.tick()
            return [srv.result(r) for r in rids]
        finally:
            faults.reset()

    clean = serve("")
    _tl.reset()
    faulted = serve("oom:tick:2")
    if faulted != clean:
        raise AssertionError(
            f"resilience smoke: tokens diverged after an injected OOM "
            f"retry ({faulted} vs {clean})")
    oom_retries = int(monitor.get_stat("resilience.oom_retries").get())
    if oom_retries < 1:
        raise AssertionError(
            "resilience smoke: injected OOM engaged no retry "
            "(resilience.oom_retries == 0)")
    # deadline shed: saturate both slots, then an impossible TTL on a
    # queued third request — the next tick must shed it with the
    # timeout status while the active requests keep decoding
    srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=32)
    for p in prompts:
        srv.submit(p, max_new_tokens=8)
    rid = srv.submit(prompts[0], max_new_tokens=4, ttl_s=0.001)
    _time.sleep(0.01)
    while srv.pending():
        srv.tick()
    if srv.status(rid) != "timeout":
        raise AssertionError(
            f"resilience smoke: expired request not shed "
            f"(status={srv.status(rid)!r})")
    sheds = int(monitor.get_stat("resilience.deadline_sheds").get())
    if sheds < 1:
        raise AssertionError(
            "resilience smoke: deadline shed recorded no counter")
    return {"ok": True, "oom_retries": oom_retries,
            "deadline_sheds": sheds,
            "tokens": sum(len(t) for t in faulted)}


def _paged_smoke():
    """Paged KV-cache round, run by ``--config gpt --small`` (CI): a
    mixed-length batch must produce tokens bit-identical to the
    contiguous slab, resident blocks must stay well under slab
    provisioning, and a repeated-prefix workload must register prefix
    hits — a silent paged-parity or allocator regression fails CI
    before the layout ever defaults on."""
    import numpy as np
    import jax

    from paddle_tpu.text import gpt, serving

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    sys_prefix = [int(x) for x in rng.integers(1, 100, 8)]
    prompts = [sys_prefix + [int(x) for x in rng.integers(1, 100, n)]
               for n in (3, 5, 1)]

    def serve(layout):
        # the slab provisions max_len=64 rows for EVERY slot; the mixed
        # 9-13-token prompts + 6 generated cross 2-3 blocks each — the
        # resident-vs-slab gap below is the layout's whole point
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                                   layout=layout, block_size=8)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        while srv.pending():
            srv.tick_block(4)
        toks = [srv.result(r) for r in rids]
        stats = srv._pool.stats() if srv._pool is not None else None
        srv.close()
        return toks, stats

    cont, _ = serve("contiguous")
    paged, stats = serve("paged")
    if paged != cont:
        raise AssertionError(
            f"paged smoke: paged/contiguous token divergence "
            f"({paged} vs {cont})")
    if stats["prefix_hits"] < 1:
        raise AssertionError(
            f"paged smoke: shared prefix registered no hits ({stats})")
    # resident HBM: peak mapped blocks vs the slab's provisioning for
    # the same server (max_batch * nmax blocks, via the real rounding)
    from paddle_tpu.text import kv_pool as _kvp

    slab_blocks = 2 * (_kvp.round_len(64, 8) // 8)
    ratio = stats["peak_blocks_in_use"] / slab_blocks
    if ratio > 0.5 + 1e-9:
        raise AssertionError(
            f"paged smoke: peak resident blocks "
            f"{stats['peak_blocks_in_use']}/{slab_blocks} exceed 50% of "
            f"slab provisioning for this mixed-length batch")
    return {"ok": True, "prefix_hits": stats["prefix_hits"],
            "cow_copies": stats["cow_copies"],
            "resident_vs_slab": round(ratio, 3)}


def _fleet_smoke():
    """Disaggregated-fleet round, run by ``--config gpt --small`` (CI):
    a loopback fleet (router + 2 decode replicas + 1 prefill worker)
    must produce greedy tokens bit-identical to a single
    ``DecodeServer`` on the same request stream, and a wedge injected
    into one replica mid-stream must re-route its queued work to the
    survivor (``fleet.reroutes`` asserted) with every request's tokens
    still bit-identical — a silent fleet-parity or re-route regression
    fails CI before a real replica ever dies."""
    import numpy as np
    import jax

    from paddle_tpu import faults, resilience, telemetry as _tl
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import fleet, gpt, serving

    if not _tl.enabled():
        return {"ok": True, "skipped": "PADDLE_TPU_TELEMETRY=0"}
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(1, 100, n)]
               for n in (4, 6, 20, 5)]

    def single(**kw):
        srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=48,
                                   **kw)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        while srv.pending():
            srv.tick()
        toks = [srv.result(r) for r in rids]
        srv.close()
        return toks

    ref = single()
    # loopback fleet: long prompts (>= 16 tokens) prefill OFF the token
    # loop, rows injected — tokens must stay bit-identical
    worker = fleet.PrefillWorker(params, cfg, max_len=48)
    router = fleet.Router(
        [serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
         for _ in range(2)],
        prefill=[worker], prefill_threshold=16)
    rids = [router.submit(p, max_new_tokens=6) for p in prompts]
    while router.pending():
        router.tick()
    got = [router.result(r) for r in rids]
    tracks = router.fleet_trace()
    router.close()
    if got != ref:
        raise AssertionError(
            f"fleet smoke: loopback fleet diverged from the single "
            f"server ({got} vs {ref})")
    handoffs = int(monitor.get_stat("fleet.prefill_handoffs").get())
    if handoffs < 1:
        raise AssertionError(
            "fleet smoke: the long prompt never handed off to the "
            "prefill worker (fleet.prefill_handoffs == 0)")
    # observability round: the handed-off request must leave a COMPLETE
    # router -> worker -> replica trace (one trace_id on all three
    # track kinds) — a lost hop truncates every production waterfall
    def _tids(prefix):
        return {s["trace_id"] for nm, spans in tracks.items()
                if nm.startswith(prefix) for s in spans}
    complete = _tids("router") & _tids("worker-") & _tids("replica-")
    if not complete:
        raise AssertionError(
            f"fleet smoke: no request traced across all three process "
            f"tracks (tracks: { {nm: len(s) for nm, s in tracks.items()} })")
    names = {s["name"] for spans in tracks.values() for s in spans
             if s["trace_id"] in complete}
    need = {"queue_wait", "route", "inject", "decode", "retire"}
    if not (need <= names
            and any(n.startswith("prefill_chunk[") for n in names)):
        raise AssertionError(
            f"fleet smoke: traced request is missing spans "
            f"({sorted(need - names)} absent from {sorted(names)})")
    if not resilience.enabled():
        return {"ok": True, "prefill_handoffs": handoffs,
                "reroutes": "skipped: PADDLE_TPU_RESILIENCE=0"}
    # wedge round: saturate both replicas (1 slot each + queued work),
    # wedge the first mid-stream — its queued request must re-route to
    # the survivor and every token stream stay bit-identical
    ref2 = single(async_dispatch=True)
    r0 = int(monitor.get_stat("fleet.reroutes").get())
    env = {k: os.environ.get(k) for k in ("PADDLE_TPU_STEP_BUDGET_S",
                                          "PADDLE_TPU_FAULT_WEDGE_S")}
    os.environ["PADDLE_TPU_STEP_BUDGET_S"] = "0.25"
    os.environ["PADDLE_TPU_FAULT_WEDGE_S"] = "0.8"
    faults.install("wedge:tick:1")
    try:
        router = fleet.Router(
            [serving.DecodeServer(params, cfg, max_batch=1, max_len=48,
                                  async_dispatch=True)
             for _ in range(2)])
        rids = [router.submit(p, max_new_tokens=6) for p in prompts]
        while router.pending():
            router.tick()
        wedged = [router.result(r) for r in rids]
        router.close()
    finally:
        faults.reset()
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    if wedged != ref2:
        raise AssertionError(
            f"fleet smoke: tokens diverged after a wedged replica's "
            f"re-route ({wedged} vs {ref2})")
    reroutes = int(monitor.get_stat("fleet.reroutes").get()) - r0
    if reroutes < 1:
        raise AssertionError(
            "fleet smoke: the wedged replica's queued work never "
            "re-routed (fleet.reroutes == 0)")
    return {"ok": True, "prefill_handoffs": handoffs,
            "reroutes": reroutes}


def _stream_smoke():
    """Zero-copy KV streaming + elastic fleet round, run by ``--config
    gpt --small`` (CI): a prefill handed off CHUNK BY CHUNK over the
    raw-row transport must produce greedy tokens bit-identical to a
    single ``DecodeServer`` (``fleet.stream_chunks`` asserted — rows
    really crossed as raw buffer frames), and the autoscale drill must
    attach the registered spare on sustained overload then drain it
    back on sustained idle (``fleet.scale_outs``/``fleet.scale_ins``
    asserted) — a silent chunked-parity or topology-change regression
    fails CI before a real fleet ever streams."""
    import numpy as np
    import jax

    from paddle_tpu import telemetry as _tl
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import fleet, gpt, serving

    if not _tl.enabled():
        return {"ok": True, "skipped": "PADDLE_TPU_TELEMETRY=0"}
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    prompts = [[int(x) for x in rng.integers(1, 100, n)]
               for n in (4, 20, 6, 18)]

    def single():
        srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=48)
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        while srv.pending():
            srv.tick()
        toks = [srv.result(r) for r in rids]
        srv.close()
        return toks

    ref = single()
    env = {k: os.environ.get(k) for k in
           ("PADDLE_TPU_STREAM_CHUNK_ROWS", "PADDLE_TPU_FLEET_AUTOSCALE",
            "PADDLE_TPU_FLEET_SCALE_RUNG",
            "PADDLE_TPU_FLEET_SCALE_OUT_TICKS",
            "PADDLE_TPU_FLEET_SCALE_IN_TICKS")}
    os.environ["PADDLE_TPU_STREAM_CHUNK_ROWS"] = "4"
    try:
        worker = fleet.PrefillWorker(params, cfg, max_len=48)
        router = fleet.Router(
            [serving.DecodeServer(params, cfg, max_batch=2, max_len=48)
             for _ in range(2)],
            prefill=[worker], prefill_threshold=16)
        rids = [router.submit(p, max_new_tokens=6) for p in prompts]
        while router.pending():
            router.tick()
            if not any(r._slots or r._queue for r in router.replicas):
                time.sleep(0.002)
        got = [router.result(r) for r in rids]
        router.close()
        if got != ref:
            raise AssertionError(
                f"stream smoke: chunked streamed handoff diverged from "
                f"the single server ({got} vs {ref})")
        chunks = int(monitor.get_stat("fleet.stream_chunks").get())
        sbytes = int(monitor.get_stat("fleet.stream_bytes").get())
        if chunks < 2 or sbytes <= 0:
            raise AssertionError(
                f"stream smoke: the long prompts never streamed in "
                f"chunks (fleet.stream_chunks={chunks}, "
                f"fleet.stream_bytes={sbytes})")
        # elastic drill: sustained rung -> spare attaches; sustained
        # idle -> it drains back out, survivors untouched
        os.environ["PADDLE_TPU_FLEET_AUTOSCALE"] = "1"
        os.environ["PADDLE_TPU_FLEET_SCALE_RUNG"] = "2"
        os.environ["PADDLE_TPU_FLEET_SCALE_OUT_TICKS"] = "2"
        os.environ["PADDLE_TPU_FLEET_SCALE_IN_TICKS"] = "3"
        srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=48)
        spare = serving.DecodeServer(params, cfg, max_batch=4, max_len=48)
        router = fleet.Router([srv])
        router.register_spare(spare)
        orig = srv.load_stats
        srv.load_stats = lambda: dict(orig(), admission_rung=2,
                                      queue_depth=1)
        for _ in range(2):
            router.tick()
        live = sum(1 for r in router.replicas if r is not None)
        outs = int(monitor.get_stat("fleet.scale_outs").get())
        if live != 2 or outs != 1:
            raise AssertionError(
                f"stream smoke: sustained overload never attached the "
                f"spare (live={live}, fleet.scale_outs={outs})")
        srv.load_stats = orig
        for _ in range(3):
            router.tick()
        live = sum(1 for r in router.replicas if r is not None)
        ins = int(monitor.get_stat("fleet.scale_ins").get())
        if live != 1 or ins != 1:
            raise AssertionError(
                f"stream smoke: sustained idle never drained the spare "
                f"back (live={live}, fleet.scale_ins={ins})")
        # the drilled fleet still serves bit-identically
        rids = [router.submit(p, max_new_tokens=6) for p in prompts]
        while router.pending():
            router.tick()
        got2 = [router.result(r) for r in rids]
        router.close()
        spare.close()
        if got2 != ref:
            raise AssertionError(
                f"stream smoke: tokens diverged after the scale drill "
                f"({got2} vs {ref})")
    finally:
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return {"ok": True, "stream_chunks": chunks, "stream_bytes": sbytes,
            "scale_outs": outs, "scale_ins": ins}


def _spec_smoke():
    """Speculative-decoding round, run by ``--config gpt --small`` (CI):
    a draft-model spec server must produce greedy tokens bit-identical
    to the plain server on the same request stream while spending at
    least 1.5x fewer target-model passes per generated token, and a
    self-drafting (n-gram) server on a repetitive prompt must hold the
    same bit-parity — a silent acceptance regression or a spec/plain
    divergence fails CI before speculation ever defaults on."""
    import numpy as np
    import jax

    from paddle_tpu.text import gpt, serving

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [[int(x) for x in rng.integers(1, 100, n)] for n in (4, 7)]

    def serve(**kw):
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                                   **kw)
        rids = [srv.submit(p, max_new_tokens=12) for p in prompts]
        while srv.pending():
            srv.tick()
        toks = [srv.result(r) for r in rids]
        passes = (srv._spec_rounds + srv._spec_plain_steps
                  if srv._spec_on else srv._step_no)
        srv.close()
        return toks, passes

    ref, plain_passes = serve()
    # draft == target: every proposal is accepted, so the pass count
    # collapses toward new_tokens / K — the smoke's speedup gate
    spec, spec_passes = serve(draft_cfg=cfg, draft_params=params,
                              spec_k=4)
    if spec != ref:
        raise AssertionError(
            f"spec smoke: speculative/plain token divergence "
            f"({spec} vs {ref})")
    total = sum(len(t) for t in ref)
    ratio = (plain_passes / total) / max(spec_passes / total, 1e-9)
    if ratio < 1.5:
        raise AssertionError(
            f"spec smoke: speculation spent {spec_passes} target passes "
            f"for {total} tokens vs {plain_passes} plain — "
            f"{ratio:.2f}x < 1.5x fewer passes per token")
    # self-draft round: a repetitive prompt the host n-gram drafter can
    # exploit; parity is the assertion, speedup is reported only (the
    # n-gram hit rate on a random-model stream is workload luck)
    rep = [7, 3, 7, 3, 7, 3, 7, 3]
    def serve_rep(**kw):
        srv = serving.DecodeServer(params, cfg, max_batch=1, max_len=64,
                                   **kw)
        rid = srv.submit(rep, max_new_tokens=12)
        while srv.pending():
            srv.tick()
        toks = srv.result(rid)
        srv.close()
        return toks

    ref_rep = serve_rep()
    got_rep = serve_rep(spec_k=4)
    if got_rep != ref_rep:
        raise AssertionError(
            f"spec smoke: self-draft token divergence "
            f"({got_rep} vs {ref_rep})")
    # tree round (round 17): a draft whose argmax chain is WRONG at a
    # known position but whose top-2 sibling is right — linear
    # speculation dies at the first divergence, the tree's branch
    # recovers it, so at the same per-round row budget the tree must be
    # bit-identical to plain AND spend strictly fewer target passes
    # than linear-K
    bad = dict(params)
    bad["ln_f_b"] = params["ln_f_b"] + 30.0 * params["wte"][42]
    tree, tree_passes = serve(draft_cfg=cfg, draft_params=bad,
                              spec_tree=4)
    if tree != ref:
        raise AssertionError(
            f"spec smoke: tree/plain token divergence "
            f"({tree} vs {ref})")
    lin, lin_passes = serve(draft_cfg=cfg, draft_params=bad, spec_k=4)
    if lin != ref:
        raise AssertionError(
            f"spec smoke: biased-draft linear/plain divergence "
            f"({lin} vs {ref})")
    if tree_passes >= lin_passes:
        raise AssertionError(
            f"spec smoke: tree verify spent {tree_passes} target passes "
            f"vs linear-K's {lin_passes} at the same 4-row budget — "
            f"branching bought nothing")
    return {"ok": True, "plain_target_passes": plain_passes,
            "spec_target_passes": spec_passes,
            "passes_per_token_speedup": round(ratio, 3),
            "tree_target_passes": tree_passes,
            "linear_target_passes_biased": lin_passes}


def _mixed_smoke():
    """Budgeted-admission round, run by ``--config gpt --small`` (CI):
    chunked-prefill co-scheduling must produce greedy tokens
    bit-identical to monolithic admission on the same mixed stream
    (contiguous AND paged), actually interleave its chunks
    (``serving.prefill_chunks_interleaved`` asserted), and hold the
    mixed decode-gap p99 at or below the monolithic server's — a
    silent parity or co-scheduling regression fails CI before
    ``PADDLE_TPU_PREFILL_BUDGET`` ever defaults on."""
    import numpy as np
    import jax

    from paddle_tpu import telemetry as _tl
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import gpt, serving

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    shorts = [[int(x) for x in rng.integers(1, 100, n)] for n in (4, 6, 5)]
    long_p = [int(x) for x in rng.integers(1, 100, 48)]
    budget = 8

    def serve(budget_, layout="contiguous"):
        srv = serving.DecodeServer(params, cfg, max_batch=4, max_len=64,
                                   layout=layout,
                                   prefill_budget=budget_)
        sched = [(0, p) for p in shorts] + [(3, long_p)]
        rids, gaps, it = [], [], 0
        while sched or srv.pending():
            act = len(srv._slots) > 0
            t0 = time.perf_counter()
            while sched and sched[0][0] <= it:
                rids.append(srv.submit(sched.pop(0)[1],
                                       max_new_tokens=6))
            srv.tick()
            if act:
                gaps.append((time.perf_counter() - t0) * 1e3)
            it += 1
        # no srv.close(): it would evict the compiled executables the
        # next pass needs (see bench_mixed) — GC reclaims the KV cache
        return [srv.result(r) for r in rids], gaps

    for layout in ("contiguous", "paged"):
        ref, _ = serve(0, layout)
        got, _ = serve(budget, layout)
        if got != ref:
            raise AssertionError(
                f"mixed smoke: budgeted/monolithic token divergence "
                f"under {layout} ({got} vs {ref})")
    if not _tl.enabled():
        return {"ok": True, "gap_assert": "skipped: PADDLE_TPU_TELEMETRY=0"}
    c0 = int(monitor.get_stat("serving.prefill_chunks_interleaved").get())
    # warm both arms, then measure (compile noise out of the gaps)
    serve(0), serve(budget)
    passes_mono = [serve(0)[1] for _ in range(2)]
    chunks0 = int(
        monitor.get_stat("serving.prefill_chunks_interleaved").get())
    passes = [serve(budget)[1] for _ in range(2)]
    chunks = int(
        monitor.get_stat("serving.prefill_chunks_interleaved").get())
    # the 48-token long prompt at budget 8 walks ceil(48/8)=6 chunks
    # per budgeted pass — zero means the claim gate never engaged
    if chunks - chunks0 < 6:
        raise AssertionError(
            f"mixed smoke: budgeted admission interleaved "
            f"{chunks - chunks0} chunks (expected >= 6) — the claim "
            f"gate never engaged (c0={c0})")

    def p99(g):
        return float(np.percentile(np.asarray(g), 99)) if g else 0.0

    gap_bud = min(p99(g) for g in passes)
    gap_mono = min(p99(g) for g in passes_mono)
    tol = float(os.environ.get("BENCH_MIXED_SMOKE_TOL", "1.0"))
    if gap_bud > gap_mono * tol:
        raise AssertionError(
            f"mixed smoke: budgeted mixed decode-gap p99 "
            f"({gap_bud:.2f}ms) exceeds monolithic "
            f"({gap_mono:.2f}ms) x {tol} — co-scheduling is "
            f"stalling instead of absorbing the long prefill")
    return {"ok": True, "chunks_interleaved": chunks - chunks0,
            "gap_p99_budgeted_ms": round(gap_bud, 2),
            "gap_p99_monolithic_ms": round(gap_mono, 2)}


def _overload_smoke():
    """Overload-drill round, run by ``--config gpt --small`` (CI): with
    a tight TPOT SLO and an injected per-tick delay
    (``delay:tick:0:0.03``) the admission controller must climb the
    degradation ladder off real windowed p99s
    (``admission.degradations`` asserted), bound the low-priority queue
    with sheds (``admission.sheds_class0`` asserted; a shed request
    carries the ``rejected`` status and raises
    ``resilience.Overloaded`` from ``result()``), keep a high-priority
    request alive to completion, reset to rung 0 once the burst drains
    (idle-window reset), and add ZERO compiled executables after
    ``warmup()`` — a mid-serving retrace from budget-rung switching is
    the regression this guards."""
    import time as _time

    import numpy as np
    import jax

    from paddle_tpu import faults, flags, resilience, telemetry as _tl
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import gpt, serving

    if not flags.admission_enabled():
        return {"ok": True, "skipped": "PADDLE_TPU_ADMISSION=0"}
    if not _tl.enabled():
        return {"ok": True, "skipped": "PADDLE_TPU_TELEMETRY=0"}

    def cnt(name):
        try:
            return int(monitor.get_stat(name).get())
        except Exception:
            return 0

    env = {"PADDLE_TPU_SLO_TPOT_MS": "10",
           "PADDLE_TPU_SLO_WINDOW_S": "0.1",
           "PADDLE_TPU_ADMISSION_QUEUE_CAP": "1"}
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    bulk_prompts = [[int(x) for x in rng.integers(1, 100, 24)]
                    for _ in range(8)]
    gold_prompt = [int(x) for x in rng.integers(1, 100, 6)]
    try:
        faults.reset()
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=64,
                                   prefill_budget=32)
        if srv._adm is None:
            raise AssertionError(
                "overload smoke: PADDLE_TPU_ADMISSION=1 but the server "
                "built no admission controller")
        srv.warmup()
        keys0 = set(serving._STEP_CACHE.keys())
        _tl.reset()
        c0 = {n: cnt(n) for n in ("admission.degradations",
                                  "admission.sheds_class0")}
        faults.install("delay:tick:0:0.03")
        gold = srv.submit(gold_prompt, max_new_tokens=1, priority=2,
                          tenant="gold")
        bulk = [srv.submit(p, max_new_tokens=12, priority=0,
                           tenant="bulk") for p in bulk_prompts]
        rung_max = 0
        t0 = _time.perf_counter()
        while srv.pending() and _time.perf_counter() - t0 < 30:
            srv.tick()
            rung_max = max(rung_max, srv._adm.rung)
        if srv.status(gold) != "ok":
            raise AssertionError(
                f"overload smoke: high-priority request did not survive "
                f"the burst (status={srv.status(gold)!r})")
        rejected = [r for r in bulk if srv.status(r) == "rejected"]
        if not rejected:
            raise AssertionError(
                "overload smoke: no low-priority request was shed at "
                "queue cap 1 under an 8-request burst")
        try:
            srv.result(rejected[0])
            raise AssertionError(
                "overload smoke: a rejected request's result() returned "
                "instead of raising resilience.Overloaded")
        except resilience.Overloaded:
            pass
        degr = cnt("admission.degradations") - c0["admission.degradations"]
        sheds0 = (cnt("admission.sheds_class0")
                  - c0["admission.sheds_class0"])
        if degr < 1 or rung_max < 2:
            raise AssertionError(
                f"overload smoke: SLO breach climbed no ladder "
                f"(degradations={degr}, rung_max={rung_max}) with decode "
                f"gaps ~30ms against a 10ms TPOT SLO")
        if sheds0 < 1:
            raise AssertionError(
                "overload smoke: sheds engaged no admission.sheds_class0 "
                "counter")
        # burst drained: idle ticks must walk the controller back to
        # rung 0 (the sample-free idle window resets it outright)
        t_idle = _time.perf_counter()
        while srv._adm.rung > 0 and _time.perf_counter() - t_idle < 3.0:
            srv.tick()
            _time.sleep(0.01)
        recovery_s = _time.perf_counter() - t_idle
        if srv._adm.rung != 0:
            raise AssertionError(
                f"overload smoke: controller stuck at rung "
                f"{srv._adm.rung} {recovery_s:.2f}s after the burst "
                f"drained")
        added = set(serving._STEP_CACHE.keys()) - keys0
        if added:
            raise AssertionError(
                f"overload smoke: budget-rung switching retraced "
                f"mid-serving — new executables {sorted(added)}")
        return {"ok": True, "rung_max": rung_max, "degradations": degr,
                "sheds_class0": sheds0, "rejected": len(rejected),
                "recovery_s": round(recovery_s, 3)}
    finally:
        faults.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _multilora_smoke():
    """Multi-tenant adapter round, run by ``--config gpt --small`` (CI):
    a 2-adapter batch must match each adapter's solo (merged-tree)
    greedy decode token-for-token, a JSON-schema-constrained request
    must complete PARSEABLE JSON, and serving the mixed stream after
    ``warmup()`` must add zero ``_STEP_CACHE`` entries — a gather/mask
    parity or retrace regression fails CI before a pool ever ships."""
    import json as _json

    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import adapters, gpt, lora, serving

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))

    def mk_adapter(seed):
        key = jax.random.PRNGKey(seed)
        ad = lora.split_lora(lora.lora_init(params, cfg, rank=4,
                                            key=key))[1]
        out = {}
        for name, v in ad.items():
            if name.endswith("_lora_b"):
                key, sub = jax.random.split(key)
                out[name] = 0.3 * jax.random.normal(sub, v.shape,
                                                    jnp.float32)
            else:
                out[name] = v
        return out

    ads = {"prod-a": mk_adapter(1), "prod-b": mk_adapter(2)}
    pool = adapters.AdapterPool(params, cfg, rank=4, max_adapters=2)
    for name, ad in ads.items():
        pool.register(name, ad)
    rng = np.random.default_rng(7)
    prompts = {name: [int(x) for x in rng.integers(1, 100, 5)]
               for name in ads}

    def solo_greedy(p, prompt, max_new):
        from paddle_tpu.text import generate as G
        cache = G.init_cache(cfg, 1, cfg.max_seq_len)
        out, tok = [], None
        for pos in range(len(prompt) + max_new - 1):
            cur = prompt[pos] if pos < len(prompt) else tok
            l, cache = G.decode_step(p, cache,
                                     jnp.asarray([cur], jnp.int32),
                                     pos, cfg)
            if pos >= len(prompt) - 1:
                tok = int(np.asarray(jnp.argmax(l, -1))[0])
                out.append(tok)
        return out

    # token id == char code: the schema automaton walks decoded bytes
    vocab = [chr(i) for i in range(cfg.vocab_size)]
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"}}}
    spec = adapters.JsonSchemaConstraint(schema, vocab)

    srv = serving.DecodeServer(params, cfg, max_batch=3, max_len=64,
                               adapter_pool=pool)
    srv.warmup(sample=True, constrained=True)
    keys0 = set(serving._STEP_CACHE.keys())
    rids = {name: srv.submit(prompts[name], max_new_tokens=10,
                             adapter=name) for name in ads}
    rid_c = srv.submit([int(x) for x in rng.integers(1, 100, 4)],
                       max_new_tokens=20, constraint=spec)
    while srv.pending():
        srv.tick()
    got = {name: srv.result(r) for name, r in rids.items()}
    text = "".join(vocab[t] for t in srv.result(rid_c))
    srv.close()
    for name in ads:
        want = solo_greedy(lora.join_lora(params, ads[name]),
                           prompts[name], 10)
        if got[name] != want:
            raise AssertionError(
                f"multilora smoke: adapter {name!r} batched tokens "
                f"diverge from its merged-tree solo decode "
                f"({got[name]} vs {want})")
    doc = _json.loads(text)                  # raises = smoke fails
    if not isinstance(doc.get("ok"), bool):
        raise AssertionError(
            f"multilora smoke: constrained output {text!r} is not the "
            f"schema's shape")
    added = set(serving._STEP_CACHE.keys()) - keys0
    if added:
        raise AssertionError(
            f"multilora smoke: post-warmup serving retraced — new "
            f"executables {sorted(added)}")
    return {"ok": True, "adapters": len(ads),
            "constrained_json": text}


def _prefix_smoke():
    """Fleet-scale prefix-cache round, run by ``--config gpt --small``
    (CI): on a shared preamble that diverges MID-BLOCK, token-granular
    radix matching must register a strictly higher prefix hit rate than
    the whole-block baseline (``PADDLE_TPU_KV_RADIX=0``) with greedy
    tokens bit-identical across both arms and the contiguous slab; a
    spill->restore cycle (cold chains demoted to host RAM, re-admitted
    through the existing inject executables) must stay greedy
    bit-identical while saving >= 90% of the re-prefill rows; and the
    second spill->restore cycle must add zero new executables."""
    import numpy as np
    import jax

    from paddle_tpu.text import gpt, serving

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    # 20-token preamble over 8-token blocks: the divergence point (20)
    # sits mid-block, so whole-block matching can only share 16 tokens
    # while the radix split shares all 20
    pre = [int(x) for x in rng.integers(1, 100, 20)]
    prompts = [pre + [int(x) for x in rng.integers(1, 100, 4)]
               for _ in range(3)]

    env_keys = ("PADDLE_TPU_KV_RADIX", "PADDLE_TPU_KV_SPILL_MB")
    env0 = {k: os.environ.get(k) for k in env_keys}

    def _set(**env):
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def serve(layout, radix):
        _set(PADDLE_TPU_KV_RADIX=radix, PADDLE_TPU_KV_SPILL_MB=None)
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=40,
                                   layout=layout, block_size=8)
        toks = []
        for p in prompts:           # sequential: later prompts adopt
            rid = srv.submit(p, max_new_tokens=6)
            while srv.pending():
                srv.tick()
            toks.append(srv.result(rid))
        stats = srv._pool.stats() if srv._pool is not None else None
        srv.close()
        return toks, stats

    try:
        cont, _ = serve("contiguous", "1")
        tok_radix, s_radix = serve("paged", "1")
        tok_block, s_block = serve("paged", "0")
        if tok_radix != cont or tok_block != cont:
            raise AssertionError(
                f"prefix smoke: paged arms diverged from the contiguous "
                f"slab (radix {tok_radix} / block {tok_block} vs {cont})")

        def rate(s):
            return s["prefix_hits"] / max(
                1, s["prefix_hits"] + s["prefix_misses"])

        if s_radix["radix_splits"] < 1:
            raise AssertionError(
                f"prefix smoke: the mid-block divergence never split a "
                f"radix node ({s_radix})")
        if rate(s_radix) <= rate(s_block):
            raise AssertionError(
                f"prefix smoke: token-granular hit rate "
                f"{rate(s_radix):.3f} does not beat the whole-block "
                f"baseline {rate(s_block):.3f}")

        # spill->restore: serve, demote the whole cold chain to host
        # RAM, re-serve — bit-identical tokens, >= 90% of re-prefill
        # rows adopted from restored blocks instead of recomputed
        _set(PADDLE_TPU_KV_RADIX="1", PADDLE_TPU_KV_SPILL_MB="4")
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=40,
                                   layout="paged", block_size=8)
        pool = srv._pool
        spill_prompt = prompts[0]            # 3 full blocks, aligned

        def cycle():
            rid = srv.submit(spill_prompt, max_new_tokens=6)
            while srv.pending():
                srv.tick()
            first = srv.result(rid)
            for _ in range(16):
                if not pool._interned:
                    break
                srv._evict_or_spill(8)
            hits0 = pool.prefix_hits
            rid = srv.submit(spill_prompt, max_new_tokens=6)
            while srv.pending():
                srv.tick()
            return first, srv.result(rid), pool.prefix_hits - hits0

        first, again, saved = cycle()
        if first != cont[0]:
            raise AssertionError(
                f"prefix smoke: spill-arm serve diverged from the "
                f"contiguous slab ({first} vs {cont[0]})")
        s = pool.stats()
        if s["spilled_blocks"] < 1 or s["restored_blocks"] < 1:
            raise AssertionError(
                f"prefix smoke: spill->restore cycle never moved a "
                f"block through host RAM ({s})")
        if again != first:
            raise AssertionError(
                f"prefix smoke: tokens diverged after a spill->restore "
                f"cycle ({again} vs {first})")
        need = 0.9 * (len(spill_prompt) - 1)
        if saved < need:
            raise AssertionError(
                f"prefix smoke: restore saved only {saved} re-prefill "
                f"rows (< {need:.0f} of {len(spill_prompt) - 1})")
        keys0 = set(serving._STEP_CACHE.keys())
        first2, again2, _ = cycle()          # post-warmup pass
        if again2 != first or first2 != first:
            raise AssertionError(
                f"prefix smoke: second spill->restore cycle diverged "
                f"({first2}/{again2} vs {first})")
        added = set(serving._STEP_CACHE.keys()) - keys0
        if added:
            raise AssertionError(
                f"prefix smoke: post-warmup spill->restore retraced — "
                f"new executables {sorted(added)}")
        hit_rate = rate(pool.stats())
        srv.close()
    finally:
        _set(**env0)
    return {"ok": True, "radix_hit_rate": round(rate(s_radix), 3),
            "block_hit_rate": round(rate(s_block), 3),
            "radix_splits": s_radix["radix_splits"],
            "spilled_blocks": s["spilled_blocks"],
            "restored_blocks": s["restored_blocks"],
            "spill_cycle_hit_rate": round(hit_rate, 3)}


def _moe_smoke():
    """MoE serving round, run by ``--config gpt --small`` (CI): joint-
    routing decode through the Engine's moe_* kinds must be greedy
    bit-identical to the capacity-free dense-eval reference on BOTH
    layouts at a dropless capacity factor with ZERO device-counted
    drops; the capacity-overflow drop counter must equal host-replayed
    routing exactly at cf=0.5; a re-serve after warmup must add zero
    executables."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import gpt, moe_serving, serving
    from paddle_tpu.text.moe import MoEConfig

    base = dict(vocab_size=128, hidden_size=64, num_layers=2,
                num_heads=4, max_seq_len=64)
    cfg = gpt.GPTConfig(moe=MoEConfig(num_experts=4, top_k=2,
                                      capacity_factor=1.25,
                                      router_noise=0.0), **base)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(23)
    prompts = [[int(x) for x in rng.integers(1, 120, n)] for n in (6, 5)]
    ref = [moe_serving.dense_reference_greedy(params, cfg, p, 8, 40)
           for p in prompts]

    def serve(**kw):
        srv = serving.DecodeServer(params, cfg, max_batch=2, max_len=40,
                                   **kw)
        rids = [srv.submit(p, max_new_tokens=8) for p in prompts]
        while srv.pending():
            srv.tick()
        return srv, [srv.result(r) for r in rids], srv.load_stats()

    srv_c, toks_c, ls_c = serve()
    srv_p, toks_p, ls_p = serve(layout="paged", block_size=8)
    for name, toks, ls in (("contiguous", toks_c, ls_c),
                           ("paged", toks_p, ls_p)):
        if toks != ref:
            raise AssertionError(
                f"moe smoke: {name} joint-routing tokens diverged from "
                f"the dense-eval reference ({toks} vs {ref})")
        if ls["moe_dropped_tokens"] != 0:
            raise AssertionError(
                f"moe smoke: {name} dropless round counted "
                f"{ls['moe_dropped_tokens']} dropped assignments")

    # post-warmup: the same shapes must hit the Engine LRU, never the
    # compiler (srv_p stays open — close() evicts by config VALUE and
    # both servers share the cfg)
    keys0 = set(serving._STEP_CACHE.keys())
    rid = srv_c.submit(prompts[0], max_new_tokens=8)
    while srv_c.pending():
        srv_c.tick()
    again = srv_c.result(rid)
    added = set(serving._STEP_CACHE.keys()) - keys0
    srv_c.close()
    srv_p.close()
    if again != ref[0]:
        raise AssertionError(
            f"moe smoke: warm re-serve diverged ({again} vs {ref[0]})")
    if added:
        raise AssertionError(
            f"moe smoke: post-warmup re-serve retraced — new "
            f"executables {sorted(added)}")

    # capacity overflow: zeroed router -> uniform softmax -> top_k
    # tie-break sends every token to experts {0, 1}; at cf=0.5 with
    # max_batch=2 the capacity is C=1, a schedule the host replays
    # exactly — the device counter must equal it
    ocfg = gpt.GPTConfig(moe=MoEConfig(num_experts=4, top_k=2,
                                       capacity_factor=0.5,
                                       router_noise=0.0), **base)
    oparams = gpt.init_params(ocfg, jax.random.PRNGKey(3))
    oparams["blocks"]["moe"]["router_w"] = jnp.zeros_like(
        oparams["blocks"]["moe"]["router_w"])
    L = ocfg.num_layers
    srv = serving.DecodeServer(oparams, ocfg, max_batch=2, max_len=32)
    rids = [srv.submit([1, 2], max_new_tokens=4),
            srv.submit([3, 4, 5], max_new_tokens=4)]
    exp_dropped = 0
    while srv.pending():
        active = sum(1 for st in srv._slots.values()
                     if not st.get("admitting"))
        srv.tick()
        if active:
            exp_dropped += 2 * L * max(0, active - 1)
    dropped = srv.load_stats()["moe_dropped_tokens"]
    srv.close()
    if exp_dropped <= 0:
        raise AssertionError("moe smoke: overflow schedule never bit")
    if dropped != exp_dropped:
        raise AssertionError(
            f"moe smoke: device drop counter {dropped} != host-replayed "
            f"routing {exp_dropped} — 'bounded drop rate' is a guess")
    return {"ok": True, "expert_load": ls_c["moe_expert_load"],
            "overflow_drops": dropped, "drop_counter_exact": True}


def bench_gpt(small: bool):
    if small:
        rec = _run_gpt_rung(-1)
        rec["decode_smoke"] = _decode_smoke()
        # training hot path rides the same CI smoke: grad-accum + async +
        # prefetch fit parity vs the sync loop (BENCH gets a train number)
        rec["train_smoke"] = _train_smoke()
        # resilience layer rides the CI smoke too: an injected fault
        # round proves the retry chain + deadline shedding still work
        # (counters asserted inside)
        rec["resilience_smoke"] = _resilience_smoke()
        # paged KV cache rides the CI smoke: parity + prefix hits +
        # resident-blocks-vs-slab asserted (see _paged_smoke)
        rec["paged_smoke"] = _paged_smoke()
        # disaggregated fleet rides the CI smoke: loopback parity +
        # wedge re-route counter asserted (see _fleet_smoke)
        rec["fleet_smoke"] = _fleet_smoke()
        # zero-copy KV streaming + elastic fleet ride the CI smoke:
        # chunked raw-row handoff bit-parity + the autoscale drill
        # (scale-out to a spare, scale-in on idle) asserted — see
        # _stream_smoke
        rec["stream_smoke"] = _stream_smoke()
        # speculative decoding rides the CI smoke: draft-model and
        # self-draft bit-parity + >=1.5x fewer target passes per token
        # asserted (see _spec_smoke)
        rec["spec_smoke"] = _spec_smoke()
        # budgeted admission rides the CI smoke: chunked-prefill
        # co-scheduling bit-parity (contiguous + paged) + interleave
        # counter + mixed decode-gap bound asserted (see _mixed_smoke)
        rec["mixed_smoke"] = _mixed_smoke()
        # admission control rides the CI smoke: SLO-driven ladder climb,
        # low-priority sheds + Overloaded, idle recovery to rung 0, and
        # zero mid-serving retraces asserted (see _overload_smoke)
        rec["overload_smoke"] = _overload_smoke()
        # fleet-scale prefix cache rides the CI smoke: token-granular
        # hit rate beats the whole-block baseline, spill->restore
        # bit-parity with >=90% re-prefill rows saved, zero post-warmup
        # retraces asserted (see _prefix_smoke)
        rec["prefix_smoke"] = _prefix_smoke()
        # multi-tenant adapter serving rides the CI smoke: 2-adapter
        # batch parity vs merged-tree solo decode + a JSON-schema-
        # constrained request completing valid JSON + zero post-warmup
        # retraces asserted (see _multilora_smoke)
        rec["multilora_smoke"] = _multilora_smoke()
        # MoE serving rides the CI smoke: joint-routing decode parity
        # vs the capacity-free dense-eval reference on both layouts,
        # exact host-replayed drop accounting at overflow, zero
        # post-warmup retraces asserted (see _moe_smoke)
        rec["moe_smoke"] = _moe_smoke()
        # provenance-schema gate (CI): a bench line whose provenance
        # block is missing or incomplete must fail the smoke
        prov = rec.get("provenance")
        missing = [k for k in _PROVENANCE_KEYS
                   if not isinstance(prov, dict) or k not in prov]
        if missing:
            raise AssertionError(
                f"provenance block missing keys {missing} "
                f"(block: {prov!r})")
        return rec

    # full ladder, in THIS process (a chip belongs to one process), with a
    # static HBM-footprint pre-filter so hopeless rungs don't burn 2-min
    # OOM compiles.  TOURNAMENT: the rung *order* encodes an MFU guess,
    # but the guess has been wrong before — so instead of headlining the
    # first fitting rung, keep measuring until BENCH_LADDER_TOP rungs
    # have succeeded (default 3) and headline the best measured MFU.  A
    # rung that runs out of device memory is the ladder's own business
    # (next rung); anything else a rung raises ends the run.
    from paddle_tpu.resilience import is_oom

    hbm = _hbm_bytes()
    top_k = int(os.environ.get("BENCH_LADDER_TOP", "3"))
    rungs = list(_gpt_rungs())
    results = []
    last_fail = None
    budget_s = float(os.environ.get("BENCH_TOURNAMENT_BUDGET", "1500"))
    t_start = time.perf_counter()
    for i, (name, cfg_kwargs, B, T, iters, sd, accum, fused) in enumerate(
            rungs):
        if len(results) >= top_k:
            break
        if results and time.perf_counter() - t_start > budget_s:
            # one number is banked: don't let the tournament's extra arms
            # overrun the caller's budget
            _log(f"[bench] tournament budget ({budget_s:.0f}s) spent — "
                 f"headlining best of {len(results)} measured rung(s)")
            break
        if not _gpt_rung_fits(name, cfg_kwargs, B, T, sd, hbm, accum,
                              fused):
            _log(f"[bench] {name}: skipped (estimated footprint exceeds "
                 f"{hbm / 1e9:.0f} GB HBM)")
            continue
        _log(f"[bench] {name}: attempting")
        try:
            results.append(_run_gpt_rung(i))
        except Exception as e:  # noqa: BLE001 - only an OOM is stepped past
            if not is_oom(e):
                raise
            last_fail = f"{name}: out of device memory"
            _log(f"[bench] {last_fail}; trying next rung")
    if results:
        # achieved FLOPs/s orders identically to MFU on one chip (same
        # peak divisor)
        best = max(results, key=lambda r: (r.get("flops_per_s")
                                           or r.get("mfu") or 0.0))
        if len(results) > 1:
            best = dict(best)
            best["candidates"] = [
                {"metric": r["metric"], "mfu": r.get("mfu"),
                 "value": r.get("value"), "step_ms": r.get("step_ms")}
                for r in results]
        _log("[bench] tournament: "
             + "; ".join(f"{r['metric']}={r.get('mfu')}" for r in results)
             + f" -> headline {best['metric']}")
        return best
    raise RuntimeError(f"no GPT rung ran (last: {last_fail})")


def bench_bert(small: bool):
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.text import bert

    dev = jax.devices()[0]
    if small:
        cfg = bert.BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                              num_heads=4, max_seq_len=128)
        ladder, T, K, iters = [2], 128, 20, 3
    else:
        cfg = bert.bert_base()
        # B=64 first (round-5: B=32 measured MFU 0.311 with HBM to
        # spare — bigger batches fill the MXU; the walk falls back on OOM)
        ladder, T, K, iters = [64, 32, 16, 8], 512, 76, 10

    opt = AdamW(learning_rate=1e-4)
    key = jax.random.PRNGKey(0)

    def make_batch(B):
        rng = np.random.default_rng(0)
        return {
            "input_ids": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32),
            "mlm_positions": jnp.asarray(
                np.sort(rng.integers(0, T, (B, K)), axis=1), jnp.int32),
            "mlm_labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, K)), jnp.int32),
            "nsp_labels": jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32),
        }

    @jax.jit
    def step(params, opt_state, batch, step_i):
        loss, grads = jax.value_and_grad(bert.pretrain_loss)(
            params, batch, cfg)
        params, opt_state = opt.apply_gradients(
            grads, params, opt_state, lr=1e-4, step=step_i)
        return params, opt_state, loss

    last_err = None
    for B in ladder:
        try:
            params = bert.init_params(cfg, key)
            opt_state = opt.init_state(params)
            batch = make_batch(B)
            params, opt_state, loss = step(params, opt_state, batch, 1)
            # the OOM that steps this ladder down must surface inside
            # THIS try
            jax.block_until_ready(loss)
            break
        except Exception as e:
            last_err = e
            _log(f"[bench] bert B={B} failed ({type(e).__name__}); "
                 f"trying next")
    else:
        raise last_err

    st = {"p": params, "o": opt_state, "l": loss}

    def one():
        st["p"], st["o"], st["l"] = step(st["p"], st["o"], batch, 1)

    dt = _time_steps(one, iters, lambda: (st["p"], st["o"], st["l"]))
    # matmul-weight flops: blocks + mlm head (tied wte, applied on K of T)
    D, F, L, V = cfg.hidden_size, cfg.ffn_size, cfg.num_layers, cfg.vocab_size
    per_tok = 6 * L * (4 * D * D + 2 * D * F) + 12 * L * D * T
    per_seq = per_tok * T + 6 * (V * D + D * D) * K
    samp_s = B / dt
    peak = _peak_flops(dev)
    mfu = (per_seq * samp_s / peak) if peak else None
    _log(f"[bench] bert_base: {samp_s:,.1f} seq/s ({samp_s * T:,.0f} tok/s) "
         f"step={dt * 1e3:.1f}ms loss={float(st['l']):.4f} "
         f"MFU={'null' if mfu is None else f'{mfu:.3f}'}")
    if mfu is not None and mfu >= 1.0:
        raise RuntimeError(f"implausible MFU {mfu:.1f} — timing sync is "
                           f"not covering device execution")
    return {"metric": "sequences_per_sec_per_chip_bert_base",
            "value": round(samp_s, 2), "unit": "sequences/s/chip",
            "device": dev.platform, "step_ms": round(dt * 1e3, 2),
            **_mfu_fields(mfu)}


def _layer_train_bench(name, net, X, Y, iters, lr=0.01, flops_per_step=None,
                       amp=False):
    """Shared TrainStep-based bench for Layer models (LeNet/ResNet).

    ``amp=True`` traces the step under ``paddle_tpu.amp.auto_cast`` (O1
    bf16 white-list — the casts bake into the compiled program), the
    TPU-first training config: conv/matmul ride the MXU at bf16 instead
    of fp32."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from paddle_tpu import nn
    from paddle_tpu.amp import auto_cast
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import Momentum

    dev = jax.devices()[0]
    # device-resident inputs: numpy args re-upload per step (38.5 MB of
    # fp32 images per call at ResNet-50 B=64), and that transfer would
    # be timed as the step
    X, Y = jnp.asarray(X), jnp.asarray(Y)
    opt = Momentum(learning_rate=lr, momentum=0.9, parameters=net.parameters())
    step = TrainStep(net, nn.functional.cross_entropy, opt)
    loss_box = {}

    def one():
        loss_box["l"] = step(X, Y)

    with auto_cast() if amp else contextlib.nullcontext():
        dt = _time_steps(one, iters,
                         lambda: (step._params, step._buffers,
                                  step._opt_state, loss_box["l"].value))
    B = X.shape[0]
    samp_s = B / dt
    out = {"metric": f"samples_per_sec_per_chip_{name}",
           "value": round(samp_s, 1), "unit": "samples/s/chip",
           "device": dev.platform, "step_ms": round(dt * 1e3, 2),
           "vs_baseline": 0.0}
    if flops_per_step is not None:
        peak = _peak_flops(dev)
        mfu = (flops_per_step / dt / peak) if peak else None
        if mfu is not None and mfu >= 1.0:
            raise RuntimeError(f"implausible MFU {mfu:.1f} — timing sync "
                               f"is not covering device execution")
        out.update(_mfu_fields(mfu))
    _log(f"[bench] {name}: {samp_s:,.1f} samples/s step={dt * 1e3:.1f}ms "
         f"loss={float(loss_box['l'].value):.4f}"
         + (f" MFU={out['mfu']:.3f}"
            if out.get("mfu") is not None else ""))
    return out


def bench_mnist(small: bool):
    import numpy as np

    from paddle_tpu.vision.models import LeNet

    B = 64 if small else 512
    rng = np.random.default_rng(0)
    X = rng.standard_normal((B, 1, 28, 28), dtype=np.float32)
    Y = rng.integers(0, 10, (B,)).astype(np.int64)
    return _layer_train_bench("mnist_lenet", LeNet(), X, Y,
                              iters=3 if small else 20)


def bench_resnet(small: bool):
    import numpy as np

    from paddle_tpu.vision.models import resnet50

    if small:
        ladder, hw, iters = [2], 64, 2
    else:
        # batch LADDER (like bert): B=64 measured only MFU 0.088 on the
        # v5e — per-step overhead and under-filled convs dominate small
        # batches; walk down from 256 on OOM
        ladder, hw, iters = [256, 128, 64], 224, 10
    rng = np.random.default_rng(0)

    def run(B, amp):
        X = rng.standard_normal((B, 3, hw, hw), dtype=np.float32)
        Y = rng.integers(0, 1000, (B,)).astype(np.int64)
        # ResNet-50 fwd ~= 4.1 GFLOPs per 224x224 image; training ~= 3x
        flops = (3 * 2 * 2.05e9 * B * (hw / 224.0) ** 2 if hw >= 64
                 else None)
        name = "resnet50_amp" if amp else "resnet50"
        return _layer_train_bench(name, resnet50(), X, Y, iters,
                                  flops_per_step=flops, amp=amp)

    # headline = bf16 AMP (the TPU-first config: convs on the MXU at
    # bf16); the fp32 run — the reference's static ResNet-50 config — is
    # recorded alongside for parity at the same batch
    amp_res = last_err = None
    for B in ladder:
        try:
            amp_res = run(B, amp=True)
            amp_res["batch"] = B
            break
        except Exception as e:  # noqa: BLE001 - OOM: walk down
            _log(f"[bench] resnet50_amp B={B} failed "
                 f"({type(e).__name__}); trying next batch")
            last_err = e
    if amp_res is None:
        raise last_err
    # guarded: the ladder picked B by the AMP arm's fit; fp32 needs ~2x
    # the activation memory, and its OOM must not discard the measured
    # AMP headline
    try:
        fp32_res = run(amp_res["batch"], amp=False)
        amp_res["fp32"] = {k: fp32_res[k] for k in
                           ("value", "step_ms", "mfu", "vs_baseline")
                           if k in fp32_res}
    except Exception as e:  # noqa: BLE001 - record absence, keep headline
        _log(f"[bench] resnet50 fp32 parity arm failed at "
             f"B={amp_res['batch']} ({type(e).__name__}) — AMP headline "
             f"stands alone")
        amp_res["fp32"] = {"error": f"{type(e).__name__}"[:120]}
    return amp_res


def bench_int8(small: bool):
    """ResNet-50 INFERENCE throughput: calibrated int8 (s8 MXU, 2x bf16
    peak on v5e) vs fp32 vs bf16 — the deploy path the reference serves
    through TensorRT int8 engines, executed natively by XLA here."""
    import contextlib

    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.amp import auto_cast
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.quantization import PostTrainingQuantization, \
        convert_to_int8
    from paddle_tpu.vision.models import resnet50
    import paddle_tpu as paddle

    dev = jax.devices()[0]
    if small:
        B, hw, iters, calib_n = 2, 64, 2, 1
    else:
        B, hw, iters, calib_n = 64, 224, 10, 2
    rng = np.random.default_rng(0)
    X = rng.standard_normal((B, 3, hw, hw), dtype=np.float32)
    # device-resident once: a numpy X re-uploads 38 MB per call,
    # swamping the inference being measured (see _layer_train_bench)
    X = jnp.asarray(X)
    net = resnet50()
    net.eval()

    def _infer_throughput(model, amp=False):
        with paddle.no_grad():
            with auto_cast() if amp else contextlib.nullcontext():
                fwd = jax.jit(lambda xv: model(Tensor(xv)).value)
                box = {}

                def one():
                    box["y"] = fwd(jnp.asarray(X))

                dt = _time_steps(one, iters, lambda: box["y"])
        return B / dt

    fp32_s = _infer_throughput(net)
    bf16_s = _infer_throughput(net, amp=True)
    # calibration runs the float model EAGERLY (forward hooks observe each
    # layer's input), one dispatch per op, so keep the calibration batch
    # small: abs-max scales only need a
    # representative activation range, not the bench batch size
    calib = [rng.standard_normal((min(B, 8), 3, hw, hw), dtype=np.float32)
             for _ in range(calib_n)]
    ptq = PostTrainingQuantization(net, calib, algo="abs_max").quantize()
    qnet = convert_to_int8(net, ptq)
    int8_s = _infer_throughput(qnet)
    _log(f"[bench] resnet50 infer: int8 {int8_s:,.1f} vs bf16 {bf16_s:,.1f} "
         f"vs fp32 {fp32_s:,.1f} samples/s (B={B}, {hw}x{hw})")
    return {"metric": "samples_per_sec_per_chip_resnet50_int8_infer",
            "value": round(int8_s, 1), "unit": "samples/s/chip",
            "device": dev.platform,
            "bf16_samples_s": round(bf16_s, 1),
            "fp32_samples_s": round(fp32_s, 1),
            "int8_vs_bf16": round(int8_s / bf16_s, 3) if bf16_s else None,
            "vs_baseline": 0.0}


def bench_decode(small: bool):
    """Autoregressive decode throughput (tokens/s), float vs weight-only
    int8 (text/woq.py).  Decode reads every weight per token — the
    bandwidth-bound regime where int8 weights approach 2x bf16; the
    measured ratio calibrates that roofline claim on the real chip."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import generate, gpt, woq

    dev = jax.devices()[0]
    if small:
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=4, max_seq_len=64)
        B, new_toks, iters = 2, 8, 2
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=1024,
                            num_layers=24, num_heads=16, max_seq_len=2048)
        B, new_toks, iters = 8, 64, 3
    params = jax.device_get(gpt.init_params(cfg, jax.random.PRNGKey(0)))
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, 8)), jnp.int32)
    key = jax.random.PRNGKey(1)

    def tok_s(p):
        box = {}

        def one():
            box["y"] = generate.generate(p, cfg, prompt,
                                         max_new_tokens=new_toks,
                                         temperature=0.0, key=key)

        # first_token_ms: post-warmup latency of a single-token continue
        # (prefill the prompt + 1 decode step) — its executable warms on
        # the first call, then one timed run; kept OUT of the throughput
        # timing so re-launch compiles can't pollute the headline
        def one_tok():
            y = generate.generate(p, cfg, prompt, max_new_tokens=1,
                                  temperature=0.0, key=key)
            jax.block_until_ready(y)

        one_tok()  # compile + warmup (persistent cache hit on relaunch)
        t0 = time.perf_counter()
        one_tok()
        ft_ms = (time.perf_counter() - t0) * 1e3
        dt = _time_steps(one, iters, lambda: box["y"])
        # every call runs P-1 prefill + new_toks decode steps, each a full
        # weight read — count them all, not just the new tokens
        return {"tok_s": B * (prompt.shape[1] + new_toks - 1) / dt,
                "first_token_ms": round(ft_ms, 2)}

    makers = {"float": lambda: params,
              "int8": lambda: woq.quantize_gpt_int8(params),
              "int4": lambda: woq.quantize_gpt_int4(params)}
    # the int4 arm measures the Pallas W4 kernel on a TPU.  setdefault:
    # an operator's explicit =0 pins the XLA dequant arm.
    if dev.platform == "tpu":
        os.environ.setdefault("PADDLE_TPU_W4_KERNEL", "1")
    out = {"metric": "tokens_per_sec_decode_gpt350m_int8w",
           "unit": "tokens/s/chip", "device": dev.platform,
           "vs_baseline": 0.0}
    res = _arm_results(list(makers), lambda a: tok_s(makers[a]()))
    return _assemble_arm_record(out, res, list(makers), "float", "int8",
                                "gpt decode")


def bench_decode_long(small: bool):
    """Decode attention throughput vs CONTEXT LENGTH — the flash-decode
    arm (tok/s at pre-filled context 1k/4k/16k; flash-decode kernel
    on/off x KV-cache dtype fp32/bf16/int8).

    Decode attention reads the whole [L, B, T, Hkv, hd] cache per token,
    so past short contexts the decode rate is cache-bytes/sec — this arm
    measures exactly that regime (weight reads are identical across
    arms, so the ratios isolate the attention path).  The cache is
    pre-filled with synthetic K/V (throughput does not depend on the
    values); each measured step is the jitted donated ``decode_step`` at
    a fixed long position.  On the CPU rehearsal (--cpu --small) it
    first runs the interpret-mode parity gate, then a tiny timed sweep
    with interpret mode standing in for the kernel arm."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import flags
    from paddle_tpu.text import generate, gpt
    from paddle_tpu.ops import decode_attention as da

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if small:
        contexts, B, iters = (128, 256), 2, 2
        cfg_kwargs = dict(vocab_size=512, hidden_size=256, num_layers=2,
                          num_heads=2, num_kv_heads=1,
                          max_seq_len=max(contexts) + 8)
    else:
        contexts, B, iters = (1024, 4096, 16384), 8, 8
        # GQA 8/2 at hd=128 (the kernel's shape gate takes hd 128/256):
        # the modern serving shape the kernel's Hkv-head consumption
        # exists for; 24 layers keep the cache the dominant HBM stream
        # at 16k (int8 16k cache ~0.4 GB vs ~6 GB fp32 — the sweep's
        # whole point)
        cfg_kwargs = dict(vocab_size=50304, hidden_size=1024,
                          num_layers=24, num_heads=8, num_kv_heads=2,
                          max_seq_len=max(contexts) + 8)
    cfg = gpt.GPTConfig(**cfg_kwargs)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))

    parity = None
    if not on_tpu:
        # interpret-mode parity gate: the kernel must match the XLA
        # einsum path before any number is reported (CPU acceptance)
        old_int = da._INTERPRET
        da._INTERPRET = True
        try:
            _decode_long_parity(generate, gpt, cfg, params)
            parity = "ok"
        finally:
            da._INTERPRET = old_int

    def measure(ctx: int, kernel: bool, kv: str) -> dict:
        saved = {k: os.environ.get(k) for k in
                 ("PADDLE_TPU_FLASH_DECODE", "PADDLE_TPU_KV_DTYPE")}
        os.environ["PADDLE_TPU_FLASH_DECODE"] = "1" if kernel else "0"
        if kv == "fp32":
            os.environ["PADDLE_TPU_KV_DTYPE"] = "fp32"
        elif kv == "int8":
            os.environ["PADDLE_TPU_KV_DTYPE"] = "int8"
        else:
            os.environ.pop("PADDLE_TPU_KV_DTYPE", None)
        old_int = da._INTERPRET
        if kernel and not on_tpu:
            da._INTERPRET = True  # CPU smoke: interpret IS the kernel path
        try:
            step = generate._jit_by_cfg("decode", generate.decode_step,
                                        cfg)
            # ctx + 128 keeps the allocated length kernel-tileable (the
            # contexts are 128-multiples); init_cache would round up
            # anyway, but an arm labeled flash_* must never silently
            # measure the einsum fallback — assert engagement below
            cache = da.random_filled_cache(
                generate.init_cache(cfg, B, ctx + 128),
                jax.random.PRNGKey(1), amp=0.1)
            q_shape = (B, 1, cfg.num_heads, cfg.head_dim)
            # per-layer cache slice shape [B, T, Hkv, hd] (leading L off)
            active = bool(da.supported(q_shape, cache["k"].shape[1:]))
            if kernel and not active:
                raise RuntimeError(
                    f"kernel shape gate rejected {cache['k'].shape} — "
                    f"the flash arm would measure the XLA path")
            tok = jnp.zeros((B,), jnp.int32)
            box = {"cache": cache}

            def one():
                _, box["cache"] = step(params, box["cache"], tok, ctx)

            dt = _time_steps(one, iters, lambda: box["cache"])
            return {"tok_s": round(B / dt, 2),
                    "step_ms": round(dt * 1e3, 3)}
        finally:
            da._INTERPRET = old_int
            # RESTORE the operator's exported flag values (an exported
            # opt-out must survive the sweep)
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    table = {}
    for ctx in contexts:
        row = {}
        for kernel in (False, True):
            for kv in (("fp32", "bf16", "int8") if kernel
                       else ("fp32", "bf16")):
                name = f"{'flash' if kernel else 'xla'}_{kv}"
                row[name] = measure(ctx, kernel, kv)
                _log(f"[bench] decode_long ctx={ctx} {name}: {row[name]}")
        base = row.get("xla_fp32", {}).get("tok_s")
        best = row.get("flash_int8", {}).get("tok_s")
        if base and best:
            row["flash_int8_vs_xla_fp32"] = round(best / base, 3)
        table[str(ctx)] = row
    longest = table[str(max(contexts))]
    head = (longest.get("flash_int8", {}).get("tok_s")
            or longest.get("xla_bf16", {}).get("tok_s")
            or longest.get("xla_fp32", {}).get("tok_s") or 0.0)
    out = {"metric": "tokens_per_sec_decode_long_ctx",
           "value": head, "unit": "tokens/s/chip",
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "batch": B, "contexts": list(contexts),
           "donate": flags.donate_decode(),
           "by_context": table,
           "vs_baseline": 0.0}
    if parity is not None:
        out["interpret_parity"] = parity
    ratio = longest.get("flash_int8_vs_xla_fp32")
    if ratio is not None:
        out["flash_int8_vs_xla_fp32_at_max_ctx"] = ratio
    return out


def _decode_long_parity(generate, gpt, cfg, params):
    """Interpret-mode gate for the CPU smoke: kernel-on decode logits
    must match the einsum path (and greedy argmax exactly) for bf16 and
    int8 caches before the arm reports any throughput number."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import decode_attention as da

    saved = {k: os.environ.get(k) for k in
             ("PADDLE_TPU_FLASH_DECODE", "PADDLE_TPU_KV_DTYPE")}
    for kv in ("", "int8"):
        if kv:
            os.environ["PADDLE_TPU_KV_DTYPE"] = kv
        else:
            os.environ.pop("PADDLE_TPU_KV_DTYPE", None)
        try:
            cache = da.random_filled_cache(
                generate.init_cache(cfg, 2, 128), jax.random.PRNGKey(2))
            tok = jnp.asarray([3, 7], jnp.int32)
            os.environ["PADDLE_TPU_FLASH_DECODE"] = "1"
            lk, _ = generate.decode_step(params, dict(cache), tok, 100,
                                         cfg)
            os.environ["PADDLE_TPU_FLASH_DECODE"] = "0"
            lx, _ = generate.decode_step(params, dict(cache), tok, 100,
                                         cfg)
            np.testing.assert_allclose(np.asarray(lk), np.asarray(lx),
                                       atol=5e-2, rtol=5e-2)
            if kv != "int8":
                assert (np.asarray(jnp.argmax(lk, -1))
                        == np.asarray(jnp.argmax(lx, -1))).all()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


def bench_serving(small: bool):
    """Continuous-batching DecodeServer throughput (round-5 verdict Next
    #2): batch 8, 128-token prompts, 128 new tokens each, measured with
    the device-resident block tick (one host fetch per 64 tokens;
    BENCH_SERVING_BLOCK overrides) — bf16
    vs weight-only int8 (W8A16) vs int4.  The int8/int4-vs-bf16 ratios
    are the first on-device evidence for the woq bandwidth claim
    (text/woq.py: decode reads every weight once per token)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import flags
    from paddle_tpu.text import gpt, serving, woq

    dev = jax.devices()[0]
    if small:
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=4, max_seq_len=64)
        B, p_len, new_toks, block, iters = 2, 8, 8, 4, 1
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=1024,
                            num_layers=24, num_heads=16, max_seq_len=2048)
        # block 64: tokens-per-dispatch is the lever when serving is
        # dispatch-latency-bound — 2 dispatches per 128-token pass,
        # trading result-latency granularity the bench doesn't score.
        # BENCH_SERVING_BLOCK overrides for sweeps.
        B, p_len, new_toks, block, iters = 8, 128, 128, 64, 2
        # Validated once here: a block not
        # dividing new_toks would overrun finished slots in the timed
        # pass and silently skew tok_s; a non-int would kill every arm.
        env_block = os.environ.get("BENCH_SERVING_BLOCK")
        if env_block:
            try:
                cand = int(env_block)
            except ValueError:
                raise SystemExit(f"BENCH_SERVING_BLOCK={env_block!r} is "
                                 f"not an integer")
            if cand < 1 or new_toks % cand:
                raise SystemExit(f"BENCH_SERVING_BLOCK={cand} must divide "
                                 f"new_tokens={new_toks}")
            block = cand
    params = jax.device_get(gpt.init_params(cfg, jax.random.PRNGKey(0)))

    def serving_tree(tree):
        """Deploy form of a param tree: fp32 leaves (except the small
        quantization scales) become bf16, and EVERY leaf becomes a device
        array — a numpy leaf left in the tree would re-transfer host->
        device on every jitted call, charging the quantized arms (whose
        wpe/LN/bias leaves pass through woq untouched) a per-tick
        transfer the bf16 arm doesn't pay."""
        def conv(d):
            out = {}
            for k_, v in d.items():
                if isinstance(v, dict):
                    out[k_] = conv(v)
                elif (np.asarray(v).dtype == np.float32
                      and not k_.endswith("_s")):
                    out[k_] = jnp.asarray(v, jnp.bfloat16)
                else:
                    out[k_] = jnp.asarray(v)
            return out
        return conv(tree)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (B, p_len))

    # async dispatch (one block in flight) is the serving default — the
    # tokens are bit-identical to the sync path (tests pin the parity);
    # BENCH_SERVING_ASYNC=0 pins an A/B's sync arm
    use_async = os.environ.get("BENCH_SERVING_ASYNC", "1") != "0"

    def make_srv(p):
        return serving.DecodeServer(p, cfg, max_batch=B,
                                    max_len=p_len + new_toks,
                                    async_dispatch=use_async)

    def serve_pass(p):
        srv = make_srv(p)
        for b in range(B):
            srv.submit(prompts[b], max_new_tokens=new_toks)
        while srv.pending():
            srv.tick_block(block)
        return srv

    def tok_s(p):
        from paddle_tpu import telemetry as _tl

        # explicit warmup: pre-compile the prefill bucket + block step
        # (and the persistent compile cache makes relaunches disk reads),
        # so the timed passes and the first-token diagnostic are pure
        # device/host work
        t0 = time.perf_counter()
        srv = make_srv(p)
        srv.warmup(prompt_lens=[p_len], blocks=(block,))
        warmup_s = time.perf_counter() - t0
        # post-warmup first-token latency: submit() runs the compiled
        # prefill and yields the request's first token at admission
        t0 = time.perf_counter()
        srv.submit(prompts[0], max_new_tokens=new_toks)
        first_ms = (time.perf_counter() - t0) * 1e3
        srv = serve_pass(p)          # steady-state warm pass
        _sync_all(srv.cache)
        # telemetry window = the timed passes only: BENCH_*.json carries
        # the warm-path TTFT/TPOT DISTRIBUTION, not just the means
        _tl.reset()
        t0 = time.perf_counter()
        for _ in range(iters):
            srv = serve_pass(p)
        _sync_all(srv.cache)
        dt = (time.perf_counter() - t0) / iters
        # prefill tokens are device work too, but the serving headline is
        # the GENERATED rate (prompts admit in one prefill step each)
        rec = {"tok_s": B * new_toks / dt,
               "first_token_ms": round(first_ms, 2),
               "warmup_s": round(warmup_s, 2)}
        rec["telemetry"] = (_tl.latency_summary("serving.")
                            if _tl.enabled() else {"enabled": False})
        return rec

    makers = {"bf16": lambda: params,
              "int8": lambda: woq.quantize_gpt_int8(params),
              "int4": lambda: woq.quantize_gpt_int4(params)}
    # the int4 arm measures the Pallas W4 kernel on a TPU (setdefault:
    # an operator's explicit =0 pins the XLA dequant arm)
    if dev.platform == "tpu":
        os.environ.setdefault("PADDLE_TPU_W4_KERNEL", "1")
    out = {"metric": "tokens_per_sec_serving_gpt350m_bf16",
           "unit": "tokens/s/chip",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "batch": B, "prompt_len": p_len, "new_tokens": new_toks,
           "block": block, "async": use_async,
           "donate": flags.donate_decode(),
           "vs_baseline": 0.0}
    res = _arm_results(list(makers),
                       lambda a: tok_s(serving_tree(makers[a]())))
    return _assemble_arm_record(out, res, list(makers), "bf16", "bf16",
                                "serving")


def bench_paged(small: bool):
    """Paged KV cache vs the contiguous slab (round 8): a mixed-length
    continuous-batching pass measured under both layouts — generated
    tok/s, resident KV HBM per request (peak mapped blocks x block
    bytes vs the slab's per-slot provisioning), and the prefix-cache
    hit rate on a repeated-system-prompt workload.  The memory ratio is
    the paged layout's reason to exist: a slab provisions worst-case
    context for every slot; the pool holds only blocks actual tokens
    crossed."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import flags
    from paddle_tpu.text import gpt, serving

    dev = jax.devices()[0]
    if small:
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=4, max_seq_len=128)
        # the CPU-small tok/s is host-dispatch-bound noise (passes are
        # ~16 tiny dispatches); the arm's load-bearing smoke numbers are
        # the memory ratio + hit rate, which are deterministic
        B, max_len, new_toks, block, bs, iters = 4, 64, 8, 4, 8, 2
        p_lens = (6, 12, 20, 9)
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=1024,
                            num_layers=24, num_heads=16, max_seq_len=2048)
        B, max_len, new_toks, block, bs, iters = 8, 1024, 64, 32, 16, 2
        # the mixed-length point: slots sized for 1024 rows but holding
        # 64-320-token contexts — the slab pays 1024 rows per slot
        # anyway, the pool pays only crossed blocks
        p_lens = (64, 128, 256, 320, 96, 64, 192, 128)
    rng = np.random.default_rng(0)
    sys_prefix = [int(x) for x in rng.integers(1, cfg.vocab_size, 2 * bs)]
    prompts = [sys_prefix + [int(x) for x in
                             rng.integers(1, cfg.vocab_size, n)]
               for n in p_lens]
    params = jax.device_get(gpt.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(jnp.asarray, params)

    def serve_pass(layout):
        srv = serving.DecodeServer(params, cfg, max_batch=B,
                                   max_len=max_len, layout=layout,
                                   block_size=bs)
        for i, p in enumerate(prompts):
            srv.submit(p, max_new_tokens=new_toks)
        while srv.pending():
            srv.tick_block(block)
        stats = srv._pool.stats() if srv._pool is not None else None
        toks = srv._results
        srv.close()
        return toks, stats

    def measure(layout):
        serve_pass(layout)                    # warm pass (compiles)
        t0 = time.perf_counter()
        stats = None
        for _ in range(iters):
            toks, stats = serve_pass(layout)
        dt = (time.perf_counter() - t0) / iters
        return len(prompts) * new_toks / dt, stats

    cont_tok_s, _ = measure("contiguous")
    paged_tok_s, stats = measure("paged")
    # byte math host-side from the config (constructing a probe server
    # would allocate a second slab-equivalent pool on device right after
    # the measured passes): per-block bytes across every pool leaf
    # (values + int8 scale planes)
    from paddle_tpu.text import generate as _gen, kv_pool as _kvp

    nmax = _kvp.round_len(max_len, bs) // bs
    store_itemsize = np.dtype(_gen._kv_store_dtype(cfg)).itemsize
    block_rows = cfg.num_layers * bs * cfg.kv_heads
    block_bytes = 2 * block_rows * cfg.head_dim * store_itemsize
    if store_itemsize == 1:                    # int8: fp32 scale planes
        block_bytes += 2 * block_rows * 4
    resident_mb = stats["peak_blocks_in_use"] * block_bytes / len(prompts) \
        / 1e6
    slab_mb = nmax * block_bytes / 1e6        # per-slot slab provisioning
    hits = stats["prefix_hits"]
    hit_rate = hits / max(1, hits + stats["prefix_misses"])
    rec = {"metric": "tokens_per_sec_serving_paged_kv",
           "unit": "tokens/s/chip",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "batch": B, "max_len": max_len, "new_tokens": new_toks,
           "block": block, "kv_block_size": bs,
           "prompt_lens": list(p_lens),
           "value": round(paged_tok_s, 2),
           "contiguous_tok_s": round(cont_tok_s, 2),
           "paged_vs_contiguous": round(paged_tok_s / max(cont_tok_s,
                                                          1e-9), 3),
           "resident_hbm_per_request_mb": round(resident_mb, 3),
           "slab_hbm_per_request_mb": round(slab_mb, 3),
           "resident_vs_slab": round(resident_mb / max(slab_mb, 1e-9), 3),
           "prefix_hit_rate": round(hit_rate, 3),
           "cow_copies": stats["cow_copies"],
           "kv_dtype": flags.kv_cache_dtype() or "compute",
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


def bench_fleet(small: bool):
    """Disaggregated serving fleet vs the single server (round 9): a
    mixed long-prompt/short-prompt workload driven through a 1-router/
    2-replica loopback fleet with a dedicated prefill worker, against
    the same stream on one ``DecodeServer``.

    The load-bearing number is the DECODE LOOP GAP p99 — the wall of
    one drive-loop iteration while requests are mid-decode, which is
    the inter-token latency a decoding request actually perceives.
    The serving ``tpot_ms`` histogram can't see a prefill stall (its
    tick window opens after admission), but the loop gap does: on a
    single server a long prompt's admission prefill runs INSIDE the
    loop and every active request's next token waits on it; with
    disaggregated prefill the worker thread runs it off the loop and
    the decode side only pays a row-injection scatter.  Asserted (the
    round-9 acceptance bar, on CPU): mixed-workload fleet gap p99 <=
    short-prompts-only gap p99 ON THE SAME FLEET TOPOLOGY x
    BENCH_FLEET_TOL — same replicas, same per-iteration dispatch
    count, the only difference is whether long prompts exist, so the
    ratio isolates the stall.  Default 4.0: in the in-process loopback
    the worker's prefill COMPUTES on the same host cores the decode
    ticks use (a real fleet pins workers to their own chips), which
    measures as ~2.1-3.2x on the CPU-small box — while the stall this
    guards against is ~200x (a ~1000ms single-server mixed gap p99
    from the 192-token prefill, against ~5ms short-only ticks)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import telemetry as _tl
    from paddle_tpu.text import fleet, gpt, serving

    dev = jax.devices()[0]
    if small:
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=4, max_seq_len=256)
        n_short, p_short, p_long, new_toks = 6, 8, 192, 16
        long_at = (4, 8)              # iterations where longs arrive
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=768,
                            num_layers=12, num_heads=12, max_seq_len=2048)
        n_short, p_short, p_long, new_toks = 6, 64, 1536, 64
        long_at = (8, 24)
    max_len = p_long + new_toks
    B = n_short + len(long_at)
    rng = np.random.default_rng(0)
    shorts = [[int(x) for x in rng.integers(1, cfg.vocab_size, p_short)]
              for _ in range(n_short)]
    longs = [[int(x) for x in rng.integers(1, cfg.vocab_size, p_long)]
             for _ in long_at]
    params = jax.device_get(gpt.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(jnp.asarray, params)

    def schedule(mixed: bool):
        sched = [(0, p) for p in shorts]
        if mixed:
            sched += list(zip(long_at, longs))
        return sorted(sched, key=lambda x: x[0])

    def drive(obj, active_fn, sched):
        """Run one schedule; returns (tokens, gap list ms, wall s):
        gaps sample iterations that started with work mid-decode —
        including any submit that lands inside them, which is exactly
        where a single server pays the long prefill."""
        sched = list(sched)
        rids, gaps = [], []
        it = 0
        t_start = time.perf_counter()
        while sched or obj.pending():
            act = active_fn() > 0
            t0 = time.perf_counter()
            while sched and sched[0][0] <= it:
                rids.append(obj.submit(sched.pop(0)[1],
                                       max_new_tokens=new_toks))
            obj.tick()
            if act:
                gaps.append((time.perf_counter() - t0) * 1e3)
            it += 1
        wall = time.perf_counter() - t_start
        return [obj.result(r) for r in rids], gaps, wall

    def single_arm(mixed: bool):
        def run():
            srv = serving.DecodeServer(params, cfg, max_batch=B,
                                       max_len=max_len)
            out = drive(srv, lambda: len(srv._slots), schedule(mixed))
            srv.close()
            return out
        run()                                  # warm pass (compiles)
        _tl.reset()
        return run()

    def fleet_arm(mixed: bool):
        def run():
            worker = fleet.PrefillWorker(params, cfg, max_len=max_len)
            router = fleet.Router(
                [serving.DecodeServer(params, cfg, max_batch=B // 2,
                                      max_len=max_len)
                 for _ in range(2)],
                prefill=[worker],
                prefill_threshold=(p_short + p_long) // 2)
            out = drive(
                router,
                lambda: sum(len(r._slots) for r in router.replicas),
                schedule(mixed))
            router.close()
            return out
        run()                                  # warm pass (compiles)
        _tl.reset()
        toks, gaps, wall = run()
        # telemetry captured PER PASS so the reported block always
        # describes the pass whose gap numbers the record carries
        tel = (_tl.latency_summary("serving.") if _tl.enabled()
               else {"enabled": False})
        return toks, gaps, wall, tel

    def p(gaps, q):
        return float(np.percentile(np.asarray(gaps), q)) if gaps else 0.0

    toks_short, gaps_short, _ = single_arm(mixed=False)
    toks_single, gaps_single, wall_single = single_arm(mixed=True)
    _, gaps_fshort, _, _ = fleet_arm(mixed=False)
    # best-of-2 on the asserted arm: a genuine prefill stall is
    # deterministic (the admission runs in-loop every pass), host
    # scheduler noise is not — the min-p99 pass carries the assert
    passes = [fleet_arm(mixed=True) for _ in range(2)]
    toks_fleet, gaps_fleet, wall_fleet, fleet_tel = min(
        passes, key=lambda r: p(r[1], 99))
    if toks_fleet != toks_single:
        raise AssertionError(
            f"fleet bench: fleet tokens diverged from the single server "
            f"on the same stream ({toks_fleet} vs {toks_single})")
    tol = float(os.environ.get("BENCH_FLEET_TOL", "4.0"))
    gap99_short, gap99_single = p(gaps_short, 99), p(gaps_single, 99)
    gap99_fshort, gap99_fleet = p(gaps_fshort, 99), p(gaps_fleet, 99)
    if gap99_fleet > gap99_fshort * tol:
        raise AssertionError(
            f"fleet bench: mixed-workload decode gap p99 with "
            f"disaggregated prefill ({gap99_fleet:.1f}ms) exceeds "
            f"{tol}x the short-prompts-only baseline on the same fleet "
            f"({gap99_fshort:.1f}ms) — long prompts are stalling the "
            f"token loop again")
    total_toks = sum(len(t) for t in toks_fleet)
    # tracing-overhead arm (round 20): the tracing plane — trace mint
    # at submit, span-ring records on every hop, piggyback collection
    # on replies — must be invisible in the numbers.  Same mixed
    # workload, same topology, same telemetry (metrics) plane, only
    # PADDLE_TPU_TRACE flipped: tok/s and gap p99 with tracing ON must
    # land within BENCH_TRACE_TOL (3%) of tracing OFF (best-of-2 both
    # arms — the spans are host dicts keyed off a request field, so a
    # miss here is a hot-path regression, not noise).
    trace_tol = float(os.environ.get("BENCH_TRACE_TOL", "0.03"))
    prev_tr = os.environ.get("PADDLE_TPU_TRACE")
    os.environ["PADDLE_TPU_TRACE"] = "0"
    try:
        off_passes = [fleet_arm(mixed=True) for _ in range(2)]
    finally:
        if prev_tr is None:
            os.environ.pop("PADDLE_TPU_TRACE", None)
        else:
            os.environ["PADDLE_TPU_TRACE"] = prev_tr
    toks_off, gaps_off, _, _ = min(off_passes, key=lambda r: p(r[1], 99))
    if toks_off != toks_single:
        raise AssertionError(
            "fleet bench: tracing-off fleet tokens diverged from the "
            "single server — TELEMETRY=0 is not a no-op")
    gap99_off = p(gaps_off, 99)
    tok_s_on = total_toks / min(r[2] for r in passes)
    tok_s_off = total_toks / min(r[2] for r in off_passes)
    if tok_s_on < tok_s_off * (1 - trace_tol):
        raise AssertionError(
            f"fleet bench: tracing costs throughput — "
            f"{tok_s_on:.1f} tok/s on vs {tok_s_off:.1f} off "
            f"(> {trace_tol:.0%} regression)")
    if gap99_fleet > gap99_off * (1 + trace_tol) + 1.0:
        raise AssertionError(
            f"fleet bench: tracing costs decode-gap latency — "
            f"p99 {gap99_fleet:.2f}ms on vs {gap99_off:.2f}ms off "
            f"(> {trace_tol:.0%} + 1ms regression)")
    rec = {"metric": "tokens_per_sec_serving_fleet",
           "unit": "tokens/s/chip",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "replicas": 2, "prefill_workers": 1,
           "short_prompts": n_short, "prompt_len_short": p_short,
           "long_prompts": len(long_at), "prompt_len_long": p_long,
           "new_tokens": new_toks,
           "value": round(total_toks / wall_fleet, 2),
           "single_server_tok_s": round(total_toks / wall_single, 2),
           "fleet_vs_single": round(wall_single / max(wall_fleet, 1e-9),
                                    3),
           "decode_gap_p50_ms": round(p(gaps_fleet, 50), 2),
           "decode_gap_p99_ms": round(gap99_fleet, 2),
           "fleet_short_only_gap_p99_ms": round(gap99_fshort, 2),
           "single_mixed_gap_p99_ms": round(gap99_single, 2),
           "single_short_only_gap_p99_ms": round(gap99_short, 2),
           "tracing_off_gap_p99_ms": round(gap99_off, 2),
           "tracing_overhead_tok_s": round(
               1.0 - tok_s_on / max(tok_s_off, 1e-9), 4),
           "tracing_tolerance": trace_tol,
           "gap_tolerance": tol,
           "telemetry": fleet_tel,
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


def bench_stream(small: bool):
    """Zero-copy KV streaming transport vs the retired pickle
    whole-walk handoff (round 18): N long prompts driven through a
    1-router / 2-replica fleet with one prefill worker, once over a
    ``>Q``-length-prefixed-pickle pipe replying whole walks (the old
    wire format, kept here ONLY as the baseline), once over the
    raw-row chunked protocol (dtype-tagged header frame + contiguous
    buffer frames, rows injected per chunk while the worker computes
    the next).

    The load-bearing number is HANDOFF TTFT p99 — submit at the router
    to first token, measured per request at the drive loop
    (``max_new_tokens=1`` makes completion == first token, so the
    transport's poll granularity can't blur it).  Both arms pay a full
    host serialize/copy/deserialize through bytes (the pickle blob vs
    the exact socket codec's encode/decode), so the delta isolates
    what the protocol changes: no object deserialization on the KV
    path, and per-chunk injection OVERLAPPING the worker's walk —
    request k's rows land while walk k still runs, instead of after
    walk + whole-blob pickle roundtrip + monolithic inject.  Asserted:
    chunked TTFT p99 STRICTLY beats the pickle whole-walk baseline,
    tokens bit-identical across both arms and the single server, zero
    chunk frames in the baseline / >= 2 per long prompt in the
    streamed arm, and the lint's pickle ban holds on the shipped
    transport."""
    import pickle
    import queue as _q

    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import telemetry as _tl
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import fleet, gpt, serving

    dev = jax.devices()[0]
    if small:
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=4, max_seq_len=256)
        n_long, p_long, chunk = 6, 192, 48
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=768,
                            num_layers=12, num_heads=12, max_seq_len=2048)
        n_long, p_long, chunk = 6, 1536, 256
    max_len = p_long + 8
    rng = np.random.default_rng(0)
    prompts = [[int(x) for x in rng.integers(1, cfg.vocab_size, p_long)]
               for _ in range(n_long)]
    params = jax.device_get(gpt.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(jnp.asarray, params)

    class _Pipe:
        """In-process endpoint pair that round-trips every message
        through BYTES — ``codec='pickle'`` is the retired wire format
        (one ``>Q``-prefixed ``pickle.dumps`` blob per message),
        ``codec='raw'`` is the shipped socket codec
        (``_encode_msg``/``_decode_msg``) minus the kernel, buffer
        copies included.  Both arms pay host serialization; neither
        gets reference-passing for free."""

        def __init__(self, codec):
            self.codec, self.bytes = codec, 0
            a, b = _q.Queue(), _q.Queue()
            self.client = _Pipe._End(self, a, b)
            self.worker = _Pipe._End(self, b, a)

        class _End:
            def __init__(self, pipe, sq, rq):
                self._pipe, self._sq, self._rq = pipe, sq, rq

            def send(self, obj):
                if self._pipe.codec == "pickle":
                    blob = pickle.dumps(obj)
                    self._pipe.bytes += 8 + len(blob)
                    self._sq.put(("p", blob, None))
                    return
                hdr, arrays = fleet._encode_msg(obj)
                bufs = []
                for a in arrays:
                    try:
                        mv = memoryview(a).cast("B")
                    except (ValueError, TypeError):
                        mv = memoryview(np.ascontiguousarray(a)
                                        .reshape(-1).view(np.uint8))
                    bufs.append(bytearray(mv))
                    self._pipe.bytes += 9 + mv.nbytes
                self._pipe.bytes += 9 + len(hdr)
                self._sq.put(("r", hdr, bufs))

            def recv(self, timeout: float = 0.0):
                try:
                    kind, a, b = self._rq.get(
                        timeout=max(float(timeout), 1e-4))
                except _q.Empty:
                    return None
                if kind == "p":
                    return pickle.loads(a)
                return fleet._decode_msg(a, b)

            def close(self):
                pass

    def drive(router, rids_out):
        """Submit everything, tick to done; returns per-request TTFT ms
        (submit -> status ok, max_new_tokens=1)."""
        t_sub, ttft = {}, {}
        for p in prompts:
            rid = router.submit(p, max_new_tokens=1)
            t_sub[rid] = time.perf_counter()
            rids_out.append(rid)
        open_ = set(t_sub)
        deadline = time.time() + 600.0
        while router.pending() and time.time() < deadline:
            router.tick()
            now = time.perf_counter()
            for rid in [r for r in open_
                        if router.status(r) == "ok"]:
                ttft[rid] = (now - t_sub[rid]) * 1e3
                open_.discard(rid)
            if not any(r._slots or r._queue for r in router.replicas
                       if r is not None):
                time.sleep(0.001)
        if router.pending():
            raise AssertionError("stream bench: fleet never drained")
        for rid in open_:
            ttft[rid] = (time.perf_counter() - t_sub[rid]) * 1e3
        return [ttft[r] for r in sorted(ttft)]

    def arm(codec):
        env = os.environ.get("PADDLE_TPU_STREAM_CHUNK_ROWS")
        os.environ["PADDLE_TPU_STREAM_CHUNK_ROWS"] = (
            "0" if codec == "pickle" else str(chunk))
        try:
            def run():
                pipe = _Pipe(codec)
                worker = fleet.PrefillWorker(params, cfg, max_len=max_len,
                                             endpoint=pipe.worker)
                worker.start()
                router = fleet.Router(
                    [serving.DecodeServer(params, cfg, max_batch=3,
                                          max_len=max_len)
                     for _ in range(2)],
                    prefill=[pipe.client], prefill_threshold=32)
                rids = []
                t0 = time.perf_counter()
                ttfts = drive(router, rids)
                wall = time.perf_counter() - t0
                toks = [router.result(r) for r in rids]
                router.close()
                worker.close()
                return toks, ttfts, wall, pipe.bytes

            run()                              # warm pass (compiles)
            _tl.reset()
            passes = [run() for _ in range(2)]
            # best-of-2 p99: protocol costs are deterministic, host
            # scheduler noise is not
            return min(passes,
                       key=lambda r: float(np.percentile(r[1], 99)))
        finally:
            if env is None:
                os.environ.pop("PADDLE_TPU_STREAM_CHUNK_ROWS", None)
            else:
                os.environ["PADDLE_TPU_STREAM_CHUNK_ROWS"] = env

    # single-server reference for bit-parity
    srv = serving.DecodeServer(params, cfg, max_batch=n_long,
                               max_len=max_len)
    ref_rids = [srv.submit(p, max_new_tokens=1) for p in prompts]
    while srv.pending():
        srv.tick()
    ref = [srv.result(r) for r in ref_rids]
    srv.close()

    toks_p, ttft_p, wall_p, bytes_p = arm("pickle")
    chunks_p = int(monitor.get_stat("fleet.stream_chunks").get())
    toks_r, ttft_r, wall_r, bytes_r = arm("raw")
    chunks_r = int(monitor.get_stat("fleet.stream_chunks").get())
    sbytes_r = int(monitor.get_stat("fleet.stream_bytes").get())

    if toks_p != ref or toks_r != ref:
        raise AssertionError(
            f"stream bench: transport arms diverged from the single "
            f"server (pickle={toks_p == ref}, raw={toks_r == ref})")
    if _tl.enabled():
        if chunks_p != 0:
            raise AssertionError(
                f"stream bench: the whole-walk baseline emitted chunk "
                f"frames (fleet.stream_chunks={chunks_p})")
        if chunks_r < 2 * n_long:
            raise AssertionError(
                f"stream bench: long prompts crossed in "
                f"{chunks_r} chunks, expected >= {2 * n_long} "
                f"(chunk_rows={chunk}, prompt={p_long})")
    p99_p = float(np.percentile(ttft_p, 99))
    p99_r = float(np.percentile(ttft_r, 99))
    if p99_r >= p99_p:
        raise AssertionError(
            f"stream bench: chunked raw-row TTFT p99 ({p99_r:.1f}ms) "
            f"does not beat the pickle whole-walk baseline "
            f"({p99_p:.1f}ms) — the overlap is gone")
    # the shipped transport carries zero pickle sites (the lint rule
    # the bench claim rests on)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import check_instrumented as _ci
    fleet_src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "paddle_tpu", "text", "fleet.py")
    with open(fleet_src, encoding="utf-8") as f:
        leaks = _ci.scan_pickle_ban_source(f.read(), "fleet.py")
    if leaks:
        raise AssertionError(
            f"stream bench: pickle sites on the KV handoff path: "
            f"{leaks}")

    rec = {"metric": "handoff_ttft_p99_ms_stream",
           "unit": "ms",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "replicas": 2, "prefill_workers": 1,
           "long_prompts": n_long, "prompt_len": p_long,
           "chunk_rows": chunk,
           "value": round(p99_r, 2),
           "pickle_ttft_p99_ms": round(p99_p, 2),
           "ttft_speedup": round(p99_p / max(p99_r, 1e-9), 3),
           "ttft_p50_ms": round(float(np.percentile(ttft_r, 50)), 2),
           "pickle_ttft_p50_ms": round(float(np.percentile(ttft_p, 50)),
                                       2),
           "stream_chunks": chunks_r,
           "stream_bytes": sbytes_r,
           "wire_bytes_raw": bytes_r,
           "wire_bytes_pickle": bytes_p,
           "raw_mb_per_s": round(bytes_r / max(wall_r, 1e-9) / 2**20, 1),
           "pickle_mb_per_s": round(bytes_p / max(wall_p, 1e-9) / 2**20,
                                    1),
           "wall_s_raw": round(wall_r, 3),
           "wall_s_pickle": round(wall_p, 3),
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


def bench_prefix(small: bool):
    """Fleet-scale prefix cache (round 16): a multi-tenant
    shared-preamble workload — T tenants, each issuing R requests that
    share a per-tenant preamble diverging MID-BLOCK — driven through a
    2-replica fleet under three routing/matching policies, against the
    same stream on one double-width server.

    Arms (same schedule, fresh routers, warm pass first):

    1. **affinity** — token-granular radix matching + prefix-aware
       routing (the headline): a tenant's requests land where its KV
       already lives, and admission recomputes only the unshared tail.
    2. **block** — ``PADDLE_TPU_KV_RADIX=0``: whole-block matching,
       affinity routing unchanged — isolates the token-granular win.
    3. **no-affinity** — ``PADDLE_TPU_PREFIX_ROUTE=0``: radix matching
       on, pure load-order routing — the load triple actively steers a
       tenant AWAY from its warm replica (its resident chains raise
       that replica's kv-utilization), so every crossing pays the full
       preamble prefill again.

    TTFT is measured over the steady-state phase only (requests 2..R
    per tenant; the unavoidable first-touch prefills run before the
    telemetry reset), from the serving ``ttft_ms`` histogram.  The
    paged admission executable is ``pow2(n - shared)`` wide, so prefix
    adoption shrinks the admission compute itself — which is what the
    TTFT spread measures.  Asserted: greedy tokens bit-identical across
    every arm and the single server; token-granular hit rate strictly
    above the whole-block baseline; affinity steady-state TTFT p99 <=
    no-affinity p99 x BENCH_PREFIX_TOL (default 1.0 — strictly no
    worse, and in practice several x better); ``fleet.prefix_routed``
    > 0; zero post-warmup retraces per fleet arm."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import telemetry as _tl
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import fleet, gpt, serving

    dev = jax.devices()[0]
    if small:
        # hidden 256 x 4L: big enough that a cold 256-wide admission
        # costs real wall time next to a warm 8-wide one — the TTFT
        # spread IS the measurement, and a toy model hides it
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=256,
                            num_layers=4, num_heads=8, max_seq_len=512)
        # T=3 tenants over 2 replicas: the ODD split keeps a chain-sized
        # kv-utilization gap between the replicas, so the load-order
        # baseline is structurally steered onto cold replicas (an even
        # tenant split can tie on utilization and accidentally mimic
        # affinity, which would null the TTFT comparison)
        T, R, p_pre, p_tail, new_toks = 3, 6, 460, 6, 8
        blocks_fleet, blocks_single = 224, 256
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=768,
                            num_layers=12, num_heads=12,
                            max_seq_len=2048)
        T, R, p_pre, p_tail, new_toks = 3, 6, 1500, 20, 32
        blocks_fleet, blocks_single = 640, 768
    max_len = cfg.max_seq_len
    rng = np.random.default_rng(5)
    pres = [[int(x) for x in rng.integers(1, cfg.vocab_size, p_pre)]
            for _ in range(T)]
    reqs = [[pres[t] + [int(x) for x in
                        rng.integers(1, cfg.vocab_size, p_tail)]
             for _ in range(R)] for t in range(T)]
    sched1 = [reqs[t][0] for t in range(T)]          # first touch
    sched2 = [reqs[t][r] for r in range(1, R) for t in range(T)]
    params = jax.device_get(gpt.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(jnp.asarray, params)

    env_keys = ("PADDLE_TPU_KV_RADIX", "PADDLE_TPU_PREFIX_ROUTE")
    env0 = {k: os.environ.get(k) for k in env_keys}

    def _set(**env):
        for k, v in env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    def drive(obj, prompts_):
        """Closed-loop (one request in flight, tenants interleaved):
        TTFT then measures the admission prefill the routing policy
        chose, not queue wait — open-loop arrival buries the ~10x
        executable-width spread under identical queueing delay."""
        rids = []
        for p in prompts_:
            rids.append(obj.submit(p, max_new_tokens=new_toks))
            while obj.pending():
                obj.tick()
        return [obj.result(r) for r in rids]

    def fleet_arm(radix, route):
        _set(PADDLE_TPU_KV_RADIX=radix, PADDLE_TPU_PREFIX_ROUTE=route)

        def mk():
            return fleet.Router(
                [serving.DecodeServer(params, cfg, max_batch=2,
                                      max_len=max_len, layout="paged",
                                      block_size=8,
                                      num_blocks=blocks_fleet)
                 for _ in range(2)])

        def run(router):
            toks = drive(router, sched1)
            _tl.reset()              # steady-state phase only
            t0 = time.perf_counter()
            toks += drive(router, sched2)
            wall = time.perf_counter() - t0
            ttft = (_tl.latency_summary("serving.").get("ttft_ms", {})
                    if _tl.enabled() else {})
            routed = (int(monitor.get_stat("fleet.prefix_routed").get())
                      if _tl.enabled() else 0)
            pools = [r._pool.stats() for r in router.replicas]
            return toks, ttft, routed, pools, wall

        # the warm router stays OPEN through the measured pass:
        # close() purges the Engine's executable caches by config, so
        # closing it first would hand the measured pass cold compiles
        warm = mk()
        run(warm)
        keys0 = set(serving._STEP_CACHE.keys())
        meas = mk()
        out = run(meas)
        added = set(serving._STEP_CACHE.keys()) - keys0
        warm.close()
        meas.close()
        if added:
            raise AssertionError(
                f"prefix bench: post-warmup pass retraced — new "
                f"executables {sorted(added)}")
        return out

    def single_arm():
        _set(PADDLE_TPU_KV_RADIX="1", PADDLE_TPU_PREFIX_ROUTE=None)

        def mk():
            return serving.DecodeServer(params, cfg, max_batch=4,
                                        max_len=max_len, layout="paged",
                                        block_size=8,
                                        num_blocks=blocks_single)

        def run(srv):
            toks = drive(srv, sched1)
            t0 = time.perf_counter()
            toks += drive(srv, sched2)
            wall = time.perf_counter() - t0
            return toks, wall

        warm = mk()
        run(warm)                              # warm pass (compiles)
        meas = mk()
        out = run(meas)
        warm.close()
        meas.close()
        return out

    def rate(pools):
        h = sum(p["prefix_hits"] for p in pools)
        m = sum(p["prefix_misses"] for p in pools)
        return h / max(1, h + m)

    try:
        toks_aff, ttft_aff, routed, pools_aff, wall_aff = \
            fleet_arm("1", "1")
        toks_blk, _, _, pools_blk, _ = fleet_arm("0", "1")
        toks_noaf, ttft_noaf, _, _, wall_noaf = fleet_arm("1", "0")
        toks_single, wall_single = single_arm()
    finally:
        _set(**env0)
    for name, toks in (("affinity", toks_aff), ("block", toks_blk),
                       ("no-affinity", toks_noaf)):
        if toks != toks_single:
            raise AssertionError(
                f"prefix bench: {name} fleet tokens diverged from the "
                f"single server on the same stream")
    if rate(pools_aff) <= rate(pools_blk):
        raise AssertionError(
            f"prefix bench: token-granular hit rate "
            f"{rate(pools_aff):.4f} does not beat the whole-block "
            f"baseline {rate(pools_blk):.4f}")
    if _tl.enabled():
        if routed < 1:
            raise AssertionError(
                "prefix bench: prefix affinity never decided a "
                "dispatch (fleet.prefix_routed == 0)")
        tol = float(os.environ.get("BENCH_PREFIX_TOL", "1.0"))
        if ttft_aff and ttft_noaf \
                and ttft_aff["p99"] > ttft_noaf["p99"] * tol:
            raise AssertionError(
                f"prefix bench: steady-state TTFT p99 with prefix "
                f"routing ({ttft_aff['p99']:.1f}ms) exceeds {tol}x the "
                f"load-order baseline ({ttft_noaf['p99']:.1f}ms) — "
                f"affinity is not landing tenants on their warm "
                f"replica")
    rows_saved = sum(p["prefix_hits"] for p in pools_aff)
    total_toks = sum(len(t) for t in toks_aff[T:])   # steady phase
    rec = {"metric": "prefix_cache_ttft_p99_ms", "unit": "ms",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "tenants": T, "requests_per_tenant": R,
           "preamble_len": p_pre, "tail_len": p_tail,
           "new_tokens": new_toks, "replicas": 2,
           "value": round(ttft_aff.get("p99", 0.0), 2),
           "ttft_p50_ms": round(ttft_aff.get("p50", 0.0), 2),
           "ttft_p99_noaffinity_ms": round(ttft_noaf.get("p99", 0.0),
                                           2),
           "ttft_p50_noaffinity_ms": round(ttft_noaf.get("p50", 0.0),
                                           2),
           "prefix_hit_rate": round(rate(pools_aff), 4),
           "prefix_hit_rate_block": round(rate(pools_blk), 4),
           "recompute_rows_saved": rows_saved,
           "radix_splits": sum(p["radix_splits"] for p in pools_aff),
           "prefix_routed": routed,
           "steady_tok_s": round(total_toks / max(wall_aff, 1e-9), 2),
           "steady_tok_s_noaffinity": round(
               total_toks / max(wall_noaf, 1e-9), 2),
           "single_server_tok_s": round(
               total_toks / max(wall_single, 1e-9), 2),
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


def bench_mixed(small: bool):
    """Stall-free continuous batching (round 12): the SAME single-server
    mixed long-prompt/short-prompt stream driven through monolithic
    admission (prefill_budget=0 — a long prompt's whole prefill runs
    inside one scheduler round) and budgeted admission
    (``PADDLE_TPU_PREFILL_BUDGET``-style chunked-prefill co-scheduling:
    at most ``budget`` prefill tokens per round, interleaved with the
    decode steps).

    The load-bearing number is the DECODE LOOP GAP p99 (bench_fleet's
    metric): the wall of one drive-loop iteration while requests are
    mid-decode.  Monolithic admission pays the long prompt's entire
    prefill inside one iteration — every decoding request's next token
    waits on it; budgeted admission bounds each iteration at one
    budget-width chunk.  Asserted (the round-12 acceptance bar): the
    budgeted mixed gap p99 improves >= BENCH_MIXED_TOL x (default 5)
    over monolithic on the same topology, with throughput within
    BENCH_MIXED_TPS_TOL (default 10%) and greedy tokens bit-identical
    — the co-scheduling must never trade correctness or tokens/s for
    the latency win."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import telemetry as _tl
    from paddle_tpu.text import gpt, serving

    dev = jax.devices()[0]
    # Workload shape (both arms identical): a 2-slot server carries a
    # CONTINUOUS stream of short requests (one in flight at all times —
    # the "decode traffic" whose gap is under test) while a handful of
    # LONG prompts arrive mid-stream and contend for the second slot.
    # The long prompts are long enough that their monolithic prefill
    # (quadratic attention + full-prompt MLP in ONE round) dwarfs the
    # per-round decode cost; the short stream is long enough that the
    # wall clock is decode-dominated, so the budgeted arm's extra
    # chunk dispatches stay inside the throughput tolerance.
    if small:
        # fp32: XLA CPU emulates bf16 matmuls; the arms compare
        # scheduling, not dtype emulation
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=512, num_layers=2,
                            num_heads=8, max_seq_len=2048,
                            dtype=jnp.float32)
        p_short, p_long = 8, 1984
        short_new, long_new = 8, 16
        budget = 96
        short_every, n_short = 10, 15          # stream: it 0..140
        long_at = (20, 60, 100)
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=768,
                            num_layers=12, num_heads=12, max_seq_len=2048)
        p_short, p_long = 64, 1536
        short_new, long_new = 8, 16
        budget = 192
        short_every, n_short = 10, 15
        long_at = (20, 60, 100)
    max_len = p_long + long_new
    B = 2
    rng = np.random.default_rng(0)
    shorts = [(short_every * i,
               [int(x) for x in rng.integers(1, cfg.vocab_size, p_short)])
              for i in range(n_short)]
    longs = [(a, [int(x) for x in rng.integers(1, cfg.vocab_size, p_long)])
             for a in long_at]
    params = jax.device_get(gpt.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(jnp.asarray, params)

    def schedule():
        return sorted(shorts + longs, key=lambda x: x[0])

    def drive(srv):
        """bench_fleet's drive loop: gaps sample iterations that ran
        with requests in flight — including any submit landing inside
        them, which is exactly where monolithic admission stalls."""
        sched = schedule()
        rids, gaps = [], []
        it = 0
        t_start = time.perf_counter()
        while sched or srv.pending():
            t0 = time.perf_counter()
            while sched and sched[0][0] <= it:
                _, prompt = sched.pop(0)
                rids.append(srv.submit(
                    prompt, max_new_tokens=(long_new if len(prompt) > 100
                                            else short_new)))
            act = len(srv._slots) > 0 or srv.pending()
            srv.tick()
            if act:
                gaps.append((time.perf_counter() - t0) * 1e3)
            it += 1
        wall = time.perf_counter() - t_start
        return [srv.result(r) for r in rids], gaps, wall

    def arm(budget_):
        def run():
            # no srv.close(): close() evicts this config's executables
            # from the shared step cache, which would force the measured
            # pass to recompile what the warm pass just built — the GC
            # reclaims the per-server KV cache when srv goes out of scope
            srv = serving.DecodeServer(params, cfg, max_batch=B,
                                       max_len=max_len,
                                       prefill_budget=budget_)
            return drive(srv)
        run()                                  # warm pass (compiles)
        _tl.reset()
        # best-of-2 on the measured pass: the admission stall under
        # test is deterministic (it re-runs every pass), host scheduler
        # noise is not — min-p99 carries the assert
        passes = [run() for _ in range(2)]
        toks, gaps, wall = min(
            passes,
            key=lambda r: float(np.percentile(np.asarray(r[1]), 99))
            if r[1] else 0.0)
        tel = (_tl.latency_summary("serving.") if _tl.enabled()
               else {"enabled": False})
        return toks, gaps, wall, tel

    def p(gaps, q):
        return float(np.percentile(np.asarray(gaps), q)) if gaps else 0.0

    toks_mono, gaps_mono, wall_mono, _ = arm(0)
    toks_bud, gaps_bud, wall_bud, tel_bud = arm(budget)
    if toks_bud != toks_mono:
        raise AssertionError(
            f"mixed bench: budgeted admission tokens diverged from "
            f"monolithic on the same stream ({toks_bud} vs {toks_mono})")
    tol = float(os.environ.get("BENCH_MIXED_TOL", "5.0"))
    tps_tol = float(os.environ.get("BENCH_MIXED_TPS_TOL", "0.10"))
    gap99_mono, gap99_bud = p(gaps_mono, 99), p(gaps_bud, 99)
    if gap99_bud * tol > gap99_mono:
        raise AssertionError(
            f"mixed bench: budgeted mixed decode gap p99 "
            f"({gap99_bud:.1f}ms) is not >= {tol}x better than "
            f"monolithic ({gap99_mono:.1f}ms) — chunked-prefill "
            f"co-scheduling is not absorbing the long-prompt stall")
    total_toks = sum(len(t) for t in toks_bud)
    tok_s_mono = total_toks / max(wall_mono, 1e-9)
    tok_s_bud = total_toks / max(wall_bud, 1e-9)
    if tok_s_bud < tok_s_mono * (1.0 - tps_tol):
        raise AssertionError(
            f"mixed bench: budgeted admission throughput "
            f"({tok_s_bud:.1f} tok/s) fell more than "
            f"{tps_tol:.0%} below monolithic ({tok_s_mono:.1f} tok/s) "
            f"— the latency win must not cost tokens/s")
    rec = {"metric": "decode_gap_p99_mixed_budgeted",
           "unit": "ms",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "short_prompts": n_short, "prompt_len_short": p_short,
           "long_prompts": len(long_at), "prompt_len_long": p_long,
           "new_tokens_short": short_new, "new_tokens_long": long_new,
           "prefill_budget": budget,
           "value": round(gap99_bud, 2),
           "decode_gap_p50_ms": round(p(gaps_bud, 50), 2),
           "monolithic_gap_p99_ms": round(gap99_mono, 2),
           "gap_improvement": round(gap99_mono / max(gap99_bud, 1e-9),
                                    2),
           "tokens_per_sec": round(tok_s_bud, 2),
           "monolithic_tokens_per_sec": round(tok_s_mono, 2),
           "gap_tolerance": tol, "tps_tolerance": tps_tol,
           "telemetry": tel_bud,
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


def bench_overload(small: bool):
    """Overload drill (round 13): one server, one injected per-tick
    delay (``delay:tick:0:0.02`` — deterministic latency so the drill
    runs on tiny CPU models), driven at STEADY load (~2/3 of slot
    capacity) and then at 4x-capacity BURST with a TTFT SLO installed.

    The acceptance bar this bench encodes: under the burst the
    admission controller must climb the degradation ladder off real
    windowed TTFT p99s (``admission.degradations``), shed low-priority
    work (queue-cap sheds + door sheds, ``admission.sheds_class0``)
    while every HIGH-priority request completes with TTFT p99 within
    BENCH_OVERLOAD_TOL (default 2x) of the steady phase; after the
    burst drains the controller must walk back to rung 0 within ~2 SLO
    windows (one draining window + one idle-reset window); and the
    whole drill must add ZERO compiled executables after ``warmup()``
    — budget-rung switches ride pre-warmed widths, never a mid-serving
    retrace.  A final arm replays the burst with
    ``PADDLE_TPU_ADMISSION=0``: the unbounded FIFO queue shows what the
    controller is protecting against (``protection_factor`` = off/on
    gold TTFT p99, asserted >= 2)."""
    import numpy as np
    import jax

    from paddle_tpu import faults, flags, telemetry as _tl
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import gpt, serving

    dev = jax.devices()[0]
    if not flags.admission_enabled():
        raise AssertionError(
            "overload bench needs PADDLE_TPU_ADMISSION unset/1 "
            "(the off switch is under test in its own arm)")
    if not _tl.enabled():
        raise AssertionError(
            "overload bench needs PADDLE_TPU_TELEMETRY=1 (the SLO "
            "control loop reads the telemetry histograms)")

    def cnt(name):
        try:
            return int(monitor.get_stat(name).get())
        except Exception:
            return 0

    n_ticks = 60 if small else 150
    B = 4
    bulk_new, bulk_len = 2, 24
    window_s = 0.2
    env = {"PADDLE_TPU_SLO_TTFT_MS": "80",
           "PADDLE_TPU_SLO_WINDOW_S": str(window_s),
           "PADDLE_TPU_ADMISSION_QUEUE_CAP": "8"}
    saved = {k: os.environ.get(k) for k in ("PADDLE_TPU_ADMISSION",
                                            *env)}
    os.environ.update(env)
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=64)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    bulk_prompt = [int(x) for x in rng.integers(1, 100, bulk_len)]
    gold_prompt = [int(x) for x in rng.integers(1, 100, 6)]

    def drive(srv, bulk_per_tick, track=None):
        """One phase: submit bulk_per_tick(it) low-priority requests
        each tick plus one 3-token high-priority probe every 5 ticks
        (first token + two decode gaps — a TTFT-dominated latency
        probe), then drain.  Returns (all gold walls ms, walls of the
        golds submitted with the ladder ENGAGED (rung >= 1), bulk
        rids)."""
        golds, gold_done, bulk_rids = {}, {}, []
        it = 0
        while it < n_ticks or srv.pending():
            if it < n_ticks:
                for _ in range(bulk_per_tick(it)):
                    bulk_rids.append(srv.submit(
                        bulk_prompt, max_new_tokens=bulk_new,
                        priority=0, tenant="bulk"))
                if it % 5 == 2:
                    eng = (srv._adm is not None
                           and srv._adm.rung >= 1)
                    golds[srv.submit(gold_prompt, max_new_tokens=3,
                                     priority=2, tenant="gold")] = \
                        (time.perf_counter(), eng)
            srv.tick()
            if track is not None and srv._adm is not None:
                track["rung_max"] = max(track["rung_max"],
                                        srv._adm.rung)
            now = time.perf_counter()
            for rid, (t0, _) in golds.items():
                if rid not in gold_done and srv.status(rid) == "ok":
                    gold_done[rid] = (now - t0) * 1e3
            it += 1
        if len(gold_done) != len(golds):
            missing = {rid: srv.status(rid) for rid in golds
                       if rid not in gold_done}
            raise AssertionError(
                f"overload bench: high-priority probes did not all "
                f"complete: {missing}")
        return (list(gold_done.values()),
                [gold_done[r] for r, (_, eng) in golds.items() if eng],
                bulk_rids)

    def p99(xs):
        return float(np.percentile(np.asarray(xs), 99)) if xs else 0.0

    try:
        faults.reset()
        srv = serving.DecodeServer(params, cfg, max_batch=B, max_len=64,
                                   prefill_budget=32)
        srv.warmup()
        # warm the whole drill path once (steady cadence, short) so the
        # measured phases pay device time only, then snapshot the step
        # cache — the zero-retrace assert covers everything after this
        faults.install("delay:tick:0:0.02")
        drive(srv, lambda it: 1 if it % 6 else 0)
        keys0 = set(serving._STEP_CACHE.keys())

        # -- steady phase: ~2/3 of the 4-slot capacity ------------------
        c_rej0 = cnt("serving.requests_rejected")
        track_s = {"rung_max": 0}
        gold_steady, _, _ = drive(srv, lambda it: 1 if it % 6 else 0,
                                  track_s)
        steady_rejected = cnt("serving.requests_rejected") - c_rej0

        # -- burst phase: 4x capacity -----------------------------------
        c0 = {n: cnt(n) for n in ("admission.degradations",
                                  "admission.sheds_class0",
                                  "serving.requests_rejected")}
        track_b = {"rung_max": 0}
        gold_burst, gold_eng, bulk_rids = drive(srv, lambda it: 4,
                                                track_b)
        degr = cnt("admission.degradations") - c0["admission.degradations"]
        sheds0 = (cnt("admission.sheds_class0")
                  - c0["admission.sheds_class0"])
        burst_rejected = (cnt("serving.requests_rejected")
                          - c0["serving.requests_rejected"])
        rejected_rids = [r for r in bulk_rids
                         if srv.status(r) == "rejected"]
        if degr < 1 or track_b["rung_max"] < 1:
            raise AssertionError(
                f"overload bench: 4x burst climbed no ladder "
                f"(degradations={degr}, "
                f"rung_max={track_b['rung_max']})")
        if sheds0 < 1 or not rejected_rids:
            raise AssertionError(
                f"overload bench: 4x burst shed no low-priority work "
                f"(sheds_class0={sheds0}, "
                f"rejected={len(rejected_rids)})")

        # -- deep-rung retrace coverage: if the controller stabilized
        # before the budget-switch rungs, force rung 3 and serve a few
        # requests — every width must already be warm
        forced_deep = track_b["rung_max"] < 3
        if forced_deep:
            srv._adm.rung = 3
            for _ in range(3):
                srv.submit(bulk_prompt, max_new_tokens=bulk_new,
                           priority=2, tenant="bulk")
            while srv.pending():
                srv.tick()
            srv._adm.rung = max(srv._adm.rung, 1)

        # -- recovery: idle ticks walk the ladder back to rung 0 --------
        t_idle = time.perf_counter()
        while srv._adm.rung > 0 \
                and time.perf_counter() - t_idle < 5.0:
            srv.tick()
            time.sleep(0.01)
        recovery_s = time.perf_counter() - t_idle
        if srv._adm.rung != 0:
            raise AssertionError(
                f"overload bench: controller stuck at rung "
                f"{srv._adm.rung} {recovery_s:.2f}s after the burst")
        if recovery_s > 2 * window_s + 0.3:
            raise AssertionError(
                f"overload bench: recovery took {recovery_s:.2f}s "
                f"(> 2 SLO windows + slack) — the idle-window reset "
                f"did not engage")
        added = set(serving._STEP_CACHE.keys()) - keys0
        if added:
            raise AssertionError(
                f"overload bench: mid-serving retrace — new "
                f"executables {sorted(added)}")

        tol = float(os.environ.get("BENCH_OVERLOAD_TOL", "2.0"))
        g_steady = p99(gold_steady)
        g_burst_all = p99(gold_burst)
        # the asserted number is the p99 of golds submitted AFTER the
        # ladder engaged — the acceptance bar holds "while low-priority
        # sheds engage"; the first-window (pre-engage) golds ride the
        # uncontrolled FIFO spike and are reported separately
        g_burst = p99(gold_eng) if len(gold_eng) >= 4 else g_burst_all
        if g_burst > g_steady * tol:
            raise AssertionError(
                f"overload bench: high-priority TTFT p99 under 4x "
                f"burst ({g_burst:.0f}ms) exceeds {tol}x steady "
                f"({g_steady:.0f}ms) — degradation is not protecting "
                f"the gold lane")

        # -- control arm: same burst, admission off ---------------------
        os.environ["PADDLE_TPU_ADMISSION"] = "0"
        srv_off = serving.DecodeServer(params, cfg, max_batch=B,
                                       max_len=64, prefill_budget=32)
        gold_off, _, _ = drive(srv_off, lambda it: 4)
        g_off = p99(gold_off)
        protection = g_off / max(g_burst, 1e-9)
        if protection < 2.0:
            raise AssertionError(
                f"overload bench: admission off held gold TTFT p99 at "
                f"{g_off:.0f}ms vs {g_burst:.0f}ms with it on "
                f"(protection {protection:.1f}x < 2x) — the unbounded "
                f"queue should have starved the probes")
    finally:
        faults.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    rec = {"metric": "gold_ttft_p99_burst_ms",
           "unit": "ms",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "value": round(g_burst, 1),
           "gold_p99_burst_all_ms": round(g_burst_all, 1),
           "gold_engaged_probes": len(gold_eng),
           "gold_ttft_p99_steady_ms": round(g_steady, 1),
           "gold_ttft_p99_admission_off_ms": round(g_off, 1),
           "burst_over_steady": round(g_burst / max(g_steady, 1e-9), 2),
           "protection_factor": round(protection, 1),
           "tolerance": tol,
           "steady_rejected": steady_rejected,
           "burst_rejected": burst_rejected,
           "sheds_class0": sheds0,
           "degradations": degr,
           "rung_max_steady": track_s["rung_max"],
           "rung_max_burst": track_b["rung_max"],
           "forced_deep_rung": forced_deep,
           "recovery_s": round(recovery_s, 3),
           "new_compiles": 0,
           "ticks_per_phase": n_ticks, "max_batch": B,
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


def bench_spec(small: bool):
    """Speculative decoding vs the plain continuous-batching server
    (round 11): the same greedy request stream driven through three
    servers — plain, draft-model speculation (a smaller GPT sharing the
    vocab), and model-free self-drafting (host n-gram) — measuring
    generated tok/s and TARGET PASSES PER TOKEN, the number the
    speedup actually comes from: one verify pass scores up to K
    positions, so accepted drafts amortize the target model's weight
    traffic across several tokens.

    Asserted: both speculative modes stay bit-identical to the plain
    server (greedy accept keeps the argmax chain exact), and the
    draft-model arm spends >= 1.5x fewer target passes per token — on
    this arm the draft IS the target (perfect agreement), so the gate
    checks the serving machinery's ceiling, not draft quality.  The
    self-draft arm's pass count is reported unasserted: its n-gram hit
    rate is workload-dependent (repetitive streams win, random streams
    fall back to plain steps).

    Round 17 adds the TREE arm: the same stream through linear-K and
    tree-N speculation at the SAME per-round row budget (N == K),
    driven by a draft engineered to argmax WRONG with the truth at its
    top-2 — the regime where linear dies at the first divergence and
    the tree's sibling branch recovers the tail.  Asserted: tree
    verify stays bit-identical to plain AND spends strictly fewer
    target passes per token than linear at the equal budget; the
    accepted root-to-leaf length histogram is reported alongside.
    ``--constrained`` appends a constrained-workload arm: every
    request decodes under a token-set automaton through a tree server,
    and the run asserts ``constraint.spec_fallbacks`` stays EXACTLY
    zero — constrained slots speculate through DFA-pruned trees
    instead of falling back to plain stepping."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import flags
    from paddle_tpu.framework import monitor
    from paddle_tpu.text import gpt, serving

    dev = jax.devices()[0]
    if small:
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=4, max_seq_len=128)
        dcfg = gpt.GPTConfig(vocab_size=512, hidden_size=64, num_layers=1,
                             num_heads=4, max_seq_len=128)
        B, max_len, new_toks, K, iters = 4, 64, 16, 4, 2
        p_lens = (6, 12, 20, 9)
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=1024,
                            num_layers=24, num_heads=16, max_seq_len=2048)
        # ~12x smaller drafter: the regime the technique targets — the
        # draft's per-step cost is noise next to one target pass
        dcfg = gpt.GPTConfig(vocab_size=50304, hidden_size=512,
                             num_layers=4, num_heads=8, max_seq_len=2048)
        B, max_len, new_toks, K, iters = 8, 1024, 64, 4, 2
        p_lens = (64, 128, 256, 320, 96, 64, 192, 128)
    rng = np.random.default_rng(0)
    prompts = [[int(x) for x in rng.integers(1, cfg.vocab_size, n)]
               for n in p_lens]
    params = jax.device_get(gpt.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(jnp.asarray, params)
    # small mode verifies the machinery's ceiling with draft == target
    # (every proposal accepted); full mode pays for a real small drafter
    dparams = params if small else jax.tree_util.tree_map(
        jnp.asarray,
        jax.device_get(gpt.init_params(dcfg, jax.random.PRNGKey(1))))
    if small:
        dcfg = cfg

    def serve_pass(hist=None, constraint=None, **kw):
        srv = serving.DecodeServer(params, cfg, max_batch=B,
                                   max_len=max_len, **kw)
        if hist is not None:
            # accepted-path-length histogram, sampled at the accept
            # choke point (host-side, zero device traffic)
            orig = srv._spec_tree_accept

            def counted(st, rows, tp):
                toks, sel = orig(st, rows, tp)
                hist[len(sel)] = hist.get(len(sel), 0) + 1
                return toks, sel

            srv._spec_tree_accept = counted
        for p in prompts:
            srv.submit(p, max_new_tokens=new_toks,
                       constraint=constraint)
        while srv.pending():
            srv.tick()
        toks = srv._results
        passes = (srv._spec_rounds + srv._spec_plain_steps
                  if srv._spec_on else srv._step_no)
        accept = None
        if srv._spec_on and srv._spec_prop:
            accept = srv._spec_acc / srv._spec_prop
        srv.close()
        return toks, passes, accept

    def measure(hist=None, **kw):
        serve_pass(**kw)                      # warm pass (compiles)
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = serve_pass(hist=hist, **kw)
        dt = (time.perf_counter() - t0) / iters
        toks, passes, accept = out
        total = sum(len(t) for t in toks.values())
        return toks, total / dt, passes / max(total, 1), accept

    ref, plain_tok_s, plain_ppt, _ = measure()
    draft_kw = dict(draft_cfg=dcfg, draft_params=dparams, spec_k=K)
    got_d, draft_tok_s, draft_ppt, draft_acc = measure(**draft_kw)
    got_s, self_tok_s, self_ppt, self_acc = measure(spec_k=K)
    if got_d != ref:
        raise AssertionError(
            "spec bench: draft-model speculation diverged from the "
            "plain server's greedy tokens")
    if got_s != ref:
        raise AssertionError(
            "spec bench: self-drafting diverged from the plain "
            "server's greedy tokens")
    speedup = plain_ppt / max(draft_ppt, 1e-9)
    if speedup < 1.5:
        raise AssertionError(
            f"spec bench: draft-model arm spent {draft_ppt:.3f} target "
            f"passes/token vs plain {plain_ppt:.3f} — {speedup:.2f}x "
            f"< 1.5x fewer passes per token")
    # tree arm: linear-K vs tree-N at the SAME per-round row budget,
    # driven by a target-derived biased draft (argmax wrong, truth at
    # top-2) so the comparison exercises divergence recovery, not a
    # perfect-agreement ceiling
    bparams = dict(params)
    bparams["ln_f_b"] = jnp.asarray(
        np.asarray(params["ln_f_b"])
        + 30.0 * np.asarray(params["wte"])[42])
    bias_kw = dict(draft_cfg=cfg, draft_params=bparams)
    got_bl, _, blin_ppt, _ = measure(spec_k=K, **bias_kw)
    tree_hist: dict = {}
    got_t, tree_tok_s, tree_ppt, tree_acc = measure(
        hist=tree_hist, spec_tree=K, **bias_kw)
    if got_t != ref:
        raise AssertionError(
            "spec bench: tree verify diverged from the plain server's "
            "greedy tokens")
    if got_bl != ref:
        raise AssertionError(
            "spec bench: biased-draft linear arm diverged from the "
            "plain server's greedy tokens")
    if tree_ppt >= blin_ppt:
        raise AssertionError(
            f"spec bench: tree arm spent {tree_ppt:.3f} target passes/"
            f"token vs linear-K's {blin_ppt:.3f} at the same {K}-row "
            f"budget — branching bought nothing")
    constrained = "--constrained" in sys.argv
    cons_rec = {}
    if constrained:
        # constrained-workload arm: every request under a token-set
        # automaton; tree speculation must PRUNE instead of FALL BACK
        fb_stat = monitor.get_stat("constraint.spec_fallbacks")
        allowed = [int(x) for x in
                   rng.choice(np.arange(1, cfg.vocab_size), 12,
                              replace=False)]
        cref, _, _ = serve_pass(constraint=allowed)
        fb0 = int(fb_stat.get())
        cons_hist: dict = {}
        cgot, ctok_s, cppt, _ = measure(hist=cons_hist, spec_tree=K,
                                        constraint=allowed)
        fb1 = int(fb_stat.get())
        if cgot != cref:
            raise AssertionError(
                "spec bench: constrained tree verify diverged from the "
                "plain constrained server's greedy tokens")
        if fb1 - fb0 != 0:
            raise AssertionError(
                f"spec bench: constrained tree arm tripped "
                f"{fb1 - fb0} constraint.spec_fallbacks — constrained "
                f"slots must speculate via pruned trees, not fall back")
        cons_rec = {
            "constrained_tok_s": round(ctok_s, 2),
            "constrained_passes_per_token": round(cppt, 3),
            "constrained_spec_fallbacks": fb1 - fb0,
            "constrained_accept_len_hist": {
                str(k): v for k, v in sorted(cons_hist.items())},
        }
    rec = {"metric": "tokens_per_sec_serving_speculative",
           "unit": "tokens/s/chip",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "batch": B, "max_len": max_len, "new_tokens": new_toks,
           "spec_k": K, "prompt_lens": list(p_lens),
           "draft_is_target": small,
           "value": round(draft_tok_s, 2),
           "plain_tok_s": round(plain_tok_s, 2),
           "self_draft_tok_s": round(self_tok_s, 2),
           "plain_passes_per_token": round(plain_ppt, 3),
           "draft_passes_per_token": round(draft_ppt, 3),
           "self_draft_passes_per_token": round(self_ppt, 3),
           "passes_per_token_speedup": round(speedup, 3),
           "draft_accept_rate": (round(draft_acc, 3)
                                 if draft_acc is not None else None),
           "self_draft_accept_rate": (round(self_acc, 3)
                                      if self_acc is not None else None),
           # tree arm (equal row budget, biased-target draft): the
           # passes-per-token pair IS the headline claim — one
           # tree-masked pass covers what linear loses at its first
           # divergence — and the histogram shows WHERE the tree's
           # extra tokens come from (accepted path lengths > 1)
           "spec_tree_nodes": K,
           "tree_tok_s": round(tree_tok_s, 2),
           "tree_passes_per_token": round(tree_ppt, 3),
           "linear_biased_passes_per_token": round(blin_ppt, 3),
           "tree_accept_rate": (round(tree_acc, 3)
                                if tree_acc is not None else None),
           "tree_accept_len_hist": {
               str(k): v for k, v in sorted(tree_hist.items())},
           **cons_rec,
           "kv_dtype": flags.kv_cache_dtype() or "compute",
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


def bench_multilora(small: bool):
    """Batched multi-LoRA serving vs sequential per-adapter passes
    (round 14, the S-LoRA/Punica shape): N products, each a LoRA over
    one shared base model, each with a request in flight.  The batched
    arm serves all N in ONE batch — per-slot adapter gather inside the
    jitted step — while the sequential baseline re-points the same
    server at one product at a time (the only option without the
    gather: N passes, N-1 idle slots each), measuring aggregate tok/s
    across the whole product set.

    Asserted: the batched arm's per-request tokens are bit-identical to
    the sequential arm's (the gather IS the merge), aggregate
    throughput is >= 2x sequential, and the measured passes add zero
    ``_STEP_CACHE`` entries after the warm pass (no mid-serving
    retraces)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu import flags
    from paddle_tpu.text import adapters, gpt, lora, serving

    dev = jax.devices()[0]
    if small:
        cfg = gpt.GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                            num_heads=4, max_seq_len=128)
        N, rank, max_len, new_toks, iters = 4, 4, 64, 16, 2
        p_lens = (6, 12, 9, 15)
    else:
        cfg = gpt.GPTConfig(vocab_size=50304, hidden_size=1024,
                            num_layers=24, num_heads=16, max_seq_len=2048)
        N, rank, max_len, new_toks, iters = 8, 16, 1024, 64, 2
        p_lens = (64, 128, 256, 96, 64, 192, 128, 320)
    names = [f"prod-{i}" for i in range(N)]
    params = jax.tree_util.tree_map(
        jnp.asarray, jax.device_get(gpt.init_params(cfg,
                                                    jax.random.PRNGKey(0))))

    def mk_adapter(seed):
        key = jax.random.PRNGKey(seed)
        ad = lora.split_lora(lora.lora_init(params, cfg, rank=rank,
                                            key=key))[1]
        out = {}
        for leaf, v in ad.items():
            if leaf.endswith("_lora_b"):
                key, sub = jax.random.split(key)
                out[leaf] = 0.1 * jax.random.normal(sub, v.shape,
                                                    jnp.float32)
            else:
                out[leaf] = v
        return out

    pool = adapters.AdapterPool(params, cfg, rank=rank, max_adapters=N)
    for i, name in enumerate(names):
        pool.register(name, mk_adapter(i + 1))
    rng = np.random.default_rng(0)
    prompts = {name: [int(x) for x in rng.integers(1, cfg.vocab_size, n)]
               for name, n in zip(names, p_lens)}

    def serve_pass(jobs):
        """jobs: list of (adapter_name, prompt) served in one batch —
        the server geometry (and so every executable) is IDENTICAL
        across arms; only occupancy differs."""
        srv = serving.DecodeServer(params, cfg, max_batch=N,
                                   max_len=max_len, adapter_pool=pool)
        rids = [(name, srv.submit(p, max_new_tokens=new_toks,
                                  adapter=name)) for name, p in jobs]
        while srv.pending():
            srv.tick()
        out = {name: srv.result(r) for name, r in rids}
        srv.close()
        return out

    all_jobs = [(name, prompts[name]) for name in names]

    def batched_pass():
        return serve_pass(all_jobs)

    def sequential_pass():
        out = {}
        for job in all_jobs:
            out.update(serve_pass([job]))
        return out

    batched_pass()                            # warm (compiles)
    sequential_pass()
    keys0 = set(serving._STEP_CACHE.keys())
    t0 = time.perf_counter()
    got_b = None
    for _ in range(iters):
        got_b = batched_pass()
    dt_b = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    got_s = None
    for _ in range(iters):
        got_s = sequential_pass()
    dt_s = (time.perf_counter() - t0) / iters
    if got_b != got_s:
        raise AssertionError(
            "multilora bench: batched multi-adapter tokens diverge "
            "from sequential per-adapter serving")
    added = set(serving._STEP_CACHE.keys()) - keys0
    if added:
        raise AssertionError(
            f"multilora bench: measured passes retraced — new "
            f"executables {sorted(added)}")
    total = sum(len(t) for t in got_b.values())
    tok_s_b, tok_s_s = total / dt_b, total / dt_s
    speedup = tok_s_b / max(tok_s_s, 1e-9)
    if small and speedup < 2.0:
        raise AssertionError(
            f"multilora bench: batched {tok_s_b:.1f} tok/s vs "
            f"sequential {tok_s_s:.1f} — {speedup:.2f}x < 2x aggregate "
            f"throughput")
    rec = {"metric": "tokens_per_sec_serving_multilora",
           "unit": "tokens/s/chip",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "adapters": N, "rank": rank, "batch": N,
           "max_len": max_len, "new_tokens": new_toks,
           "prompt_lens": list(p_lens),
           "value": round(tok_s_b, 2),
           "sequential_tok_s": round(tok_s_s, 2),
           "aggregate_speedup": round(speedup, 3),
           "kv_dtype": flags.kv_cache_dtype() or "compute",
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


def bench_moe(small: bool):
    """MoE serving (round 19): joint expert routing through the
    Engine's moe_* kinds vs the capacity-free dense evaluation, plus a
    drop-rate-vs-capacity-factor sweep.

    Arms (same prompts, warm pass first):

    1. **dispatch** — DecodeServer steady decode tok/s with the routed
       tail (top-k experts per token, capacity-bounded joint routing),
       at the structurally dropless cf = E/k (capacity >= batch, so
       routing cannot drop and tokens are reference-exact).
    2. **dense_eval** — the same batch stepped through
       ``dense_eval_decode_step`` (EVERY expert computed for every
       token, gate-weighted): the compute ceiling expert dispatch
       exists to undercut, and simultaneously the parity reference —
       arm 1's greedy tokens must equal arm 2's token for token.

    Sweep: capacity_factor in {0.5, 1.0, 2.0, E/k} at full occupancy;
    drop rate = dropped / (dropped + kept) from the device counters.
    Asserted: bit parity dispatch == dense_eval; drop rate > 0 at
    cf=0.5 and exactly 0 at cf=E/k; zero post-warmup retraces in the
    timed arm.  Prompts are short (the admission prefill is one
    executable vs the dense arm's python loop — keeping it tiny makes
    both arms ~pure decode)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from paddle_tpu.text import generate, gpt, moe_serving, serving
    from paddle_tpu.text.moe import MoEConfig

    dev = jax.devices()[0]
    E, K = 8, 2
    # fp32 compute: the routed tail and the dense evaluation sum the
    # same expert terms in different einsum orders, so bf16 rounding
    # can flip a greedy argmax on a random-init model at this width —
    # fp32 keeps the order-divergence ~1e-7, far under any logit gap,
    # and the bit-parity gate below stays meaningful
    if small:
        base = dict(vocab_size=512, hidden_size=256, num_layers=4,
                    num_heads=8, max_seq_len=256, dtype=jnp.float32)
        B, p_len, new_toks, sweep_toks = 4, 4, 64, 16
    else:
        base = dict(vocab_size=2048, hidden_size=512, num_layers=8,
                    num_heads=8, max_seq_len=512, dtype=jnp.float32)
        B, p_len, new_toks, sweep_toks = 8, 4, 128, 32
    max_len = p_len + new_toks + 8

    def mcfg(cf):
        return gpt.GPTConfig(moe=MoEConfig(num_experts=E, top_k=K,
                                           capacity_factor=cf,
                                           router_noise=0.0), **base)

    cf_free = float(E) / K                   # C >= B for any B: dropless
    cfg = mcfg(cf_free)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(19)
    prompts = [[int(x) for x in rng.integers(1, base["vocab_size"], p_len)]
               for _ in range(B)]

    def drive(srv, n_new):
        rids = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
        while srv.pending():
            srv.tick()
        return [srv.result(r) for r in rids]

    # dispatch arm: warm pass compiles, timed pass must not — the
    # server stays open between them (close() evicts by config value)
    srv = serving.DecodeServer(params, cfg, max_batch=B,
                               max_len=max_len)
    drive(srv, new_toks)
    keys0 = set(serving._STEP_CACHE.keys())
    t0 = time.perf_counter()
    toks_route = drive(srv, new_toks)
    wall_route = time.perf_counter() - t0
    added = set(serving._STEP_CACHE.keys()) - keys0
    srv.close()
    if added:
        raise AssertionError(
            f"moe bench: timed dispatch arm retraced — new executables "
            f"{sorted(added)}")

    # dense-eval arm: batch cache, shared scalar pos (prompts are
    # equal-length), greedy feed — timed over the decode phase
    dstep = jax.jit(lambda p_, c_, t_, pos_: moe_serving
                    .dense_eval_decode_step(p_, c_, t_, pos_, cfg))

    def dense_run():
        cache = generate.init_cache(cfg, B, max_len)
        tok = jnp.asarray([p[0] for p in prompts], jnp.int32)
        for i in range(p_len - 1):
            _, cache = dstep(params, cache, tok, jnp.int32(i))
            tok = jnp.asarray([p[i + 1] for p in prompts], jnp.int32)
        out = [[] for _ in range(B)]
        t1 = time.perf_counter()
        pos = p_len - 1
        for _ in range(new_toks):
            logits, cache = dstep(params, cache, tok, jnp.int32(pos))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            for b, t in enumerate(np.asarray(tok)):
                out[b].append(int(t))
            pos += 1
        jax.block_until_ready(logits)
        return out, time.perf_counter() - t1

    dense_run()                              # warm the dense-eval jit
    toks_dense, wall_dense = dense_run()
    if toks_route != toks_dense:
        raise AssertionError(
            f"moe bench: dispatch tokens diverged from the dense-eval "
            f"ceiling ({toks_route} vs {toks_dense})")
    tok_s_route = B * new_toks / max(wall_route, 1e-9)
    tok_s_dense = B * new_toks / max(wall_dense, 1e-9)

    # capacity sweep: fresh cfg per cf (cf is a jit key by design —
    # capacity is a shape)
    sweep = []
    for cf in (0.5, 1.0, 2.0, cf_free):
        scfg = mcfg(cf)
        sp = params if cf == cf_free else gpt.init_params(
            scfg, jax.random.PRNGKey(0))
        ssrv = serving.DecodeServer(sp, scfg, max_batch=B,
                                    max_len=max_len)
        drive(ssrv, sweep_toks)              # warm
        t0 = time.perf_counter()
        drive(ssrv, sweep_toks)
        wall = time.perf_counter() - t0
        ls = ssrv.load_stats()               # totals over both passes
        ssrv.close()
        kept = sum(ls["moe_expert_load"])
        dropped = ls["moe_dropped_tokens"]
        sweep.append({"capacity_factor": cf,
                      "drop_rate": round(
                          dropped / max(1, dropped + kept), 4),
                      "dropped": dropped,
                      "tok_s": round(B * sweep_toks / max(wall, 1e-9),
                                     2)})
    if sweep[0]["dropped"] <= 0:
        raise AssertionError(
            f"moe bench: cf=0.5 at full occupancy never dropped — the "
            f"sweep is not exercising capacity ({sweep})")
    if sweep[-1]["dropped"] != 0:
        raise AssertionError(
            f"moe bench: structurally dropless cf={cf_free} counted "
            f"{sweep[-1]['dropped']} drops ({sweep})")

    rec = {"metric": "moe_dispatch_tok_s", "unit": "tokens/s",
           "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
               timespec="seconds"),
           "device": dev.platform,
           "device_kind": str(getattr(dev, "device_kind", "")),
           "num_experts": E, "top_k": K, "batch": B,
           "new_tokens": new_toks,
           "value": round(tok_s_route, 2),
           "dense_eval_tok_s": round(tok_s_dense, 2),
           "vs_dense_eval": round(tok_s_route / max(tok_s_dense, 1e-9),
                                  3),
           "capacity_sweep": sweep,
           "vs_baseline": 0.0}
    return _stamp_provenance(rec, dev)


_CONFIGS = {"gpt": bench_gpt, "train": bench_train, "mnist": bench_mnist,
            "resnet": bench_resnet, "bert": bench_bert, "int8": bench_int8,
            "decode": bench_decode, "decode_long": bench_decode_long,
            "serving": bench_serving, "paged": bench_paged,
            "fleet": bench_fleet, "stream": bench_stream,
            "spec": bench_spec,
            "mixed": bench_mixed, "overload": bench_overload,
            "multilora": bench_multilora, "prefix": bench_prefix,
            "moe": bench_moe}


def main():
    argv = sys.argv[1:]
    if "--cpu" in argv:
        from paddle_tpu.framework.platform import force_cpu

        force_cpu(1)

    import jax

    from paddle_tpu.framework.platform import init_compile_cache

    dev = jax.devices()[0]
    if "--cpu" not in argv and dev.platform != "tpu":
        raise SystemExit(
            f"[bench] no TPU: jax found {dev.platform!r} "
            f"({getattr(dev, 'device_kind', '')}).  Nothing was "
            f"measured.  `--cpu --small` runs the CPU rehearsal.")
    # repeated runs of one checkout skip recompiles
    cache_dir = init_compile_cache()
    small = "--small" in argv
    _log(f"[bench] device={dev.platform}/{getattr(dev, 'device_kind', '')} "
         f"small={small} compile_cache={cache_dir}")

    if "--gpt-rung" in argv:  # one named ladder rung, JSON on stdout
        sel = argv[argv.index("--gpt-rung") + 1]
        names = [r[0] for r in _gpt_rungs()]
        if sel not in names:
            raise SystemExit(f"unknown rung {sel!r}; available: {names}")
        print(json.dumps(_run_gpt_rung(names.index(sel))), flush=True)
        return

    which = None
    if "--config" in argv:
        which = argv[argv.index("--config") + 1]

    # a config that raises ends the run with a traceback and a non-zero
    # exit: a failure is never recorded and carried past
    results = {}
    if which:
        results[which] = _CONFIGS[which](small)
    elif "--all" in argv:
        # --small smoke must not clobber the measured table; smoke
        # details go to a sibling file
        details_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_DETAILS_SMALL.json" if small else "BENCH_DETAILS.json")
        for name, fn in _CONFIGS.items():
            results[name] = _stamp_provenance(fn(small), dev)
            # written incrementally: a run cut at its time limit keeps
            # the configs already measured
            tmp = details_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(results, f, indent=2)
            os.replace(tmp, details_path)
    else:
        results["gpt"] = bench_gpt(small)

    head = results.get("gpt") or next(iter(results.values()))
    print(json.dumps(_stamp_provenance(dict(head), dev)), flush=True)


def _no_flash_requested() -> bool:
    return os.environ.get("PADDLE_TPU_NO_FLASH", "") not in ("", "0")


if __name__ == "__main__":
    main()
