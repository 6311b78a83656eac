"""Shared activation-checkpointing policy names → jax checkpoint policies.

Reference capability: RecomputeOptimizer's checkpoint list
(fluid/optimizer.py:5288) names WHICH activations to keep; jax expresses
the same control as a saveable-predicate policy on ``jax.checkpoint``.
One resolver serves every surface that takes a policy name —
GPTConfig.remat_policy (text/gpt.py), DistributedStrategy
.recompute_configs.policy (distributed/fleet/strategy.py), the generic
PipelineLayer remat, and the ``PADDLE_TPU_REMAT_POLICY`` override.

Accepted names (aliases map to the same policy):
* ``None`` / ``"none"`` / ``"full"`` / ``"nothing_saveable"`` — save
  nothing: full recompute, maximum memory saving;
* ``"dots"`` / ``"dots_saveable"`` — keep matmul outputs, recompute only
  cheap elementwise ops.  A matmul output is kept also when a kernel made
  it: the flash-attention forward's ``out`` (the ``p @ v`` product) and its
  log-sum-exp come out of a ``pallas_call``, which ``checkpoint_dots``
  cannot see, so the two names ``ops/flash_attention`` tags them with are
  saved as well and the backward pass does not run the forward kernel
  again.  Cost per checkpointed layer: ``B*T*H*D`` activations plus
  ``B*H*T`` floats; without flash attention the names match nothing;
* ``"dots_no_batch"`` / ``"dots_with_no_batch_dims_saveable"`` — keep
  only non-batch matmul outputs (weights-stationary contractions; the
  attention products carry batch dimensions and are recomputed, on the
  kernel path too);
* ``"everything"`` / ``"everything_saveable"`` — keep all residuals
  (checkpoint becomes a no-op; useful for A/B isolation).
"""
from __future__ import annotations

import jax

from .flash_attention import SAVED_LSE, SAVED_OUT

_ALIASES = {
    None: None, "none": None, "full": None, "nothing_saveable": None,
    "dots": "dots", "dots_saveable": "dots",
    "dots_no_batch": "dots_no_batch",
    "dots_with_no_batch_dims_saveable": "dots_no_batch",
    "everything": "everything", "everything_saveable": "everything",
}


def canonical(name: str | None) -> str | None:
    """Alias → canonical policy name (None / 'dots' / 'dots_no_batch' /
    'everything').  Estimators must key on THIS, not the raw string, or
    alias spellings silently desynchronize memory models from the
    compiled program."""
    if name not in _ALIASES:
        raise ValueError(
            f"unknown recompute/remat policy {name!r}; choose from "
            f"{sorted(k for k in _ALIASES if isinstance(k, str))} or None")
    return _ALIASES[name]


def resolve(name: str | None):
    """Policy name → jax checkpoint policy (None = save nothing)."""
    canon = canonical(name)
    if canon is None:
        return None
    cp = jax.checkpoint_policies
    return {
        "dots": cp.save_from_both_policies(
            cp.checkpoint_dots,
            cp.save_only_these_names(SAVED_OUT, SAVED_LSE)),
        "dots_no_batch": cp.checkpoint_dots_with_no_batch_dims,
        "everything": cp.everything_saveable,
    }[canon]
