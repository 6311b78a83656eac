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
  cheap elementwise ops;
* ``"dots_no_batch"`` / ``"dots_with_no_batch_dims_saveable"`` — keep
  only non-batch matmul outputs (weights-stationary contractions);
* ``"everything"`` / ``"everything_saveable"`` — keep all residuals
  (checkpoint becomes a no-op; useful for A/B isolation).
"""
from __future__ import annotations

import jax

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
    return {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch":
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        "everything": jax.checkpoint_policies.everything_saveable,
    }[canon]
