"""Mixture-of-Experts layers with expert parallelism (the 'ep' mesh axis).

Capability beyond the reference: xymyeah/Paddle has no MoE/expert parallel
(`grep -rni 'moe'` over python/paddle/distributed is empty — SURVEY.md §2.3).
The TPU build adds it as a first-class parallel axis.

GShard-style design (dispatch/combine einsums, not gather/scatter): the
router produces a dispatch mask [tokens, experts, capacity]; two einsums move
tokens to expert buffers and back.  Under pjit with the expert dim of the
weights and buffers sharded P('ep', ...), XLA lowers the dispatch einsums to
all_to_all over the ep axis — the exact comm pattern hand-written MoE
frameworks issue, derived from shardings.  Static shapes throughout
(capacity-bounded, overflow tokens dropped) keep it jit-compatible.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from . import woq
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass
class MoEConfig:
    num_experts: int = 8
    capacity_factor: float = 1.25
    router_noise: float = 0.0          # jitter std for exploration
    aux_loss_weight: float = 0.01      # load-balancing loss (GShard eq. 4)
    top_k: int = 2


def init_moe_params(key, d_model: int, d_ff: int, cfg: MoEConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    E = cfg.num_experts
    s = 0.02
    return {
        "router_w": s * jax.random.normal(k1, (d_model, E), jnp.float32),
        "w_in": s * jax.random.normal(k2, (E, d_model, d_ff), jnp.float32),
        "b_in": jnp.zeros((E, d_ff), jnp.float32),
        "w_out": s * jax.random.normal(k3, (E, d_ff, d_model), jnp.float32),
        "b_out": jnp.zeros((E, d_model), jnp.float32),
    }


def moe_param_shardings(ep="ep", mp=None) -> dict:
    """Experts shard over 'ep'; inside each expert the ffn dim may shard over
    'mp' (expert-tensor hybrid)."""
    return {
        "router_w": P(None, None),
        "w_in": P(ep, None, mp),
        "b_in": P(ep, mp),
        "w_out": P(ep, mp, None),
        "b_out": P(ep, None),
    }


def _top_k_gating(logits, k: int):
    """Returns (weights [N,k], indices [N,k]) with renormalized softmax."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx, probs


def _route(params, xf, cfg: MoEConfig, key, E: int, C: int, dtype,
           valid=None):
    """Shared router: returns (disp [N,E,C], comb [N,E,C], aux scalar).

    ``valid`` [N] bool (round-5, serving chunked prefill): tokens with
    valid=False — bucket PADDING — claim NO capacity slots (their onehot
    is zeroed before the cumsum position assignment), carry zero gates,
    and are excluded from the load-balancing statistics; a padded prompt
    chunk therefore routes exactly like its unpadded prefix."""
    N = xf.shape[0]
    logits = xf.astype(jnp.float32) @ params["router_w"]
    if cfg.router_noise > 0.0 and key is not None:
        logits = logits + cfg.router_noise * jax.random.normal(
            key, logits.shape)
    gate_w, gate_idx, probs = _top_k_gating(logits, cfg.top_k)

    v = None if valid is None else valid.reshape(N).astype(jnp.float32)
    if v is not None:
        gate_w = gate_w * v[:, None]

    # load-balancing aux loss: E * sum_e f_e * p_e  (GShard/Switch),
    # over the valid tokens only
    if v is None:
        me = jnp.mean(probs, axis=0)                                 # [E]
        fe = jnp.sum(jax.nn.one_hot(gate_idx[:, 0], E), axis=0) / N  # [E]
    else:
        denom = jnp.maximum(jnp.sum(v), 1.0)
        me = jnp.sum(probs * v[:, None], axis=0) / denom
        fe = jnp.sum(jax.nn.one_hot(gate_idx[:, 0], E) * v[:, None],
                     axis=0) / denom
    aux = E * jnp.sum(fe * me) * cfg.aux_loss_weight

    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)         # [N,k,E]
    if v is not None:
        onehot = onehot * v.astype(jnp.int32)[:, None, None]
    flat = onehot.reshape(N * cfg.top_k, E)
    pos = jnp.cumsum(flat, axis=0) * flat - 1                     # [N*k, E]
    pos = jnp.max(pos, axis=-1).reshape(N, cfg.top_k)             # [N,k]
    # pos == -1 (all-zero row: a masked pad token) claimed nothing and
    # must not be clipped into slot 0 of someone else's expert buffer
    keep = (pos >= 0) & (pos < C)
    gate_w = gate_w * keep

    disp = jnp.zeros((N, E, C), dtype)
    n_ix = jnp.arange(N)[:, None].repeat(cfg.top_k, 1)
    disp = disp.at[n_ix, gate_idx, jnp.clip(pos, 0, C - 1)].add(
        keep.astype(dtype))
    comb = jnp.zeros((N, E, C), jnp.float32)
    comb = comb.at[n_ix, gate_idx, jnp.clip(pos, 0, C - 1)].add(
        gate_w * keep)
    return disp, comb, aux


def moe_ffn_manual(params: dict, x, cfg: MoEConfig, ep_axis: str | None,
                   ep_size: int, mp_axis: str | None = None,
                   key=None, activation=jax.nn.gelu):
    """Manual-collective MoE ffn for ``shard_map`` bodies (the pipeline /
    ring-attention composition path, where GSPMD sharding propagation is
    unavailable).

    Param leaves are LOCAL shards: w_in [E_local, D, F_local] etc. with
    E_local = E/ep and F_local = F/mp; router_w replicated.  In this path
    the TOKENS are replicated over 'ep' (ep shards only the experts), so
    dispatch needs no all_to_all: each rank slices its own experts' block
    of the dispatch/combine tensors, runs only its E_local experts
    (1/ep of the FLOPs), and ONE psum over 'ep' merges the partial
    combines — numerically identical to the GSPMD lowering, with the
    Megatron column→row pattern (one more psum over 'mp') inside each
    expert.  Under sequence parallelism the routing statistics (capacity,
    aux loss) are computed per local sequence chunk rather than globally
    — same per-token assignments, chunk-local capacity accounting."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    E_local = params["w_in"].shape[0]
    E = E_local * max(ep_size, 1)
    C = max(1, math.ceil(N * cfg.top_k / E * cfg.capacity_factor))

    disp, comb, aux = _route(params, xf, cfg, key, E, C, x.dtype)

    if ep_axis is not None and ep_size > 1:
        g = jax.lax.axis_index(ep_axis)
        disp = jax.lax.dynamic_slice_in_dim(disp, g * E_local, E_local,
                                            axis=1)   # [N, E_local, C]
        comb = jax.lax.dynamic_slice_in_dim(comb, g * E_local, E_local,
                                            axis=1)

    xin = jnp.einsum("nec,nd->ecd", disp, xf)         # [E_local, C, D]
    h = activation(jnp.einsum("ecd,edf->ecf", xin,
                              woq.w(params, "w_in", x.dtype))
                   + params["b_in"][:, None].astype(x.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, woq.w(params, "w_out", x.dtype))
    if mp_axis is not None:
        out = jax.lax.psum(out, mp_axis)  # row-parallel reduce
    out = out + params["b_out"][:, None].astype(x.dtype)

    y = jnp.einsum("nec,ecd->nd", comb.astype(x.dtype), out)
    if ep_axis is not None and ep_size > 1:
        y = jax.lax.psum(y, ep_axis)      # merge the per-expert-group parts
    return y.reshape(orig_shape), aux


def moe_ffn(params: dict, x, cfg: MoEConfig, key=None, activation=jax.nn.gelu,
            valid=None, capacity: int | None = None,
            with_stats: bool = False):
    """x [..., D] → (y [..., D], aux_loss scalar).

    Capacity per expert C = ceil(N * top_k / E * capacity_factor); tokens
    over capacity are dropped (residual connection keeps them identity —
    standard GShard behavior, keeps shapes static for XLA).

    ``valid`` (round-5): boolean mask over the token dims of x — pad
    tokens route nowhere and claim no capacity (see _route).
    ``capacity`` overrides C; serving prefill passes the DROPLESS bound
    C = N (an expert can receive at most one slot per token), trading
    transient [N, E, N] dispatch memory for the guarantee that a chunked
    prompt routes identically to feeding it token-by-token.
    ``with_stats`` (round-19, MoE serving): additionally return a
    routing-stats delta ``{"dropped": int32 scalar, "load": int32 [E]}``
    computed from the dispatch mask alone — kept assignments per expert,
    and (valid tokens × top_k − kept) dropped assignments — so serving
    can thread an honest device-side drop counter through the jitted
    step without a second routing pass."""
    orig_shape = x.shape
    D = orig_shape[-1]
    xf = x.reshape(-1, D)
    N = xf.shape[0]
    E = cfg.num_experts
    C = (int(capacity) if capacity is not None
         else max(1, math.ceil(N * cfg.top_k / E * cfg.capacity_factor)))

    disp, comb, aux = _route(params, xf, cfg, key, E, C, x.dtype,
                             valid=valid)
    delta = None
    if with_stats:
        # int32 throughout (x64 is disabled): kept assignments per expert
        # from the 0/1 dispatch mask; every valid token claims exactly
        # top_k assignments, so dropped = valid * top_k - kept
        kept_e = jnp.sum(disp.astype(jnp.int32), axis=(0, 2))       # [E]
        n_valid = (jnp.int32(N) if valid is None
                   else jnp.sum(valid.reshape(-1).astype(jnp.int32)))
        delta = {"dropped": n_valid * cfg.top_k - jnp.sum(kept_e),
                 "load": kept_e}

    # route → expert ffn → route back (XLA lowers these to all_to_all when
    # the E dim is sharded over 'ep'); weights resolve through woq.w —
    # identity on float training params, fused dequant on weight-only
    # int8/int4 decode params
    xin = jnp.einsum("nec,nd->ecd", disp, xf)                     # [E,C,D]
    h = activation(jnp.einsum("ecd,edf->ecf", xin,
                              woq.w(params, "w_in", x.dtype))
                   + params["b_in"][:, None].astype(x.dtype))
    out = jnp.einsum("ecf,efd->ecd", h, woq.w(params, "w_out", x.dtype)) \
        + params["b_out"][:, None].astype(x.dtype)
    y = jnp.einsum("nec,ecd->nd", comb.astype(x.dtype), out)
    if with_stats:
        return y.reshape(orig_shape), aux, delta
    return y.reshape(orig_shape), aux


# ---------------------------------------------------------------------------
# one chip's share of a routed expert layer with zero-compute experts
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExpertShareConfig:
    """A routed expert layer as one chip of an expert-parallel deployment
    sees it: the router scores all ``n_routed`` SwiGLU experts and the
    ``n_zero`` zero-compute (identity) experts, a token takes its
    ``top_k`` best, and this chip holds the routed experts ``held`` =
    [lo, hi).  The layer computes the held experts' part of the result
    and the identity part; what the absent experts would add is left out
    (their chips add it in a deployment: no ``ep`` exchange is written).

    Two published score forms: ``"softmax"`` scores are the softmax over
    ALL the router's outputs, the ``top_k`` best taken (a selection bias
    added for the choice only) and not renormalised; ``"topk_softmax"``
    takes the ``top_k`` largest LOGITS and a softmax over those alone
    (they sum to 1; no selection bias).  ``shared_size`` > 0: a dense
    SwiGLU expert of that width on every row, beside the routed sum
    (every chip of a deployment computes a token's once, on the token's
    own chip)."""
    n_routed: int                 # routed experts the router scores
    n_zero: int                   # identity experts after them
    top_k: int
    expert_size: int              # a routed expert's SwiGLU width
    scaling: float = 1.0          # routed_scaling_factor on the whole sum
    held: tuple = (0, 0)          # [lo, hi) of the routed experts held here
    score: str = "softmax"        # or "topk_softmax"
    shared_size: int = 0          # the shared expert's SwiGLU width, or 0

    def __post_init__(self):
        if self.score not in ("softmax", "topk_softmax"):
            raise ValueError(f"unknown score form {self.score!r}")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed:
            raise ValueError(f"held {self.held} is no range of the "
                             f"{self.n_routed} routed experts")
        if self.top_k > self.n_routed + self.n_zero:
            raise ValueError("top_k exceeds the router's width")

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]

    @property
    def router_width(self) -> int:
        return self.n_routed + self.n_zero

    @property
    def selection_bias(self) -> bool:
        """The ``router_b`` leaf: the softmax form's, added for the choice."""
        return self.score == "softmax"

    def key(self) -> tuple:
        return dataclasses.astuple(self)


# what expert_share counts of a call's token-expert selections, in order:
# to a held expert, to an identity expert, to an expert another chip
# holds; then the distinct held experts hit, and whether any row selected
# at all (the calls counted: a call whose rows are all free slots is none)
SHARE_COUNTS = ("pairs_held", "pairs_zero", "pairs_absent", "experts_hit",
                "calls")


def init_expert_share(key, d_model: int, ex: ExpertShareConfig,
                      layers: int, std: float = 0.02) -> dict:
    """The router over all experts (``[L, ...]`` leaves, its selection
    bias beside it where the score form has one, the shared expert's
    three matrices where the config has one) and the held experts' three
    matrices, each a TUPLE of a leaf a layer ``[E, ...]``: a layer's
    experts cut out of a stacked leaf were copied (600 MB a layer at the
    published widths) before every use."""
    k = jax.random.split(key, 4)
    E, Fe = ex.n_held, ex.expert_size

    def nrm(kk, shape, s=std):
        return s * jax.random.normal(kk, (layers,) + shape, jnp.float32)

    def per_layer(kk, shape, s=std):
        return tuple(s * jax.random.normal(kl, shape, jnp.float32)
                     for kl in jax.random.split(kk, layers))

    out = {
        "router_w": nrm(k[0], (d_model, ex.router_width)),
        "gate_w": per_layer(k[1], (E, d_model, Fe)),
        "up_w": per_layer(k[2], (E, d_model, Fe)),
        "down_w": per_layer(k[3], (E, Fe, d_model),
                            std / math.sqrt(2 * layers)),
    }
    if ex.selection_bias:
        out["router_b"] = jnp.zeros((layers, ex.router_width), jnp.float32)
    if ex.shared_size:
        ks = jax.random.split(jax.random.fold_in(key, 4), 3)
        Fs = ex.shared_size
        out.update(
            shared_gate_w=nrm(ks[0], (d_model, Fs)),
            shared_up_w=nrm(ks[1], (d_model, Fs)),
            shared_down_w=nrm(ks[2], (Fs, d_model),
                              std / math.sqrt(2 * layers)))
    return out


def layer_of(blocks: dict, li: int) -> dict:
    """Layer ``li``'s weights of a latent config's ``blocks``: a stacked
    leaf's static slice, a per-layer tuple's member."""
    return jax.tree_util.tree_map(
        lambda v: v[li], blocks, is_leaf=lambda v: isinstance(v, tuple))


def count_expert_share(ex: ExpertShareConfig, d_model: int) -> tuple:
    """(what a layer holds outside its routed experts: the router, its
    selection bias and the shared expert where there is one; one routed
    expert)."""
    return ((d_model + ex.selection_bias) * ex.router_width
            + 3 * d_model * ex.shared_size, 3 * d_model * ex.expert_size)


def route_share(m, p, ex: ExpertShareConfig, valid=None):
    """The router on rows ``m`` [T, D], in float32: (idx [T, k] the
    selected experts, w [T, k] their scores in the config's score form:
    the softmax over all outputs at the selected ones, not renormalised
    (the selection bias chooses only), or the softmax over the selected
    logits alone).  ``valid`` [T]: a row that is padding, or a slot that
    holds no request, selects no expert (its scores are zero and its
    selections count nowhere)."""
    with jax.named_scope("moe_route"):
        logits = m.astype(jnp.float32) @ p["router_w"].astype(jnp.float32)
        if ex.score == "topk_softmax":
            top, idx = jax.lax.top_k(logits, ex.top_k)
            w = jax.nn.softmax(top, axis=-1)
        else:
            s = jax.nn.softmax(logits, axis=-1)
            _, idx = jax.lax.top_k(s + p["router_b"].astype(jnp.float32),
                                   ex.top_k)
            w = jnp.take_along_axis(s, idx, axis=-1)
        if valid is not None:
            w = jnp.where(valid[:, None], w, 0.0)
        return idx, w


def expert_share(m, p, ex: ExpertShareConfig, dt, valid=None):
    """``scaling * (sum over a token's selected HELD experts of s_e *
    expert_e(m) + sum over its selected identity experts of s_e * m)``,
    plus the shared expert's output where the config has one, on rows
    ``m`` [T, D] -> ([T, D] float32, for the caller's residual stream;
    counts int32 [5] as ``SHARE_COUNTS``).

    No token is dropped whatever the routing, and neither a shape nor the
    work depends on it: every held expert runs on every row (three
    batched matmuls, an expert a batch entry: at a step's few rows an
    expert's weights are what a matmul costs, and they are read once),
    and a row's score for an expert it did not select is zero.  The
    scores go onto the hidden activations, so the down projection sums
    over the experts in its float32 accumulator."""
    T, D = m.shape
    k, E = ex.top_k, ex.n_held
    lo, hi = ex.held
    with jax.named_scope("moe"):
        idx, w = route_share(m, p, ex, valid)
        live = (jnp.ones((T, 1), bool) if valid is None
                else valid[:, None])
        held = (idx >= lo) & (idx < hi) & live
        out = zero = None
        if ex.n_zero:
            zero = (idx >= ex.n_routed) & live
            with jax.named_scope("moe_zero"):
                z = jnp.sum(jnp.where(zero, w, 0.0), axis=-1, keepdims=True)
                out = z * m.astype(jnp.float32)
        with jax.named_scope("moe_experts"):
            # sel [T, k, E]: selection j of token t is held expert e
            sel = held[:, :, None] & (
                (idx - lo)[:, :, None] == jnp.arange(E)[None, None, :])
            score = jnp.sum(jnp.where(sel, w[:, :, None], 0.0), axis=1)
            sizes = jnp.sum(sel, axis=(0, 1), dtype=jnp.int32)
            xe = jnp.broadcast_to(m.astype(dt), (E, T, D))
            g = jnp.einsum("etd,edf->etf", xe, woq.w(p, "gate_w", dt))
            u = jnp.einsum("etd,edf->etf", xe, woq.w(p, "up_w", dt))
            h = (jax.nn.silu(g) * u).astype(jnp.float32) * score.T[:, :, None]
            routed = jnp.einsum(
                "etf,efd->td", h.astype(dt), woq.w(p, "down_w", dt),
                preferred_element_type=jnp.float32)
            out = routed if out is None else out + routed
        n_held = jnp.sum(held)
        n_zero = (jnp.sum(zero) if zero is not None
                  else jnp.zeros((), jnp.int32))
        counts = jnp.stack([
            n_held, n_zero, jnp.sum(live) * k - n_held - n_zero,
            jnp.sum(sizes > 0), jnp.any(live)]).astype(jnp.int32)
        if ex.scaling != 1.0:
            out = ex.scaling * out
        if ex.shared_size:
            with jax.named_scope("moe_shared"):
                x = m.astype(dt)
                hs = (jax.nn.silu(woq.mm(x, p, "shared_gate_w", dt))
                      * woq.mm(x, p, "shared_up_w", dt))
                out = out + jnp.dot(hs, woq.w(p, "shared_down_w", dt),
                                    preferred_element_type=jnp.float32)
        return out, counts
