"""Mamba-2 mixer (state-space duality, Dao & Gu 2024) beside attention.

A hybrid block (Falcon-H1's shape) runs this mixer IN PARALLEL with
grouped-query attention on the same normed input and adds both to the
residual stream.  Per head (``n_heads`` heads of ``head_dim``, state
``d_state``, ``n_groups`` shared B/C groups — head i reads group
``i // (n_heads / n_groups)``):

    p   = (in_multiplier * n) W_in * mup_vector    -> z | xBC | dt
    xBC = silu(causal_depthwise_conv1d(xBC, d_conv) + conv_b)
    dt  = softplus(dt + dt_bias) ;  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t       S in R^{P x N}
    y_t = S_t C_t + D x_t
    y   = GroupRMSNorm(y * silu(z)) * g                    (norm after gate)
    out = (y W_out) * out_multiplier

Two entry points share the projections, the conv and the gated norm:

* :func:`mixer_chunk` — a [B, T] chunk from an initial state: the chunked
  SSD form (chunks of ``chunk_size``: a masked quadratic product inside a
  chunk, the recurrence only across chunk boundaries), by einsums.
  ``length`` marks a padded tail: pads neither advance the state (their dt
  is zero) nor enter the conv window carried out.
* :func:`mixer_step` — one token a sequence from its state: the plain
  update.  :func:`mixer_step_pooled` is the same step on the serving
  cache's leaves as they are stored, for the slots that decode: the
  state's update in place by ``ops/ssm_update`` where that kernel runs.

The state of one sequence is ``{"ssm": [H, P, N] float32, "conv":
[d_conv - 1, conv_dim]}``: fixed size, whatever the context — the serving
cache holds it per SLOT beside the paged KV rows (text/kv_pool.py), where
no block table maps it.  Scopes: ``ssm`` around the mixer, ``ssm_conv``,
``ssm_scan`` (the chunked scan) and ``ssm_update`` (the decode update)
inside it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from . import woq

# the cache leaves of a recurrent mixer ([L, batch, ...] each; kv_pool)
STATE_LEAVES = ("ssm", "conv")


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """The mixer's published sizes and scalars (``GPTConfig.ssm``)."""
    n_heads: int = 32
    head_dim: int = 128
    d_state: int = 256
    n_groups: int = 2
    d_conv: int = 4
    chunk_size: int = 128
    in_multiplier: float = 1.0
    out_multiplier: float = 1.0
    # over the z, x, B, C, dt segments of the input projection
    multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    # the recurrent state's storage dtype in the serving cache
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(
                f"ssm n_groups {self.n_groups} must divide n_heads "
                f"{self.n_heads}")
        if len(self.multipliers) != 5:
            raise ValueError("ssm multipliers: one each for z, x, B, C, dt")

    @property
    def d_ssm(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_ssm + 2 * self.n_groups * self.d_state

    @property
    def in_dim(self) -> int:
        return self.d_ssm + self.conv_dim + self.n_heads

    def key(self) -> tuple:
        return (self.n_heads, self.head_dim, self.d_state, self.n_groups,
                self.d_conv, self.chunk_size, self.in_multiplier,
                self.out_multiplier, tuple(self.multipliers),
                str(self.state_dtype))


def count_params(s: SSMConfig, hidden: int) -> int:
    """in_proj + out_proj (no bias) + conv weight and bias + dt_bias,
    A_log, D + the gated norm's gain."""
    return (hidden * s.in_dim + s.d_ssm * hidden
            + s.conv_dim * s.d_conv + s.conv_dim + 3 * s.n_heads + s.d_ssm)


def init_params(s: SSMConfig, hidden: int, layers: int, key,
                std: float = 0.02) -> dict:
    """Stacked [L, ...] mixer leaves, float32.  ``A_log``, ``dt_bias`` and
    ``D`` follow the Mamba-2 initialisation: A uniform in 1..16, dt
    log-uniform in 1e-3..1e-1 (stored through the inverse softplus),
    D = 1."""
    k_in, k_out, k_conv, k_a, k_dt = jax.random.split(key, 5)
    L, H = layers, s.n_heads
    a = jax.random.uniform(k_a, (L, H), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(k_dt, (L, H), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "ssm_in_w": std * jax.random.normal(
            k_in, (L, hidden, s.in_dim), jnp.float32),
        "ssm_out_w": (std / math.sqrt(2 * L)) * jax.random.normal(
            k_out, (L, s.d_ssm, hidden), jnp.float32),
        "ssm_conv_w": jax.random.uniform(
            k_conv, (L, s.conv_dim, s.d_conv), jnp.float32,
            -1.0 / math.sqrt(s.d_conv), 1.0 / math.sqrt(s.d_conv)),
        "ssm_conv_b": jnp.zeros((L, s.conv_dim), jnp.float32),
        "ssm_A_log": jnp.log(a),
        "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "ssm_D": jnp.ones((L, H), jnp.float32),
        "ssm_norm_g": jnp.ones((L, s.d_ssm), jnp.float32),
    }


def zero_state(s: SSMConfig, batch: int, dtype) -> dict:
    """What a sequence starts from, one layer's leaves [batch, ...]."""
    return {
        "ssm": jnp.zeros((batch, s.n_heads, s.head_dim, s.d_state),
                         s.state_dtype),
        "conv": jnp.zeros((batch, s.d_conv - 1, s.conv_dim), dtype),
    }


def init_state(s: SSMConfig, layers: int, batch: int, dtype) -> dict:
    """Zero state leaves [L, batch, ...] (the serving cache's)."""
    return {n: jnp.zeros((layers,) + v.shape, v.dtype)
            for n, v in zero_state(s, batch, dtype).items()}


def state_bytes(s: SSMConfig, layers: int, dtype) -> int:
    """Bytes of one sequence's state over ``layers`` layers."""
    return layers * (
        s.n_heads * s.head_dim * s.d_state * jnp.dtype(s.state_dtype).itemsize
        + (s.d_conv - 1) * s.conv_dim * jnp.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# shared halves
# ---------------------------------------------------------------------------


def _project(n, p, s: SSMConfig, dt_):
    """The input projection on the block's normed input: z [.., d_ssm],
    xBC [.., conv_dim], dt [.., H], each segment under its multiplier."""
    h = n * jnp.asarray(s.in_multiplier, dt_) if s.in_multiplier != 1.0 else n
    proj = woq.mm(h, p, "ssm_in_w", dt_)
    z, xBC, dt = jnp.split(proj, [s.d_ssm, s.d_ssm + s.conv_dim], axis=-1)
    mz, mx, mb, mc, mdt = s.multipliers
    gn = s.n_groups * s.d_state
    if (mx, mb, mc) != (1.0, 1.0, 1.0):
        xBC = xBC * jnp.concatenate([
            jnp.full((s.d_ssm,), mx, dt_), jnp.full((gn,), mb, dt_),
            jnp.full((gn,), mc, dt_)])
    if mz != 1.0:
        z = z * jnp.asarray(mz, dt_)
    if mdt != 1.0:
        dt = dt * jnp.asarray(mdt, dt_)
    return z, xBC, dt


def _split_xbc(xBC, s: SSMConfig):
    """Activated conv channels -> x [.., G, H/G, P], B, C [.., G, N]."""
    lead = xBC.shape[:-1]
    gn = s.n_groups * s.d_state
    x, b, c = jnp.split(xBC, [s.d_ssm, s.d_ssm + gn], axis=-1)
    x = x.reshape(lead + (s.n_groups, s.n_heads // s.n_groups, s.head_dim))
    return (x, b.reshape(lead + (s.n_groups, s.d_state)),
            c.reshape(lead + (s.n_groups, s.d_state)))


def _dt_a(dt, p, s: SSMConfig):
    """softplus(dt + dt_bias) and A = -exp(A_log), float32, heads grouped
    [.., G, H/G]."""
    g = (s.n_groups, s.n_heads // s.n_groups)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["ssm_dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["ssm_A_log"].astype(jnp.float32))
    return dt.reshape(dt.shape[:-1] + g), a.reshape(g)


def _gate_out(y, z, p, s: SSMConfig, dt_, eps: float = 1e-5):
    """GroupRMSNorm(y * silu(z)) * g over ``n_groups`` groups of the
    d_ssm channels (norm after the gate), then the output projection."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    lead = y.shape[:-1]
    yg = y.reshape(lead + (s.n_groups, s.d_ssm // s.n_groups))
    yg = yg * jax.lax.rsqrt(
        jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + eps)
    y = (yg.reshape(lead + (s.d_ssm,))
         * p["ssm_norm_g"].astype(jnp.float32)).astype(dt_)
    out = woq.mm(y, p, "ssm_out_w", dt_)
    if s.out_multiplier != 1.0:
        out = out * jnp.asarray(s.out_multiplier, dt_)
    return out


# ---------------------------------------------------------------------------
# a chunk of positions from an initial state (prefill, the full forward)
# ---------------------------------------------------------------------------


def _ssd_scan(x, dt, a, b, c, s0, chunk: int):
    """Chunked SSD.  x [B, T, G, Hg, P], dt [B, T, G, Hg] (0 at pads),
    a [G, Hg], b / c [B, T, G, N], s0 [B, G, Hg, P, N]; all float32, T a
    multiple of ``chunk``.  Returns (y [B, T, G, Hg, P], final state)."""
    B, T, G, Hg, P = x.shape
    nc, Q = T // chunk, chunk
    xs = (x * dt[..., None]).reshape(B, nc, Q, G, Hg, P)
    b = b.reshape(B, nc, Q, G, -1)
    c = c.reshape(B, nc, Q, G, -1)
    da = (dt * a).reshape(B, nc, Q, G, Hg)
    cs = jnp.cumsum(da, axis=2)                          # [B, nc, Q, G, Hg]
    # inside a chunk: y_l += sum_{s<=l} exp(cs_l - cs_s) (C_l . B_s) x_s
    seg = cs[:, :, :, None] - cs[:, :, None]             # [B, nc, l, s, G, Hg]
    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    cb = jnp.einsum("bclgn,bcsgn->bclsg", c, b)
    y = jnp.einsum("bclsg,bclsgh,bcsghp->bclghp", cb, decay, xs)
    # what each chunk adds to the state at its own end
    to_end = jnp.exp(cs[:, :, -1:] - cs)                 # [B, nc, Q, G, Hg]
    add = jnp.einsum("bcsgn,bcsgh,bcsghp->bcghpn", b, to_end, xs)
    total = jnp.exp(cs[:, :, -1])                        # [B, nc, G, Hg]

    def carry(state, inp):
        add_c, total_c = inp
        return state * total_c[..., None, None] + add_c, state

    final, before = jax.lax.scan(
        carry, s0, (jnp.moveaxis(add, 1, 0), jnp.moveaxis(total, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                  # [B, nc, G, Hg, P, N]
    # across chunks: the state before the chunk, decayed to position l
    y = y + jnp.einsum("bclgn,bcghpn,bclgh->bclghp", c, before, jnp.exp(cs))
    return y.reshape(B, T, G, Hg, P), final


def mixer_chunk(n, p, cfg, state: dict, length=None):
    """The mixer over a chunk ``n`` [B, T, D] (the block's normed input)
    continuing ``state`` ({"ssm": [B, H, P, N], "conv": [B, d_conv - 1,
    conv_dim]}).  ``length`` (traced scalar, default T): positions at and
    past it are padding — they leave the state where position
    ``length - 1`` put it and stay out of the conv window carried out
    (their own outputs are garbage the caller never reads).  Returns
    (out [B, T, D], new state)."""
    s, dt_ = cfg.ssm, cfg.dtype
    B, T, _ = n.shape
    K = s.d_conv
    with jax.named_scope("ssm"):
        z, xBC, dt = _project(n, p, s, dt_)
        with jax.named_scope("ssm_conv"):
            win = jnp.concatenate([state["conv"].astype(dt_), xBC], axis=1)
            w = p["ssm_conv_w"].astype(dt_)              # [conv_dim, K]
            conv = sum(win[:, k:k + T] * w[:, k] for k in range(K))
            xBC = jax.nn.silu(conv + p["ssm_conv_b"].astype(dt_))
            # the last K-1 real inputs: rows [length, length + K - 1) of
            # the window (which leads with the K-1 rows carried in)
            n_real = T if length is None else length
            new_conv = jax.lax.dynamic_slice_in_dim(win, n_real, K - 1, 1)
        with jax.named_scope("ssm_scan"):
            x, b, c = _split_xbc(xBC.astype(jnp.float32), s)
            dt, a = _dt_a(dt, p, s)
            if length is not None:
                dt = jnp.where((jnp.arange(T) < length)[None, :, None, None],
                               dt, 0.0)
            Q = min(s.chunk_size, T)
            pad = -T % Q
            if pad:
                x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad))
                                       + ((0, 0),) * (v.ndim - 2))
                               for v in (x, dt, b, c))
            G, Hg = s.n_groups, s.n_heads // s.n_groups
            s0 = state["ssm"].astype(jnp.float32).reshape(
                B, G, Hg, s.head_dim, s.d_state)
            y, final = _ssd_scan(x, dt, a, b, c, s0, Q)
            y = y[:, :T] + x[:, :T] * p["ssm_D"].astype(
                jnp.float32).reshape(G, Hg)[..., None]
        out = _gate_out(y.reshape(B, T, s.d_ssm), z, p, s, dt_)
    new = {"ssm": final.reshape(state["ssm"].shape).astype(
               state["ssm"].dtype),
           "conv": new_conv.astype(state["conv"].dtype)}
    return out, new


# ---------------------------------------------------------------------------
# one token from the state (decode)
# ---------------------------------------------------------------------------


def _step(n, p, cfg, conv, update):
    """One position ``n`` [B, 1, D] through the mixer from the conv window
    ``conv`` [B, d_conv - 1, conv_dim], around ``update``: (dt [B, G, Hg],
    A [G, Hg], x [B, G, Hg, P], B and C [B, G, N], float32) -> (S C [B, G,
    Hg, P] read off the state it advanced, that state in the form its
    caller keeps).  Returns (out [B, 1, D], the state, the window rolled
    on)."""
    s, dt_ = cfg.ssm, cfg.dtype
    B = n.shape[0]
    with jax.named_scope("ssm"):
        z, xBC, dt = _project(n, p, s, dt_)
        with jax.named_scope("ssm_conv"):
            win = jnp.concatenate([conv.astype(dt_), xBC], axis=1)
            act = jnp.einsum("bkc,ck->bc", win, p["ssm_conv_w"].astype(dt_))
            xBC = jax.nn.silu(act + p["ssm_conv_b"].astype(dt_))
        with jax.named_scope("ssm_update"):
            x, b, c = _split_xbc(xBC.astype(jnp.float32), s)   # [B, G, ..]
            dt, a = _dt_a(dt[:, 0], p, s)                      # [B, G, Hg]
            y, state = update(dt, a, x, b, c)
            y = y + x * p["ssm_D"].astype(jnp.float32).reshape(a.shape)[
                ..., None]
        out = _gate_out(y.reshape(B, 1, s.d_ssm), z, p, s, dt_)
    return out, state, win[:, 1:].astype(conv.dtype)


def mixer_step(n, p, cfg, state: dict):
    """The mixer on ONE position ``n`` [B, 1, D] from ``state``: roll the
    conv window, one state update, one readout.  Returns (out [B, 1, D],
    new state)."""
    s = cfg.ssm

    def update(dt, a, x, b, c):
        s0 = state["ssm"].astype(jnp.float32).reshape(
            x.shape + (s.d_state,))
        new = (s0 * jnp.exp(dt * a)[..., None, None]
               + jnp.einsum("bgh,bghp,bgn->bghpn", dt, x, b))
        return jnp.einsum("bghpn,bgn->bghp", new, c), new

    out, new_ssm, new_conv = _step(n, p, cfg, state["conv"], update)
    return out, {"ssm": new_ssm.reshape(state["ssm"].shape).astype(
                     state["ssm"].dtype),
                 "conv": new_conv}


# ---------------------------------------------------------------------------
# one token a slot from the serving cache's leaves (the paged decode step)
# ---------------------------------------------------------------------------


def _per_slot(mask, leaf, axis: int):
    """``mask`` [batch] shaped to broadcast against ``leaf``, whose batch
    axis is ``axis``."""
    return mask.reshape((1,) * axis + (-1,)
                        + (1,) * (leaf.ndim - axis - 1))


def from_zero(state: dict, pos, axis: int) -> dict:
    """The state a step continues: zero where the slot feeds a sequence's
    first position (so a slot reused after retirement never sees the last
    tenant's state), else what the cache holds."""
    return {n: jnp.where(_per_slot(pos == 0, v, axis),
                         jnp.zeros((), v.dtype), v)
            for n, v in state.items()}


def keep_idle(new: dict, old: dict, live, axis: int) -> dict:
    """A slot that does not decode in this step (free, admitting, between
    the chunks of a prefill) keeps its state bit for bit."""
    return {n: jnp.where(_per_slot(live, old[n], axis), new[n], old[n])
            for n in new}


def _advance_layer(leaves: dict, layer, live, pos, step) -> tuple:
    """``step`` (start state of every slot -> (result, new state)) on layer
    ``layer`` of ``leaves`` ([L, batch, ...] each): the layer cut out, first
    positions zeroed, idle slots put back, the layer written back.  Returns
    (result, the leaves)."""
    with jax.named_scope("ssm"):
        old = {k: v[layer] for k, v in leaves.items()}
        start = from_zero(old, pos, 0)
    res, new = step(start)
    with jax.named_scope("ssm"):
        new = keep_idle(new, old, live, 0)
        leaves = {k: jax.lax.dynamic_update_index_in_dim(v, new[k], layer, 0)
                  for k, v in leaves.items()}
    return res, leaves


def mixer_step_pooled(n, p, cfg, leaves: dict, layer, live, pos):
    """:func:`mixer_step` on the serving cache's state leaves as they are
    stored ([L, slots, ...] each, ``STATE_LEAVES``), at layer ``layer``
    (the layer's place in the leaves: a Python int or a traced scalar): the
    slots ``live`` [slots] names advance by one position ``n`` [slots, 1,
    D], from zero where ``pos`` [slots] is 0; every other slot keeps its
    state bit for bit.  Returns (out [slots, 1, D], the leaves).

    Where ``ops/ssm_update`` runs, the recurrent state never leaves its
    leaf: the kernel reads and writes the decoding slots' state of this
    layer in place and nothing here has a layer's state as its result; the
    conv window (under a hundredth of the state) keeps the slice and
    selects.
    Elsewhere the layer of both leaves is cut out, stepped and written
    back."""
    from ..ops import ssm_update

    s = cfg.ssm
    if not ssm_update.available(leaves["ssm"].shape, leaves["ssm"].dtype,
                                s.n_groups):
        return _advance_layer(leaves, layer, live, pos,
                              lambda start: mixer_step(n, p, cfg, start))
    B = n.shape[0]

    def update(dt, a, x, b, c):
        y, leaf = ssm_update.state_update(
            leaves["ssm"], layer, live, pos,
            (dt[..., None] * x).reshape(B, s.n_heads, s.head_dim),
            jnp.exp(dt * a).reshape(B, s.n_heads), b, c)
        return y.reshape(x.shape), leaf

    def step(start):
        out, ssm_leaf, new_conv = _step(n, p, cfg, start["conv"], update)
        return (out, ssm_leaf), {"conv": new_conv}

    (out, ssm_leaf), conv = _advance_layer({"conv": leaves["conv"]}, layer,
                                           live, pos, step)
    return out, dict(conv, ssm=ssm_leaf)
