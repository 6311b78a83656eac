"""Generic pipeline segmentation: LayerDesc / SharedLayerDesc / PipelineLayer.

Reference capability: fleet/meta_parallel/parallel_layers/pp_layers.py —
``LayerDesc`` (:23) lazily describes one layer, ``SharedLayerDesc`` (:62)
marks weights reused by several stages (tied embeddings), ``PipelineLayer``
(:76) partitions the list into contiguous stage segments and wires p2p
send/recv between per-process stage programs.

TPU-first re-design.  The reference runs one *different* program per stage
process (MPMD); XLA SPMD compiles ONE program for every device, so
heterogeneous stages become per-stage ``lax.switch`` branches and the stage
state becomes data:

* each stage's own params/buffers are flattened into one f32 vector, padded
  to the longest stage, and stacked ``[S, L]`` sharded ``P('pp')`` — rank s
  physically holds only its own stage's weights (the reference's per-process
  partition);
* boundary activations are flattened + padded to one common ``[A]`` buffer
  riding ``lax.ppermute`` over the 'pp' mesh axis (send_v2/recv_v2 analog);
* ``SharedLayerDesc`` weights live in a separate replicated tree; every
  stage that references the key reads the same arrays, and shard_map's AD
  transpose psums their gradients over 'pp' automatically — the reference's
  ``allreduce_shared_weight_gradients`` (pp_layers.py:188) for free.

Three schedules, selectable via ``build_train_step(schedule=)`` — the
first two match the reference SectionWorker's ``schedule_mode``
(section_worker.cc:130-183); the third goes beyond the reference:

* ``"1f1b"`` (default): one scan whose every tick runs ONE forward
  micro-batch step and ONE backward micro-batch step per stage — micro-batch
  m runs forward on stage s at tick ``m + s`` and backward at tick
  ``m + 2(S-1) - s``.  The backward slot re-runs the stage forward under
  ``jax.vjp`` from a ring buffer of the last ``min(M, 2S-1)`` stage *inputs*
  (plus the pre-update buffer vector, so BN recompute sees the same state),
  so activation memory is flat in the micro-batch count M.
* ``"fthenb"``: autodiff over the F-then-B scan (micro-batch m enters at
  tick m, leaves at tick m + S - 1) — simpler, but the scan stores residuals
  for every tick, so activation memory grows with M.
* ``"interleaved"`` (+ ``n_virtual=v``): Megatron-style virtual pipeline
  stages — each rank holds v round-robin model chunks, shrinking the
  pipeline bubble by ~v at the cost of more in-flight activations.  The
  schedule itself is generated and dependency-validated as data in
  pp_schedule.py and executed by :class:`InterleavedPipelineTrainStep`.

The flagship GPT path (text/gpt_hybrid.py) keeps its hand-built
Megatron-aware 1F1B; this module generalizes the same schedule to
*arbitrary Layer lists* (ResNet, BERT, mixed conv/fc models).

Cost model for heterogeneous stages (this module's whole point — and its
price).  XLA SPMD compiles ONE program for every device, so per-stage
differences become padding, not divergence:

* **weights**: each stage's params flatten into one f32 vector padded to
  the LARGEST stage's size ``Lp`` — per-device weight memory is
  ``max_s |params_s|``, not ``|params_s|``.  ``seg_method="parameters"``
  exists to balance exactly this.
* **boundary activations**: every ppermute hop carries the LARGEST
  boundary's flat size ``A = max_s |x_s|`` — a conv stack whose early
  feature maps are 10x its late ones pays the early size on every hop.
* **compute**: a ``lax.switch`` runs only the selected branch — stage
  FLOPs do NOT pad up; per-tick wall-clock is the SLOWEST stage (ordinary
  pipeline balance, same as the reference's per-process stages).

So padding hurts memory/bandwidth, never FLOPs.  When stage sizes are
badly skewed, rebalance with ``seg_method="parameters"`` or hand-place
cuts; ``PipelineTrainStep.padding_report()`` quantifies the current waste
(tests/test_pp_layers.py exercises a 16x-skewed stack against it).
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.autograd import no_grad
from ..core.tensor import Tensor
from ..framework import random as _random
from ..nn.layer_base import Layer

__all__ = ["LayerDesc", "SharedLayerDesc", "PipelineLayer"]


class LayerDesc:
    """Lazy layer description (reference pp_layers.py:23)."""

    def __init__(self, layer_class, *args, **kwargs):
        if not issubclass(layer_class, Layer):
            raise TypeError(f"LayerDesc needs a Layer subclass, got "
                            f"{layer_class!r}")
        self.layer_class = layer_class
        self.args = args
        self.kwargs = kwargs

    def build(self) -> Layer:
        return self.layer_class(*self.args, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    """A layer whose weights are shared across every stage that names the
    same ``key`` (reference pp_layers.py:62 — tied embedding/logits).

    ``forward_func(layer, x)`` customizes the reuse (e.g. the logits head
    multiplies by the embedding table's transpose)."""

    def __init__(self, key: str, layer_class, *args,
                 forward_func: Callable | None = None, **kwargs):
        super().__init__(layer_class, *args, **kwargs)
        self.shared_key = key
        self.forward_func = forward_func


class _Item(NamedTuple):
    kind: str            # "layer" | "shared" | "fn"
    layer: Any           # Layer or plain callable
    fwd: Callable | None  # custom forward (shared descs)
    shared_key: str | None


class _PackMeta(NamedTuple):
    """Static recipe for flattening a pytree of arrays into one f32 vector."""
    treedef: Any
    shapes: tuple
    dtypes: tuple
    offsets: tuple
    size: int


def _meta_of(tree) -> _PackMeta:
    """Works on concrete arrays and on eval_shape's ShapeDtypeStructs."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes, dtypes, offsets = [], [], []
    off = 0
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        dtype = jnp.dtype(getattr(leaf, "dtype", None)
                          or jnp.result_type(leaf))
        shapes.append(shape)
        dtypes.append(dtype)
        offsets.append(off)
        off += int(np.prod(shape)) if shape else 1
    return _PackMeta(treedef, tuple(shapes), tuple(dtypes), tuple(offsets), off)


def _pack(tree, meta: _PackMeta, pad_to: int):
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((pad_to,), jnp.float32)
    vec = jnp.concatenate(
        [jnp.asarray(l).astype(jnp.float32).reshape(-1) for l in leaves])
    return jnp.pad(vec, (0, pad_to - meta.size))


def _unpack(vec, meta: _PackMeta):
    leaves = []
    for shape, dtype, off in zip(meta.shapes, meta.dtypes, meta.offsets):
        n = int(np.prod(shape)) if shape else 1
        leaf = lax.slice_in_dim(vec, off, off + n).reshape(shape).astype(dtype)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(meta.treedef, leaves)


def _wrap_tree(x):
    return jax.tree_util.tree_map(
        lambda a: Tensor(a, stop_gradient=True) if not isinstance(a, Tensor)
        else a, x)


def _unwrap_tree(x):
    return jax.tree_util.tree_map(
        lambda t: t.value if isinstance(t, Tensor) else t, x,
        is_leaf=lambda t: isinstance(t, Tensor))


def _current_lr_of(optimizer, step: int) -> float:
    from ..optimizer.lr import LRScheduler

    if isinstance(optimizer._lr, LRScheduler):
        return float(optimizer._lr.lr_at(step))
    return optimizer.get_lr()


def _check_batch_divisible(X, n_micro: int, dp: int):
    for leaf in jax.tree_util.tree_leaves(X):
        B = np.shape(leaf)[0]
        if B % (n_micro * dp):
            raise ValueError(
                f"global batch {B} must divide by n_micro*dp = "
                f"{n_micro * dp}")


def _put_batch(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.asarray(
            a.value if isinstance(a, Tensor) else a), sharding), tree,
        is_leaf=lambda a: isinstance(a, Tensor))


def _apply_item(item: _Item, params, bufs, x, training: bool):
    """Run one list item functionally; returns (y, new_bufs)."""
    from ..jit import _swap_state

    if item.kind == "fn":
        with no_grad():
            y = item.layer(_wrap_tree(x))
        return _unwrap_tree(y), bufs
    layer = item.layer
    layer.training = training
    with _swap_state(layer, params, bufs) as (_, named_b):
        with no_grad():
            if item.fwd is not None:
                y = item.fwd(layer, _wrap_tree(x))
            else:
                args = x if isinstance(x, tuple) else (x,)
                y = layer(*[_wrap_tree(a) for a in args])
        new_bufs = {k: t._value for k, t in named_b.items()}
    return _unwrap_tree(y), new_bufs


class PipelineLayer(Layer):
    """Partition an arbitrary layer list into ``num_stages`` pipeline stages
    (reference pp_layers.py:76).

    ``layers``: list of Layer / LayerDesc / SharedLayerDesc / plain callables
    (pure tensor functions, e.g. reshapes).
    ``seg_method``: "uniform" (equal layer counts) or "parameters" (balance
    parameter numel across stages).

    Eager ``forward`` runs the whole list serially (the single-process
    parity path); :meth:`build_train_step` compiles the pp-parallel step.
    """

    def __init__(self, layers, num_stages: int, seg_method: str = "uniform"):
        super().__init__()
        if num_stages < 1:
            raise ValueError("num_stages must be >= 1")
        self.num_stages = num_stages
        self._shared_layers: dict[str, Layer] = {}
        items: list[_Item] = []
        for i, entry in enumerate(layers):
            if isinstance(entry, SharedLayerDesc):
                if entry.shared_key not in self._shared_layers:
                    self._shared_layers[entry.shared_key] = entry.build()
                layer = self._shared_layers[entry.shared_key]
                items.append(_Item("shared", layer, entry.forward_func,
                                   entry.shared_key))
            elif isinstance(entry, LayerDesc):
                items.append(_Item("layer", entry.build(), None, None))
            elif isinstance(entry, Layer):
                items.append(_Item("layer", entry, None, None))
            elif callable(entry):
                items.append(_Item("fn", entry, None, None))
            else:
                raise TypeError(f"unsupported pipeline entry: {entry!r}")
        if len(items) < num_stages:
            raise ValueError(
                f"cannot split {len(items)} layers into {num_stages} stages")
        self._items = items
        # register sublayers so parameters()/state_dict() see everything once
        for key, l in self._shared_layers.items():
            self.add_sublayer(f"shared_{key}", l)
        for i, it in enumerate(items):
            if it.kind == "layer":
                self.add_sublayer(f"layer_{i}", it.layer)
        self._bounds = self._segment(seg_method)

    # -- segmentation ------------------------------------------------------
    def _segment(self, method: str):
        self._seg_method = method
        return self._segment_bounds(method, self.num_stages)

    def _segment_bounds(self, method: str, S: int):
        n = len(self._items)
        if method == "uniform":
            weights = [1.0] * n
        elif method == "parameters":
            weights = []
            for it in self._items:
                if it.kind == "fn":
                    weights.append(0.0)
                else:
                    weights.append(float(sum(
                        int(np.prod(p.shape)) for p in it.layer.parameters())
                        ) + 1e-3)
        else:
            raise ValueError(f"unknown seg_method {method!r}")
        total = sum(weights)
        bounds = [0]
        acc = 0.0
        for i, w in enumerate(weights):
            acc += w
            remaining_items = n - (i + 1)
            remaining_stages = S - len(bounds)
            if (acc >= total * len(bounds) / S
                    and len(bounds) < S
                    and remaining_items >= remaining_stages):
                bounds.append(i + 1)
        while len(bounds) < S:  # degenerate weights: pad cuts from the tail
            bounds.append(n - (S - len(bounds)))
        bounds.append(n)
        return bounds

    def stage_items(self, s: int) -> list:
        return self._items[self._bounds[s]: self._bounds[s + 1]]

    # -- serial (parity) path ----------------------------------------------
    def forward(self, x):
        for it in self._items:
            if it.kind == "fn":
                x = it.layer(x)
            elif it.fwd is not None:
                x = it.fwd(it.layer, x)
            else:
                x = it.layer(*(x if isinstance(x, tuple) else (x,)))
        return x

    # -- pipeline-parallel compiled step -------------------------------------
    def build_train_step(self, mesh: Mesh, optimizer, loss_fn,
                         n_micro: int, example_input, dp_axis: str = "dp",
                         pp_axis: str = "pp", remat: bool = True,
                         schedule: str = "1f1b", n_virtual: int = 1):
        """Compile the pp(+dp)-parallel train step over ``mesh``.

        ``example_input``: one (global-batch) input array/pytree used to
        trace boundary shapes — its per-micro-batch slice must be valid.
        ``schedule``: "1f1b" (activation memory bounded by the in-flight
        window — reference section_worker.cc schedule_mode 1), "fthenb"
        (autodiff over the forward scan, residuals for every tick —
        schedule_mode 0), or "interleaved" (virtual pipeline stages:
        each rank holds ``n_virtual`` model chunks round-robin, shrinking
        the pipeline bubble by ~n_virtual — beyond the reference, which
        has only modes 0/1; see pp_schedule.py).  With one stage all
        collapse to the same loop.
        ``remat``: rematerialize stage forwards in the backward pass — under
        "fthenb" this is what keeps the scan's residuals to one boundary
        buffer per tick; under "1f1b"/"interleaved" it bounds the
        *within-tick* VJP residuals to the branch inputs (the cross-tick
        window is already flat in M by construction).
        Returns a step object: call ``(X, Y) -> loss``.
        """
        if schedule == "interleaved":
            return InterleavedPipelineTrainStep(
                self, mesh, optimizer, loss_fn, n_micro, example_input,
                dp_axis, pp_axis, remat, n_virtual)
        if n_virtual != 1:
            raise ValueError("n_virtual > 1 requires schedule='interleaved'")
        return PipelineTrainStep(self, mesh, optimizer, loss_fn, n_micro,
                                 example_input, dp_axis, pp_axis, remat,
                                 schedule)


class PipelineTrainStep:
    """Stateful wrapper around the compiled pp train step (the role of the
    reference's PipelineParallel.train_batch, pipeline_parallel.py:109)."""

    def __init__(self, pl: PipelineLayer, mesh: Mesh, optimizer, loss_fn,
                 n_micro: int, example_input, dp_axis: str, pp_axis: str,
                 remat: bool, schedule: str = "1f1b"):
        S = mesh.shape[pp_axis]
        if S != pl.num_stages:
            raise ValueError(f"mesh '{pp_axis}' size {S} != num_stages "
                             f"{pl.num_stages}")
        if schedule not in ("1f1b", "fthenb"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        dp = mesh.shape.get(dp_axis, 1)
        self.pl = pl
        self.mesh = mesh
        self._dp = dp
        self.optimizer = optimizer
        self.n_micro = n_micro
        self._step = 0
        training = pl.training

        # ---- per-stage state packing (params P('pp')-stacked, shared repl.)
        from ..jit import _split_state as _jit_split_state

        stage_ptrees, stage_btrees = [], []
        for s in range(S):
            pt, bt = {}, {}
            for j, it in enumerate(pl.stage_items(s)):
                if it.kind != "layer":
                    continue
                p, b = _jit_split_state(it.layer)
                pt[str(j)] = p
                bt[str(j)] = b
            stage_ptrees.append(pt)
            stage_btrees.append(bt)
        shared_p, shared_b = {}, {}
        for key, l in pl._shared_layers.items():
            shared_p[key], sb = _jit_split_state(l)
            if sb:
                raise NotImplementedError(
                    "SharedLayerDesc layers with buffers are not supported "
                    "(their per-stage updates would diverge)")
        self._pmetas = [_meta_of(t) for t in stage_ptrees]
        self._bmetas = [_meta_of(t) for t in stage_btrees]
        Lp = max(m.size for m in self._pmetas) or 1
        Lb = max((m.size for m in self._bmetas), default=1) or 1
        pvec = jnp.stack([_pack(t, m, Lp)
                          for t, m in zip(stage_ptrees, self._pmetas)])
        bvec = jnp.stack([_pack(t, m, Lb)
                          for t, m in zip(stage_btrees, self._bmetas)])

        # ---- boundary activation metas (trace stage chains with eval_shape)
        def mb_slice(tree):
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros((np.shape(a)[0] // (n_micro * max(dp, 1)),)
                                    + tuple(np.shape(a)[1:]),
                                    jnp.asarray(a).dtype), tree)

        def run_stage_concrete(s, ptree, btree, sp, x):
            new_b = dict(btree)
            for j, it in enumerate(pl.stage_items(s)):
                if it.kind == "layer":
                    x, nb = _apply_item(it, ptree[str(j)], btree[str(j)], x,
                                        training)
                    new_b[str(j)] = nb
                elif it.kind == "shared":
                    x, _ = _apply_item(it, sp[it.shared_key], {}, x, training)
                else:
                    x, _ = _apply_item(it, None, None, x, training)
            return x, new_b

        x_meta = [None] * S  # input boundary meta per stage (s>=1)
        x_abs = mb_slice(example_input)
        for s in range(S):
            if s >= 1:
                x_meta[s] = _meta_of(x_abs)
            x_abs = jax.eval_shape(
                functools.partial(run_stage_concrete, s, stage_ptrees[s],
                                  stage_btrees[s], shared_p), x_abs)[0]
        out_meta = _meta_of(x_abs)  # last stage's output (loss head input)
        A = max([m.size for m in x_meta if m is not None] + [out_meta.size],
                default=1) or 1
        self._x_metas = x_meta
        self._out_meta = out_meta
        self._A = A

        # ---- per-stage switch branches (uniform signature; flags pick the
        # outputs so all three uses share one stage-application body):
        # fthenb ticks need (y, new_bv, loss); 1F1B forward slots own the
        # buffer updates (y, new_bv); 1F1B backward slots VJP the
        # stage+masked-head unit (y, loss)
        def make_branch(s, *, emit_bv: bool, emit_loss: bool):
            pm, bm = self._pmetas[s], self._bmetas[s]

            def branch(pv, bv, sp, x_flat, x0, y_lbl, key):
                ptree = _unpack(pv, pm)
                btree = _unpack(bv, bm)
                x = x0 if s == 0 else _unpack(x_flat, x_meta[s])
                with _random.rng_scope(key):
                    y, new_b = run_stage_concrete(s, ptree, btree, sp, x)
                loss = jnp.zeros((), jnp.float32)
                if s == S - 1:
                    # nothing consumes the last stage's forward output
                    # (fthenb: the head is here; 1f1b: the same-tick
                    # backward recomputes it inside its VJP)
                    y_send = jnp.zeros((A,), jnp.float32)
                    if emit_loss:
                        loss = loss_fn(_wrap_tree(y),
                                       Tensor(y_lbl, stop_gradient=True))
                        loss = (loss.value if isinstance(loss, Tensor)
                                else loss).astype(jnp.float32)
                else:
                    y_send = _pack(y, x_meta[s + 1], A)
                out = (y_send,)
                if emit_bv:
                    out += (lax.stop_gradient(_pack(new_b, bm, Lb)),)
                if emit_loss:
                    out += (loss,)
                return out

            return branch

        branches = [make_branch(s, emit_bv=True, emit_loss=True)
                    for s in range(S)]
        fwd_branches = [make_branch(s, emit_bv=True, emit_loss=False)
                        for s in range(S)]
        full_branches = [make_branch(s, emit_bv=False, emit_loss=True)
                         for s in range(S)]
        perm = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [(i, (i - 1) % S) for i in range(S)]
        dp_ax = dp_axis if dp > 1 else None

        def pp_loss(pv_loc, bv_loc, sp, X, Y, key):
            s_idx = lax.axis_index(pp_axis)
            pv = pv_loc[0]
            bv = bv_loc[0]
            M = n_micro
            Xmb = jax.tree_util.tree_map(
                lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), X)
            Ymb = jax.tree_util.tree_map(
                lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), Y)
            ticks = M + S - 1
            keys = jax.random.split(key, ticks)

            step_branch = branches
            if remat:
                step_branch = [jax.checkpoint(b) for b in branches]

            def tick(carry, inp):
                x_flat, bv_c, loss_acc = carry
                t, k_t = inp
                in_idx = jnp.clip(t, 0, M - 1)
                out_idx = jnp.clip(t - (S - 1), 0, M - 1)
                x0 = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, in_idx,
                                                       keepdims=False), Xmb)
                y_lbl = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, out_idx,
                                                       keepdims=False), Ymb)
                k_t = jax.random.fold_in(k_t, s_idx)
                y_flat, bv_n, l = lax.switch(s_idx, step_branch, pv, bv_c, sp,
                                             x_flat, x0, y_lbl, k_t)
                # stage s holds real data only for ticks s..s+M-1 — outside
                # that window the input is fill/drain garbage, which must not
                # contaminate running statistics (BN buffers)
                valid = (t >= s_idx) & (t < s_idx + M)
                bv_n = jnp.where(valid, bv_n, bv_c)
                loss_acc = loss_acc + jnp.where(t >= S - 1, l, 0.0)
                x_send = lax.ppermute(y_flat, pp_axis, perm)
                return (x_send, bv_n, loss_acc), None

            init = (jnp.zeros((A,), jnp.float32), bv,
                    jnp.zeros((), jnp.float32))
            (_, bv_new, loss_sum), _ = lax.scan(tick, init,
                                                (jnp.arange(ticks), keys))
            loss = lax.psum(loss_sum, pp_axis) / M
            if dp_ax:
                loss = lax.pmean(loss, dp_ax)
            for ax in mesh.axis_names:
                if ax not in (dp_axis, pp_axis) and mesh.shape[ax] > 1:
                    loss = lax.pmean(loss, ax)
            return loss, bv_new[None]

        other_axes = tuple(ax for ax in mesh.axis_names
                           if ax not in (dp_axis, pp_axis)
                           and mesh.shape[ax] > 1)

        def pp_1f1b(pv_loc, bv_loc, sp, X, Y, key):
            """Per-rank interleaved schedule: returns (loss, local stage
            grads, shared grads, new buffers) — no outer autodiff needed.
            Micro-batch m: forward on stage s at tick m + s, backward at
            tick m + 2(S-1) - s (the wave reflects off the last stage,
            whose loss-head VJP runs in the same tick as its forward)."""
            s_idx = lax.axis_index(pp_axis)
            pv = pv_loc[0]
            bv = bv_loc[0]
            M = n_micro
            Xmb = jax.tree_util.tree_map(
                lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), X)
            Ymb = jax.tree_util.tree_map(
                lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), Y)
            BUF = min(M, 2 * S - 1)
            ticks = M + 2 * (S - 1)
            g_sp0 = jax.tree_util.tree_map(jnp.zeros_like, sp)

            def tick(carry, t):
                x_fwd, dx_bwd, bv_c, buf_x, buf_bv, g_pv, g_sp, loss_acc = \
                    carry

                # ---- forward slot: micro-batch t - s
                f_m = t - s_idx
                f_valid = (f_m >= 0) & (f_m < M)
                f_idx = jnp.clip(f_m, 0, M - 1)
                x0_f = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, f_idx,
                                                       keepdims=False), Xmb)
                ylbl_f = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, f_idx,
                                                       keepdims=False), Ymb)
                k_f = jax.random.fold_in(jax.random.fold_in(key, f_idx),
                                         s_idx)
                y_f, bv_n = lax.switch(s_idx, fwd_branches, pv, bv_c, sp,
                                       x_fwd, x0_f, ylbl_f, k_f)
                # ring buffer of stage INPUTS (+ the pre-update buffer
                # vector, so the backward recompute sees the same BN state);
                # guard so drain ticks can't clobber an unconsumed slot
                buf_x = jnp.where(
                    f_valid,
                    lax.dynamic_update_index_in_dim(buf_x, x_fwd,
                                                    f_idx % BUF, 0), buf_x)
                buf_bv = jnp.where(
                    f_valid,
                    lax.dynamic_update_index_in_dim(buf_bv, bv_c,
                                                    f_idx % BUF, 0), buf_bv)
                bv_next = jnp.where(f_valid, bv_n, bv_c)
                x_fwd_next = lax.ppermute(y_f, pp_axis, perm)

                # ---- backward slot: micro-batch t - 2(S-1) + s
                b_m = t - 2 * (S - 1) + s_idx
                b_valid = (b_m >= 0) & (b_m < M)
                b_idx = jnp.clip(b_m, 0, M - 1)
                x_saved = lax.dynamic_index_in_dim(buf_x, b_idx % BUF,
                                                   keepdims=False)
                bv_saved = lax.dynamic_index_in_dim(buf_bv, b_idx % BUF,
                                                    keepdims=False)
                x0_b = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, b_idx,
                                                       keepdims=False), Xmb)
                y_lbl = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, b_idx,
                                                       keepdims=False), Ymb)
                k_b = jax.random.fold_in(jax.random.fold_in(key, b_idx),
                                         s_idx)

                def run(pv_, sp_, xf_):
                    return lax.switch(s_idx, full_branches, pv_, bv_saved,
                                      sp_, xf_, x0_b, y_lbl, k_b)

                if remat:
                    # bound the within-tick residuals to the branch inputs;
                    # prevent_cse=False — the scan provides CSE protection
                    # (see text/gpt.py).  Same env overrides as gpt.py so
                    # an A/B of the variants covers pp too.
                    from ..ops.remat_policies import resolve as _rp

                    _cse = os.environ.get(
                        "PADDLE_TPU_REMAT_PREVENT_CSE", "") == "1"
                    run = jax.checkpoint(
                        run, prevent_cse=_cse,
                        policy=_rp(os.environ.get(
                            "PADDLE_TPU_REMAT_POLICY") or None))
                (_, loss_mb), vjp_fn = jax.vjp(run, pv, sp, x_saved)
                valid = b_valid.astype(jnp.float32)
                # last stage's cotangent comes from its own head; others
                # receive dL/dy from stage s+1's backward slot
                dy = jnp.where(s_idx == S - 1, jnp.zeros_like(dx_bwd),
                               dx_bwd) * valid
                g_pv_t, g_sp_t, dx = vjp_fn((dy, valid / M))
                g_pv = g_pv + g_pv_t
                g_sp = jax.tree_util.tree_map(jnp.add, g_sp, g_sp_t)
                loss_acc = loss_acc + valid * loss_mb
                dx_next = lax.ppermute(dx, pp_axis, perm_bwd)
                return (x_fwd_next, dx_next, bv_next, buf_x, buf_bv, g_pv,
                        g_sp, loss_acc), None

            init = (jnp.zeros((A,), jnp.float32),
                    jnp.zeros((A,), jnp.float32), bv,
                    jnp.zeros((BUF, A), jnp.float32),
                    jnp.zeros((BUF, Lb), jnp.float32),
                    jnp.zeros_like(pv), g_sp0, jnp.zeros((), jnp.float32))
            (_, _, bv_new, _, _, g_pv, g_sp, loss_sum), _ = lax.scan(
                tick, init, jnp.arange(ticks))

            loss = lax.psum(loss_sum, pp_axis) / M
            # shared weights live replicated across pp — their true grad is
            # the SUM of the per-stage pieces (the reference's
            # allreduce_shared_weight_gradients, pp_layers.py:188)
            g_sp = lax.psum(g_sp, pp_axis)
            mean_axes = (dp_axis,) * (dp > 1) + other_axes
            if mean_axes:
                loss = lax.pmean(loss, mean_axes)
                g_pv = lax.pmean(g_pv, mean_axes)
                g_sp = lax.pmean(g_sp, mean_axes)
            return loss, g_pv[None], g_sp, bv_new[None]

        data_spec = P(dp_axis) if dp > 1 else P()
        in_specs = (P(pp_axis, None), P(pp_axis, None), P(), data_spec,
                    data_spec, P())
        if schedule == "1f1b" and S > 1:
            sharded_1f1b = shard_map(
                pp_1f1b, mesh=mesh, in_specs=in_specs,
                out_specs=(P(), P(pp_axis, None), P(), P(pp_axis, None)),
                check_vma=False)

            def step_fn(ptree, opt_state, bv, X, Y, key, lr, step):
                loss, g_stages, g_shared, bv_new = sharded_1f1b(
                    ptree["stages"], bv, ptree["shared"], X, Y, key)
                grads = {"stages": g_stages, "shared": g_shared}
                new_p, new_o = optimizer.apply_gradients(
                    grads, ptree, opt_state, lr=lr, step=step + 1)
                return new_p, new_o, bv_new, loss
        else:
            sharded = shard_map(
                pp_loss, mesh=mesh, in_specs=in_specs,
                out_specs=(P(), P(pp_axis, None)), check_vma=False)

            def step_fn(ptree, opt_state, bv, X, Y, key, lr, step):
                def loss_of(pt):
                    return sharded(pt["stages"], bv, pt["shared"], X, Y, key)

                (loss, bv_new), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(ptree)
                new_p, new_o = optimizer.apply_gradients(
                    grads, ptree, opt_state, lr=lr, step=step + 1)
                return new_p, new_o, bv_new, loss

        self._params = {"stages": pvec, "shared": shared_p}
        pv_shard = NamedSharding(mesh, P(pp_axis, None))
        repl = NamedSharding(mesh, P())
        shared_shard = jax.tree_util.tree_map(lambda _: repl, shared_p)
        p_shardings = {"stages": pv_shard, "shared": shared_shard}
        self._params = jax.device_put(self._params, p_shardings)
        self._bvec = jax.device_put(bvec, pv_shard)
        # jit propagates the params' shardings onto the moment buffers
        self._opt_state = jax.jit(optimizer.init_state)(self._params)
        self._data_sharding = NamedSharding(mesh, data_spec)
        self._compiled = jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def padding_report(self) -> dict:
        """Quantify the heterogeneous-stage padding cost (see the module
        docstring's cost model): per-stage real parameter/boundary sizes
        vs the padded sizes every device actually pays.

        Returns {"param_sizes", "param_padded", "param_waste_frac",
        "boundary_sizes", "boundary_padded", "boundary_waste_frac"}."""
        p_sizes = [m.size for m in self._pmetas]
        # every real ppermute hop: the inter-stage boundaries AND the last
        # stage's output (it rides the same padded buffer)
        b_sizes = [m.size for m in self._x_metas if m is not None] \
            + [self._out_meta.size]
        Lp = max(p_sizes) or 1
        A = self._A
        n = len(p_sizes)
        p_waste = 1.0 - sum(p_sizes) / (n * Lp)
        b_waste = (1.0 - sum(b_sizes) / (len(b_sizes) * A)) if b_sizes \
            else 0.0
        return {"param_sizes": p_sizes, "param_padded": Lp,
                "param_waste_frac": p_waste,
                "boundary_sizes": b_sizes, "boundary_padded": A,
                "boundary_waste_frac": b_waste}

    def __call__(self, X, Y):
        _check_batch_divisible(X, self.n_micro, self._dp)
        X = _put_batch(X, self._data_sharding)
        Y = _put_batch(Y, self._data_sharding)
        key = _random.next_key()
        lr = _current_lr_of(self.optimizer, self._step)
        # pass the 0-based step; step_fn's +1 makes Adam's first update t=1
        self._params, self._opt_state, self._bvec, loss = self._compiled(
            self._params, self._opt_state, self._bvec, X, Y, key, lr,
            self._step)
        self._step += 1
        return Tensor(loss, stop_gradient=True)

    def sync_to_model(self):
        """Unpack the packed stage vectors back into the Layers' Parameters
        (for eval / state_dict / checkpointing after training)."""
        pl = self.pl
        pvec = np.asarray(self._params["stages"])
        bvec = np.asarray(self._bvec)
        for s in range(pl.num_stages):
            ptree = _unpack(jnp.asarray(pvec[s]), self._pmetas[s])
            btree = _unpack(jnp.asarray(bvec[s]), self._bmetas[s])
            for j, it in enumerate(pl.stage_items(s)):
                if it.kind != "layer":
                    continue
                for k, p in it.layer.named_parameters():
                    p._value = ptree[str(j)][k]
                for k, b in it.layer.named_buffers():
                    b._value = btree[str(j)][k]
        for key, l in pl._shared_layers.items():
            for k, p in l.named_parameters():
                p._value = self._params["shared"][key][k]


class InterleavedPipelineTrainStep:
    """Interleaved-1F1B (virtual pipeline stages) train step.

    Megatron-LM style: the layer list is cut into ``S * v`` virtual stages
    and virtual stage ``j`` lives on rank ``j % S`` (chunk ``j // S``), so
    consecutive stages sit on consecutive ranks and every hop — including
    the chunk-boundary wrap from rank S-1 back to rank 0 — is one
    ``lax.ppermute`` neighbor step on the 'pp' ring.  The pipeline fill is
    paid in chunk units, shrinking the bubble fraction by ~v (the
    reference's SectionWorker has only F-then-B and flat 1F1B).

    SPMD shape: the schedule is data (pp_schedule.build's dependency-
    validated [ticks, S] slot table).  One ``lax.scan`` tick stashes the
    activations/cotangents that arrived over the ring, then runs a 3-way
    ``lax.switch`` — forward slot, backward (VJP) slot, or idle — so each
    rank pays only its scheduled chunk-exec per tick (XLA conditionals
    execute only the taken branch), then both ppermutes run
    unconditionally (collectives must be uniform across ranks).

    Per-rank state: params pvec rank-major ``[S*v, Lp]`` sharded P('pp')
    (local rows = this rank's v chunks), activation ring ``[v, BUF, A]``
    and cotangent ring ``[v, BUF, A]`` with BUF = the schedule's measured
    max in-flight window.  Stages with buffers (BatchNorm) are rejected —
    their update order under interleaving is schedule-dependent; use
    schedule='1f1b' for those models.
    """

    def __init__(self, pl: PipelineLayer, mesh: Mesh, optimizer, loss_fn,
                 n_micro: int, example_input, dp_axis: str, pp_axis: str,
                 remat: bool, n_virtual: int):
        from .pp_schedule import build as _build_schedule

        S = mesh.shape[pp_axis]
        if S != pl.num_stages:
            raise ValueError(f"mesh '{pp_axis}' size {S} != num_stages "
                             f"{pl.num_stages}")
        v = int(n_virtual)
        if v < 1:
            raise ValueError("n_virtual must be >= 1")
        V = S * v
        if len(pl._items) < V:
            raise ValueError(
                f"cannot split {len(pl._items)} layers into {V} virtual "
                f"stages (num_stages={S} x n_virtual={v})")
        dp = mesh.shape.get(dp_axis, 1)
        M = n_micro
        self.pl = pl
        self.mesh = mesh
        self._dp = dp
        self._v = v
        self.optimizer = optimizer
        self.n_micro = M
        self._step = 0
        training = pl.training
        sched = _build_schedule(S, v, M)
        self._sched = sched
        BUF = sched.buf

        bounds = pl._segment_bounds(pl._seg_method, V)
        self._vbounds = bounds

        def vstage_items(j):
            return pl._items[bounds[j]: bounds[j + 1]]

        from ..jit import _split_state as _jit_split_state

        stage_ptrees = []
        for j in range(V):
            pt = {}
            for i, it in enumerate(vstage_items(j)):
                if it.kind != "layer":
                    continue
                p, b = _jit_split_state(it.layer)
                if b:
                    raise NotImplementedError(
                        "interleaved schedule does not support stages with "
                        "buffers (running BatchNorm stats update in "
                        "schedule-dependent order); use schedule='1f1b'")
                pt[str(i)] = p
            stage_ptrees.append(pt)
        shared_p = {}
        for key, l in pl._shared_layers.items():
            shared_p[key], sb = _jit_split_state(l)
            if sb:
                raise NotImplementedError(
                    "SharedLayerDesc layers with buffers are not supported")
        self._pmetas = [_meta_of(t) for t in stage_ptrees]
        Lp = max(m.size for m in self._pmetas) or 1
        # rank-major packing: row r*v + c  =  virtual stage c*S + r, so
        # P('pp') sharding hands each rank exactly its v chunks
        rows = []
        for r in range(S):
            for c in range(v):
                j = c * S + r
                rows.append(_pack(stage_ptrees[j], self._pmetas[j], Lp))
        pvec = jnp.stack(rows)

        # ---- boundary activation metas
        def mb_slice(tree):
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros((np.shape(a)[0] // (M * max(dp, 1)),)
                                    + tuple(np.shape(a)[1:]),
                                    jnp.asarray(a).dtype), tree)

        def run_stage_concrete(j, ptree, sp, x):
            for i, it in enumerate(vstage_items(j)):
                if it.kind == "layer":
                    x, _ = _apply_item(it, ptree[str(i)], {}, x, training)
                elif it.kind == "shared":
                    x, _ = _apply_item(it, sp[it.shared_key], {}, x, training)
                else:
                    x, _ = _apply_item(it, None, None, x, training)
            return x

        x_meta = [None] * V
        x_abs = mb_slice(example_input)
        for j in range(V):
            if j >= 1:
                x_meta[j] = _meta_of(x_abs)
            x_abs = jax.eval_shape(
                functools.partial(run_stage_concrete, j, stage_ptrees[j],
                                  shared_p), x_abs)
        out_meta = _meta_of(x_abs)
        A = max([m.size for m in x_meta if m is not None] + [out_meta.size],
                default=1) or 1
        self._x_metas = x_meta
        self._out_meta = out_meta
        self._A = A

        def make_branch(j, *, emit_loss: bool):
            pm = self._pmetas[j]

            def branch(pv_row, sp, x_flat, x0, y_lbl, key):
                ptree = _unpack(pv_row, pm)
                x = x0 if j == 0 else _unpack(x_flat, x_meta[j])
                with _random.rng_scope(key):
                    y = run_stage_concrete(j, ptree, sp, x)
                loss = jnp.zeros((), jnp.float32)
                if j == V - 1:
                    y_send = jnp.zeros((A,), jnp.float32)
                    if emit_loss:
                        loss = loss_fn(_wrap_tree(y),
                                       Tensor(y_lbl, stop_gradient=True))
                        loss = (loss.value if isinstance(loss, Tensor)
                                else loss).astype(jnp.float32)
                else:
                    y_send = _pack(y, x_meta[j + 1], A)
                return (y_send, loss) if emit_loss else (y_send,)

            return branch

        fwd_branches = [make_branch(j, emit_loss=False) for j in range(V)]
        full_branches = [make_branch(j, emit_loss=True) for j in range(V)]
        perm = [(i, (i + 1) % S) for i in range(S)]
        perm_bwd = [(i, (i - 1) % S) for i in range(S)]
        other_axes = tuple(ax for ax in mesh.axis_names
                           if ax not in (dp_axis, pp_axis)
                           and mesh.shape[ax] > 1)
        TBL = jnp.asarray(sched.table)       # [ticks, S, 3]
        RCF = jnp.asarray(sched.recv_f)
        RCB = jnp.asarray(sched.recv_b)

        def pp_interleaved(pv_loc, sp, X, Y, key):
            s_idx = lax.axis_index(pp_axis)
            M_ = M
            Xmb = jax.tree_util.tree_map(
                lambda a: a.reshape((M_, a.shape[0] // M_) + a.shape[1:]), X)
            Ymb = jax.tree_util.tree_map(
                lambda a: a.reshape((M_, a.shape[0] // M_) + a.shape[1:]), Y)
            g_sp0 = jax.tree_util.tree_map(jnp.zeros_like, sp)

            def tick(carry, trow):
                (x_in, d_in, store_x, store_d, g_pv, g_sp,
                 loss_acc) = carry
                tbl_row, rcf_row, rcb_row = trow

                # ---- stash what arrived over the ring last tick
                fv, fc, fs = (rcf_row[s_idx, 0], rcf_row[s_idx, 1],
                              rcf_row[s_idx, 2])
                upd_x = lax.dynamic_update_slice(
                    store_x, x_in[None, None, :], (fc, fs, 0))
                store_x = jnp.where(fv == 1, upd_x, store_x)
                bv_, bc, bs = (rcb_row[s_idx, 0], rcb_row[s_idx, 1],
                               rcb_row[s_idx, 2])
                upd_d = lax.dynamic_update_slice(
                    store_d, d_in[None, None, :], (bc, bs, 0))
                store_d = jnp.where(bv_ == 1, upd_d, store_d)

                # ---- this tick's slot
                kind = tbl_row[s_idx, 0]
                c = tbl_row[s_idx, 1]
                m = tbl_row[s_idx, 2]
                j = c * S + s_idx
                mslot = m % BUF
                pv_row = lax.dynamic_index_in_dim(pv_loc, c, keepdims=False)
                x0 = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, m, keepdims=False),
                    Xmb)
                y_lbl = jax.tree_util.tree_map(
                    lambda a: lax.dynamic_index_in_dim(a, m, keepdims=False),
                    Ymb)
                x_flat = lax.dynamic_slice(store_x, (c, mslot, 0),
                                           (1, 1, A)).reshape(A)
                dy_in = lax.dynamic_slice(store_d, (c, mslot, 0),
                                          (1, 1, A)).reshape(A)
                # fwd and its bwd recompute must see the SAME rng stream
                k_t = jax.random.fold_in(jax.random.fold_in(key, m), j)

                def fwd_slot(_):
                    (y_send,) = lax.switch(j, fwd_branches, pv_row, sp,
                                           x_flat, x0, y_lbl, k_t)
                    return (y_send, jnp.zeros((A,), jnp.float32), g_pv,
                            g_sp, jnp.zeros((), jnp.float32))

                def bwd_slot(_):
                    def run(pvr, sp_, xf_):
                        return lax.switch(j, full_branches, pvr, sp_, xf_,
                                          x0, y_lbl, k_t)

                    if remat:
                        from ..ops.remat_policies import resolve as _rp

                        _cse = os.environ.get(
                            "PADDLE_TPU_REMAT_PREVENT_CSE", "") == "1"
                        run_ = jax.checkpoint(
                            run, prevent_cse=_cse,
                            policy=_rp(os.environ.get(
                                "PADDLE_TPU_REMAT_POLICY") or None))
                    else:
                        run_ = run
                    (_, loss_mb), vjp_fn = jax.vjp(run_, pv_row, sp, x_flat)
                    dy = jnp.where(j == V - 1, jnp.zeros_like(dy_in), dy_in)
                    g_row, g_sp_t, dx = vjp_fn(
                        (dy, jnp.ones((), jnp.float32) / M_))
                    new_row = lax.dynamic_index_in_dim(
                        g_pv, c, keepdims=False) + g_row
                    g_pv_n = lax.dynamic_update_index_in_dim(
                        g_pv, new_row, c, 0)
                    g_sp_n = jax.tree_util.tree_map(jnp.add, g_sp, g_sp_t)
                    return (jnp.zeros((A,), jnp.float32), dx, g_pv_n,
                            g_sp_n, loss_mb)

                def idle_slot(_):
                    return (jnp.zeros((A,), jnp.float32),
                            jnp.zeros((A,), jnp.float32), g_pv, g_sp,
                            jnp.zeros((), jnp.float32))

                y_send, d_send, g_pv, g_sp, loss_add = lax.switch(
                    kind, [fwd_slot, bwd_slot, idle_slot], 0)
                x_out = lax.ppermute(y_send, pp_axis, perm)
                d_out = lax.ppermute(d_send, pp_axis, perm_bwd)
                return (x_out, d_out, store_x, store_d, g_pv, g_sp,
                        loss_acc + loss_add), None

            init = (jnp.zeros((A,), jnp.float32),
                    jnp.zeros((A,), jnp.float32),
                    jnp.zeros((v, BUF, A), jnp.float32),
                    jnp.zeros((v, BUF, A), jnp.float32),
                    jnp.zeros_like(pv_loc), g_sp0,
                    jnp.zeros((), jnp.float32))
            (_, _, _, _, g_pv, g_sp, loss_sum), _ = lax.scan(
                tick, init, (TBL, RCF, RCB))
            loss = lax.psum(loss_sum, pp_axis) / M_
            g_sp = lax.psum(g_sp, pp_axis)
            mean_axes = (dp_axis,) * (dp > 1) + other_axes
            if mean_axes:
                loss = lax.pmean(loss, mean_axes)
                g_pv = lax.pmean(g_pv, mean_axes)
                g_sp = lax.pmean(g_sp, mean_axes)
            return loss, g_pv, g_sp

        data_spec = P(dp_axis) if dp > 1 else P()
        sharded = shard_map(
            pp_interleaved, mesh=mesh,
            in_specs=(P(pp_axis, None), P(), data_spec, data_spec, P()),
            out_specs=(P(), P(pp_axis, None), P()), check_vma=False)

        def step_fn(ptree, opt_state, X, Y, key, lr, step):
            loss, g_stages, g_shared = sharded(
                ptree["stages"], ptree["shared"], X, Y, key)
            grads = {"stages": g_stages, "shared": g_shared}
            new_p, new_o = optimizer.apply_gradients(
                grads, ptree, opt_state, lr=lr, step=step + 1)
            return new_p, new_o, loss

        self._params = {"stages": pvec, "shared": shared_p}
        pv_shard = NamedSharding(mesh, P(pp_axis, None))
        repl = NamedSharding(mesh, P())
        shared_shard = jax.tree_util.tree_map(lambda _: repl, shared_p)
        self._params = jax.device_put(
            self._params, {"stages": pv_shard, "shared": shared_shard})
        self._opt_state = jax.jit(optimizer.init_state)(self._params)
        self._data_sharding = NamedSharding(mesh, data_spec)
        self._compiled = jax.jit(step_fn, donate_argnums=(0, 1))

    def schedule_report(self) -> dict:
        """Bubble accounting straight from the validated slot table."""
        s = self._sched
        return {"ticks": s.ticks, "n_virtual": s.n_virtual,
                "buf": s.buf, "idle_frac": s.idle_frac,
                "useful_slots": 2 * s.n_stages * s.n_virtual * s.n_micro}

    def __call__(self, X, Y):
        _check_batch_divisible(X, self.n_micro, self._dp)
        X = _put_batch(X, self._data_sharding)
        Y = _put_batch(Y, self._data_sharding)
        key = _random.next_key()
        lr = _current_lr_of(self.optimizer, self._step)
        self._params, self._opt_state, loss = self._compiled(
            self._params, self._opt_state, X, Y, key, lr, self._step)
        self._step += 1
        return Tensor(loss, stop_gradient=True)

    def sync_to_model(self):
        """Unpack rank-major stage vectors back into the Layers."""
        pl = self.pl
        S, v = pl.num_stages, self._v
        pvec = np.asarray(self._params["stages"])
        for r in range(S):
            for c in range(v):
                j = c * S + r
                ptree = _unpack(jnp.asarray(pvec[r * v + c]),
                                self._pmetas[j])
                items = pl._items[self._vbounds[j]: self._vbounds[j + 1]]
                for i, it in enumerate(items):
                    if it.kind != "layer":
                        continue
                    for k, p in it.layer.named_parameters():
                        p._value = ptree[str(i)][k]
        for key, l in pl._shared_layers.items():
            for k, p in l.named_parameters():
                p._value = self._params["shared"][key][k]
