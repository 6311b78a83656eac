"""DataParallel wrapper + grad sync.

Reference: paddle.DataParallel (fluid/dygraph/parallel.py:382) + C++ Reducer
(imperative/reducer.cc — size-bucketed grad allreduce overlapping backward,
unused-parameter graph walk).

TPU-first: under SPMD there is nothing to overlap by hand — when the batch is
sharded on 'dp', XLA inserts (and schedules/overlaps) the gradient
all-reduces itself, bucketing included.  The wrapper therefore:
  * eager multi-device mode: shards input batches over 'dp' on the way in,
    and provides the explicit ``sync_gradients`` used by the eager loop
    (psum of leaf grads over 'dp' — the Reducer's job, one fused call);
  * inside jit/pjit: a no-op passthrough.
``no_sync`` matches the reference API (skip grad sync for gradient
accumulation)."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import autograd as _autograd
from ..core.tensor import Tensor
from ..nn.layer_base import Layer
from .env import get_mesh, has_mesh


def assign_group_by_size(params, group_size_bytes: int,
                         first_group_bytes: int | None = None):
    """Bucket parameters for fused gradient reduction (the reference
    ``AssignGroupBySize``, imperative/reducer.cc:226).

    Parameters are walked in REVERSE registration order (their grads become
    final roughly in that order during backward); the first bucket is
    capped at ``first_group_bytes`` (reference ``last_comm_buffer_size``)
    so the earliest-ready grads flush without waiting to fill a full
    bucket, and buckets never mix dtypes (their grads are concatenated
    into one array).  Returns a list of lists of params."""
    groups: list[list] = []
    cur: list = []
    cur_bytes = 0
    cur_dtype = None
    cap = first_group_bytes if first_group_bytes is not None \
        else group_size_bytes
    for p in reversed(list(params)):
        nbytes = int(np.prod(p.shape or (1,))) * jnp.dtype(p.dtype).itemsize
        if cur and (cur_dtype != p.dtype or cur_bytes + nbytes > cap):
            groups.append(cur)
            cur, cur_bytes = [], 0
            cap = group_size_bytes
        cur.append(p)
        cur_bytes += nbytes
        cur_dtype = p.dtype
    if cur:
        groups.append(cur)
    return groups


class Reducer:
    """Bucketed as-ready gradient reduction (reference imperative/
    reducer.cc): size-ordered buckets over the parameter list, each flushed
    with ONE fused collective the moment its last member's gradient
    becomes final during backward (leaf grad-ready hooks on the tape), so
    the reduction of early buckets overlaps the rest of backward via JAX
    async dispatch.

    Reduction semantics: MEAN over the ``axis`` rank blocks.  Under a
    multi-process (multi-controller) run each process contributes its
    process-local gradients (``jax.make_array_from_process_local_data``
    assembles the stacked global array); under the single controller the
    already-global gradients are tiled into the rank slots, so the mean is
    an exact no-op on the values while still exercising the same fused
    collective — one code path, both worlds."""

    def __init__(self, params, axis: str = "dp",
                 comm_buffer_bytes: int = 25 << 20,
                 first_bucket_bytes: int = 1 << 20,
                 find_unused_parameters: bool = False, on_flush=None):
        import weakref

        from jax import shard_map

        self.axis = axis
        self._find_unused = find_unused_parameters
        self._params = [p for p in params
                        if getattr(p, "trainable", True)
                        and not p.stop_gradient]
        self.groups = assign_group_by_size(self._params, comm_buffer_bytes,
                                           first_bucket_bytes)
        self._group_of = {id(p): gi for gi, g in enumerate(self.groups)
                          for p in g}
        self._on_flush = on_flush
        self._enabled = True
        # the reduction communicator, built ONCE (per-flush construction
        # would defeat jax.jit's identity-keyed cache and recompile every
        # bucket every step).  Multi-process: one mesh slot per PROCESS
        # (each contributes its whole local grads regardless of how many
        # devices it owns on the training mesh's dp axis); single
        # controller: the training mesh's dp axis.
        if jax.process_count() > 1:
            per_proc = {}
            for d in jax.devices():
                per_proc.setdefault(d.process_index, d)
            comm_devs = [per_proc[p] for p in sorted(per_proc)]
            self._comm_mesh = jax.sharding.Mesh(np.array(comm_devs),
                                                (axis,))
            self._n_blocks = len(comm_devs)
        else:
            mesh = get_mesh()
            self._comm_mesh = mesh
            self._n_blocks = mesh.shape.get(axis, 1)
        self._reduce_jit = jax.jit(shard_map(
            lambda x: jax.lax.pmean(x[0], axis), mesh=self._comm_mesh,
            in_specs=P(axis), out_specs=P(), check_vma=False))
        self._reset()
        # weakref trampoline: the global hook must not pin this Reducer
        # (and its parameters' grad arrays) for the life of the process
        ref = weakref.ref(self)
        holder = {}

        def hook(t):
            r = ref()
            if r is None:
                holder["remove"]()
                return
            r._ready(t)

        holder["remove"] = _autograd.add_leaf_grad_ready_hook(hook)
        self._remove_hook = holder["remove"]

    def _reset(self):
        self._pending = [len(g) for g in self.groups]
        self._flushed = [False] * len(self.groups)

    def remove(self):
        self._remove_hook()

    def set_enabled(self, flag: bool):
        self._enabled = flag

    def _ready(self, t):
        gi = self._group_of.get(id(t))
        if gi is None or not self._enabled:
            return
        if self._flushed[gi]:
            # a NEW backward re-entering a bucket flushed by a previous one
            # (gradient accumulation without no_sync): re-arm it.  Flushing
            # again is exact — ranks hold reduced(prev) + local(new), and
            # mean(reduced + local) = reduced + mean(local).
            self._flushed[gi] = False
            self._pending[gi] = len(self.groups[gi])
        self._pending[gi] -= 1
        if self._pending[gi] == 0:
            self._flush(gi)

    def _flush(self, gi: int):
        group = self.groups[gi]
        self._flushed[gi] = True
        flat = jnp.concatenate([
            jnp.ravel(p.grad.value if p.grad is not None
                      else jnp.zeros(p.shape, p.dtype)) for p in group])
        n = self._n_blocks
        if n > 1:
            sh = NamedSharding(self._comm_mesh, P(self.axis))
            if jax.process_count() > 1:
                # every process contributes its LOCAL grads as one block
                # of the stacked [n, L] global array
                stacked = jax.make_array_from_process_local_data(
                    sh, np.asarray(flat)[None], (n, flat.shape[0]))
            else:
                stacked = jax.device_put(
                    jnp.broadcast_to(flat, (n,) + flat.shape), sh)
            reduced = self._reduce_jit(stacked)
        else:
            reduced = flat
        off = 0
        for p in group:
            k = int(np.prod(p.shape or (1,)))
            pg = reduced[off:off + k].reshape(p.shape)
            p.grad = Tensor(pg, stop_gradient=True)
            off += k
        if self._on_flush is not None:
            self._on_flush(gi, [p for p in group])

    def finalize(self):
        """End-of-backward sweep (reference Reducer::FinalizeBackward):
        zero-fill unused parameters (find_unused_parameters) and flush any
        bucket whose members were not all reached, then re-arm for the
        next backward."""
        if not self._enabled:
            self._reset()
            return
        for gi, group in enumerate(self.groups):
            if self._flushed[gi]:
                continue
            missing = [p for p in group if p.grad is None]
            if missing and not self._find_unused:
                raise RuntimeError(
                    f"Reducer: {len(missing)} parameter(s) produced no "
                    "gradient this backward (e.g. an untaken branch). "
                    "Construct DataParallel with "
                    "find_unused_parameters=True to zero-fill them "
                    "(reference reducer.cc unused-variable walk)")
            self._flush(gi)
        self._reset()


class DataParallel(Layer):
    """``local_grads`` selects the Reducer mode: None (auto) enables the
    explicit bucketed reduction exactly when gradients are process-local —
    i.e. under a multi-controller run (jax.process_count() > 1).  Under the
    single controller SPMD already returns globally-reduced grads, so the
    Reducer is pure (mean of identical rank blocks) and stays off unless
    forced with ``local_grads=True`` (used by tests and by manual
    shard_map training loops that produce per-rank grads)."""

    def __init__(self, layers: Layer, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 local_grads: bool | None = None):
        super().__init__()
        self._layers = layers
        self._sync_enabled = True
        self._find_unused = find_unused_parameters
        self._comm_buffer_bytes = int(comm_buffer_size * 1024 * 1024)
        if local_grads is None:
            local_grads = jax.process_count() > 1
        self._reducer = None
        if local_grads and has_mesh() \
                and get_mesh().shape.get("dp", 1) > 1:
            self._reducer = Reducer(
                layers.parameters(), axis="dp",
                comm_buffer_bytes=self._comm_buffer_bytes,
                first_bucket_bytes=int(last_comm_buffer_size * 1024 * 1024),
                find_unused_parameters=find_unused_parameters)

    def forward(self, *inputs, **kwargs):
        # multi-controller: every rank computes on its own LOCAL batch (the
        # reference per-rank semantics) and the Reducer merges grads —
        # resharding different per-rank values onto one global array would
        # silently build an inconsistent "global" input
        if jax.process_count() > 1:
            return self._layers(*inputs, **kwargs)
        if has_mesh() and get_mesh().shape.get("dp", 1) > 1:
            sharded = []
            sh = NamedSharding(get_mesh(), P("dp"))
            for x in inputs:
                if isinstance(x, Tensor):
                    try:
                        x = Tensor(jax.device_put(x.value, sh),
                                   stop_gradient=x.stop_gradient)
                    except Exception:
                        pass
                sharded.append(x)
            inputs = tuple(sharded)
        return self._layers(*inputs, **kwargs)

    def overlap_optimizer_update(self, optimizer):
        """Overlap gradient all-reduce with the optimizer update (the
        reference ParallelExecutor's pipelining: bucket k+1's fused
        allreduce runs while bucket k's update kernels execute).

        Wires the Reducer's as-ready bucket flush to
        ``optimizer.step_group``: each bucket's eager update dispatches
        the moment its fused collective does, and JAX async dispatch
        pipelines the next bucket's reduction behind it (the VJP closures
        captured their primals at forward time, so updating parameter
        values mid-backward cannot perturb still-running grad math).  The
        training loop's ``optimizer.step()`` then only closes the round —
        stragglers and unused parameters.  Requires the explicit-Reducer
        mode (``local_grads=True`` or a multi-process run) and no global
        ``grad_clip``."""
        if self._reducer is None:
            raise RuntimeError(
                "overlap_optimizer_update needs the explicit Reducer "
                "(DataParallel(local_grads=True) on a dp>1 mesh, or a "
                "multi-process run); under single-controller SPMD XLA "
                "already schedules/overlaps the collectives")
        if getattr(optimizer, "_grad_clip", None) is not None:
            raise ValueError(
                "global grad_clip needs every gradient before any update; "
                "overlap_optimizer_update is unavailable with grad_clip")
        self._reducer._on_flush = \
            lambda gi, params: optimizer.step_group(params)
        return self

    def close(self):
        """Detach the Reducer's grad-ready hook (safe to call twice; also
        happens automatically when the DataParallel is garbage-collected —
        the hook holds only a weakref)."""
        if self._reducer is not None:
            self._reducer.remove()
            self._reducer = None

    @contextlib.contextmanager
    def no_sync(self):
        self._sync_enabled = False
        if self._reducer is not None:
            self._reducer.set_enabled(False)
        try:
            yield
        finally:
            self._sync_enabled = True
            if self._reducer is not None:
                self._reducer.set_enabled(True)

    def scale_loss(self, loss):
        return loss  # SPMD mean-loss semantics already global

    def apply_collective_grads(self):
        self.sync_gradients()

    def sync_gradients(self):
        """The Reducer's job (imperative/reducer.cc): bucketed grad
        allreduce + unused-parameter handling.

        Under single-controller SPMD the allreduce half is subsumed: grads
        of a dp-sharded batch arrive globally reduced (XLA inserted — and
        bucketed/overlapped — the collectives during backward), so no
        explicit communication remains to issue here.  What does remain is
        the unused-parameter walk: params untouched by this backward get
        zero grads so optimizer accumulator updates stay rank-consistent
        (the reference marks them via a graph walk so its allreduce doesn't
        hang; ours would silently skip the optimizer update instead — same
        divergence, same cure)."""
        if not self._sync_enabled:
            return
        if self._reducer is not None:
            # buckets whose members all fired already flushed DURING
            # backward (as-ready hooks); this sweeps the stragglers +
            # unused params
            self._reducer.finalize()
            return
        if self._find_unused:
            for p in self._layers.parameters():
                if p.grad is None and getattr(p, "trainable", True):
                    p.grad = Tensor(jnp.zeros_like(p.value))

    # delegate everything else
    def __getattr__(self, name):
        try:
            return super().__getattr__(name)
        except AttributeError:
            return getattr(self.__dict__.get("_sub_layers", {}).get("_layers"), name)

    def state_dict(self, *a, **kw):
        return self._layers.state_dict(*a, **kw)

    def set_state_dict(self, sd, *a, **kw):
        return self._layers.set_state_dict(sd, *a, **kw)
