"""Collective communication API.

Reference: python/paddle/distributed/collective.py (all_reduce :413,
all_gather :587, scatter :665, barrier :166, alltoall :1455, send/recv
:1526/:1576) lowering to c_* NCCL ops (operators/collective/).

TPU-first, two layers:

1. **Primitives** — used *inside* ``shard_map`` bodies on raw arrays, mapping
   1:1 onto XLA collectives over ICI (psum / all_gather / psum_scatter /
   all_to_all / ppermute).  This is the layer the framework's own parallel
   code (Reducer, pipeline, ring attention) is written in.
2. **Eager API** — Tensor-level functions matching the reference signatures.
   A Tensor is a *global* (possibly sharded) array under single-controller
   SPMD, so e.g. ``all_reduce`` means "psum over the group axis of this
   array's shards" and executes a tiny jitted shard_map.

``use_calc_stream`` / c_sync_* stream ops have no analog: XLA schedules
async collectives itself (SURVEY.md §2.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map as _shard_map

from ..core.tensor import Tensor
from .env import get_mesh
from .topology import CommGroup

__all__ = [
    "ReduceOp", "new_group", "all_reduce", "all_gather", "reduce_scatter",
    "broadcast", "reduce", "scatter", "alltoall", "barrier", "send", "recv",
    "prim",
]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


_GROUPS: dict[int, CommGroup] = {}
_NEXT_GID = [1]


def new_group(ranks=None, backend=None, axis: str | None = None) -> CommGroup:
    """Create a communicator.  TPU-native: a group IS a mesh axis; ranks lists
    are kept for reference-API introspection only."""
    mesh = get_mesh()
    if axis is None:
        # default: the first (outermost) axis — matches reference global group
        axis = mesh.axis_names[0]
    g = CommGroup(axis, ranks if ranks is not None else list(range(mesh.devices.size)),
                  id=_NEXT_GID[0])
    _GROUPS[g.id] = g
    _NEXT_GID[0] += 1
    return g


def _axis_of(group) -> str:
    if group is None:
        return get_mesh().axis_names[0]
    if isinstance(group, str):
        return group
    return group.axis


# ---------------------------------------------------------------------------
# layer 1: primitives (inside shard_map)
# ---------------------------------------------------------------------------


class prim:
    """XLA collective primitives over a named mesh axis (shard_map scope)."""

    @staticmethod
    def all_reduce(x, op=ReduceOp.SUM, group=None):
        ax = _axis_of(group)
        if op == ReduceOp.SUM:
            return jax.lax.psum(x, ax)
        if op == ReduceOp.MAX:
            return jax.lax.pmax(x, ax)
        if op == ReduceOp.MIN:
            return jax.lax.pmin(x, ax)
        if op == ReduceOp.AVG:
            return jax.lax.pmean(x, ax)
        if op == ReduceOp.PROD:
            return jnp.exp(jax.lax.psum(jnp.log(x), ax))
        raise ValueError(op)

    @staticmethod
    def all_gather(x, group=None, axis=0):
        return jax.lax.all_gather(x, _axis_of(group), axis=axis, tiled=True)

    @staticmethod
    def reduce_scatter(x, group=None, axis=0):
        return jax.lax.psum_scatter(x, _axis_of(group), scatter_dimension=axis, tiled=True)

    @staticmethod
    def all_to_all(x, group=None, split_axis=0, concat_axis=0):
        ax = _axis_of(group)
        return jax.lax.all_to_all(x, ax, split_axis=split_axis, concat_axis=concat_axis,
                                  tiled=True)

    @staticmethod
    def broadcast(x, src=0, group=None):
        ax = _axis_of(group)
        idx = jax.lax.axis_index(ax)
        masked = jnp.where(idx == src, x, jnp.zeros_like(x))
        return jax.lax.psum(masked, ax)

    @staticmethod
    def ppermute(x, perm, group=None):
        return jax.lax.ppermute(x, _axis_of(group), perm)

    @staticmethod
    def send_recv_ring(x, group=None, shift=1):
        """x_i → x_{(i+shift) mod n}: the pipeline/ring-attention edge move."""
        ax = _axis_of(group)
        n = jax.lax.axis_size(ax)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return jax.lax.ppermute(x, ax, perm)

    @staticmethod
    def axis_index(group=None):
        return jax.lax.axis_index(_axis_of(group))


# ---------------------------------------------------------------------------
# layer 2: eager Tensor API (single-controller global-array semantics)
# ---------------------------------------------------------------------------


def _run_collective(x: Tensor, body, in_spec, out_spec) -> Tensor:
    mesh = get_mesh()
    fn = _shard_map(body, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
                    check_vma=False)
    v = x.value if isinstance(x, Tensor) else x
    # reshard onto the mesh (eager tensors are usually committed to one
    # device; the collective needs the stacked layout distributed)
    v = jax.device_put(v, NamedSharding(mesh, in_spec))
    out = jax.jit(fn)(v)
    return Tensor(out)


def _check_stacked(tensor, ax, opname):
    """Eager collectives use the STACKED-PER-RANK convention: under the
    single controller there is no 'my rank's tensor' — the reference's
    per-rank inputs are represented as ONE global array whose leading dim
    concatenates every rank's contribution (dim0 = group_size * per_rank
    rows).  Anything else is silently wrong, so validate loudly."""
    from .env import axis_size

    n = axis_size(ax)
    v = tensor.value if isinstance(tensor, Tensor) else tensor
    shape = jnp.shape(v)
    if not shape or shape[0] % n:
        raise ValueError(
            f"{opname}: leading dim {shape[0] if shape else '<scalar>'} "
            f"must be a multiple of group size {n} — eager collectives "
            f"take the stacked-per-rank layout (rank i's tensor at rows "
            f"[i*B, (i+1)*B)); a replicated per-rank tensor must be "
            f"stacked/tiled first (see distributed/collective.py docstring)")
    return n


def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Stacked-per-rank input [n*B, ...] → in-place result [B, ...]
    replicated: the sum (or max/min/avg/prod) over the n rank blocks —
    reference all_reduce semantics under a single controller."""
    ax = _axis_of(group)
    _check_stacked(tensor, ax, "all_reduce")
    out = _run_collective(
        tensor,
        lambda x: prim.all_reduce(x, op, ax),
        P(ax), P(),
    )
    tensor._value = out.value  # reference all_reduce is in-place
    return tensor


def all_gather(tensor_list, tensor: Tensor, group=None, sync_op=True):
    """Stacked-per-rank input; result (list of per-rank tensors) replicated."""
    ax = _axis_of(group)
    n = _check_stacked(tensor, ax, "all_gather")
    gathered = _run_collective(
        tensor, lambda x: prim.all_gather(x, ax, axis=0), P(ax), P(),
    )
    if tensor_list is not None:
        parts = jnp.split(gathered.value, n, axis=0)
        tensor_list.extend(Tensor(p) for p in parts)
    return gathered


def reduce_scatter(tensor: Tensor, op=ReduceOp.SUM, group=None):
    ax = _axis_of(group)
    _check_stacked(tensor, ax, "reduce_scatter")
    return _run_collective(
        tensor, lambda x: prim.reduce_scatter(x, ax, axis=0), P(ax), P(ax),
    )


def broadcast(tensor: Tensor, src=0, group=None, sync_op=True):
    ax = _axis_of(group)
    _check_stacked(tensor, ax, "broadcast")
    out = _run_collective(
        tensor, lambda x: prim.broadcast(x, src, ax), P(ax), P(),
    )
    tensor._value = out.value
    return tensor


def reduce(tensor: Tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # single-controller: reduce == all_reduce (result visible globally)
    return all_reduce(tensor, op, group)


def scatter(tensor: Tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Global→sharded: slice the source data across the axis.

    ``src`` is accepted for reference-API parity but is meaningless under a
    single controller: there is only one copy of ``tensor_list`` (it IS the
    source rank's data)."""
    ax = _axis_of(group)
    from .env import axis_size

    n = axis_size(ax)
    if tensor_list is not None:
        if len(tensor_list) != n:
            raise ValueError(
                f"scatter: tensor_list has {len(tensor_list)} entries; the "
                f"group size is {n} (one tensor per rank)")
        src_val = jnp.concatenate([t.value if isinstance(t, Tensor) else t
                                   for t in tensor_list], axis=0)
    else:
        _check_stacked(tensor, ax, "scatter")
        src_val = tensor.value
    mesh = get_mesh()
    sharded = jax.device_put(src_val, NamedSharding(mesh, P(ax)))
    tensor._value = sharded
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    ax = _axis_of(group)
    if isinstance(in_tensor_list, (list, tuple)):
        from .env import axis_size

        if len(in_tensor_list) != axis_size(ax):
            raise ValueError(
                f"alltoall: {len(in_tensor_list)} tensors for a group of "
                f"size {axis_size(ax)} (need one per rank)")
        x = Tensor(jnp.concatenate([t.value for t in in_tensor_list], axis=0))
    else:
        _check_stacked(in_tensor_list, ax, "alltoall")
        x = in_tensor_list
    out = _run_collective(
        x, lambda v: prim.all_to_all(v, ax, split_axis=0, concat_axis=0), P(ax), P(ax),
    )
    if out_tensor_list is not None:
        from .env import axis_size

        parts = jnp.split(out.value, axis_size(ax), axis=0)
        out_tensor_list.extend(Tensor(p) for p in parts)
    return out


def barrier(group=None):
    # XLA programs are bulk-synchronous; a psum over a scalar is a true barrier
    ax = _axis_of(group)
    t = Tensor(jnp.zeros((get_mesh().shape.get(ax, 1),), jnp.float32))
    all_reduce(t, ReduceOp.SUM, group)


def send(tensor: Tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv between eager ranks does not exist under "
        "single-controller SPMD; use prim.ppermute inside shard_map (pipeline "
        "edges) — see distributed.pipeline"
    )


recv = send


def get_group(gid: int) -> CommGroup:
    return _GROUPS[gid]
