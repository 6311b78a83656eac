"""What the Pallas TPU kernel modules share: the row-block geometry of the
row-sweep kernels and the one platform test.

Routing is decided from what the code can observe before it compiles:
the platform (``on_tpu``) and static shapes (each module's gate).  A
shape the gate accepts is compiled as part of the caller's step; what
the chip's compiler then refuses is an exception that reaches the
caller — nothing is probed ahead of time and nothing is retried on XLA.
Interpret mode is entered only by a test flipping a module's
``_INTERPRET``.

A Mosaic kernel cannot be partitioned by GSPMD ("wrap the call in a
shard_map").  A step that jit partitions over a mesh of several chips
therefore traces under :func:`partitioned`, which names the mesh and the
axes its batch and head dimensions are split over; each kernel's public
entry then runs per shard through :meth:`Partition.shard_map`.
"""
from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple

import jax
from jax.sharding import PartitionSpec as P

# Shared block geometry for row-sweep kernels (fused_norm, fused_ce): one
# row-block of fp32 working set per buffer, a handful of buffers resident —
# well under the ~16 MB VMEM core budget.  Single-site so a retune for a
# new TPU generation applies to every kernel at once.
BLOCK_BYTES = 2 * 1024 * 1024
ROW_PAD = 8  # row counts are padded up to this multiple before blocking


def row_block(N: int, row_elems: int, limit: int = BLOCK_BYTES) -> int | None:
    """Largest row-block size dividing ``N`` whose fp32 working block of
    ``row_elems`` columns fits the budget; None if no candidate divides."""
    for bn in (256, 128, 64, 32, 16, 8):
        if N % bn == 0 and bn * row_elems * 4 <= limit:
            return bn
    return None


def pad_rows(N: int) -> int:
    return -(-N // ROW_PAD) * ROW_PAD


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class Partition(NamedTuple):
    """How the step being traced is split: ``batch``/``heads`` name the
    mesh axis (or None) the leading batch dim / the head dim go over."""
    mesh: object
    batch: str | None
    heads: str | None

    def size(self, axis: str | None) -> int:
        return 1 if axis is None else self.mesh.shape[axis]

    def spec(self, ndim: int, batch: int | None = None,
             heads: int | None = None) -> P:
        dims = [None] * ndim
        if batch is not None:
            dims[batch] = self.batch
        if heads is not None:
            dims[heads] = self.heads
        return P(*dims)

    def shard_map(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)


_tracing = threading.local()


@contextlib.contextmanager
def partitioned(mesh, batch: str | None = None, heads: str | None = None):
    """While this is active, kernels traced on this thread run per shard
    of ``mesh``.  A mesh of one device needs no wrapping and sets none."""
    prev = partition()
    _tracing.part = (Partition(mesh, batch, heads) if mesh.size > 1
                     else None)
    try:
        yield
    finally:
        _tracing.part = prev


def partition() -> Partition | None:
    return getattr(_tracing, "part", None)
