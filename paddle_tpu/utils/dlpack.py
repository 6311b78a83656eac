"""DLPack zero-copy tensor interop (reference framework/dlpack_tensor.cc,
paddle.utils.dlpack.to_dlpack/from_dlpack)."""
from __future__ import annotations

from ..core.tensor import Tensor


def to_dlpack(tensor):
    """Tensor → DLPack capsule (zero-copy where the backend allows)."""
    v = tensor.value if isinstance(tensor, Tensor) else tensor
    return v.__dlpack__()


def from_dlpack(capsule_or_array) -> Tensor:
    """DLPack capsule / __dlpack__ object → Tensor."""
    import jax

    arr = jax.dlpack.from_dlpack(capsule_or_array)
    return Tensor(arr, stop_gradient=True)
