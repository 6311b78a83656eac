"""Tensor — the eager (dygraph) tensor.

Reference capability: VarBase (/root/reference/paddle/fluid/imperative/layer.h:66
— tensor + grad var + autograd meta) over framework::Tensor
(framework/tensor.h:89).  TPU-first: the storage is a ``jax.Array`` living in
HBM managed by PJRT — there is no custom allocator layer to build; PJRT's
buffer manager plays the role of memory/allocation/* in the reference.

Most math methods are attached by ``paddle_tpu.tensor_api`` (single source of
truth shared between the functional API and Tensor methods, mirroring how the
reference generates ``core.ops.*`` bindings per op —
pybind/op_function_generator.cc:518).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd
from .dtype import convert_dtype, dtype_name, get_default_dtype
from .place import Place, current_jax_device, current_place


class Tensor:
    __slots__ = (
        "_value",
        "stop_gradient",
        "grad",
        "_node",
        "_out_index",
        "_hooks",
        "name",
        "persistable",
        "_sharding_spec",
        "trainable",
        "__weakref__",
    )

    def __init__(self, value, stop_gradient: bool = True, name: str | None = None):
        if isinstance(value, Tensor):
            value = value._value
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad: "Tensor | None" = None
        self._node: "autograd.TapeNode | None" = None
        self._out_index = 0
        self._hooks: list = []
        self.name = name
        self.persistable = False
        self._sharding_spec = None  # PartitionSpec for distributed layouts
        self.trainable = True

    # -- basic properties ---------------------------------------------------
    @property
    def value(self):
        return self._value

    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def dtype(self):
        return np.dtype(self._value.dtype).type

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self) -> Place:
        try:
            dev = next(iter(self._value.devices())) if hasattr(self._value, "devices") else None
        except Exception:
            dev = None
        if dev is None:
            return current_place()
        return Place(dev.platform, dev.id)

    @property
    def is_leaf(self):
        return self._node is None

    # -- conversion ---------------------------------------------------------
    def numpy(self):
        return np.asarray(self._value)

    def item(self):
        return self._value.item()

    def tolist(self):
        return np.asarray(self._value).tolist()

    def __array__(self, dtype=None):
        a = np.asarray(self._value)
        return a.astype(dtype) if dtype is not None else a

    def astype(self, dtype):
        from .dispatch import dispatch

        d = convert_dtype(dtype)
        return dispatch(lambda x: x.astype(d), self, op_name="cast")

    cast = astype

    def clone(self):
        from .dispatch import dispatch

        return dispatch(lambda x: x + 0, self, op_name="clone")

    def detach(self):
        t = Tensor(self._value, stop_gradient=True, name=self.name)
        return t

    def cpu(self):
        from .place import _find_device

        d = _find_device("cpu", 0)
        return Tensor(jax.device_put(self._value, d), stop_gradient=self.stop_gradient)

    def to(self, device=None, dtype=None):
        out = self
        if dtype is not None:
            out = out.astype(dtype)
        if device is not None:
            from .place import set_device, current_jax_device
            import paddle_tpu.core.place as _p

            if isinstance(device, str):
                if ":" in device:
                    ty, ix = device.split(":")
                    dev = _p._find_device(ty, int(ix))
                else:
                    dev = _p._find_device(device, 0)
            else:
                dev = device.jax_device
            out = Tensor(jax.device_put(out._value, dev), stop_gradient=out.stop_gradient)
        return out

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph)

    def _accumulate_grad(self, g):
        from .selected_rows import RowSparseGrad

        if self.grad is None:
            self.grad = Tensor(g, stop_gradient=True)
        elif isinstance(self.grad._value, RowSparseGrad):
            self.grad = Tensor(self.grad._value.add(g), stop_gradient=True)
        elif isinstance(g, RowSparseGrad):
            self.grad = Tensor(jnp.asarray(self.grad._value)
                               + g.to_dense(), stop_gradient=True)
        else:
            self.grad = Tensor(self.grad._value + g, stop_gradient=True)

    def clear_gradient(self):
        self.grad = None

    clear_grad = clear_gradient

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Removable:
            def remove(_s):
                try:
                    self._hooks.remove(hook)
                except ValueError:
                    pass

        return _Removable()

    # -- indexing -----------------------------------------------------------
    def __getitem__(self, idx):
        from .dispatch import dispatch

        idx = _unwrap_index(idx)
        return dispatch(lambda x: x[idx], self, op_name="getitem")

    def __setitem__(self, idx, v):
        from .dispatch import dispatch

        idx = _unwrap_index(idx)
        args = (self, v) if isinstance(v, Tensor) else (self,)
        if isinstance(v, Tensor):
            out = dispatch(lambda x, vv: x.at[idx].set(vv), self, v, op_name="setitem")
        else:
            out = dispatch(lambda x: x.at[idx].set(v), self, op_name="setitem")
        # in-place semantics: rebind storage + tape position
        self._value = out._value
        self._node = out._node
        self._out_index = out._out_index
        if not out.stop_gradient:
            self.stop_gradient = False

    def __len__(self):
        if not self._value.shape:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- misc ---------------------------------------------------------------
    def __repr__(self):
        g = ", stop_gradient=" + str(self.stop_gradient)
        return (
            f"Tensor(shape={self.shape}, dtype={dtype_name(self.dtype)}{g},\n"
            f"       {np.asarray(self._value)!r})"
        )

    def __bool__(self):
        return bool(self._value)

    def __float__(self):
        return float(self._value)

    def __int__(self):
        return int(self._value)

    def __hash__(self):
        return id(self)

    # numpy priority so ndarray + Tensor defers to us
    __array_priority__ = 100


def _unwrap_index(idx):
    if isinstance(idx, Tensor):
        return idx._value
    if isinstance(idx, tuple):
        return tuple(i._value if isinstance(i, Tensor) else i for i in idx)
    return idx


class Parameter(Tensor):
    """Trainable tensor (reference: framework.py Parameter / VarBase param).
    stop_gradient defaults False; carries optional PartitionSpec for SPMD."""

    __slots__ = ()

    def __init__(self, value, name: str | None = None, trainable: bool = True):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor equivalent."""
    if isinstance(data, Tensor):
        v = data._value
        if dtype is not None:
            v = v.astype(convert_dtype(dtype))
        return Tensor(v, stop_gradient=stop_gradient)
    d = convert_dtype(dtype)
    if d is None:
        arr = np.asarray(data)
        if arr.dtype == np.float64:
            arr = arr.astype(get_default_dtype())
        elif arr.dtype == np.int64:
            arr = arr.astype(convert_dtype("int64"))
        v = arr
    else:
        v = np.asarray(data, dtype=np.dtype(d) if d is not jnp.bfloat16 else None)
        if d is jnp.bfloat16:
            v = v.astype(jnp.bfloat16)
    dev = place.jax_device if isinstance(place, Place) else current_jax_device()
    val = jax.device_put(v, dev)
    return Tensor(val, stop_gradient=stop_gradient)
