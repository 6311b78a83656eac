"""nn.functional — neural-net ops.

Reference capability: python/paddle/nn/functional/* backed by the C++/CUDA
operator library (/root/reference/paddle/fluid/operators — conv via cuDNN,
softmax/layer_norm/batch_norm CUDA kernels, fused attention precursors in
operators/fused/).  TPU-first: every op is a pure jax function lowered by XLA
onto MXU/VPU; XLA fuses elementwise chains into matmul epilogues, so the
reference's hand-fused kernels (fused_fc_elementwise_layernorm, skip_layernorm
…) need no explicit analog.  Flash attention is the exception — provided as a
Pallas kernel in paddle_tpu.ops and routed via scaled_dot_product_attention.

Convs use NCHW at the API (reference default data_format) but lower through
lax.conv_general_dilated which XLA lays out optimally for the MXU.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import dispatch
from ...core.dtype import convert_dtype
from ...core.tensor import Tensor
from ...framework import random as _random


def _v(x):
    return x.value if isinstance(x, Tensor) else x


# ---------------------------------------------------------------------------
# activations (reference operators/activation_op.* + gelu_op, prelu_op …)
# ---------------------------------------------------------------------------


def relu(x):
    return dispatch(jax.nn.relu, x, op_name="relu")


def relu6(x):
    return dispatch(jax.nn.relu6, x, op_name="relu6")


def leaky_relu(x, negative_slope=0.01):
    return dispatch(lambda a: jax.nn.leaky_relu(a, negative_slope), x, op_name="leaky_relu")


def elu(x, alpha=1.0):
    return dispatch(lambda a: jax.nn.elu(a, alpha), x, op_name="elu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return dispatch(lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)), x, op_name="selu")


def celu(x, alpha=1.0):
    return dispatch(lambda a: jax.nn.celu(a, alpha), x, op_name="celu")


def gelu(x, approximate=False):
    return dispatch(lambda a: jax.nn.gelu(a, approximate=approximate), x, op_name="gelu")


def sigmoid(x):
    return dispatch(jax.nn.sigmoid, x, op_name="sigmoid")


def log_sigmoid(x):
    return dispatch(jax.nn.log_sigmoid, x, op_name="log_sigmoid")


def hardsigmoid(x, slope=0.1666667, offset=0.5):
    return dispatch(lambda a: jnp.clip(slope * a + offset, 0.0, 1.0), x, op_name="hardsigmoid")


def hardswish(x):
    return dispatch(lambda a: a * jnp.clip(a + 3.0, 0.0, 6.0) / 6.0, x, op_name="hardswish")


def hardtanh(x, min=-1.0, max=1.0):
    return dispatch(lambda a: jnp.clip(a, min, max), x, op_name="hardtanh")


def hardshrink(x, threshold=0.5):
    return dispatch(
        lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0).astype(a.dtype), x, op_name="hardshrink"
    )


def softshrink(x, threshold=0.5):
    return dispatch(
        lambda a: jnp.sign(a) * jnp.maximum(jnp.abs(a) - threshold, 0.0), x, op_name="softshrink"
    )


def tanhshrink(x):
    return dispatch(lambda a: a - jnp.tanh(a), x, op_name="tanhshrink")


def swish(x):
    return dispatch(jax.nn.silu, x, op_name="swish")


silu = swish


def mish(x):
    return dispatch(lambda a: a * jnp.tanh(jax.nn.softplus(a)), x, op_name="mish")


def tanh(x):
    return dispatch(jnp.tanh, x, op_name="tanh")


def softplus(x, beta=1.0, threshold=20.0):
    return dispatch(
        lambda a: jnp.where(beta * a > threshold, a, jax.nn.softplus(beta * a) / beta),
        x,
        op_name="softplus",
    )


def softsign(x):
    return dispatch(jax.nn.soft_sign, x, op_name="softsign")


def prelu(x, weight):
    def fn(a, w):
        wb = w.reshape((1, -1) + (1,) * (a.ndim - 2)) if w.size > 1 else w
        return jnp.where(a > 0, a, wb * a)

    return dispatch(fn, x, weight, op_name="prelu")


def softmax(x, axis=-1, dtype=None):
    d = convert_dtype(dtype)

    def fn(a):
        if d is not None:
            a = a.astype(d)
        return jax.nn.softmax(a, axis=axis)

    return dispatch(fn, x, op_name="softmax")


def log_softmax(x, axis=-1, dtype=None):
    d = convert_dtype(dtype)

    def fn(a):
        if d is not None:
            a = a.astype(d)
        return jax.nn.log_softmax(a, axis=axis)

    return dispatch(fn, x, op_name="log_softmax")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    k = _random.next_key()

    def fn(a):
        g = jax.random.gumbel(k, a.shape, a.dtype)
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            onehot = jnp.zeros_like(y).at[
                tuple(
                    jnp.indices(y.shape)[i] if i != axis % y.ndim else idx
                    for i in range(y.ndim)
                )
            ].set(1.0)
            y = onehot + y - jax.lax.stop_gradient(y)
        return y

    return dispatch(fn, x, op_name="gumbel_softmax")


def glu(x, axis=-1):
    def fn(a):
        a1, a2 = jnp.split(a, 2, axis=axis)
        return a1 * jax.nn.sigmoid(a2)

    return dispatch(fn, x, op_name="glu")


# ---------------------------------------------------------------------------
# linear / embedding
# ---------------------------------------------------------------------------


def linear(x, weight, bias=None):
    """y = x @ W + b; W is [in, out] (reference matmul_v2 + elementwise_add)."""
    if bias is None:
        return dispatch(lambda a, w: a @ w, x, weight, op_name="linear")
    return dispatch(lambda a, w, b: a @ w + b, x, weight, bias, op_name="linear")


def embedding(x, weight, padding_idx=None, sparse=False):
    """reference lookup_table_v2: gather rows; padding_idx row gets zero grad.

    sparse=True on the EAGER path produces a ``RowSparseGrad`` for the weight
    (the SelectedRows capability: lookup_table's is_sparse grad consumed by
    lazy_mode optimizers) instead of a dense scatter over the full table.
    Under jit the dense path always applies — XLA fuses the scatter."""
    idx = _v(x)

    def fn(w):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    from ...core import autograd as _ag

    w_val = _v(weight)
    eager = not isinstance(w_val, jax.core.Tracer) and \
        not isinstance(idx, jax.core.Tracer)
    if (sparse and eager and isinstance(weight, Tensor)
            and not weight.stop_gradient and _ag.is_grad_enabled()):
        from ...core.selected_rows import RowSparseGrad

        out_val = fn(w_val)

        def sparse_vjp(cts):
            ct = jnp.asarray(cts[0])
            rows = idx.reshape(-1)
            vals = ct.reshape((-1,) + ct.shape[idx.ndim:])
            if padding_idx is not None:
                keep = (rows != padding_idx)
                vals = jnp.where(keep[:, None], vals, 0)
            return (RowSparseGrad(rows, vals, w_val.shape),)

        node = _ag.record(sparse_vjp, [weight],
                          [(out_val.shape, out_val.dtype)],
                          name="embedding_sparse")
        t = Tensor(out_val, stop_gradient=False)
        t._node = node
        t._out_index = 0
        return t

    return dispatch(fn, weight, op_name="embedding")


def one_hot(x, num_classes):
    return Tensor(jax.nn.one_hot(_v(x), num_classes))


def bilinear(x1, x2, weight, bias=None):
    def fn(a, b, w):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        return out

    out = dispatch(fn, x1, x2, weight, op_name="bilinear")
    if bias is not None:
        out = dispatch(lambda o, bb: o + bb, out, bias, op_name="bilinear_bias")
    return out


# ---------------------------------------------------------------------------
# convolution / pooling (reference conv_op + cuDNN; here lax.conv on MXU)
# ---------------------------------------------------------------------------


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(i) for i in v)
    return (int(v),) * n


def _conv_nd(a, w, bias, stride, padding, dilation, groups, nd, data_format,
             preferred_element_type=None):
    # a: N C ...spatial (NCHW api); w stored [out_c, in_c/groups, *k] (reference layout)
    # preferred_element_type: accumulation dtype override — the int8
    # inference path (quantization/int8_infer.py) requests s32 accumulation
    # for s8 x s8 convolutions
    chan_last = data_format in ("NHWC", "NLC", "NDHWC")
    if chan_last:
        a = jnp.moveaxis(a, -1, 1)
    stride = _pair(stride, nd)
    dilation = _pair(dilation, nd)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        p = _pair(padding, nd) if not (
            isinstance(padding, (list, tuple)) and len(padding) == 2 * nd
        ) else tuple(padding)
        if len(p) == nd:
            pad = [(pi, pi) for pi in p]
        else:
            pad = [(p[2 * i], p[2 * i + 1]) for i in range(nd)]
    dn = jax.lax.conv_dimension_numbers(a.shape, w.shape, _dim_str(nd))
    out = jax.lax.conv_general_dilated(
        a, w, window_strides=stride, padding=pad,
        rhs_dilation=dilation, feature_group_count=groups,
        dimension_numbers=dn,
        preferred_element_type=preferred_element_type,
    )
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    if chan_last:
        out = jnp.moveaxis(out, 1, -1)
    return out


def _dim_str(nd):
    spatial = "DHW"[-nd:]
    return (f"NC{spatial}", f"OI{spatial}", f"NC{spatial}")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW"):
    args = (x, weight) + ((bias,) if bias is not None else ())

    def fn(a, w, *b):
        return _conv_nd(a, w, b[0] if b else None, stride, padding, dilation, groups, 2, data_format)

    return dispatch(fn, *args, op_name="conv2d")


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL"):
    args = (x, weight) + ((bias,) if bias is not None else ())

    def fn(a, w, *b):
        return _conv_nd(a, w, b[0] if b else None, stride, padding, dilation, groups, 1, data_format)

    return dispatch(fn, *args, op_name="conv1d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW"):
    args = (x, weight) + ((bias,) if bias is not None else ())

    def fn(a, w, *b):
        return _conv_nd(a, w, b[0] if b else None, stride, padding, dilation, groups, 3, data_format)

    return dispatch(fn, *args, op_name="conv3d")


def conv2d_transpose(
    x, weight, bias=None, stride=1, padding=0, output_padding=0, dilation=1, groups=1,
    data_format="NCHW", output_size=None,
):
    """reference conv2d_transpose_op; weight layout [in_c, out_c/groups, kh, kw]."""
    nd = 2
    stride_ = _pair(stride, nd)
    dil = _pair(dilation, nd)
    pad_in = _pair(padding, nd)
    opad = _pair(output_padding, nd)

    def fn(a, w, *b):
        # shared transpose-conv math lives in _conv_transpose_impl (defined
        # below; also serves conv1d/3d_transpose) — one copy of the
        # flip/regroup/lhs_dilation formulation
        return _conv_transpose_impl(a, w, b[0] if b else None, stride,
                                    padding, output_padding, dilation,
                                    groups, nd, data_format == "NHWC",
                                    output_size)

    args = (x, weight) + ((bias,) if bias is not None else ())
    return dispatch(fn, *args, op_name="conv2d_transpose")


def _pool(a, nd, kernel, stride, padding, mode, ceil_mode=False, count_include_pad=True):
    k = _pair(kernel, nd)
    s = _pair(stride if stride is not None else kernel, nd)
    p = _pair(padding, nd)
    window = (1, 1) + k
    strides = (1, 1) + s
    pads = ((0, 0), (0, 0)) + tuple((pi, pi) for pi in p)
    if mode == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(a, init, jax.lax.max, window, strides, pads)
        return out
    # avg
    out = jax.lax.reduce_window(a, 0.0, jax.lax.add, window, strides, pads)
    if count_include_pad or _all_zero(p):
        return out / float(np.prod(k))
    ones = jnp.ones(a.shape[2:], a.dtype)
    cnt = jax.lax.reduce_window(
        ones, 0.0, jax.lax.add, k, s, tuple((pi, pi) for pi in p)
    )
    return out / cnt


def _all_zero(p):
    return all(pi == 0 for pi in p)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, return_mask=False, data_format="NCHW"):
    def fn(a):
        if data_format == "NHWC":
            a = jnp.moveaxis(a, -1, 1)
        out = _pool(a, 2, kernel_size, stride, padding, "max")
        if data_format == "NHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return dispatch(fn, x, op_name="max_pool2d")


def avg_pool2d(
    x, kernel_size, stride=None, padding=0, ceil_mode=False, count_include_pad=True, data_format="NCHW"
):
    def fn(a):
        if data_format == "NHWC":
            a = jnp.moveaxis(a, -1, 1)
        out = _pool(a, 2, kernel_size, stride, padding, "avg", count_include_pad=count_include_pad)
        if data_format == "NHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return dispatch(fn, x, op_name="avg_pool2d")


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False):
    def fn(a):
        return _pool(a, 1, kernel_size, stride, padding, "max")

    return dispatch(fn, x, op_name="max_pool1d")


def avg_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False, count_include_pad=True):
    def fn(a):
        return _pool(a, 1, kernel_size, stride, padding, "avg", count_include_pad=count_include_pad)

    return dispatch(fn, x, op_name="avg_pool1d")


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    os = _pair(output_size, 2)

    def fn(a):
        if data_format == "NHWC":
            a = jnp.moveaxis(a, -1, 1)
        n, c, h, w = a.shape
        oh, ow = os
        # split into oh x ow cells (equal-size when divisible; general via mean over index windows)
        if h % oh == 0 and w % ow == 0:
            out = a.reshape(n, c, oh, h // oh, ow, w // ow).mean(axis=(3, 5))
        else:
            hs = [int(math.floor(i * h / oh)) for i in range(oh + 1)]
            ws = [int(math.floor(i * w / ow)) for i in range(ow + 1)]
            rows = []
            for i in range(oh):
                cols = []
                for j in range(ow):
                    cols.append(a[:, :, hs[i]:hs[i + 1], ws[j]:ws[j + 1]].mean(axis=(2, 3)))
                rows.append(jnp.stack(cols, axis=-1))
            out = jnp.stack(rows, axis=-2)
        if data_format == "NHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return dispatch(fn, x, op_name="adaptive_avg_pool2d")


def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    os = _pair(output_size, 2)

    def fn(a):
        n, c, h, w = a.shape
        oh, ow = os
        assert h % oh == 0 and w % ow == 0, "adaptive_max_pool2d needs divisible sizes"
        return a.reshape(n, c, oh, h // oh, ow, w // ow).max(axis=(3, 5))

    return dispatch(fn, x, op_name="adaptive_max_pool2d")


def adaptive_avg_pool1d(x, output_size):
    def fn(a):
        n, c, l = a.shape
        o = int(output_size)
        assert l % o == 0
        return a.reshape(n, c, o, l // o).mean(axis=3)

    return dispatch(fn, x, op_name="adaptive_avg_pool1d")


# ---------------------------------------------------------------------------
# normalisation (reference batch_norm_op/layer_norm_op/group_norm_op CUDA)
# ---------------------------------------------------------------------------


def batch_norm(
    x, running_mean, running_var, weight=None, bias=None, training=False,
    momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None,
):
    """Functional batch norm.  In training mode also *returns* updated running
    stats is handled by the BatchNorm layer (stats are buffers there); here we
    compute with either batch stats (training) or running stats."""
    axis = 1 if data_format.startswith("NC") else -1

    use_batch_stats = training and not (use_global_stats is True)
    reduce_axes = None

    def fn(a, *rest):
        # rest holds only the PROVIDED affine params, in (weight, bias)
        # order - bias-without-weight must not read weight's slot
        it = iter(rest)
        w = next(it) if weight is not None else None
        b = next(it) if bias is not None else None
        rm, rv = _v(running_mean), _v(running_var)
        ax = axis % a.ndim
        raxes = tuple(i for i in range(a.ndim) if i != ax)
        if use_batch_stats:
            m = jnp.mean(a, axis=raxes)
            v = jnp.var(a, axis=raxes)
        else:
            m, v = rm, rv
        shape = [1] * a.ndim
        shape[ax] = a.shape[ax]
        out = (a - m.reshape(shape)) * jax.lax.rsqrt(v.reshape(shape) + epsilon)
        if w is not None:
            out = out * w.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return dispatch(fn, *args, op_name="batch_norm")


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    nd = len(tuple(normalized_shape))

    def fn(a, *rest):
        # rest holds only the PROVIDED affine params, in (weight, bias)
        # order — bias-without-weight must not read weight's slot
        it = iter(rest)
        w = next(it) if weight is not None else None
        b = next(it) if bias is not None else None
        if nd == 1 and a.ndim >= 2 and \
                os.environ.get("PADDLE_TPU_FUSED_LN", "") == "1":
            # Pallas row-statistics kernel on a TPU for aligned shapes
            # (fused_layer_norm's static gate; this same XLA expression
            # otherwise).  Opt-in; on-device parity against the XLA
            # oracle is the kernels phase of chip_smoke.py.
            from ...ops.fused_norm import fused_layer_norm

            return fused_layer_norm(a, weight=w, bias=b, eps=epsilon)
        axes = tuple(range(a.ndim - nd, a.ndim))
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + epsilon)
        if w is not None:
            out = out * w
        if b is not None:
            out = out + b
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return dispatch(fn, *args, op_name="layer_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW"):
    def fn(a, *rest):
        # rest holds only the PROVIDED affine params, in (weight, bias)
        # order - bias-without-weight must not read weight's slot
        it = iter(rest)
        w = next(it) if weight is not None else None
        b = next(it) if bias is not None else None
        axes = tuple(range(2, a.ndim))
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * jax.lax.rsqrt(v + eps)
        shape = (1, -1) + (1,) * (a.ndim - 2)
        if w is not None:
            out = out * w.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return dispatch(fn, *args, op_name="instance_norm")


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5, data_format="NCHW"):
    def fn(a, *rest):
        # rest holds only the PROVIDED affine params, in (weight, bias)
        # order - bias-without-weight must not read weight's slot
        it = iter(rest)
        w = next(it) if weight is not None else None
        b = next(it) if bias is not None else None
        n, c = a.shape[:2]
        spatial = a.shape[2:]
        g = a.reshape(n, num_groups, c // num_groups, *spatial)
        axes = tuple(range(2, g.ndim))
        m = jnp.mean(g, axis=axes, keepdims=True)
        v = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - m) * jax.lax.rsqrt(v + epsilon)).reshape(a.shape)
        shape = (1, c) + (1,) * len(spatial)
        if w is not None:
            out = out * w.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return dispatch(fn, *args, op_name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0):
    def fn(a):
        sq = a * a
        half = size // 2
        # sum over channel window
        pads = [(0, 0)] * a.ndim
        pads[1] = (half, size - 1 - half)
        padded = jnp.pad(sq, pads)
        window = [1] * a.ndim
        window[1] = size
        s = jax.lax.reduce_window(padded, 0.0, jax.lax.add, tuple(window), (1,) * a.ndim, "VALID")
        return a / (k + alpha * s) ** beta

    return dispatch(fn, x, op_name="local_response_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12):
    def fn(a):
        n = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(n, epsilon)

    return dispatch(fn, x, op_name="normalize")


# ---------------------------------------------------------------------------
# dropout (reference dropout_op: upscale_in_train default)
# ---------------------------------------------------------------------------


def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None):
    if not training or p == 0.0:
        if training or mode == "upscale_in_train" or p == 0.0:
            return x if isinstance(x, Tensor) else Tensor(_v(x))
        # downscale_in_infer: train keeps magnitude, infer scales by (1-p)
        return dispatch(lambda a: a * (1.0 - p), x, op_name="dropout_infer")
    k = _random.next_key()

    def fn(a):
        shape = list(a.shape)
        if axis is not None:
            axes = (axis,) if isinstance(axis, int) else tuple(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(k, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)

    return dispatch(fn, x, op_name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    ax = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p, training, axis=ax)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    ax = (0, 1) if data_format == "NCDHW" else (0, 4)
    return dropout(x, p, training, axis=ax)


def alpha_dropout(x, p=0.5, training=True):
    if not training or p == 0.0:
        return x
    k = _random.next_key()
    alpha = 1.6732632423543772
    scale_ = 1.0507009873554805
    alpha_p = -alpha * scale_

    def fn(a):
        keep = jax.random.bernoulli(k, 1.0 - p, a.shape)
        q = 1.0 - p
        a_ = (q + alpha_p**2 * q * p) ** -0.5
        b_ = -a_ * alpha_p * p
        return (a_ * jnp.where(keep, a, alpha_p) + b_).astype(a.dtype)

    return dispatch(fn, x, op_name="alpha_dropout")


# ---------------------------------------------------------------------------
# losses (reference cross_entropy_op, bce, smooth_l1, kldiv …)
# ---------------------------------------------------------------------------


def _reduce(val, reduction):
    if reduction == "mean":
        return jnp.mean(val)
    if reduction == "sum":
        return jnp.sum(val)
    return val


def cross_entropy(
    input, label, weight=None, ignore_index=-100, reduction="mean",
    soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0,
):
    lbl = _v(label)

    def fn(logits, *rest):
        w = rest[0] if weight is not None else None
        if use_softmax:
            logp = jax.nn.log_softmax(logits, axis=axis)
        else:
            logp = jnp.log(jnp.maximum(logits, 1e-30))
        if soft_label:
            tgt = lbl
            if label_smoothing:
                n = logits.shape[axis]
                tgt = tgt * (1 - label_smoothing) + label_smoothing / n
            loss = -jnp.sum(tgt * logp, axis=axis)
        else:
            li = lbl
            if li.ndim == logp.ndim:
                li = jnp.squeeze(li, axis=axis)
            li = li.astype(jnp.int32)
            valid = li != ignore_index
            safe = jnp.where(valid, li, 0)
            picked = jnp.take_along_axis(
                logp, safe[..., None], axis=axis
            ).squeeze(axis)
            if label_smoothing:
                n = logits.shape[axis]
                smooth = jnp.mean(logp, axis=axis)
                picked = (1 - label_smoothing) * picked + label_smoothing * smooth
            loss = jnp.where(valid, -picked, 0.0)
            if w is not None:
                loss = loss * jnp.take(w, safe)
            if reduction == "mean":
                denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
                if w is not None:
                    denom = jnp.maximum(jnp.sum(jnp.take(w, safe) * valid), 1e-12)
                return jnp.sum(loss) / denom
        return _reduce(loss, reduction)

    args = [input] + ([weight] if weight is not None else [])
    return dispatch(fn, *args, op_name="cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100, axis=-1,
                               return_softmax=False):
    loss = cross_entropy(logits, label, soft_label=soft_label, ignore_index=ignore_index,
                         reduction="none", axis=axis)
    # keepdim semantics of the reference op: loss has size-1 trailing axis
    from ... import tensor_api as P

    loss = P.unsqueeze(loss, axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    lbl = _v(label)

    def fn(logp, *rest):
        w = rest[0] if weight is not None else None
        valid = lbl != ignore_index
        safe = jnp.where(valid, lbl, 0).astype(jnp.int32)
        picked = jnp.take_along_axis(logp, safe[..., None], axis=-1).squeeze(-1)
        loss = jnp.where(valid, -picked, 0.0)
        if w is not None:
            wp = jnp.take(w, safe)
            loss = loss * wp
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(wp * valid), 1e-12)
        return _reduce(loss, reduction)

    args = [input] + ([weight] if weight is not None else [])
    return dispatch(fn, *args, op_name="nll_loss")


def mse_loss(input, label, reduction="mean"):
    return dispatch(
        lambda a, b: _reduce((a - b) ** 2, reduction), input, label, op_name="mse_loss"
    )


def l1_loss(input, label, reduction="mean"):
    return dispatch(
        lambda a, b: _reduce(jnp.abs(a - b), reduction), input, label, op_name="l1_loss"
    )


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    def fn(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce(loss, reduction)

    return dispatch(fn, input, label, op_name="smooth_l1_loss")


def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    def fn(p, t, *rest):
        w = rest[0] if weight is not None else None
        p = jnp.clip(p, 1e-12, 1.0 - 1e-12)
        loss = -(t * jnp.log(p) + (1 - t) * jnp.log(1 - p))
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)

    args = [input, label] + ([weight] if weight is not None else [])
    return dispatch(fn, *args, op_name="bce")


def binary_cross_entropy_with_logits(input, label, weight=None, reduction="mean", pos_weight=None):
    pw = _v(pos_weight) if pos_weight is not None else None

    def fn(z, t, *rest):
        w = rest[0] if weight is not None else None
        # numerically stable: max(z,0) - z*t + log(1+exp(-|z|))
        loss = jnp.maximum(z, 0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))
        if pw is not None:
            loss = loss * (t * (pw - 1) + 1)
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)

    args = [input, label] + ([weight] if weight is not None else [])
    return dispatch(fn, *args, op_name="bce_logits")


def kl_div(input, label, reduction="mean"):
    def fn(logp, t):
        loss = t * (jnp.log(jnp.maximum(t, 1e-30)) - logp)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce(loss, reduction)

    return dispatch(fn, input, label, op_name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    def fn(a, b, t):
        return _reduce(jnp.maximum(0.0, -t * (a - b) + margin), reduction)

    return dispatch(fn, input, other, label, op_name="margin_ranking_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    def fn(a, t):
        loss = jnp.where(t == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce(loss, reduction)

    return dispatch(fn, input, label, op_name="hinge_embedding_loss")


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def fn(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.maximum(
            jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis), eps
        )
        return num / den

    return dispatch(fn, x1, x2, op_name="cosine_similarity")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0, reduction="sum"):
    nz = _v(normalizer) if normalizer is not None else None

    def fn(z, t):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * t + (1 - p) * (1 - t)
        a_t = alpha * t + (1 - alpha) * (1 - t)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if nz is not None:
            loss = loss / nz
        return _reduce(loss, reduction)

    return dispatch(fn, logit, label, op_name="sigmoid_focal_loss")


def square_error_cost(input, label):
    return dispatch(lambda a, b: (a - b) ** 2, input, label, op_name="square_error_cost")


def label_smooth(label, prior_dist=None, epsilon=0.1):
    def fn(t):
        n = t.shape[-1]
        if prior_dist is not None:
            return (1 - epsilon) * t + epsilon * _v(prior_dist)
        return (1 - epsilon) * t + epsilon / n

    return dispatch(fn, label, op_name="label_smooth")


# ---------------------------------------------------------------------------
# attention — routed to Pallas flash attention on TPU (paddle_tpu.ops)
# ---------------------------------------------------------------------------


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True):
    """q,k,v: [B, T, H, D] (paddle convention). Uses the Pallas flash kernel
    when available (TPU), else the XLA softmax path."""
    from ...ops import attention as _attn

    return _attn.scaled_dot_product_attention(
        query, key, value, attn_mask=attn_mask, dropout_p=dropout_p,
        is_causal=is_causal, training=training,
    )


# ---------------------------------------------------------------------------
# shape ops / misc
# ---------------------------------------------------------------------------


def pad(x, pad_width, mode="constant", value=0.0, data_format="NCHW"):
    from ... import tensor_api as P

    return P.pad(x, pad_width, mode=mode, value=value, data_format=data_format)


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
                data_format="NCHW"):
    def fn(a):
        chan_last = data_format == "NHWC"
        if chan_last:
            a = jnp.moveaxis(a, -1, 1)
        n, c, h, w = a.shape
        if size is not None:
            oh, ow = _pair(size, 2)
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) else (scale_factor,) * 2
            oh, ow = int(h * sf[0]), int(w * sf[1])
        m = {"nearest": "nearest", "bilinear": "linear", "bicubic": "cubic"}[mode]
        if align_corners and mode in ("bilinear", "bicubic") and oh > 1 and ow > 1:
            # corner-aligned sampling: src position of out pixel o is
            # o*(in-1)/(out-1); jax.image.resize only does half-pixel, so use
            # scale_and_translate with the matching affine map
            sh = (oh - 1) / (h - 1) if h > 1 else 1.0
            sw = (ow - 1) / (w - 1) if w > 1 else 1.0
            scale = jnp.array([sh, sw], jnp.float32)
            # scale_and_translate samples src=(o+0.5-t)/s-0.5; t=0.5-0.5s
            # yields the corner-aligned map src = o/s
            trans = jnp.array([0.5 - 0.5 * sh, 0.5 - 0.5 * sw], jnp.float32)
            out = jax.image.scale_and_translate(
                a, (n, c, oh, ow), spatial_dims=(2, 3), scale=scale,
                translation=trans,
                method="linear" if mode == "bilinear" else "cubic",
            )
        else:
            out = jax.image.resize(a, (n, c, oh, ow), method=m)
        if chan_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    return dispatch(fn, x, op_name="interpolate")


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor

    def fn(a):
        n, c, h, w = a.shape
        oc = c // (r * r)
        out = a.reshape(n, oc, r, r, h, w)
        out = jnp.transpose(out, (0, 1, 4, 2, 5, 3))
        return out.reshape(n, oc, h * r, w * r)

    return dispatch(fn, x, op_name="pixel_shuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    k = _pair(kernel_sizes, 2)
    s = _pair(strides, 2)
    p = _pair(paddings, 2)
    d = _pair(dilations, 2)

    def fn(a):
        n, c, h, w = a.shape
        patches = jax.lax.conv_general_dilated_patches(
            a, filter_shape=k, window_strides=s,
            padding=tuple((pi, pi) for pi in p), rhs_dilation=d,
        )
        # output [N, C*kh*kw, L]
        return patches.reshape(n, c * k[0] * k[1], -1)

    return dispatch(fn, x, op_name="unfold")


def sequence_mask(lengths, maxlen=None, dtype="int64"):
    lv = _v(lengths)
    ml = int(maxlen) if maxlen is not None else int(np.asarray(lv).max())
    out = (jnp.arange(ml)[None, :] < lv[..., None]).astype(convert_dtype(dtype))
    return Tensor(out)


def temporal_shift(x, seg_num, shift_ratio=0.25):
    def fn(a):
        nt, c, h, w = a.shape
        n = nt // seg_num
        v = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([v[:, 1:, :fold], jnp.zeros_like(v[:, :1, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(v[:, :1, fold:2 * fold]), v[:, :-1, fold:2 * fold]], axis=1)
        rest = v[:, :, 2 * fold:]
        return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)

    return dispatch(fn, x, op_name="temporal_shift")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros", align_corners=True):
    gv = _v(grid)

    def fn(a):
        n, c, h, w = a.shape
        gx = (gv[..., 0] + 1) * (w - 1) / 2 if align_corners else ((gv[..., 0] + 1) * w - 1) / 2
        gy = (gv[..., 1] + 1) * (h - 1) / 2 if align_corners else ((gv[..., 1] + 1) * h - 1) / 2
        x0 = jnp.floor(gx).astype(jnp.int32)
        y0 = jnp.floor(gy).astype(jnp.int32)
        x1, y1 = x0 + 1, y0 + 1

        def gather_px(xi, yi):
            xi_c = jnp.clip(xi, 0, w - 1)
            yi_c = jnp.clip(yi, 0, h - 1)
            valid = ((xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)).astype(a.dtype)
            # a: n c h w; index per-batch
            batch_idx = jnp.arange(n)[:, None, None]
            px = a[batch_idx, :, yi_c, xi_c]  # n, oh, ow, c
            return px * valid[..., None]

        wa = ((x1 - gx) * (y1 - gy))[..., None]
        wb = ((gx - x0) * (y1 - gy))[..., None]
        wc = ((x1 - gx) * (gy - y0))[..., None]
        wd = ((gx - x0) * (gy - y0))[..., None]
        out = (
            gather_px(x0, y0) * wa + gather_px(x1, y0) * wb
            + gather_px(x0, y1) * wc + gather_px(x1, y1) * wd
        )
        return jnp.moveaxis(out, -1, 1)

    return dispatch(fn, x, op_name="grid_sample")


def affine_grid(theta, out_shape, align_corners=True):
    def fn(th):
        n, _, h, w = [int(s) for s in (_v(out_shape) if isinstance(out_shape, Tensor) else out_shape)]
        if align_corners:
            ys = jnp.linspace(-1, 1, h)
            xs = jnp.linspace(-1, 1, w)
        else:
            ys = (jnp.arange(h) + 0.5) * 2 / h - 1
            xs = (jnp.arange(w) + 0.5) * 2 / w - 1
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        grid = jnp.stack([gx, gy, jnp.ones_like(gx)], axis=-1)  # h w 3
        out = jnp.einsum("hwi,nji->nhwj", grid, th)
        return out

    return dispatch(fn, theta, op_name="affine_grid")


def diag_embed(x, offset=0, dim1=-2, dim2=-1):
    def fn(a):
        n = a.shape[-1]
        out = jnp.zeros(a.shape + (n,), a.dtype)
        idx = jnp.arange(n)
        out = out.at[..., idx, idx].set(a)
        return out

    return dispatch(fn, x, op_name="diag_embed")




# ---------------------------------------------------------------------------
# pooling / conv completions (reference operators/pool_op.cc 3D variants,
# conv_transpose_op.cc 1D/3D)
# ---------------------------------------------------------------------------


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW"):
    def fn(a):
        if data_format == "NDHWC":
            a = jnp.moveaxis(a, -1, 1)
        out = _pool(a, 3, kernel_size, stride, padding, "max")
        if data_format == "NDHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return dispatch(fn, x, op_name="max_pool3d")


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, data_format="NCDHW"):
    def fn(a):
        if data_format == "NDHWC":
            a = jnp.moveaxis(a, -1, 1)
        out = _pool(a, 3, kernel_size, stride, padding, "avg",
                    count_include_pad=count_include_pad)
        if data_format == "NDHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return dispatch(fn, x, op_name="avg_pool3d")


def _adaptive_cells(length, out):
    return [int(math.floor(i * length / out)) for i in range(out + 1)]


def _adaptive_pool_nd(a, sizes, reduce_fn, nd):
    lead = a.shape[:-nd]
    if all(a.shape[-nd + i] % sizes[i] == 0 for i in range(nd)):
        shape = list(lead)
        for i in range(nd):
            shape += [sizes[i], a.shape[len(lead) + i] // sizes[i]]
        r = a.reshape(shape)
        axes = tuple(len(lead) + 2 * i + 1 for i in range(nd))
        return reduce_fn(r, axes)
    # general: per-cell windows (python loops — shapes are static)
    import itertools

    grids = [_adaptive_cells(a.shape[len(lead) + i], sizes[i])
             for i in range(nd)]
    cells = []
    for idx in itertools.product(*(range(s) for s in sizes)):
        sl = tuple(slice(None) for _ in lead) + tuple(
            slice(grids[i][idx[i]], grids[i][idx[i] + 1]) for i in range(nd))
        cells.append(reduce_fn(a[sl], tuple(range(len(lead),
                                                  len(lead) + nd))))
    out = jnp.stack(cells, axis=-1)
    return out.reshape(lead + tuple(sizes))


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW"):
    os3 = _pair(output_size, 3)

    def fn(a):
        if data_format == "NDHWC":
            a = jnp.moveaxis(a, -1, 1)
        out = _adaptive_pool_nd(a, os3, lambda v, ax: v.mean(axis=ax), 3)
        if data_format == "NDHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return dispatch(fn, x, op_name="adaptive_avg_pool3d")


def adaptive_max_pool3d(x, output_size, data_format="NCDHW"):
    os3 = _pair(output_size, 3)

    def fn(a):
        if data_format == "NDHWC":
            a = jnp.moveaxis(a, -1, 1)
        out = _adaptive_pool_nd(a, os3, lambda v, ax: v.max(axis=ax), 3)
        if data_format == "NDHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return dispatch(fn, x, op_name="adaptive_max_pool3d")


def adaptive_max_pool1d(x, output_size, return_mask=False):
    def fn(a):
        return _adaptive_pool_nd(a, [int(output_size)],
                                 lambda v, ax: v.max(axis=ax), 1)

    return dispatch(fn, x, op_name="adaptive_max_pool1d")


def _conv_transpose_impl(a, w, b, stride, padding, output_padding, dilation,
                         groups, nd, chan_last, output_size=None):
    stride_ = _pair(stride, nd)
    dil = _pair(dilation, nd)
    pad_in = _pair(padding, nd)
    opad = _pair(output_padding, nd)
    if chan_last:
        a = jnp.moveaxis(a, -1, 1)
    if output_size is not None:
        # reference semantics: output_size resolves the transposed-conv
        # output ambiguity by choosing output_padding — the two arguments
        # are mutually exclusive (the reference raises on both)
        if any(p != 0 for p in opad):
            raise ValueError(
                "output_padding and output_size may not both be set")
        osz = _pair(output_size, nd)
        opad = []
        for i in range(nd):
            k_eff = (w.shape[2 + i] - 1) * dil[i] + 1
            base = (a.shape[2 + i] - 1) * stride_[i] - 2 * pad_in[i] + k_eff
            extra = int(osz[i]) - base
            if not 0 <= extra < max(1, stride_[i]):
                raise ValueError(
                    f"output_size {osz[i]} unreachable for dim {i}: base "
                    f"{base}, stride {stride_[i]}")
            opad.append(extra)
    kshape = w.shape  # (in, out/groups, k...)
    pads = []
    for i in range(nd):
        k_eff = (kshape[2 + i] - 1) * dil[i] + 1
        pads.append((k_eff - 1 - pad_in[i],
                     k_eff - 1 - pad_in[i] + opad[i]))
    w_flip = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    w_t = jnp.swapaxes(w_flip, 0, 1)
    if groups > 1:
        w_t = jnp.reshape(
            jnp.swapaxes(jnp.reshape(
                w_flip, (groups, kshape[0] // groups) + kshape[1:]), 1, 2),
            (kshape[1] * groups, kshape[0] // groups) + kshape[2:])
    out = jax.lax.conv_general_dilated(
        a, w_t, window_strides=(1,) * nd, padding=pads,
        lhs_dilation=stride_, rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=jax.lax.conv_dimension_numbers(
            a.shape, w_t.shape, _dim_str(nd)))
    if b is not None:
        out = out + b.reshape((1, -1) + (1,) * nd)
    if chan_last:
        out = jnp.moveaxis(out, 1, -1)
    return out


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCL", output_size=None):
    args = (x, weight) + ((bias,) if bias is not None else ())

    def fn(a, w, *b):
        return _conv_transpose_impl(a, w, b[0] if b else None, stride,
                                    padding, output_padding, dilation,
                                    groups, 1, data_format == "NLC",
                                    output_size)

    return dispatch(fn, *args, op_name="conv1d_transpose")


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1,
                     data_format="NCDHW", output_size=None):
    args = (x, weight) + ((bias,) if bias is not None else ())

    def fn(a, w, *b):
        return _conv_transpose_impl(a, w, b[0] if b else None, stride,
                                    padding, output_padding, dilation,
                                    groups, 3, data_format == "NDHWC",
                                    output_size)

    return dispatch(fn, *args, op_name="conv3d_transpose")


# ---------------------------------------------------------------------------
# loss / activation completions (reference warpctc_op, log_loss_op,
# npair_loss, hierarchical_sigmoid_op, maxout_op, thresholded_relu)
# ---------------------------------------------------------------------------


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss (reference warpctc_op) as a pure lax.scan forward DP over
    the standard extended label sequence; differentiable by jax autodiff
    (grad of logsumexp DP == the forward-backward soft alignment).

    log_probs: [T, B, C] raw logits (softmax applied internally, matching
    the reference's warpctc on activations); labels: [B, L] int padded.
    """
    lab = _v(labels)
    in_len = _v(input_lengths).astype(jnp.int32)
    lab_len = _v(label_lengths).astype(jnp.int32)

    def fn(acts):
        T, B, C = acts.shape
        logp = jax.nn.log_softmax(acts.astype(jnp.float32), axis=-1)
        L = lab.shape[1]
        S = 2 * L + 1
        # extended sequence: blank, l1, blank, l2, ... blank
        ext = jnp.full((B, S), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab.astype(jnp.int32))
        neg_inf = jnp.float32(-1e30)
        # allow skip from s-2 when ext[s] != blank and ext[s] != ext[s-2]
        can_skip = jnp.concatenate(
            [jnp.zeros((B, 2), bool),
             (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2])], axis=1)
        alpha0 = jnp.full((B, S), neg_inf)
        alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
        first_lab = jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0]
        alpha0 = alpha0.at[:, 1].set(
            jnp.where(lab_len > 0, first_lab, neg_inf))

        def step(alpha, lp_t):
            prev1 = jnp.concatenate(
                [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            prev2 = jnp.concatenate(
                [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
            prev2 = jnp.where(can_skip, prev2, neg_inf)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, prev1), prev2)
            emit = jnp.take_along_axis(lp_t, ext, axis=1)
            return merged + emit, merged + emit

        _, alphas = jax.lax.scan(step, alpha0, logp[1:])
        alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # [T,B,S]
        # per-sample: read alpha at t = in_len-1, s in {2*lab_len, 2*lab_len-1}
        t_idx = jnp.clip(in_len - 1, 0, T - 1)
        a_T = alphas[t_idx, jnp.arange(B)]  # [B, S]
        s_last = jnp.clip(2 * lab_len, 0, S - 1)
        s_prev = jnp.clip(2 * lab_len - 1, 0, S - 1)
        ll = jnp.logaddexp(
            jnp.take_along_axis(a_T, s_last[:, None], 1)[:, 0],
            jnp.where(lab_len > 0,
                      jnp.take_along_axis(a_T, s_prev[:, None], 1)[:, 0],
                      neg_inf))
        loss = -ll
        if reduction == "mean":
            return (loss / jnp.maximum(lab_len, 1)).mean()
        if reduction == "sum":
            return loss.sum()
        return loss

    return dispatch(fn, log_probs, op_name="ctc_loss")


def log_loss(input, label, epsilon=1e-4):
    def fn(p, y):
        p = jnp.clip(p, epsilon, 1 - epsilon)
        return -y * jnp.log(p) - (1 - y) * jnp.log(1 - p)

    return dispatch(fn, input, label, op_name="log_loss")


def dice_loss(input, label, epsilon=1e-5):
    def fn(p, y):
        yh = jax.nn.one_hot(y.squeeze(-1), p.shape[-1], dtype=p.dtype)
        inter = (p * yh).sum(axis=tuple(range(1, p.ndim)))
        union = p.sum(axis=tuple(range(1, p.ndim))) + yh.sum(
            axis=tuple(range(1, p.ndim)))
        return (1 - (2 * inter + epsilon) / (union + epsilon)).mean()

    return dispatch(fn, input, label, op_name="dice_loss")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def fn(a, p):
        logits = a @ p.T  # [B, B]
        y = _v(labels).reshape(-1)
        same = (y[:, None] == y[None, :]).astype(a.dtype)
        tgt = same / same.sum(-1, keepdims=True)
        ce = (-tgt * jax.nn.log_softmax(logits, -1)).sum(-1).mean()
        reg = l2_reg * ((a * a).sum(-1) + (p * p).sum(-1)).mean() / 2
        return ce + reg

    return dispatch(fn, anchor, positive, op_name="npair_loss")


@functools.lru_cache(maxsize=64)
def _hsigmoid_paths(num_classes: int):
    """Root-to-leaf paths in the complete binary tree with `num_classes`
    leaves and num_classes-1 internal nodes (heap layout: internal node i
    has children 2i+1, 2i+2; node >= num_classes-1 is leaf num=node-(C-1)).
    Returns (nodes [C, D], codes [C, D], mask [C, D]) numpy constants."""
    C = num_classes
    paths, codes = [], []
    for y in range(C):
        node = y + C - 1  # leaf position in the full heap
        p, cds = [], []
        while node > 0:
            parent = (node - 1) // 2
            cds.append(node == 2 * parent + 2)  # right child → bit 1
            p.append(parent)
            node = parent
        paths.append(p[::-1])
        codes.append(cds[::-1])
    D = max(len(p) for p in paths)
    nodes = np.zeros((C, D), np.int32)
    bits = np.zeros((C, D), np.float32)
    mask = np.zeros((C, D), np.float32)
    for y in range(C):
        L = len(paths[y])
        nodes[y, :L] = paths[y]
        bits[y, :L] = codes[y]
        mask[y, :L] = 1.0
    return nodes, bits, mask


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False):
    """Hierarchical sigmoid over the complete binary tree with num_classes
    leaves (reference hierarchical_sigmoid_op default-path mode): per-class
    root→leaf node/code paths are exact precomputed constants, so the loss
    normalizes over classes for any num_classes (not only powers of two)."""
    nodes_np, bits_np, mask_np = _hsigmoid_paths(int(num_classes))

    def fn(x, w, *b):
        y = _v(label).reshape(-1)
        nodes = jnp.asarray(nodes_np)[y]  # [B, D]
        bits = jnp.asarray(bits_np)[y]
        mask = jnp.asarray(mask_np)[y]
        wn = w[nodes]  # [B, D, dim]
        logit = (x[:, None, :] * wn).sum(-1)  # [B, D]
        if b:
            logit = logit + b[0].reshape(-1)[nodes]
        # bit==1 → sigmoid(logit); bit==0 → 1-sigmoid; masked steps 0
        nll = (jax.nn.softplus(logit) - bits * logit) * mask
        return nll.sum(-1).mean()

    args = (input, weight) + ((bias,) if bias is not None else ())
    return dispatch(fn, *args, op_name="hsigmoid_loss")


def maxout(x, groups, axis=1):
    def fn(a):
        ax = axis if axis >= 0 else a.ndim + axis
        c = a.shape[ax]
        shape = list(a.shape)
        shape[ax:ax + 1] = [c // groups, groups]
        return a.reshape(shape).max(axis=ax + 1)

    return dispatch(fn, x, op_name="maxout")


def thresholded_relu(x, threshold=1.0):
    return dispatch(lambda a: jnp.where(a > threshold, a, 0.0), x,
                    op_name="thresholded_relu")


def gather_tree(ids, parents):
    """Beam-search backtrace (reference gather_tree_op): follow parent
    pointers from the last step to assemble full beams. [T, B, W] ids."""
    idv = _v(ids)
    pv = _v(parents)
    T = idv.shape[0]

    def step(nxt_parent, t):
        ids_t = idv[t]
        par_t = pv[t]
        sel = jnp.take_along_axis(ids_t, nxt_parent, axis=1)
        new_parent = jnp.take_along_axis(par_t, nxt_parent, axis=1)
        return new_parent, sel

    init = jnp.broadcast_to(jnp.arange(idv.shape[2], dtype=pv.dtype)[None],
                            idv.shape[1:])
    _, out = jax.lax.scan(step, init, jnp.arange(T - 1, -1, -1))
    return Tensor(out[::-1])


def _inplace_apply(name, x, fn):
    """Snapshot-based in-place (same discipline as tensor_api._inplace: the
    recorded tape edge must point upstream, never at x itself)."""
    from ...core import autograd as _ag

    if (isinstance(x, Tensor) and not x.stop_gradient and x._node is None
            and _ag.is_grad_enabled()):
        raise RuntimeError(
            f"{name}: a leaf Tensor that requires grad cannot be used in an "
            "in-place operation")
    snap = Tensor(x._value, stop_gradient=x.stop_gradient)
    snap._node = x._node
    snap._out_index = x._out_index
    out = fn(snap)
    x._value = out.value
    x._node, x._out_index = out._node, out._out_index
    x.stop_gradient = out.stop_gradient
    return x


def elu_(x, alpha=1.0):
    return _inplace_apply("elu_", x, lambda s: elu(s, alpha))


def relu_(x):
    return _inplace_apply("relu_", x, relu)


def softmax_(x, axis=-1):
    return _inplace_apply("softmax_", x, lambda s: softmax(s, axis))


def tanh_(x):
    return _inplace_apply("tanh_", x, tanh)



# ---------------------------------------------------------------------------
# static-graph duality: wrap every public op so calls on static Variables
# record into the active Program (core/static_mode.py) — one implementation
# serves dygraph, jit, and Program/Executor modes.
# ---------------------------------------------------------------------------
def _wrap_for_static():
    import sys as _sys
    import types as _types

    from ...core.static_mode import static_aware as _sa

    mod = _sys.modules[__name__]
    for name in list(vars(mod)):
        f = getattr(mod, name)
        if (isinstance(f, _types.FunctionType) and not name.startswith("_")
                and f.__module__ == __name__):
            setattr(mod, name, _sa(f))


_wrap_for_static()
