"""paddle_tpu.nn.functional — functional NN ops.

Reference surface: upstream python/paddle/nn/functional/ (unverified, see
SURVEY.md §2.2). Everything lowers to jax/XLA; convolutions and matmuls hit
the MXU, elementwise ops fuse into them. AMP hooks at the op level.
"""
from __future__ import annotations

import math as _math

import jax
import jax.numpy as jnp
import numpy as np

from ...core.autograd import apply, is_grad_enabled
from ...core.random import next_key
from ...core.tensor import Tensor
from ...ops._base import amp_autocast, ensure_tensor

# ---------------------------------------------------------------------------
# activations


def _unary(jfn, name):
    def f(x, name_=None):
        return apply(jfn, ensure_tensor(x), name=name)
    f.__name__ = name
    return f


relu = _unary(jax.nn.relu, "relu")
relu6 = _unary(jax.nn.relu6, "relu6")
sigmoid = _unary(jax.nn.sigmoid, "sigmoid")
tanh = _unary(jnp.tanh, "tanh")
silu = _unary(jax.nn.silu, "silu")
swish = silu
mish = _unary(lambda a: a * jnp.tanh(jax.nn.softplus(a)), "mish")
hardswish = _unary(jax.nn.hard_swish, "hardswish")
hardsigmoid = _unary(lambda a: jnp.clip(a / 6.0 + 0.5, 0.0, 1.0),
                     "hardsigmoid")
softsign = _unary(jax.nn.soft_sign, "softsign")
tanhshrink = _unary(lambda a: a - jnp.tanh(a), "tanhshrink")


def relu_(x):
    from ...ops.indexing import inplace_rebind
    return inplace_rebind(x, relu)


def gelu(x, approximate=False, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jax.nn.gelu(a, approximate=approximate), x,
                 name="gelu")


def leaky_relu(x, negative_slope=0.01, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jax.nn.leaky_relu(a, negative_slope), x,
                 name="leaky_relu")


def elu(x, alpha=1.0, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jax.nn.elu(a, alpha), x, name="elu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: scale * jnp.where(a > 0, a,
                                             alpha * jnp.expm1(a)), x,
                 name="selu")


def celu(x, alpha=1.0, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jax.nn.celu(a, alpha), x, name="celu")


def prelu(x, weight, data_format="NCHW", name=None):
    x, weight = ensure_tensor(x), ensure_tensor(weight)

    def f(a, w):
        if w.size > 1:
            shape = [1] * a.ndim
            ch_axis = 1 if data_format[1] == "C" else a.ndim - 1
            shape[ch_axis] = w.size
            w = w.reshape(shape)
        return jnp.where(a > 0, a, w * a)
    return apply(f, x, weight, name="prelu")


def hardtanh(x, min=-1.0, max=1.0, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jnp.clip(a, min, max), x, name="hardtanh")


def hardshrink(x, threshold=0.5, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0), x,
                 name="hardshrink")


def softshrink(x, threshold=0.5, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jnp.where(a > threshold, a - threshold,
                                     jnp.where(a < -threshold, a + threshold,
                                               0.0)), x, name="softshrink")


def softplus(x, beta=1.0, threshold=20.0, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jnp.where(a * beta > threshold, a,
                                     jax.nn.softplus(a * beta) / beta), x,
                 name="softplus")


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jnp.where(a > threshold, a, value), x,
                 name="thresholded_relu")


def maxout(x, groups, axis=1, name=None):
    x = ensure_tensor(x)

    def f(a):
        ax = axis % a.ndim
        c = a.shape[ax]
        new_shape = a.shape[:ax] + (c // groups, groups) + a.shape[ax + 1:]
        return jnp.max(a.reshape(new_shape), axis=ax + 1)
    return apply(f, x, name="maxout")


def softmax(x, axis=-1, dtype=None, name=None):
    x = ensure_tensor(x)
    if dtype is not None:
        x = x.astype(dtype)
    return apply(lambda a: jax.nn.softmax(a, axis=axis), x, name="softmax")


def log_softmax(x, axis=-1, dtype=None, name=None):
    x = ensure_tensor(x)
    if dtype is not None:
        x = x.astype(dtype)
    return apply(lambda a: jax.nn.log_softmax(a, axis=axis), x,
                 name="log_softmax")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    x = ensure_tensor(x)
    k = next_key()

    def f(a):
        g = jax.random.gumbel(k, a.shape, a.dtype)
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.zeros_like(y)
            y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis,
                                        inplace=False)
            y = y_hard - jax.lax.stop_gradient(y) + y
        return y
    return apply(f, x, name="gumbel_softmax")


def glu(x, axis=-1, name=None):
    x = ensure_tensor(x)
    return apply(lambda a: jax.nn.glu(a, axis=axis), x, name="glu")

# ---------------------------------------------------------------------------
# linear / embedding


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b. NOTE reference weight layout: [in_features, out_features]."""
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    x, weight = amp_autocast((x, weight), "matmul")
    if bias is None:
        return apply(lambda a, w: jnp.matmul(a, w), x, weight, name="linear")
    bias = ensure_tensor(bias)
    (bias,) = amp_autocast((bias,), "matmul")
    return apply(lambda a, w, b: jnp.matmul(a, w) + b, x, weight, bias,
                 name="linear")


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    x, weight = ensure_tensor(x), ensure_tensor(weight)

    def f(w, i):
        out = jnp.take(w, i, axis=0)
        if padding_idx is not None:
            mask = (i == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out
    return apply(f, weight, x.detach(), name="embedding")


def one_hot(x, num_classes, name=None):
    x = ensure_tensor(x)
    return Tensor(jax.nn.one_hot(x._data, num_classes))


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    label = ensure_tensor(label)
    k = label.shape[-1]

    def f(lab):
        if prior_dist is not None:
            return (1 - epsilon) * lab + epsilon * jnp.asarray(
                prior_dist._data if isinstance(prior_dist, Tensor)
                else prior_dist)
        return (1 - epsilon) * lab + epsilon / k
    return apply(f, label, name="label_smooth")

# ---------------------------------------------------------------------------
# convolution (NCHW default, matching the reference)


def _conv_nd(x, weight, bias, stride, padding, dilation, groups,
             data_format, nd, name):
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    x, weight = amp_autocast((x, weight), "conv")
    stride = (stride,) * nd if isinstance(stride, int) else tuple(stride)
    dilation = (dilation,) * nd if isinstance(dilation, int) \
        else tuple(dilation)

    if isinstance(padding, str):
        pad = padding.upper()  # 'SAME' | 'VALID'
    elif isinstance(padding, int):
        pad = [(padding, padding)] * nd
    else:
        padding = list(padding)
        if len(padding) == nd:
            pad = [(int(p), int(p)) for p in padding]
        else:  # pairs
            pad = [(int(padding[2 * i]), int(padding[2 * i + 1]))
                   for i in range(nd)]

    if data_format in ("NCHW", "NCL", "NCDHW"):
        lhs_spec = "NC" + "DHW"[3 - nd:]
    else:
        lhs_spec = "N" + "DHW"[3 - nd:] + "C"
    rhs_spec = "OI" + "DHW"[3 - nd:]
    out_spec = lhs_spec
    dn = jax.lax.conv_dimension_numbers(
        tuple(x.shape), tuple(weight.shape), (lhs_spec, rhs_spec, out_spec))

    def f(a, w, *b):
        out = jax.lax.conv_general_dilated(
            a, w, window_strides=stride, padding=pad,
            rhs_dilation=dilation, dimension_numbers=dn,
            feature_group_count=groups,
            preferred_element_type=a.dtype)
        if b:
            bias_shape = [1] * out.ndim
            c_axis = lhs_spec.index("C")
            bias_shape[c_axis] = b[0].shape[0]
            out = out + b[0].reshape(bias_shape)
        return out
    if bias is not None:
        bias = ensure_tensor(bias)
        (bias,) = amp_autocast((bias,), "conv")
        return apply(f, x, weight, bias, name=name)
    return apply(f, x, weight, name=name)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 1, "conv1d")


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 2, "conv2d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups,
                    data_format, 3, "conv3d")


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCHW", name=None):
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    x, weight = amp_autocast((x, weight), "conv")
    nd = 2
    stride = (stride,) * nd if isinstance(stride, int) else tuple(stride)
    dilation = (dilation,) * nd if isinstance(dilation, int) \
        else tuple(dilation)
    if isinstance(padding, int):
        pads = [(padding, padding)] * nd
    elif isinstance(padding, str):
        pads = padding.upper()
    else:
        pads = [(int(p), int(p)) for p in padding]
    opad = (output_padding,) * nd if isinstance(output_padding, int) \
        else tuple(output_padding)
    lhs_spec = "NCHW" if data_format == "NCHW" else "NHWC"
    # (dimension numbers are built inside f from the TRANSFORMED
    # kernel's OIHW layout)
    if output_size is not None and isinstance(pads, str):
        raise NotImplementedError(
            "conv2d_transpose: output_size with string padding is not "
            "supported (the implied output_padding needs explicit "
            "pad amounts)")
    if output_size is not None:
        # reference semantics: output_size picks the output_padding
        # implied by out = (in-1)*s - 2p + d(k-1) + 1 + opad
        sp = [lhs_spec.index(c) for c in "HW"]
        osize = (output_size,) * nd if isinstance(output_size, int) \
            else tuple(int(s) for s in output_size)
        opad = tuple(
            osize[i] - ((x.shape[sp[i]] - 1) * stride[i]
                        - pads[i][0] - pads[i][1]
                        + dilation[i] * (weight.shape[2 + i] - 1) + 1)
            for i in range(nd))
        if any(o < 0 or o >= stride[i] for i, o in enumerate(opad)):
            raise ValueError(
                f"output_size {osize} unreachable for this "
                f"stride/padding/kernel (implied output_padding "
                f"{opad})")

    def f(a, w, *b):
        if isinstance(pads, str):
            pad_cfg = pads
        else:
            # transpose conv padding: SAME-style inverse of forward
            # padding; output_padding extends the HIGH side
            pad_cfg = [
                (dilation[i] * (w.shape[2 + i] - 1) - pads[i][0],
                 dilation[i] * (w.shape[2 + i] - 1) - pads[i][1]
                 + opad[i])
                for i in range(nd)]
        # Kernel transpose done manually (jax 0.9 dropped the
        # transpose_kernel kwarg): the transposed conv IS a forward
        # conv on the stride-dilated input with the kernel spatially
        # FLIPPED and its in/out axes swapped. Reference weight layout
        # is [in, out/groups, kh, kw]; the equivalent forward-conv
        # kernel is [out, in/groups, kh, kw] (grouped swap).
        cin, cog = w.shape[0], w.shape[1]
        wt = w.reshape((groups, cin // groups, cog) + w.shape[2:])
        wt = jnp.swapaxes(wt, 1, 2).reshape(
            (groups * cog, cin // groups) + w.shape[2:])
        wt = wt[:, :, ::-1, ::-1]
        out = jax.lax.conv_general_dilated(
            a, wt, window_strides=(1, 1), padding=pad_cfg,
            lhs_dilation=stride, rhs_dilation=dilation,
            dimension_numbers=jax.lax.conv_dimension_numbers(
                a.shape, wt.shape, (lhs_spec, "OIHW", lhs_spec)),
            feature_group_count=groups)
        if b:
            c_axis = lhs_spec.index("C")
            shape = [1] * out.ndim
            shape[c_axis] = b[0].shape[0]
            out = out + b[0].reshape(shape)
        return out
    if bias is not None:
        bias = ensure_tensor(bias)
        (bias,) = amp_autocast((bias,), "conv")
        return apply(f, x, weight, bias, name="conv2d_transpose")
    return apply(f, x, weight, name="conv2d_transpose")

# ---------------------------------------------------------------------------
# pooling (NCHW)


def _pool2d(x, kernel, stride, padding, reducer, init, ceil_mode, mean_div,
            name, exclusive=True, data_format="NCHW",
            divisor_override=None):
    if data_format != "NCHW":
        raise NotImplementedError(
            f"{name}: data_format={data_format!r} is not supported "
            "(NCHW only — a silent NHWC pool would reduce W and C "
            "together)")
    if divisor_override is not None:
        raise NotImplementedError(
            f"{name}: divisor_override is not supported")
    x = ensure_tensor(x)
    k = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
    stride = stride or k
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    if isinstance(padding, int):
        p = [(0, 0), (0, 0), (padding, padding), (padding, padding)]
    elif isinstance(padding, str):
        p = padding.upper()
        if ceil_mode:
            raise NotImplementedError(
                f"{name}: ceil_mode with string padding is not "
                "supported")
    else:
        p = [(0, 0), (0, 0)] + [(int(a), int(a)) for a in padding]
    if ceil_mode and not isinstance(p, str):
        # include the last partial window (reference/torch semantics):
        # extend the HIGH pad so out = ceil((size+2p-k)/s)+1, clamped so
        # the last window still STARTS inside input+pad_low. Extra pad
        # uses `init` (max: -inf) and contributes 0 to the avg count —
        # exactly the exclusive divisor the reference uses.
        for i in (0, 1):
            size = int(x.shape[2 + i])
            lo, hi = p[2 + i]
            span = size + lo + hi - k[i]
            out_floor = span // s[i] + 1
            out_ceil = -(-span // s[i]) + 1
            if out_ceil > out_floor and \
                    (out_ceil - 1) * s[i] < size + lo:
                p[2 + i] = (lo, hi + (out_ceil - 1) * s[i] + k[i]
                            - size - lo - hi)

    def f(a):
        window = (1, 1) + k
        strides = (1, 1) + s
        pad_cfg = p
        out = jax.lax.reduce_window(a, init, reducer, window, strides,
                                    pad_cfg)
        if mean_div:
            if exclusive:  # divide by the VALID element count
                ones = jnp.ones_like(a)
                cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add,
                                            window, strides, pad_cfg)
            else:          # reference exclusive=False: full window size
                cnt = float(k[0] * k[1])
            out = out / cnt
        return out
    return apply(f, x, name=name)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    if return_mask:
        return _max_pool2d_with_mask(x, kernel_size, stride, padding,
                                     ceil_mode)
    return _pool2d(x, kernel_size, stride, padding, jax.lax.max,
                   -jnp.inf, ceil_mode, False, "max_pool2d",
                   data_format=data_format)


def _max_pool2d_with_mask(x, kernel_size, stride, padding, ceil_mode):
    """(out, indices) — indices are flat per-channel H·W argmax positions
    (the reference's max_unpool convention). Patch extraction is a pair
    of static gathers; use the maskless path when indices aren't needed
    (it lowers to reduce_window)."""
    if ceil_mode:
        raise NotImplementedError("max_pool2d(return_mask=True) with "
                                  "ceil_mode is not supported")
    if isinstance(padding, str):
        raise NotImplementedError(
            f"max_pool2d(return_mask=True) with padding={padding!r}; "
            "use integer padding on the mask path")
    x = ensure_tensor(x)
    t2 = lambda v: (v, v) if isinstance(v, int) else tuple(v)
    kh, kw = t2(kernel_size)
    sh, sw = t2(stride if stride is not None else kernel_size)
    ph, pw = t2(padding)

    def f(a):
        N, C, H, W = a.shape
        ap = jnp.pad(a, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                     constant_values=-jnp.inf)
        oh = (H + 2 * ph - kh) // sh + 1
        ow = (W + 2 * pw - kw) // sw + 1
        hidx = jnp.arange(oh)[:, None] * sh + jnp.arange(kh)[None, :]
        widx = jnp.arange(ow)[:, None] * sw + jnp.arange(kw)[None, :]
        p1 = ap[:, :, hidx, :]                 # [N, C, OH, kh, Wp]
        p2 = p1[:, :, :, :, widx]              # [N, C, OH, kh, OW, kw]
        patches = p2.transpose(0, 1, 2, 4, 3, 5).reshape(
            N, C, oh, ow, kh * kw)
        out = jnp.max(patches, axis=-1)
        am = jnp.argmax(patches, axis=-1)
        r, c = am // kw, am % kw
        habs = jnp.arange(oh)[None, None, :, None] * sh + r - ph
        wabs = jnp.arange(ow)[None, None, None, :] * sw + c - pw
        flat = (habs * W + wabs).astype(jnp.int32)
        return out, flat

    out, mask = apply(f, x, name="max_pool2d_mask")
    return out, mask.detach()


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW",
               name=None):
    return _pool2d(x, kernel_size, stride, padding, jax.lax.add, 0.0,
                   ceil_mode, True, "avg_pool2d", exclusive=exclusive,
                   data_format=data_format,
                   divisor_override=divisor_override)


def max_pool1d(x, kernel_size, stride=None, padding=0, return_mask=False,
               ceil_mode=False, name=None):
    x = ensure_tensor(x)
    if return_mask:
        # W=1 window: the 2d flat H·W index IS the sequence position
        out, mask = max_pool2d(
            x.unsqueeze(-1), (kernel_size, 1), (stride or kernel_size, 1),
            (padding, 0) if isinstance(padding, int) else padding,
            ceil_mode=ceil_mode, return_mask=True)
        return out.squeeze(-1), mask.squeeze(-1)
    out = max_pool2d(x.unsqueeze(-1), (kernel_size, 1),
                     (stride or kernel_size, 1),
                     (padding, 0) if isinstance(padding, int) else padding,
                     ceil_mode=ceil_mode)
    return out.squeeze(-1)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    x = ensure_tensor(x)
    out = avg_pool2d(x.unsqueeze(-1), (kernel_size, 1),
                     (stride or kernel_size, 1),
                     (padding, 0) if isinstance(padding, int) else padding,
                     ceil_mode=ceil_mode, exclusive=exclusive)
    return out.squeeze(-1)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    x = ensure_tensor(x)
    os = (output_size, output_size) if isinstance(output_size, int) \
        else tuple(output_size)

    def f(a):
        h, w = a.shape[-2], a.shape[-1]
        oh, ow = os
        if h % oh == 0 and w % ow == 0:
            a2 = a.reshape(a.shape[:-2] + (oh, h // oh, ow, w // ow))
            return jnp.mean(a2, axis=(-3, -1))
        # general case: interpolate bin edges
        out = jax.image.resize(a, a.shape[:-2] + (oh, ow), method="linear")
        return out
    return apply(f, x, name="adaptive_avg_pool2d")


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError(
            "adaptive_max_pool2d(return_mask=True) is not supported; "
            "use max_pool2d(return_mask=True) for unpooling indices")
    x = ensure_tensor(x)
    os = (output_size, output_size) if isinstance(output_size, int) \
        else tuple(output_size)

    def f(a):
        h, w = a.shape[-2], a.shape[-1]
        oh, ow = os
        a2 = a.reshape(a.shape[:-2] + (oh, h // oh, ow, w // ow))
        return jnp.max(a2, axis=(-3, -1))
    return apply(f, x, name="adaptive_max_pool2d")


def adaptive_avg_pool1d(x, output_size, name=None):
    x = ensure_tensor(x)
    out = adaptive_avg_pool2d(x.unsqueeze(-1), (output_size, 1))
    return out.squeeze(-1)

# ---------------------------------------------------------------------------
# normalization


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    x = ensure_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    nd = len(tuple(normalized_shape))
    axes = tuple(range(x.ndim - nd, x.ndim))

    def f(a, *wb):
        mu = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = (a.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(a.dtype)
        if len(wb) == 2:
            return out * wb[0] + wb[1]
        if len(wb) == 1:
            return out * wb[0]
        return out
    args = [t for t in (weight, bias) if t is not None]
    return apply(f, x, *[ensure_tensor(t) for t in args], name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    x = ensure_tensor(x)

    def f(a, *w):
        a32 = a.astype(jnp.float32)
        ms = jnp.mean(a32 * a32, axis=-1, keepdims=True)
        out = (a32 * jax.lax.rsqrt(ms + epsilon)).astype(a.dtype)
        return out * w[0] if w else out
    args = [ensure_tensor(weight)] if weight is not None else []
    return apply(f, x, *args, name="rms_norm")


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    x = ensure_tensor(x)
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != c_axis)

    use_batch_stats = training and not use_global_stats

    def stats_shape(a):
        shape = [1] * a.ndim
        shape[c_axis] = a.shape[c_axis]
        return shape

    if use_batch_stats:
        def f(a, *wb):
            a32 = a.astype(jnp.float32)
            mu = jnp.mean(a32, axis=reduce_axes)
            var = jnp.var(a32, axis=reduce_axes)
            shape = stats_shape(a)
            out = (a32 - mu.reshape(shape)) * jax.lax.rsqrt(
                var.reshape(shape) + epsilon)
            out = out.astype(a.dtype)
            if len(wb) == 2:
                out = out * wb[0].reshape(shape) + wb[1].reshape(shape)
            return out
        args = [ensure_tensor(t) for t in (weight, bias) if t is not None]
        out = apply(f, x, *args, name="batch_norm")
        # update running stats in place (buffers)
        a32 = x._data.astype(jnp.float32)
        mu = jnp.mean(a32, axis=reduce_axes)
        var = jnp.var(a32, axis=reduce_axes)
        running_mean._inplace_update(
            (momentum * running_mean._data + (1 - momentum) * mu)
            .astype(running_mean._data.dtype))
        running_var._inplace_update(
            (momentum * running_var._data + (1 - momentum) * var)
            .astype(running_var._data.dtype))
        return out

    def g(a, rm, rv, *wb):
        shape = stats_shape(a)
        out = (a.astype(jnp.float32) - rm.reshape(shape)) * jax.lax.rsqrt(
            rv.reshape(shape) + epsilon)
        out = out.astype(a.dtype)
        if len(wb) == 2:
            out = out * wb[0].reshape(shape) + wb[1].reshape(shape)
        return out
    args = [ensure_tensor(t) for t in (weight, bias) if t is not None]
    return apply(g, x, ensure_tensor(running_mean).detach(),
                 ensure_tensor(running_var).detach(), *args,
                 name="batch_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = ensure_tensor(x)

    def f(a, *wb):
        n, c = a.shape[0], a.shape[1]
        g = num_groups
        a2 = a.reshape((n, g, c // g) + a.shape[2:])
        axes = tuple(range(2, a2.ndim))
        mu = jnp.mean(a2, axis=axes, keepdims=True)
        var = jnp.var(a2, axis=axes, keepdims=True)
        out = ((a2 - mu) * jax.lax.rsqrt(var + epsilon)).reshape(a.shape)
        if len(wb) == 2:
            shape = [1, c] + [1] * (a.ndim - 2)
            out = out * wb[0].reshape(shape) + wb[1].reshape(shape)
        return out
    args = [ensure_tensor(t) for t in (weight, bias) if t is not None]
    return apply(f, x, *args, name="group_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    x = ensure_tensor(x)

    def f(a, *wb):
        axes = tuple(range(2, a.ndim))
        mu = jnp.mean(a, axis=axes, keepdims=True)
        var = jnp.var(a, axis=axes, keepdims=True)
        out = (a - mu) * jax.lax.rsqrt(var + eps)
        if len(wb) == 2:
            shape = [1, a.shape[1]] + [1] * (a.ndim - 2)
            out = out * wb[0].reshape(shape) + wb[1].reshape(shape)
        return out
    args = [ensure_tensor(t) for t in (weight, bias) if t is not None]
    return apply(f, x, *args, name="instance_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    x = ensure_tensor(x)

    def f(a):
        n = jnp.sum(jnp.abs(a) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return a / jnp.maximum(n, epsilon)
    return apply(f, x, name="normalize")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = ensure_tensor(x)

    def f(a):
        sq = a * a
        half = size // 2
        c = a.shape[1]
        pad = jnp.pad(sq, [(0, 0), (half, size - 1 - half)] +
                      [(0, 0)] * (a.ndim - 2))
        acc = sum(pad[:, i:i + c] for i in range(size))
        # reference semantics (and torch's): alpha scales the window
        # MEAN, not the raw sum — paddle computes the window term via
        # avg_pool, i.e. divides by `size`
        return a / (k + alpha * acc / size) ** beta
    return apply(f, x, name="local_response_norm")

# ---------------------------------------------------------------------------
# dropout


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    x = ensure_tensor(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply(lambda a: a * (1 - p), x, name="dropout")
        return x
    k = next_key()

    def f(a):
        shape = list(a.shape)
        if axis is not None:
            axes = axis if isinstance(axis, (list, tuple)) else [axis]
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(k, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)
    return apply(f, x, name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axes = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, axis=axes, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axes = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, axis=axes, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None,
                  channelwise=False):
    """SELU-preserving dropout. channelwise=True drops whole feature
    channels (axis 1) — the FeatureAlphaDropout semantics — with the
    same affine correction (ONE copy of the SELU constants)."""
    x = ensure_tensor(x)
    if not 0 <= p < 1:  # validate BEFORE the eval-mode early return
        raise ValueError(f"p must be in [0, 1), got {p}")
    if not training or p == 0.0:
        return x
    k = next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    neg = -alpha * scale

    def f(a):
        shape = a.shape if not channelwise else \
            tuple(a.shape[:2]) + (1,) * (a.ndim - 2)
        keep = jax.random.bernoulli(k, 1.0 - p, shape)
        q = 1.0 - p
        a_coef = (q + neg ** 2 * q * p) ** -0.5
        b_coef = -a_coef * p * neg
        return (a_coef * jnp.where(keep, a, neg) + b_coef).astype(a.dtype)
    return apply(f, x, name="alpha_dropout")


def feature_alpha_dropout(x, p=0.5, training=True, name=None):
    return alpha_dropout(x, p=p, training=training, channelwise=True)

# ---------------------------------------------------------------------------
# losses (functional)


def mse_loss(input, label, reduction="mean", name=None):
    input, label = ensure_tensor(input), ensure_tensor(label)
    return _reduce_loss(
        apply(lambda a, b: (a - b) ** 2, input, label, name="mse_loss"),
        reduction)


def l1_loss(input, label, reduction="mean", name=None):
    input, label = ensure_tensor(input), ensure_tensor(label)
    return _reduce_loss(
        apply(lambda a, b: jnp.abs(a - b), input, label, name="l1_loss"),
        reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    input, label = ensure_tensor(input), ensure_tensor(label)

    def f(a, b):
        d = a - b
        ad = jnp.abs(d)
        return jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
    return _reduce_loss(apply(f, input, label, name="smooth_l1"), reduction)


def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """Reference parity: paddle.nn.functional.cross_entropy (softmax+NLL
    fused — the fused GPU kernel maps to one XLA fusion on TPU)."""
    input = ensure_tensor(input)
    label = ensure_tensor(label)
    w = ensure_tensor(weight) if weight is not None else None

    if soft_label:
        def f(a, lab, *wt):
            logp = jax.nn.log_softmax(a, axis=axis) if use_softmax \
                else jnp.log(jnp.clip(a, 1e-30, None))
            loss = -jnp.sum(lab * logp, axis=axis)
            return loss
        loss = apply(f, input, label, name="cross_entropy")
        return _reduce_loss(loss, reduction)

    def f(a, li):
        if label_smoothing > 0.0:
            n = a.shape[axis]
            logp = jax.nn.log_softmax(a, axis=axis) if use_softmax \
                else jnp.log(jnp.clip(a, 1e-30, None))
            onehot = jax.nn.one_hot(li, n, axis=axis, dtype=logp.dtype)
            smooth = onehot * (1 - label_smoothing) + label_smoothing / n
            loss = -jnp.sum(smooth * logp, axis=axis)
        else:
            logp = jax.nn.log_softmax(a, axis=axis) if use_softmax \
                else jnp.log(jnp.clip(a, 1e-30, None))
            li_ = jnp.expand_dims(li, axis)
            safe = jnp.where(li_ == ignore_index, 0, li_)
            loss = -jnp.take_along_axis(logp, safe, axis=axis)
            loss = jnp.squeeze(loss, axis)
        mask = (li != ignore_index)
        loss = jnp.where(mask, loss, 0.0)
        return loss, mask

    lab = label.detach()
    if lab._data.ndim == input._data.ndim:
        lab = lab.squeeze(axis)
    lab = lab.astype(jnp.int32)
    loss, mask = apply(f, input, lab, name="cross_entropy")
    mask = mask.detach()
    if w is not None:
        # safe gather: ignore_index is out of bounds and jnp.take's
        # fill mode would inject NaN (0·NaN poisons the masked row)
        wt = apply(lambda ww, li: jnp.take(
            ww, jnp.where(li == ignore_index, 0, li), axis=0),
            w, lab, name="ce_weight")
        wt = wt * mask.astype(wt.dtype)
        loss = loss * wt
        if reduction == "mean":
            return loss.sum() / wt.sum()
    if reduction == "mean":
        denom = mask.astype(loss.dtype).sum()
        return loss.sum() / denom
    if reduction == "sum":
        return loss.sum()
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    return _nll_impl(ensure_tensor(input), label, weight, ignore_index,
                     reduction)


def _nll_impl(input, label, weight, ignore_index, reduction):
    label = ensure_tensor(label).detach().astype(jnp.int32)

    def f(a, li):
        li_ = jnp.expand_dims(li, 1)
        safe = jnp.where(li_ == ignore_index, 0, li_)
        loss = -jnp.take_along_axis(a, safe, axis=1)
        loss = jnp.squeeze(loss, 1)
        mask = (li != ignore_index)
        return jnp.where(mask, loss, 0.0), mask
    loss, mask = apply(f, input, label, name="nll_loss")
    mask = mask.detach()
    if weight is not None:
        w = ensure_tensor(weight)
        # gather weights at a SAFE index: ignore_index (-100) is out of
        # bounds, and jnp.take's fill mode would yield NaN, which then
        # poisons the masked-out row's 0·NaN product
        wt = apply(lambda ww, li: jnp.take(
            ww, jnp.where(li == ignore_index, 0, li), axis=0),
            w, label)
        wt = wt * mask.astype(wt.dtype)
        loss = loss * wt
        if reduction == "mean":
            return loss.sum() / wt.sum()
    if reduction == "mean":
        return loss.sum() / mask.astype(loss.dtype).sum()
    if reduction == "sum":
        return loss.sum()
    return loss


# the two BCE bodies are module-level: one function object, so apply()'s
# micro-jit compiles each once, not once a call
def _bce(a, b):
    a = jnp.clip(a, 1e-12, 1 - 1e-12)
    return -(b * jnp.log(a) + (1 - b) * jnp.log(1 - a))


def _bce_logits(a, b, *pw):
    max_val = jnp.clip(-a, 0, None)
    if pw:
        log_w = (pw[0] - 1) * b + 1
        return (1 - b) * a + log_w * (
            jnp.log(jnp.exp(-max_val) + jnp.exp(-a - max_val)) + max_val)
    return (1 - b) * a + max_val + jnp.log(
        jnp.exp(-max_val) + jnp.exp(-a - max_val))


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    input, label = ensure_tensor(input), ensure_tensor(label)
    loss = apply(_bce, input, label, name="bce")
    if weight is not None:
        loss = loss * ensure_tensor(weight)
    return _reduce_loss(loss, reduction)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    logit, label = ensure_tensor(logit), ensure_tensor(label)
    args = [ensure_tensor(pos_weight)] if pos_weight is not None else []
    loss = apply(_bce_logits, logit, label, *args, name="bce_logits")
    if weight is not None:
        loss = loss * ensure_tensor(weight)
    return _reduce_loss(loss, reduction)


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    input, label = ensure_tensor(input), ensure_tensor(label)

    def f(a, b):
        if log_target:
            return jnp.exp(b) * (b - a)
        return b * (jnp.log(jnp.clip(b, 1e-30, None)) - a)
    loss = apply(f, input, label, name="kl_div")
    if reduction == "batchmean":
        return loss.sum() / input.shape[0]
    return _reduce_loss(loss, reduction)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    x1, x2 = ensure_tensor(x1), ensure_tensor(x2)

    def f(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.sqrt(jnp.sum(a * a, axis=axis) *
                       jnp.sum(b * b, axis=axis))
        return num / jnp.maximum(den, eps)
    return apply(f, x1, x2, name="cosine_similarity")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    input, other, label = (ensure_tensor(input), ensure_tensor(other),
                           ensure_tensor(label))
    loss = apply(lambda a, b, y: jnp.maximum(0.0, -y * (a - b) + margin),
                 input, other, label, name="margin_ranking")
    return _reduce_loss(loss, reduction)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean",
                         name=None):
    input, label = ensure_tensor(input), ensure_tensor(label)
    loss = apply(lambda a, y: jnp.where(y == 1.0, a,
                                        jnp.maximum(0.0, margin - a)),
                 input, label, name="hinge_embedding")
    return _reduce_loss(loss, reduction)

# ---------------------------------------------------------------------------
# attention


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """[B, S, H, D] layout, matching the reference's flash-attn API.

    Dispatches to the Pallas flash-attention kernel on TPU when available
    (paddle_tpu.ops.pallas.flash_attention); XLA fallback otherwise.
    """
    q, k, v = (ensure_tensor(query), ensure_tensor(key),
               ensure_tensor(value))
    q, k, v = amp_autocast((q, k, v), "attention")
    mask = ensure_tensor(attn_mask).detach() if attn_mask is not None \
        else None

    from ...ops.pallas import flash_attention as _fa
    return _fa.flash_attention_bshd(q, k, v, mask=mask, causal=is_causal,
                                    dropout_p=dropout_p if training else 0.0)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none",
                         axis=axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss

# ---------------------------------------------------------------------------
# misc


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (reference: paddle.nn.functional.unfold), NCHW."""
    x = ensure_tensor(x)
    ks = (kernel_sizes, kernel_sizes) if isinstance(kernel_sizes, int) \
        else tuple(kernel_sizes)
    st = (strides, strides) if isinstance(strides, int) else tuple(strides)
    pd = (paddings, paddings) if isinstance(paddings, int) \
        else tuple(paddings)
    dl = (dilations, dilations) if isinstance(dilations, int) \
        else tuple(dilations)

    def f(a):
        n, c, h, w = a.shape
        patches = jax.lax.conv_general_dilated_patches(
            a, ks, st, [(pd[0], pd[0]), (pd[1], pd[1])], rhs_dilation=dl,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return patches.reshape(n, c * ks[0] * ks[1], -1)
    return apply(f, x, name="unfold")


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    x = ensure_tensor(x)
    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "bicubic": "cubic", "trilinear": "linear",
             "area": "linear"}[mode]

    def f(a):
        spatial = a.shape[2:]
        if size is not None:
            out_sp = tuple(size) if isinstance(size, (list, tuple)) \
                else (size,) * len(spatial)
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
                else [scale_factor] * len(spatial)
            out_sp = tuple(int(s * f_) for s, f_ in zip(spatial, sf))
        return jax.image.resize(a, a.shape[:2] + out_sp, method=jmode)
    return apply(f, x, name="interpolate")


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    x = ensure_tensor(x)
    r = upscale_factor

    def f(a):
        n, c, h, w = a.shape
        a = a.reshape(n, c // (r * r), r, r, h, w)
        a = jnp.transpose(a, (0, 1, 4, 2, 5, 3))
        return a.reshape(n, c // (r * r), h * r, w * r)
    return apply(f, x, name="pixel_shuffle")


def sequence_mask(lengths, maxlen=None, dtype="int64"):
    lengths = ensure_tensor(lengths)
    ml = maxlen or int(jnp.max(lengths._data))
    return Tensor((jnp.arange(ml)[None, :] <
                   lengths._data[..., None]).astype(jnp.int32))


def pad(x, pad_, mode="constant", value=0.0, data_format="NCHW", name=None):
    from ...ops.manipulation import pad as _pad
    return _pad(x, pad_, mode=mode, value=value, data_format=data_format)

from .extended import *  # noqa: E402,F401,F403
from .extended2 import *  # noqa: E402,F401,F403
from .extended3 import *  # noqa: E402,F401,F403
from .flash_attention import flashmask_attention  # noqa: E402,F401
