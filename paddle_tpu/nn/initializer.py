"""Weight initializers (paddle.nn.initializer parity).

Reference surface: upstream python/paddle/nn/initializer/ (unverified, see
SURVEY.md §2.2). Initializers draw from the framework's global threefry
stream, so paddle_tpu.seed() reproduces inits exactly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import jax.random as jrandom
import numpy as np

from ..core.random import next_key

# Eager jax compiles one program a SHAPE for every draw, fill, scale and
# shift: building a net of 95 distinct conv shapes was 300 compiles and
# 20 s. Threefry's bits depend on an element's flat index only, so a draw
# of the next power of two, cut to size, IS the draw of the shape, bit for
# bit: float32 parameters up to this many elements are drawn at a bucketed
# flat length (one program a bucket) and cut, scaled and shifted on the
# host. Larger ones, other dtypes and draws under a trace stay on the
# device, as before.
_HOST_MAX_ELEMENTS = 1 << 22


def _host_sized(shape, dtype):
    n = int(np.prod(shape))
    return 0 < n <= _HOST_MAX_ELEMENTS and np.dtype(dtype) == np.float32


def _draw(sample, shape, dtype, std=None, mean=None):
    """`mean + std * sample(key, shape, dtype)` (each term only if given)
    with the stream's next key."""
    shape, key = tuple(shape), next_key()
    if _host_sized(shape, dtype) and not isinstance(key, jax.core.Tracer):
        n = int(np.prod(shape))
        flat = sample(key, (1 << (n - 1).bit_length(),), dtype)
        x = np.asarray(flat)[:n].reshape(shape)
        scale, shift = np.float32, np.float32
    else:
        x = sample(key, shape, dtype)
        scale = shift = float
    if std is not None:
        x = scale(std) * x
    if mean is not None:
        x = shift(mean) + x
    return jnp.asarray(x)


def _fans(shape):
    shape = tuple(shape)
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv kernels [out, in, *k] — receptive field multiplies
    rf = int(np.prod(shape[2:]))
    return shape[1] * rf, shape[0] * rf


class Initializer:
    def __call__(self, shape, dtype):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, shape, dtype):
        if _host_sized(shape, dtype):
            return jnp.asarray(np.full(tuple(shape), self.value, np.float32))
        return jnp.full(tuple(shape), self.value, dtype)


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, shape, dtype):
        return _draw(jrandom.normal, shape, dtype, self.std, self.mean)


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0, a=-2.0, b=2.0):
        self.mean, self.std, self.a, self.b = mean, std, a, b

    def __call__(self, shape, dtype):
        return _draw(
            lambda k, s, d: jrandom.truncated_normal(k, self.a, self.b, s, d),
            shape, dtype, self.std, self.mean)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, shape, dtype):
        return _draw(lambda k, s, d: jrandom.uniform(
            k, s, d, minval=self.low, maxval=self.high), shape, dtype)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype):
        fi, fo = _fans(shape)
        fi = self.fan_in or fi
        fo = self.fan_out or fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        return _draw(jrandom.normal, shape, dtype, std)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype):
        fi, fo = _fans(shape)
        fi = self.fan_in or fi
        fo = self.fan_out or fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        return _draw(lambda k, s, d: jrandom.uniform(
            k, s, d, minval=-limit, maxval=limit), shape, dtype)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, shape, dtype):
        fi, _ = _fans(shape)
        fi = self.fan_in or fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope ** 2)) \
            if self.nonlinearity in ("relu", "leaky_relu") else 1.0
        std = gain / math.sqrt(fi)
        return _draw(jrandom.normal, shape, dtype, std)


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, shape, dtype):
        fi, _ = _fans(shape)
        fi = self.fan_in or fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope ** 2)) \
            if self.nonlinearity in ("relu", "leaky_relu") else 1.0
        limit = gain * math.sqrt(3.0 / fi)
        return _draw(lambda k, s, d: jrandom.uniform(
            k, s, d, minval=-limit, maxval=limit), shape, dtype)


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, shape, dtype):
        return self.gain * jrandom.orthogonal(
            next_key(), tuple(shape)[-2], shape=tuple(shape)[:-2],
        ).astype(dtype) if len(shape) == 2 else \
            self._general(shape, dtype)

    def _general(self, shape, dtype):
        rows = shape[0]
        cols = int(np.prod(shape[1:]))
        a = jrandom.normal(next_key(), (max(rows, cols), min(rows, cols)))
        q, r = jnp.linalg.qr(a)
        q = q * jnp.sign(jnp.diagonal(r))
        q = q.T if rows < cols else q
        return (self.gain * q[:rows, :cols]).reshape(shape).astype(dtype)


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def __call__(self, shape, dtype):
        from ..core.tensor import Tensor
        v = self.value._data if isinstance(self.value, Tensor) \
            else jnp.asarray(np.asarray(self.value))
        return v.reshape(tuple(shape)).astype(dtype)


class Dirac(Initializer):
    def __init__(self, groups=1):
        self.groups = groups

    def __call__(self, shape, dtype):
        out = np.zeros(shape, dtype=np.float32)
        oc, ic = shape[0], shape[1]
        centers = [s // 2 for s in shape[2:]]
        for i in range(min(oc, ic * self.groups)):
            idx = (i, i % ic) + tuple(centers)
            out[idx] = 1.0
        return jnp.asarray(out).astype(dtype)


# default initializer used by layers when weight_attr is None
_global_default = None


def set_global_initializer(weight_init, bias_init=None):
    global _global_default
    _global_default = (weight_init, bias_init)


def calculate_gain(nonlinearity, param=None):
    if nonlinearity == "tanh":
        return 5.0 / 3
    if nonlinearity == "relu":
        return math.sqrt(2.0)
    if nonlinearity == "leaky_relu":
        a = param if param is not None else 0.01
        return math.sqrt(2.0 / (1 + a ** 2))
    if nonlinearity == "selu":
        return 3.0 / 4
    return 1.0
