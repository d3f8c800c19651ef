"""Parameter initializers (reference: python/paddle/nn/initializer/).

Each initializer is a callable applied to a Parameter in place; values come
from jax.random draws off the global key.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as rnd
from ..framework.core import Tensor

__all__ = [
    "Initializer",
    "Constant",
    "Normal",
    "TruncatedNormal",
    "Uniform",
    "XavierNormal",
    "XavierUniform",
    "KaimingNormal",
    "KaimingUniform",
    "Assign",
    "Orthogonal",
    "Dirac",
    "calculate_gain",
]


def calculate_gain(nonlinearity, param=None):
    gains = {
        "sigmoid": 1.0,
        "linear": 1.0,
        "conv1d": 1.0,
        "conv2d": 1.0,
        "conv3d": 1.0,
        "tanh": 5.0 / 3.0,
        "relu": math.sqrt(2.0),
        "leaky_relu": math.sqrt(2.0 / (1 + (param if param is not None else 0.01) ** 2)),
        "selu": 3.0 / 4.0,
    }
    return gains[nonlinearity]


# One jitted executable per (shape, dtype) — init of a large model is
# thousands of tiny eager ops, each its own dispatch; sampling+affine+cast
# fused into a single cached program makes it one.
from functools import partial as _partial


@_partial(jax.jit, static_argnames=("shape", "dtype"))
def _sample_normal(key, mean, std, shape, dtype):
    return (mean + std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@_partial(jax.jit, static_argnames=("shape", "dtype"))
def _sample_truncated(key, mean, std, a, b, shape, dtype):
    v = jax.random.truncated_normal(key, a, b, shape, jnp.float32)
    return (mean + std * v).astype(dtype)


@_partial(jax.jit, static_argnames=("shape", "dtype"))
def _sample_uniform(key, low, high, shape, dtype):
    return jax.random.uniform(key, shape, jnp.float32, low, high).astype(dtype)


@_partial(jax.jit, static_argnames=("shape", "dtype"))
def _full_value(value, shape, dtype):
    return jnp.full(shape, value, dtype)


def _fans(shape):
    shape = tuple(shape)
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv: [out_c, in_c, *kernel] — matches the reference's fan computation
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


class Initializer:
    def __call__(self, param: Tensor, block=None):
        raise NotImplementedError

    def _set(self, param, value):
        param._value = value.astype(param._value.dtype)
        return param


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, param, block=None):
        return self._set(
            param, _full_value(self.value, tuple(param.shape), param.dtype))


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0, name=None):
        self.mean, self.std = mean, std

    def __call__(self, param, block=None):
        v = _sample_normal(rnd.next_key(), self.mean, self.std,
                           tuple(param.shape), param.dtype)
        return self._set(param, v)


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0, a=-2.0, b=2.0, name=None):
        self.mean, self.std, self.a, self.b = mean, std, a, b

    def __call__(self, param, block=None):
        v = _sample_truncated(rnd.next_key(), self.mean, self.std, self.a,
                              self.b, tuple(param.shape), param.dtype)
        return self._set(param, v)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0, name=None):
        self.low, self.high = low, high

    def __call__(self, param, block=None):
        v = _sample_uniform(rnd.next_key(), self.low, self.high,
                            tuple(param.shape), param.dtype)
        return self._set(param, v)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0, name=None):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, param, block=None):
        fi, fo = _fans(param.shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        v = _sample_normal(rnd.next_key(), 0.0, std, tuple(param.shape),
                           param.dtype)
        return self._set(param, v)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0, name=None):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, param, block=None):
        fi, fo = _fans(param.shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        v = _sample_uniform(rnd.next_key(), -limit, limit, tuple(param.shape),
                            param.dtype)
        return self._set(param, v)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu", name=None):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, param, block=None):
        fi, _ = _fans(param.shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = calculate_gain(self.nonlinearity, self.negative_slope)
        std = gain / math.sqrt(fi)
        v = _sample_normal(rnd.next_key(), 0.0, std, tuple(param.shape),
                           param.dtype)
        return self._set(param, v)


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu", name=None):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, param, block=None):
        fi, _ = _fans(param.shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = calculate_gain(self.nonlinearity, self.negative_slope)
        limit = gain * math.sqrt(3.0 / fi)
        v = _sample_uniform(rnd.next_key(), -limit, limit, tuple(param.shape),
                            param.dtype)
        return self._set(param, v)


class Assign(Initializer):
    def __init__(self, value, name=None):
        self.value = value

    def __call__(self, param, block=None):
        v = self.value
        if isinstance(v, Tensor):
            v = v._value
        return self._set(param, jnp.asarray(v))


class Orthogonal(Initializer):
    def __init__(self, gain=1.0, name=None):
        self.gain = gain

    def __call__(self, param, block=None):
        shape = tuple(param.shape)
        rows = shape[0]
        cols = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        flat = jax.random.normal(rnd.next_key(), (max(rows, cols), min(rows, cols)), jnp.float32)
        q, r = jnp.linalg.qr(flat)
        q = q * jnp.sign(jnp.diagonal(r))
        if rows < cols:
            q = q.T
        return self._set(param, self.gain * q[:rows, :cols].reshape(shape))


class Dirac(Initializer):
    def __init__(self, groups=1, name=None):
        self.groups = groups

    def __call__(self, param, block=None):
        shape = tuple(param.shape)
        v = np.zeros(shape, np.float32)
        out_per_group = shape[0] // self.groups
        minc = min(out_per_group, shape[1])
        centers = tuple(s // 2 for s in shape[2:])
        for g in range(self.groups):
            for i in range(minc):
                v[(g * out_per_group + i, i) + centers] = 1.0
        return self._set(param, jnp.asarray(v))


# reference-style aliases
constant = Constant
normal = Normal
uniform = Uniform
