"""Layer library.

Everything the reference's model layer uses (conv, max-pool, dropout /
dropout2d, linear — train_dist.py:53-71) plus what the extended configs
need (batch-norm for ResNet-18, layer-norm / attention / embeddings for
ViT-Tiny and the long-context ring-attention path).

Layouts are TPU-native: images are NHWC (channels-last feeds the MXU's
preferred layouts; the reference's NCHW is a GPU/cuDNN convention),
convolution kernels are HWIO.
"""

from __future__ import annotations

import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.nn.core import Lambda, Module, Params, Shape, State, fanin_uniform


class Dense(Module):
    """Affine layer — ``nn.Linear`` analog (train_dist.py:59-60)."""

    def __init__(self, features: int, *, use_bias: bool = True, dtype=jnp.float32):
        self.features = features
        self.use_bias = use_bias
        self.dtype = dtype

    def init(self, key, input_shape):
        in_f = input_shape[-1]
        kw, kb = jax.random.split(key)
        params = {"w": fanin_uniform(kw, (in_f, self.features), in_f, self.dtype)}
        if self.use_bias:
            params["b"] = fanin_uniform(kb, (self.features,), in_f, self.dtype)
        return params, {}

    def out_shape(self, input_shape):
        return input_shape[:-1] + (self.features,)

    def apply(self, params, state, x, *, train=False, key=None):
        y = x @ params["w"]
        if self.use_bias:
            y = y + params["b"]
        return y, state


class Conv2D(Module):
    """2-D convolution, NHWC/HWIO — ``nn.Conv2d`` analog
    (train_dist.py:57-58)."""

    def __init__(
        self,
        features: int,
        kernel: int | tuple[int, int],
        *,
        stride: int | tuple[int, int] = 1,
        padding: str | int = "VALID",
        use_bias: bool = True,
        dtype=jnp.float32,
    ):
        self.features = features
        self.kernel = (kernel, kernel) if isinstance(kernel, int) else tuple(kernel)
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        if isinstance(padding, int):
            padding = [(padding, padding), (padding, padding)]
        self.padding = padding
        self.use_bias = use_bias
        self.dtype = dtype

    def init(self, key, input_shape):
        c_in = input_shape[-1]
        fan_in = c_in * self.kernel[0] * self.kernel[1]
        kw, kb = jax.random.split(key)
        params = {
            "w": fanin_uniform(
                kw, self.kernel + (c_in, self.features), fan_in, self.dtype
            )
        }
        if self.use_bias:
            params["b"] = fanin_uniform(kb, (self.features,), fan_in, self.dtype)
        return params, {}

    def _spatial_out(self, hw):
        if isinstance(self.padding, str):
            if self.padding.upper() == "SAME":
                return tuple(
                    -(-d // s) for d, s in zip(hw, self.stride)
                )
            pads = [(0, 0), (0, 0)]
        else:
            pads = self.padding
        return tuple(
            (d + p[0] + p[1] - k) // s + 1
            for d, p, k, s in zip(hw, pads, self.kernel, self.stride)
        )

    def out_shape(self, input_shape):
        h, w = self._spatial_out(input_shape[:2])
        return (h, w, self.features)

    def apply(self, params, state, x, *, train=False, key=None):
        y = lax.conv_general_dilated(
            x,
            params["w"],
            window_strides=self.stride,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + params["b"]
        return y, state


class MaxPool2D(Module):
    """``F.max_pool2d`` analog (train_dist.py:65-66)."""

    def __init__(self, window: int = 2, stride: int | None = None):
        self.window = window
        self.stride = stride if stride is not None else window

    def out_shape(self, input_shape):
        h, w, c = input_shape
        return ((h - self.window) // self.stride + 1,
                (w - self.window) // self.stride + 1, c)

    def apply(self, params, state, x, *, train=False, key=None):
        y = lax.reduce_window(
            x,
            -jnp.inf,
            lax.max,
            (1, self.window, self.window, 1),
            (1, self.stride, self.stride, 1),
            "VALID",
        )
        return y, state


class AvgPool2D(Module):
    def __init__(self, window: int = 2, stride: int | None = None):
        self.window = window
        self.stride = stride if stride is not None else window

    def out_shape(self, input_shape):
        h, w, c = input_shape
        return ((h - self.window) // self.stride + 1,
                (w - self.window) // self.stride + 1, c)

    def apply(self, params, state, x, *, train=False, key=None):
        y = lax.reduce_window(
            x, 0.0, lax.add,
            (1, self.window, self.window, 1),
            (1, self.stride, self.stride, 1),
            "VALID",
        ) / (self.window * self.window)
        return y, state


class GlobalAvgPool(Module):
    """Mean over spatial dims (ResNet head)."""

    def out_shape(self, input_shape):
        return (input_shape[-1],)

    def apply(self, params, state, x, *, train=False, key=None):
        return x.mean(axis=(1, 2)), state


class Dropout(Module):
    """``F.dropout`` analog (train_dist.py:69): train-only, inverted
    scaling."""

    def __init__(self, rate: float = 0.5):
        self.rate = rate

    def apply(self, params, state, x, *, train=False, key=None):
        if not train or self.rate == 0.0:
            return x, state
        if key is None:
            raise ValueError("Dropout needs an rng key when train=True")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(key, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0), state


class Dropout2D(Module):
    """``nn.Dropout2d`` analog (train_dist.py:58,66): drops whole feature
    maps (channels), NHWC mask shape (N, 1, 1, C)."""

    def __init__(self, rate: float = 0.5):
        self.rate = rate

    def apply(self, params, state, x, *, train=False, key=None):
        if not train or self.rate == 0.0:
            return x, state
        if key is None:
            raise ValueError("Dropout2D needs an rng key when train=True")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(
            key, keep, (x.shape[0], 1, 1, x.shape[-1])
        )
        return jnp.where(mask, x / keep, 0.0), state


class BatchNorm(Module):
    """Batch normalization with running statistics carried in ``state``
    (ResNet-18 needs it; the reference's MNIST net does not use BN).

    ``momentum`` is the DECAY of the running average (Flax convention):
    ``running = momentum * running + (1 - momentum) * batch_stat``.
    torch's ``nn.BatchNorm2d(momentum=m)`` corresponds to ``1 - m`` here —
    torch's default 0.1 equals this default of 0.9; do not pass torch's
    value through unchanged."""

    def __init__(self, momentum: float = 0.9, eps: float = 1e-5):
        self.momentum = momentum
        self.eps = eps

    def init(self, key, input_shape):
        c = input_shape[-1]
        params = {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}
        state = {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}
        return params, state

    def apply(self, params, state, x, *, train=False, key=None):
        reduce_axes = tuple(range(x.ndim - 1))
        if train:
            mean = x.mean(axis=reduce_axes)
            var = x.var(axis=reduce_axes)
            new_state = {
                "mean": self.momentum * state["mean"] + (1 - self.momentum) * mean,
                "var": self.momentum * state["var"] + (1 - self.momentum) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        y = (x - mean) * lax.rsqrt(var + self.eps)
        return y * params["scale"] + params["bias"], new_state


class LayerNorm(Module):
    def __init__(self, eps: float = 1e-6):
        self.eps = eps

    def init(self, key, input_shape):
        d = input_shape[-1]
        return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}, {}

    def apply(self, params, state, x, *, train=False, key=None):
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        y = (x - mean) * lax.rsqrt(var + self.eps)
        return y * params["scale"] + params["bias"], state


class RMSNorm(Module):
    """Root-mean-square norm with a learned gain and no bias (Zhang &
    Sennrich 2019): ``x / sqrt(mean(x^2) + eps) * scale``.  The statistics
    are taken in float32 whatever ``x`` is; the result has ``x``'s dtype."""

    def __init__(self, eps: float = 1e-5):
        self.eps = eps

    def init(self, key, input_shape):
        return {"scale": jnp.ones((input_shape[-1],))}, {}

    def apply(self, params, state, x, *, train=False, key=None):
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + self.eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype), state


class GatedMLP(Module):
    """Gated feed-forward (SwiGLU, Shazeer 2020), bias-free:
    ``(act(a) * b) @ w_out`` with ``[a | b] = x @ w_in``; ``w_in`` holds the
    gate's columns first, then the value's."""

    def __init__(self, width: int, *, activation: Callable = jax.nn.silu):
        self.width = width
        self.activation = activation

    def init(self, key, input_shape):
        d = input_shape[-1]
        k1, k2 = jax.random.split(key)
        return {"w_in": fanin_uniform(k1, (d, 2 * self.width), d),
                "w_out": fanin_uniform(k2, (self.width, d), self.width)}, {}

    def apply(self, params, state, x, *, train=False, key=None):
        ab = x @ params["w_in"]
        a, b = ab[..., : self.width], ab[..., self.width:]
        return (self.activation(a) * b) @ params["w_out"], state


class Embedding(Module):
    def __init__(self, vocab: int, features: int):
        self.vocab = vocab
        self.features = features

    def init(self, key, input_shape):
        return {
            "table": jax.random.normal(key, (self.vocab, self.features)) * 0.02
        }, {}

    def out_shape(self, input_shape):
        return input_shape + (self.features,)

    def apply(self, params, state, x, *, train=False, key=None):
        return params["table"][x], state


def relu() -> Lambda:
    return Lambda(jax.nn.relu)


def gelu() -> Lambda:
    return Lambda(jax.nn.gelu)


def log_softmax() -> Lambda:
    """``F.log_softmax(x)`` head (train_dist.py:71)."""
    return Lambda(lambda x: jax.nn.log_softmax(x, axis=-1))


def flatten() -> Lambda:
    """``x.view(-1, 320)`` analog (train_dist.py:67)."""
    return Lambda(
        lambda x: x.reshape(x.shape[0], -1),
        shape_fn=lambda s: (int(math.prod(s)),),
    )
