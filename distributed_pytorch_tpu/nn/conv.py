"""Convolution / norm / pooling modules for the vision rung of the ladder
(BASELINE.json: ResNet-18 on CIFAR-10).

Layout is NHWC — the TPU-native image layout (channels-last feeds the MXU's
128-lane minor dimension directly; NCHW is the CUDA idiom and forces
transposes on TPU). BatchNorm is stateful: ``init`` returns params,
``init_state`` returns running stats, ``apply`` takes/returns state. Under
the DP engine each device normalizes with its *local* batch statistics —
the same semantics as torch DDP's default (non-synced) BatchNorm.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .core import Module, Params


class Conv2d(Module):
    """2-D convolution, NHWC, HWIO kernel, stride/padding like torch's
    Conv2d(padding=p). Kaiming-normal (fan_out, relu) init — the torchvision
    ResNet initialization."""

    def __init__(self, in_ch: int, out_ch: int, kernel: int, stride: int = 1,
                 padding: int = 0, bias: bool = False, groups: int = 1,
                 dtype=jnp.float32):
        self.in_ch = in_ch
        self.out_ch = out_ch
        self.kernel = kernel
        self.stride = stride
        self.padding = padding
        self.use_bias = bias
        self.groups = groups
        self.dtype = dtype

    def init(self, key) -> Params:
        kw, kb = jax.random.split(key)
        fan_out = self.kernel * self.kernel * self.out_ch
        std = math.sqrt(2.0 / fan_out)
        p = {"w": std * jax.random.normal(
            kw, (self.kernel, self.kernel, self.in_ch // self.groups,
                 self.out_ch), self.dtype)}
        if self.use_bias:
            p["b"] = jnp.zeros((self.out_ch,), self.dtype)
        return p

    def apply(self, params: Params, x, **_):
        from ..ops.quant import resolve_weight
        y = lax.conv_general_dilated(
            x, resolve_weight(params, "w", self.dtype),
            window_strides=(self.stride, self.stride),
            padding=[(self.padding, self.padding)] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.groups,
        )
        if self.use_bias:
            y = y + params["b"]
        return y


class BatchNorm2d(Module):
    """BatchNorm over N,H,W with running-stat state (torch semantics:
    train mode uses batch stats and updates running stats with momentum
    0.1; eval mode uses running stats).

    ``axis_name`` turns it into **SyncBatchNorm** (torch
    ``nn.SyncBatchNorm`` under DDP): inside a ``shard_map`` over that
    mesh axis, batch statistics are computed over the GLOBAL batch (one
    psum of the per-shard sum/sum-of-squares), and every replica updates
    identical running stats. Outside any binding of the axis (world-1
    runs, plain jit) it degrades to local statistics — the framework's
    0/1/N contract. Note the pure-GSPMD path needs no flag: there the
    model sees global shapes, so plain ``jnp.mean`` already reduces over
    the whole batch."""

    def __init__(self, ch: int, eps: float = 1e-5, momentum: float = 0.1,
                 axis_name: Optional[str] = None, dtype=jnp.float32):
        self.ch = ch
        self.eps = eps
        self.momentum = momentum
        self.axis_name = axis_name
        self.dtype = dtype

    def init(self, key) -> Params:
        del key
        return {"scale": jnp.ones((self.ch,), self.dtype),
                "bias": jnp.zeros((self.ch,), self.dtype)}

    def init_state(self):
        return {"mean": jnp.zeros((self.ch,), self.dtype),
                "var": jnp.ones((self.ch,), self.dtype),
                "count": jnp.zeros((), jnp.int32)}

    def _batch_stats(self, x):
        """(mean, var, n) over N,H,W — cross-replica when ``axis_name``
        is bound (sum/sum-of-squares psum: one collective, the standard
        sync-BN form), local otherwise."""
        n = x.shape[0] * x.shape[1] * x.shape[2]
        if self.axis_name is None:
            return jnp.mean(x, axis=(0, 1, 2)), jnp.var(x, axis=(0, 1, 2)), n
        s = jnp.sum(x, axis=(0, 1, 2))
        ss = jnp.sum(jnp.square(x), axis=(0, 1, 2))
        try:
            s = lax.psum(s, self.axis_name)
            ss = lax.psum(ss, self.axis_name)
            n = n * lax.psum(1, self.axis_name)
        except NameError:
            pass  # axis not bound here: local stats (0/1-device runs)
        mean = s / n
        # E[x^2]-E[x]^2 can go slightly negative from cancellation when
        # |mean| >> std; clamp like torch SyncBatchNorm or rsqrt NaNs
        return mean, jnp.maximum(ss / n - jnp.square(mean), 0.0), n

    def apply(self, params: Params, x, *, state=None, train: bool = False, **_):
        if train:
            mean, var, n = self._batch_stats(x)
            new_state = None
            if state is not None:
                m = self.momentum
                # torch tracks unbiased running var
                unbiased = var * n / max(n - 1, 1)
                new_state = {
                    "mean": (1 - m) * state["mean"] + m * mean,
                    "var": (1 - m) * state["var"] + m * unbiased,
                    "count": state["count"] + 1,
                }
        else:
            mean = state["mean"] if state is not None else jnp.zeros((self.ch,))
            var = state["var"] if state is not None else jnp.ones((self.ch,))
            new_state = state
        inv = lax.rsqrt(var + self.eps)
        y = (x - mean) * inv * params["scale"] + params["bias"]
        return y, new_state


def max_pool(x, window: int, stride: int, padding: int = 0):
    """NHWC max pooling (torch MaxPool2d equivalent)."""
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1),
        padding=((0, 0), (padding, padding), (padding, padding), (0, 0)),
    )


def global_avg_pool(x):
    """NHWC global average pool → (N, C)."""
    return jnp.mean(x, axis=(1, 2))
