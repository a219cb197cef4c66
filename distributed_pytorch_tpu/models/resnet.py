"""ResNet-18 — the vision rung of the ladder (BASELINE.json: ResNet-18 on
CIFAR-10), NHWC/TPU-native (see nn/conv.py for the layout rationale).

Structure matches torchvision resnet18: 7x7/2 stem + maxpool, four stages
of two BasicBlocks (64/128/256/512, stride 2 from stage 2), global average
pool, fc. ``small_input=True`` swaps the stem for the common CIFAR variant
(3x3/1, no maxpool). BatchNorm running stats thread through an explicit
state pytree: ``init(key) -> (params, state)``,
``apply(params, x, state=state, train=...) -> (logits, new_state)`` —
per-device batch statistics under DP by default, matching torch DDP's
default (unsynced) BatchNorm; ``sync_bn=True`` computes batch statistics
over the global batch across the ``dp`` axis (torch ``nn.SyncBatchNorm``),
which matters at small per-device batches.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.conv import BatchNorm2d, Conv2d, global_avg_pool, max_pool
from ..nn.core import Linear, Module, Params, relu


class BasicBlock(Module):
    def __init__(self, in_ch: int, out_ch: int, stride: int = 1,
                 bn_axis: str = None):
        self.conv1 = Conv2d(in_ch, out_ch, 3, stride=stride, padding=1)
        self.bn1 = BatchNorm2d(out_ch, axis_name=bn_axis)
        self.conv2 = Conv2d(out_ch, out_ch, 3, stride=1, padding=1)
        self.bn2 = BatchNorm2d(out_ch, axis_name=bn_axis)
        self.downsample = None
        if stride != 1 or in_ch != out_ch:
            self.downsample = (Conv2d(in_ch, out_ch, 1, stride=stride),
                               BatchNorm2d(out_ch, axis_name=bn_axis))

    def init(self, key) -> Params:
        ks = jax.random.split(key, 3)
        p = {"conv1": self.conv1.init(ks[0]), "bn1": self.bn1.init(ks[0]),
             "conv2": self.conv2.init(ks[1]), "bn2": self.bn2.init(ks[1])}
        if self.downsample is not None:
            p["ds_conv"] = self.downsample[0].init(ks[2])
            p["ds_bn"] = self.downsample[1].init(ks[2])
        return p

    def init_state(self):
        s = {"bn1": self.bn1.init_state(), "bn2": self.bn2.init_state()}
        if self.downsample is not None:
            s["ds_bn"] = self.downsample[1].init_state()
        return s

    def apply(self, params: Params, x, *, state=None, train: bool = False, **_):
        s = state or {}
        ns = {}
        h = self.conv1.apply(params["conv1"], x)
        h, ns["bn1"] = self.bn1.apply(params["bn1"], h,
                                      state=s.get("bn1"), train=train)
        h = relu(h)
        h = self.conv2.apply(params["conv2"], h)
        h, ns["bn2"] = self.bn2.apply(params["bn2"], h,
                                      state=s.get("bn2"), train=train)
        idn = x
        if self.downsample is not None:
            idn = self.downsample[0].apply(params["ds_conv"], x)
            idn, ns["ds_bn"] = self.downsample[1].apply(
                params["ds_bn"], idn, state=s.get("ds_bn"), train=train)
        return relu(h + idn), ns


class ResNet18(Module):
    def __init__(self, n_classes: int = 10, in_ch: int = 3,
                 small_input: bool = False, sync_bn: bool = False,
                 bn_axis: str = "dp"):
        self.small_input = small_input
        axis = bn_axis if sync_bn else None
        if small_input:
            self.stem = Conv2d(in_ch, 64, 3, stride=1, padding=1)
        else:
            self.stem = Conv2d(in_ch, 64, 7, stride=2, padding=3)
        self.bn_stem = BatchNorm2d(64, axis_name=axis)
        cfg = [(64, 64, 1), (64, 128, 2), (128, 256, 2), (256, 512, 2)]
        self.stages = []
        for (cin, cout, stride) in cfg:
            self.stages.append([BasicBlock(cin, cout, stride, bn_axis=axis),
                                BasicBlock(cout, cout, 1, bn_axis=axis)])
        self.fc = Linear(512, n_classes)

    def init(self, key) -> Tuple[Params, dict]:
        ks = jax.random.split(key, 10)
        params = {"stem": self.stem.init(ks[0]),
                  "bn_stem": self.bn_stem.init(ks[0]),
                  "fc": self.fc.init(ks[1])}
        state = {"bn_stem": self.bn_stem.init_state()}
        i = 2
        for si, stage in enumerate(self.stages):
            for bi, blk in enumerate(stage):
                name = f"s{si}b{bi}"
                params[name] = blk.init(ks[i])
                state[name] = blk.init_state()
                i += 1
        return params, state

    def apply(self, params: Params, x, *, state=None, train: bool = False, **_):
        """x: (N, H, W, C) → (logits (N, classes), new_state)."""
        s = state or {}
        ns = {}
        h = self.stem.apply(params["stem"], x)
        h, ns["bn_stem"] = self.bn_stem.apply(params["bn_stem"], h,
                                              state=s.get("bn_stem"),
                                              train=train)
        h = relu(h)
        if not self.small_input:
            h = max_pool(h, 3, 2, padding=1)
        for si, stage in enumerate(self.stages):
            for bi, blk in enumerate(stage):
                name = f"s{si}b{bi}"
                h, ns[name] = blk.apply(params[name], h,
                                        state=s.get(name), train=train)
        h = global_avg_pool(h)
        return self.fc.apply(params["fc"], h), ns
