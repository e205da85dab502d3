"""ResNet family.

Capability parity: python/paddle/vision/models/resnet.py (ResNet:151,
resnet18:272 … resnet152:352 in the reference).  TPU-native notes: each
residual block is a handful of XLA convolutions the compiler fuses with the
following BN+ReLU; the whole network jit-compiles to one executable.  BN
running stats are Buffers so the train step stays purely functional
(`functional_call(..., return_buffers=True)`).

No pretrained-weight download: this environment has no egress; pass a local
state-dict path via ``pretrained`` instead (or leave False).
"""
from __future__ import annotations

import functools

from ... import nn

__all__ = ["ResNet", "resnet18", "resnet34", "resnet50", "resnet101",
           "resnet152"]


class BasicBlock(nn.Layer):
    expansion = 1

    def __init__(self, inplanes, planes, stride=1, downsample=None,
                 groups=1, base_width=64, dilation=1, norm_layer=None,
                 data_format="NCHW"):
        super().__init__()
        if norm_layer is None:
            norm_layer = functools.partial(nn.BatchNorm2D,
                                           data_format=data_format)
        if groups != 1 or base_width != 64:
            raise ValueError("BasicBlock only supports groups=1, base_width=64")
        self.conv1 = nn.Conv2D(inplanes, planes, 3, stride=stride, padding=1,
                               bias_attr=False, data_format=data_format)
        self.bn1 = norm_layer(planes)
        self.relu = nn.ReLU()
        self.conv2 = nn.Conv2D(planes, planes, 3, padding=1, bias_attr=False,
                               data_format=data_format)
        self.bn2 = norm_layer(planes)
        self.downsample = downsample
        self.stride = stride

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.bn2(self.conv2(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class BottleneckBlock(nn.Layer):
    expansion = 4

    def __init__(self, inplanes, planes, stride=1, downsample=None,
                 groups=1, base_width=64, dilation=1, norm_layer=None,
                 data_format="NCHW"):
        super().__init__()
        if norm_layer is None:
            norm_layer = functools.partial(nn.BatchNorm2D,
                                           data_format=data_format)
        width = int(planes * (base_width / 64.0)) * groups
        self.conv1 = nn.Conv2D(inplanes, width, 1, bias_attr=False,
                               data_format=data_format)
        self.bn1 = norm_layer(width)
        self.conv2 = nn.Conv2D(width, width, 3, stride=stride, padding=dilation,
                               groups=groups, dilation=dilation, bias_attr=False,
                               data_format=data_format)
        self.bn2 = norm_layer(width)
        self.conv3 = nn.Conv2D(width, planes * self.expansion, 1, bias_attr=False,
                               data_format=data_format)
        self.bn3 = norm_layer(planes * self.expansion)
        self.relu = nn.ReLU()
        self.downsample = downsample
        self.stride = stride

    def _fused_tail(self, out, identity):
        """conv3 (1x1) → train-mode bn3 → +identity → relu through the
        fused Pallas pair (``conv1x1_bn_stats`` + ``bn_apply_relu``): two
        passes over the conv output instead of XLA's three, with the
        residual read and ReLU pinned into the second.  Eligible only in
        training (eval BN needs no batch stats — XLA already folds it),
        NHWC layout, and under ``fused_epilogues_eligible`` (real TPU,
        lane-aligned channels, unsharded mesh).  Returns None when
        ineligible — the caller's plain path is the reference."""
        cv, bn = self.conv3, self.bn3
        if not (self.training and cv.data_format == "NHWC"
                and cv.kernel_size == (1, 1) and cv.stride == 1
                and cv.groups == 1 and cv.bias is None
                and bn.weight is not None and bn.bias is not None
                and not bn.use_global_stats):
            return None
        from ...ops.autotune import fused_epilogues_eligible

        cout = cv.out_channels
        if not fused_epilogues_eligible(cout):
            return None
        import jax.numpy as jnp

        from ...ops.fused_conv1x1_bn import conv1x1_bn_relu

        x = jnp.asarray(out)
        n, h, w_, cin = x.shape
        w = jnp.asarray(cv.weight.value).reshape(cout, cin).T  # [Cin, Cout]
        y, nrm, nrv = conv1x1_bn_relu(
            x.reshape(-1, cin), w,
            jnp.asarray(bn.weight.value), jnp.asarray(bn.bias.value),
            epsilon=bn.epsilon, momentum=bn.momentum,
            residual=jnp.asarray(identity).reshape(-1, cout),
            running_mean=bn._mean.value, running_var=bn._variance.value,
            fused_epilogue=True)
        bn._mean.value = nrm
        bn._variance.value = nrv
        return y.reshape(n, h, w_, cout)

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        if self.downsample is not None:
            identity = self.downsample(x)
        fused = self._fused_tail(out, identity)
        if fused is not None:
            return fused
        out = self.bn3(self.conv3(out))
        return self.relu(out + identity)


class ResNet(nn.Layer):
    """ResNet model from "Deep Residual Learning for Image Recognition".

    Args match the reference surface (resnet.py:174): ``block`` class,
    ``depth`` in {18, 34, 50, 101, 152}, ``num_classes`` (≤0 disables the
    fc head), ``with_pool``.
    """

    _layer_cfg = {
        18: [2, 2, 2, 2],
        34: [3, 4, 6, 3],
        50: [3, 4, 6, 3],
        101: [3, 4, 23, 3],
        152: [3, 8, 36, 3],
    }

    def __init__(self, block, depth, num_classes=1000, with_pool=True,
                 data_format="NCHW", stem_space_to_depth=False):
        super().__init__()
        layers = self._layer_cfg[depth]
        self.num_classes = num_classes
        self.with_pool = with_pool
        self.data_format = data_format
        # TPU stem optimization: rewrite the 7x7/s2 conv on 3 channels (MXU
        # utilization-bound: C=3 of 128 lanes) as the EQUIVALENT 4x4/s1
        # conv on the 2x2 space-to-depth input (12 channels) — same math,
        # same parameters (weights re-gathered per forward, so checkpoints
        # stay in the canonical layout).  Measured v5e: stem 1.40 -> 1.00
        # ms at B=128 (tools/resnet_mfu_analysis.md).  NHWC only.
        if stem_space_to_depth and data_format != "NHWC":
            from ...framework.errors import InvalidArgumentError

            raise InvalidArgumentError(
                "stem_space_to_depth is an NHWC-layout optimization; use "
                "data_format='NHWC' (the TPU-preferred layout) or drop "
                "the flag")
        self.stem_space_to_depth = bool(stem_space_to_depth)
        self._norm_layer = functools.partial(nn.BatchNorm2D,
                                             data_format=data_format)
        self.inplanes = 64
        self.dilation = 1

        self.conv1 = nn.Conv2D(3, self.inplanes, 7, stride=2, padding=3,
                               bias_attr=False, data_format=data_format)
        self.bn1 = self._norm_layer(self.inplanes)
        self.relu = nn.ReLU()
        self.maxpool = nn.MaxPool2D(kernel_size=3, stride=2, padding=1,
                                    data_format=data_format)
        self.layer1 = self._make_layer(block, 64, layers[0])
        self.layer2 = self._make_layer(block, 128, layers[1], stride=2)
        self.layer3 = self._make_layer(block, 256, layers[2], stride=2)
        self.layer4 = self._make_layer(block, 512, layers[3], stride=2)
        if with_pool:
            self.avgpool = nn.AdaptiveAvgPool2D((1, 1),
                                                data_format=data_format)
        if num_classes > 0:
            self.fc = nn.Linear(512 * block.expansion, num_classes)

    def _make_layer(self, block, planes, blocks, stride=1, dilate=False):
        norm_layer = self._norm_layer
        downsample = None
        previous_dilation = self.dilation
        if dilate:
            self.dilation *= stride
            stride = 1
        if stride != 1 or self.inplanes != planes * block.expansion:
            downsample = nn.Sequential(
                nn.Conv2D(self.inplanes, planes * block.expansion, 1,
                          stride=stride, bias_attr=False,
                          data_format=self.data_format),
                norm_layer(planes * block.expansion),
            )
        layers = [block(self.inplanes, planes, stride, downsample, 1, 64,
                        previous_dilation, norm_layer,
                        data_format=self.data_format)]
        self.inplanes = planes * block.expansion
        for _ in range(1, blocks):
            layers.append(block(self.inplanes, planes, norm_layer=norm_layer,
                                data_format=self.data_format))
        return nn.Sequential(*layers)

    def _stem_s2d(self, x):
        """out[i,j,o] = Σ W[kh,kw,c] X[2i+kh-3, 2j+kw-3, c] (pad 3, stride
        2) re-indexed in 2x2 blocks: kh-3 = 2a+dy → tap a ∈ {-2..1}
        (4-wide kernel, pad (2,1)), block parity dy, packed channel
        dy*2C + dx*C + c."""
        import jax
        import jax.numpy as jnp

        from ... import nn as _nn

        B, H, W, C = x.shape
        if H % 2 or W % 2:
            # odd spatial size: the 2x2 block re-layout doesn't exist —
            # take the standard stem (same result, just slower)
            return self.conv1(x)
        x2 = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(
            0, 1, 3, 2, 4, 5).reshape(B, H // 2, W // 2, 4 * C)
        # re-gather the canonical OIHW weight as the OIHW 4x4 kernel
        w = jnp.asarray(self.conv1.weight.value)         # [O, C, 7, 7]
        w2 = jnp.zeros((w.shape[0], 4 * C, 4, 4), w.dtype)

        def taps(d):  # (tap_row a+2, kernel row kh) pairs for parity d
            return [(a + 2, 2 * a + d + 3) for a in (-2, -1, 0, 1)
                    if 0 <= 2 * a + d + 3 <= 6]

        for dy in (0, 1):
            for dx in (0, 1):
                lo = dy * 2 * C + dx * C
                for ai, kh in taps(dy):
                    for bi, kw in taps(dx):
                        w2 = w2.at[:, lo:lo + C, ai, bi].set(w[:, :, kh, kw])
        # F.conv2d: gets the AMP mixed-dtype auto-cast and the framework's
        # padding plumbing (asymmetric [top, bottom, left, right])
        return _nn.functional.conv2d(x2, w2, stride=1, padding=[2, 1, 2, 1],
                                     data_format="NHWC")

    def forward(self, x):
        if self.stem_space_to_depth:
            x = self.relu(self.bn1(self._stem_s2d(x)))
        else:
            x = self.relu(self.bn1(self.conv1(x)))
        x = self.maxpool(x)
        x = self.layer1(x)
        x = self.layer2(x)
        x = self.layer3(x)
        x = self.layer4(x)
        if self.with_pool:
            x = self.avgpool(x)
        if self.num_classes > 0:
            x = x.reshape(x.shape[0], -1)
            x = self.fc(x)
        return x


def _resnet(arch, Block, depth, pretrained, **kwargs):
    model = ResNet(Block, depth, **kwargs)
    if pretrained:
        from ...framework import serialization

        if not isinstance(pretrained, str):
            raise ValueError(
                "no pretrained-weight download in this environment: pass a "
                "local .pdparams path as `pretrained`")
        model.set_state_dict(serialization.load(pretrained))
    return model


def resnet18(pretrained=False, **kwargs):
    """ResNet-18 (reference surface: vision/models/resnet.py:272)."""
    return _resnet("resnet18", BasicBlock, 18, pretrained, **kwargs)


def resnet34(pretrained=False, **kwargs):
    """ResNet-34 (reference surface: vision/models/resnet.py:292)."""
    return _resnet("resnet34", BasicBlock, 34, pretrained, **kwargs)


def resnet50(pretrained=False, **kwargs):
    """ResNet-50 (reference surface: vision/models/resnet.py:312) — the
    flagship CNN."""
    return _resnet("resnet50", BottleneckBlock, 50, pretrained, **kwargs)


def resnet101(pretrained=False, **kwargs):
    """ResNet-101 (reference surface: vision/models/resnet.py:332)."""
    return _resnet("resnet101", BottleneckBlock, 101, pretrained, **kwargs)


def resnet152(pretrained=False, **kwargs):
    """ResNet-152 (reference surface: vision/models/resnet.py:352)."""
    return _resnet("resnet152", BottleneckBlock, 152, pretrained, **kwargs)
