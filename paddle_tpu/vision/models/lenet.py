"""LeNet (parity: python/paddle/vision/models/lenet.py:21).

The smoke model: MNIST digits, 1×28×28 input.
"""
from __future__ import annotations

import jax.numpy as jnp

from ... import nn

__all__ = ["LeNet"]


class LeNet(nn.Layer):
    def __init__(self, num_classes=10, data_format="NCHW"):
        super().__init__()
        self.num_classes = num_classes
        self.data_format = data_format
        self.features = nn.Sequential(
            nn.Conv2D(1, 6, 3, stride=1, padding=1,
                      data_format=data_format),
            nn.ReLU(),
            nn.MaxPool2D(2, 2, data_format=data_format),
            nn.Conv2D(6, 16, 5, stride=1, padding=0,
                      data_format=data_format),
            nn.ReLU(),
            nn.MaxPool2D(2, 2, data_format=data_format),
        )
        if num_classes > 0:
            self.fc = nn.Sequential(
                nn.Linear(400, 120),
                nn.Linear(120, 84),
                nn.Linear(84, num_classes),
            )

    def forward(self, inputs):
        x = self.features(inputs)
        if self.num_classes > 0:
            if self.data_format == "NHWC":
                x = jnp.transpose(jnp.asarray(x), (0, 3, 1, 2))
            x = x.reshape(x.shape[0], -1)
            x = self.fc(x)
        return x
