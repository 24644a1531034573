"""Operations of LeNet-5 training, counted from its shapes.

Only the multiply-adds of the convolutions and dense layers count, two
operations each; activations, pooling, biases and the loss are left out.
A training step per sample is the forward pass, the weight gradients
(as many multiply-adds as the forward pass) and the input gradients of
every layer but the first, whose input is the data.
"""
from __future__ import annotations


def _macs(hw, channels, num_classes):
    h, w = hw
    h1, w1 = h - 4, w - 4  # first 5x5 valid convolution
    h2, w2 = h1 // 2 - 4, w1 // 2 - 4  # 2x2 pool, second convolution
    conv1 = h1 * w1 * 6 * 25 * channels
    conv2 = h2 * w2 * 16 * 25 * 6
    flat = (h2 // 2) * (w2 // 2) * 16
    dense = flat * 120 + 120 * 84 + 84 * num_classes
    return conv1, conv2 + dense


def forward_flops(hw, channels, num_classes) -> int:
    """Operations of one sample's forward pass."""
    first, rest = _macs(hw, channels, num_classes)
    return 2 * (first + rest)


def train_flops(hw, channels, num_classes) -> int:
    """Operations of one sample's forward and backward pass."""
    first, rest = _macs(hw, channels, num_classes)
    return 2 * (2 * first + 3 * rest)


def config_flops(config: dict):
    """(train, forward) operations per sample of a configuration."""
    shape = (tuple(config["image_hw"]), config["channels"],
             config["num_classes"])
    return train_flops(*shape), forward_flops(*shape)
