"""Useful FLOPs of VGG-9 local training, from the published widths.

A 3x3 convolution with SAME padding costs ``2 * H * W * 9 * cin * cout``
FLOPs per image forward; the classifier ``2 * fc_in * classes``. Backward
costs the weight gradient (as much as the forward) plus the input
gradient (as much again) for every layer but the first, whose input is
the image. Normalisation, ReLU and pooling are elementwise and not
counted. At the paper's widths the forward is 418.8 MFLOP per 32x32
image.
"""
from __future__ import annotations


def layer_forward_flops(cfg: dict) -> list[float]:
    """Forward FLOPs per image of each weight layer, in order."""
    out = []
    side = cfg["image_size"]
    cin = cfg["in_channels"]
    k2 = cfg["kernel_size"] ** 2
    for i, cout in enumerate(cfg["channels"]):
        out.append(2.0 * side * side * k2 * cin * cout)
        cin = cout
        if i in cfg["pool_after"]:
            side //= 2
    out.append(2.0 * side * side * cin * cfg["num_classes"])
    return out


def forward_flops_per_image(cfg: dict) -> float:
    return sum(layer_forward_flops(cfg))


def train_flops_per_image(cfg: dict) -> float:
    """Forward + backward: 3x the forward, less the first layer's input
    gradient."""
    layers = layer_forward_flops(cfg)
    return 3.0 * sum(layers) - layers[0]


def useful_flops_per_round(cfg: dict, traffic: dict) -> float:
    """K clients x B images x local steps of one forward and backward."""
    return (traffic["clients_per_round"] * traffic["batch_per_client"]
            * traffic["local_steps"] * train_flops_per_image(cfg))
