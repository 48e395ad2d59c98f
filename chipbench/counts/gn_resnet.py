"""Operations of the GroupNorm ResNet, counted from its shapes.

Only the convolutions and the head are counted (2 operations per
multiply-add); GroupNorm, ReLU and the pooling are elementwise and left
out. Training counts the forward pass, the weight gradients (as many
operations as the forward) and the input gradients (as many again) of
every layer but the stem, whose input needs none. Nothing is recomputed.
"""
from __future__ import annotations

from typing import Dict, List, Tuple


def layers(config: Dict) -> List[Tuple[str, int]]:
    """(layer, forward operations per sample) in order."""
    size = config["image_size"]
    widths = config["stage_widths"]
    cut = config["cut_stage"]
    out = [("stem", 2 * size * size * 9 * 3 * widths[0])]
    cin = widths[0]
    for si, cout in enumerate(widths):
        for bi in range(config["blocks_per_stage"]):
            stride = 2 if (bi == 0 and si >= cut) else 1
            if bi == 0 and si >= cut:
                size //= stride
            c_in = cin if bi == 0 else cout
            hw = size * size
            out.append((f"s{si}b{bi}.conv1", 2 * hw * 9 * c_in * cout))
            out.append((f"s{si}b{bi}.conv2", 2 * hw * 9 * cout * cout))
            if c_in != cout:
                out.append((f"s{si}b{bi}.proj", 2 * hw * c_in * cout))
        cin = cout
    out.append(("head", 2 * widths[-1] * config["num_classes"]))
    return out


def forward_flops_per_sample(config: Dict) -> int:
    return sum(f for _, f in layers(config))


def train_flops_per_sample(config: Dict) -> int:
    ls = layers(config)
    return sum(3 * f for _, f in ls) - ls[0][1]
