"""Model FLOPs from shapes: two per multiply-accumulate of every conv and
dense layer; pools, adds, activations and the loss are not counted.

A layer list is the ``LAYERS`` of a configuration's reference: dicts with
``kind`` ("conv", "dense", "pool"), ``name``, ``cin``, ``cout``, ``k``,
``stride`` and ``hw_in`` (conv).  Training counts each trainable layer's
weight gradient (as many FLOPs as its forward) and the input derivative (as
many again) of every layer that has a trainable layer before it.
"""

from __future__ import annotations

from typing import Iterable, List


def forward_flops(layer: dict) -> int:
    if layer["kind"] == "conv":
        hw_out = -(-layer["hw_in"] // layer["stride"])
        return 2 * layer["cin"] * layer["k"] ** 2 * layer["cout"] * hw_out ** 2
    if layer["kind"] == "dense":
        return 2 * layer["cin"] * layer["cout"]
    return 0


def forward_per_sample(layers: List[dict]) -> int:
    return sum(forward_flops(l) for l in layers)


def train_per_sample(layers: List[dict], trainable: Iterable[str]) -> int:
    """Forward of the whole graph plus the backward that training needs."""
    trainable = set(trainable)
    total, upstream_trains = 0, False
    for l in layers:
        f = forward_flops(l)
        total += f
        if upstream_trains:
            total += f                    # derivative w.r.t. the input
        if l["name"] in trainable:
            total += f                    # weight gradient
            upstream_trains = True
    return total
