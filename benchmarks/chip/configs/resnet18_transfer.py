"""Plain reference of the ``resnet18_transfer`` configuration: ResNet-18
(arXiv:1512.03385) in its CIFAR form with the backbone frozen up to the
global pool, as ``resnet18_transfer.json`` states it.

A 3x3 stem convolution (64, relu), eight basic blocks of two 3x3
convolutions (the first with relu; the stage's first block strides by 2 and
projects its skip through a 1x1 strided convolution), relu after each add, a
4x4 max-pool over the last 4x4 map, and a 512-10 dense head whose softmax
lives in the cross-entropy loss.  Only the head trains.  Departures from the
paper: 32x32 inputs with a 3x3 stem and no stem pool; no batch
normalization; the global pool takes the max, not the mean.
"""

from __future__ import annotations

STAGES = ((64, 64, 1), (64, 64, 1), (64, 128, 2), (128, 128, 1),
          (128, 256, 2), (256, 256, 1), (256, 512, 2), (512, 512, 1))


def _layers():
    out = [{"kind": "conv", "name": "stem", "cin": 3, "cout": 64, "k": 3,
            "stride": 1, "hw_in": 32}]
    hw = 32
    for i, (cin, cout, s) in enumerate(STAGES):
        out.append({"kind": "conv", "name": f"r{i}_c0", "cin": cin,
                    "cout": cout, "k": 3, "stride": s, "hw_in": hw})
        out.append({"kind": "conv", "name": f"r{i}_c1", "cin": cout,
                    "cout": cout, "k": 3, "stride": 1, "hw_in": hw // s})
        if s != 1 or cin != cout:
            out.append({"kind": "conv", "name": f"r{i}_sc", "cin": cin,
                        "cout": cout, "k": 1, "stride": s, "hw_in": hw})
        hw //= s
    out.append({"kind": "pool", "name": "gap", "k": 4, "hw_in": hw})
    out.append({"kind": "dense", "name": "fc", "cin": 512, "cout": 10})
    return out


LAYERS = _layers()
TRAINABLE = ("fc",)


def features(params, x, ops, ref):
    """The frozen backbone: (B, 3, 32, 32) -> (B, 512)."""

    def conv(name, h, stride):
        p = params[name]
        return ops.conv(h, p["w"], p["b"], stride)

    h = ref.relu(conv("stem", x, 1))
    for i, (cin, cout, s) in enumerate(STAGES):
        y = conv(f"r{i}_c1", ref.relu(conv(f"r{i}_c0", h, s)), 1)
        skip = conv(f"r{i}_sc", h, s) if (s != 1 or cin != cout) else h
        h = ref.relu(y + skip)
    h = ref.maxpool(h, 4)
    return h.reshape(h.shape[0], -1)


def head(params, feats, ops):
    p = params["fc"]
    return ops.dense(feats, p["w"], p["b"])


def forward(params, x, ops, ref):
    return head(params, features(params, x, ops, ref), ops)
