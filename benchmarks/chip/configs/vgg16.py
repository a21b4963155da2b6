"""Plain reference of the ``vgg16`` configuration: VGG-16 for CIFAR-10 as
Table 2 of arXiv:1608.08710 lists it (the convolutions of config D,
arXiv:1409.1556), as ``vgg16.json`` states it.

Thirteen 3x3 same-padded convolutions with bias and relu in five blocks of
(2, 2, 3, 3, 3), each block closed by a 2x2 max-pool, then a 512-512 dense
layer with relu and a 512-10 dense layer whose softmax lives in the
cross-entropy loss.  Departure from the source: no batch normalization.
"""

from __future__ import annotations

BLOCKS = ((3, 64, 2), (64, 128, 2), (128, 256, 3), (256, 512, 3),
          (512, 512, 3))


def _layers():
    out, hw = [], 32
    for bi, (cin, cout, n) in enumerate(BLOCKS):
        for i in range(n):
            out.append({"kind": "conv", "name": f"b{bi}_c{i}",
                        "cin": cin if i == 0 else cout, "cout": cout, "k": 3,
                        "stride": 1, "hw_in": hw, "relu": True})
        out.append({"kind": "pool", "name": f"b{bi}_p", "k": 2, "hw_in": hw})
        hw //= 2
    out.append({"kind": "dense", "name": "fc0", "cin": 512, "cout": 512,
                "relu": True})
    out.append({"kind": "dense", "name": "fc1", "cin": 512, "cout": 10,
                "relu": False})
    return out


LAYERS = _layers()
TRAINABLE = tuple(l["name"] for l in LAYERS if l["kind"] in ("conv", "dense"))


def forward(params, x, ops, ref):
    """Logits of a batch ``x`` (B, 3, 32, 32)."""
    h = x
    for l in LAYERS:
        if l["kind"] == "conv":
            p = params[l["name"]]
            h = ops.conv(h, p["w"], p["b"], l["stride"])
        elif l["kind"] == "pool":
            h = ref.maxpool(h, l["k"])
            continue
        else:
            p = params[l["name"]]
            h = ops.dense(h.reshape(h.shape[0], -1), p["w"], p["b"])
        if l["relu"]:
            h = ref.relu(h)
    return h
