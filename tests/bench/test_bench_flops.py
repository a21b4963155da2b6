"""Model FLOPs from shapes against hand counts."""

import benchutil
from chipbench import flops
from chipbench.harness import load_module

CONFIGS = benchutil.BENCH / "configs"


def macs(cin, cout, k, hw):
    return cin * k * k * cout * hw * hw


def test_vgg16_forward_and_training():
    vgg = load_module(CONFIGS / "vgg16.py", "vgg16_ref")
    # conv MACs per sample, block by block at 32, 16, 8, 4, 2 pixels
    conv = (macs(3, 64, 3, 32) + macs(64, 64, 3, 32)
            + macs(64, 128, 3, 16) + macs(128, 128, 3, 16)
            + macs(128, 256, 3, 8) + 2 * macs(256, 256, 3, 8)
            + macs(256, 512, 3, 4) + 2 * macs(512, 512, 3, 4)
            + 3 * macs(512, 512, 3, 2))
    dense = 512 * 512 + 512 * 10
    fwd = 2 * (conv + dense)
    assert flops.forward_per_sample(vgg.LAYERS) == fwd
    assert 0.62e9 < fwd < 0.64e9              # about 0.63 GFLOP a sample
    # training: weight gradients of every layer, input derivatives of all
    # but the first conv
    first = 2 * macs(3, 64, 3, 32)
    assert flops.train_per_sample(vgg.LAYERS, vgg.TRAINABLE) \
        == 3 * fwd - first


def test_resnet18_transfer_forward_and_training():
    res = load_module(CONFIGS / "resnet18_transfer.py", "resnet18_ref")
    conv = (macs(3, 64, 3, 32) + 4 * macs(64, 64, 3, 32)
            + macs(64, 128, 3, 16) + 3 * macs(128, 128, 3, 16)
            + macs(64, 128, 1, 16)
            + macs(128, 256, 3, 8) + 3 * macs(256, 256, 3, 8)
            + macs(128, 256, 1, 8)
            + macs(256, 512, 3, 4) + 3 * macs(512, 512, 3, 4)
            + macs(256, 512, 1, 4))
    head = 512 * 10
    fwd = 2 * (conv + head)
    assert flops.forward_per_sample(res.LAYERS) == fwd
    assert 1.10e9 < fwd < 1.12e9              # about 1.1 GFLOP a sample
    # only the head trains: its weight gradient, and no input derivative
    assert flops.train_per_sample(res.LAYERS, res.TRAINABLE) \
        == fwd + 2 * head
