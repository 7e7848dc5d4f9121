"""Operation and byte counts against hand-computed values."""
from __future__ import annotations

import json
import os

import pytest

import flops

HERE = os.path.dirname(os.path.abspath(__file__))


def model(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def traffic(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_mnist_cnn_forward():
    m = model("cnn_mnist")
    # conv1 28*28*25*1*32*2, conv2 14*14*25*32*64*2
    assert flops.extract_flops(m) == 1_254_400 + 20_070_400
    # FC 3136*512*2, head 512*10*2
    assert flops.head_flops(m) == 3_211_264 + 10_240


def test_round_flops_fedmmd_mnist():
    m, t = model("cnn_mnist"), traffic("paper_mnist.fedmmd.topk")
    fwd = 1_254_400 + 20_070_400 + 3_211_264 + 10_240
    train = 3 * fwd + (1_254_400 + 20_070_400)       # + frozen stream
    want = 10 * 12 * 50 * train + 10_000 * fwd
    assert flops.model_flops_per_round(m, t) == want
    assert want == pytest.approx(0.8152e12, rel=1e-3)


def test_round_flops_fedavg_mnist():
    # the same round under FedAvg: no frozen global stream
    m, t = model("cnn_mnist"), traffic("paper_mnist.fedmmd.topk")
    t["fl"]["algorithm"] = "fedavg"
    fwd = 1_254_400 + 20_070_400 + 3_211_264 + 10_240
    assert flops.model_flops_per_round(m, t) == \
        10 * 12 * 50 * 3 * fwd + 10_000 * fwd


def test_kernel_costs():
    # gram_sum over x, y [50, 64] with 5 widths
    f, b = flops.gram_sum_cost(50, 50, 64, 5)
    assert f == 2 * 50 * 50 * 64 + 3 * 50 * 50 * 5
    assert b == 4 * (50 * 64 * 2 + 1)
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.least_time(f, b, peak)
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
