"""The operation and byte counts against values worked by hand."""

import json
import os

import pytest

from benchmarks.lib import cells, counts


def _config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet50_forward_macs():
    # stem 118,013,952 + the four stages + 2,048,000 for the classifier
    # (torchvision reports 4.09 GMACs for its v1.5 ResNet-50)
    assert counts.resnet_forward_macs(50, 224, 1000, 64) == 4_089_184_256
    assert counts.resnet_train_flops_per_image(_config("resnet50")) \
        == 6 * 4_089_184_256


@pytest.mark.parametrize("name,params", [
    ("opt-1.3b", 1_315_211_264), ("opt-1.3b-l8", 509_839_360)])
def test_opt_parameter_count(name, params):
    assert counts.decoder_params(_config(name)) == params


def test_opt_l8_flops_per_token_by_hand():
    cfg = _config("opt-1.3b-l8")
    # per layer 4 * 2048^2 + 2 * 2048 * 8192 = 50,331,648; the tied head
    # 50,272 * 2048 = 102,957,056; the position table is not a matmul
    matmul = 8 * 50_331_648 + 102_957_056
    assert counts.decoder_matmul_params(cfg) == matmul
    # causal attention at HALF of T^2: 6 products of 2 * (T^2/2) * 64
    # per head, 32 heads, 8 layers, over T tokens
    attention = 8 * 6 * (2048 * 2048 // 2) * 2 * 32 * 64 / 2048
    assert counts.decoder_train_flops_per_token(cfg, 2048) \
        == 6 * matmul + attention
    # the issue's figure: 26.5 TFLOP for a step of 4 x 2,048
    step = counts.decoder_train_flops_per_token(cfg, 2048) * 4 * 2048
    assert step == pytest.approx(26.5e12, rel=2e-3)


def test_flash_flops_and_bytes_by_hand():
    cfg = _config("opt-1.3b-l8")
    flops, nbytes = counts.flash_step_flops_and_bytes(cfg, 4, 2048)
    assert flops == 4 * 8 * 32 * 6 * 2048 * 2048 * 64
    tensor = 4 * 2048 * 32 * 64 * 2
    assert nbytes == 8 * (12 * tensor + 2 * 4 * 2048 * 32 * 4)
    # compute-bound on a v5e: 197 TFLOP/s against 819 GB/s
    assert flops / 197e12 > nbytes / 819e9
