"""lib/flops.py against hand-counted totals."""

import json
import os

from lib import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_resnet50_convolutions_by_hand():
    cfg = _config("resnet50")
    convs, features = flops.resnet_convs(cfg)
    assert features == 2048
    assert len(convs) == 1 + 16 * 3 + 4          # stem, 16 blocks, 4 shortcuts
    by_name = {c[0]: c for c in convs}
    # stem: 3 -> 64, 7x7, 224 -> 112
    assert by_name["conv1"][1:] == (3, 64, 7, 2, 112)
    # first block of stage 2 halves the map on its 3x3 (stride on conv2)
    assert by_name["block.3.conv1"][1:] == (256, 128, 1, 1, 56)
    assert by_name["block.3.conv2"][1:] == (128, 128, 3, 2, 28)
    assert by_name["block.3.downsample.conv"][1:] == (256, 512, 1, 2, 28)
    assert by_name["block.15.conv3"][1:] == (512, 2048, 1, 1, 7)


def test_resnet50_total_macs():
    cfg = _config("resnet50")
    fwd = flops.resnet_forward_flops_per_image(cfg)
    # hand count: stem 3*64*49*112^2 = 118,013,952 MACs; the four stages
    # 680,329,216 + 1,036,517,376 + 1,468,006,400 + 808,452,096 wait-free
    # check against the well-known 4.09 GMACs of torchvision's ResNet-50
    # (1000 classes, a 2048 x 1000 head of 2,048,000 MACs: 4,089,184,256)
    assert fwd % 2 == 0
    macs = fwd // 2
    assert macs == 4_089_184_256
    assert flops.resnet_train_flops_per_image(cfg) == 3 * fwd


def test_gpt2_medium_by_hand():
    cfg = _config("gpt2_medium")
    # a layer: q, k, v, o 4 * 1024^2 = 4,194,304; MLP 2 * 1024 * 4096 =
    # 8,388,608; 24 layers = 301,989,888; head 1024 * 50257 = 51,463,168
    assert flops.lm_matmul_params(cfg) == 301_989_888 + 51_463_168
    fwd = flops.lm_forward_flops_per_token(cfg, 1024)
    # attention: 4 * S * d a layer, causal half: 24 * 4 * 1024 * 1024 / 2
    assert fwd == 2 * 353_453_056 + 24 * 2 * 1024 * 1024
    assert flops.lm_train_flops_per_token(cfg, 1024) == 3 * fwd
    assert abs(3 * fwd / 1e9 - 2.27) < 0.01          # GFLOP a token


def test_attention_need_and_bound():
    cfg = _config("gpt2_medium")
    f, b = flops.attention_train_need(cfg, 4, 1024)
    # six causal-half matrix products a layer: 6 * (2*4*1024*1024*1024/2)
    assert f == 24 * 6 * 4 * 1024 * 1024 * 1024
    assert b == 24 * 12 * 4 * 1024 * 1024 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(f, b, peaks)
    assert bound == "flops" and abs(t - f / 197e12) < 1e-12
