"""``flops_per_sample`` of both configurations against values worked by
hand, the analytic first losses, and the flash kernels' cost function."""

import math
import os

import pytest

from harness import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def load(name, **traffic):
    params = spec.load_json(os.path.join(CONFIGS, name + ".json"))
    params.update(traffic)
    builder = spec.load_module(os.path.join(CONFIGS, params["builder"]))
    return builder, params


def test_resnet50_flops_by_hand():
    builder, params = load("resnet50-v1.5", batch=256)
    # stem 7x7x3x64 at 112x112; per stage: first block (with its 1x1
    # projection, the 1x1 reduce at the input resolution, the 3x3 carrying
    # the stride) + the remaining identical blocks; classifier 2048x1000
    stem = 49 * 3 * 64 * 112 * 112
    s1 = (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) * 56 * 56 \
        + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256) * 56 * 56
    s2 = 256 * 128 * 56 * 56 + (9 * 128 * 128 + 128 * 512 + 256 * 512) \
        * 28 * 28 + 3 * (512 * 128 + 9 * 128 * 128 + 128 * 512) * 28 * 28
    s3 = 512 * 256 * 28 * 28 + (9 * 256 * 256 + 256 * 1024 + 512 * 1024) \
        * 14 * 14 + 5 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024) * 14 * 14
    s4 = 1024 * 512 * 14 * 14 + (9 * 512 * 512 + 512 * 2048 + 1024 * 2048) \
        * 7 * 7 + 2 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048) * 7 * 7
    macs = stem + s1 + s2 + s3 + s4 + 2048 * 1000
    assert builder.forward_macs(params) == macs == 4089184256
    assert builder.flops_per_sample(params) == 6 * macs     # 24.5 GFLOP
    assert builder.first_loss(params) == pytest.approx(math.log(1000))


@pytest.mark.parametrize("name,seq,batch,per_token", [
    ("bert-base-uncased", 128, 128, 546.32e6),
    ("bert-base-uncased-attndrop0", 512, 32, 571.87e6),
])
def test_bert_flops_by_hand(name, seq, batch, per_token):
    builder, params = load(name, seq_len=seq, batch=batch)
    layer = 4 * 768 * 768 + 2 * 768 * 3072 + 2 * seq * 768
    heads = 20 * (768 * 768 + 768 * 30522) + 768 * 768 + 2 * 768
    macs = 12 * seq * layer + heads
    assert builder.forward_macs(params) == macs
    assert builder.flops_per_sample(params) == 6 * macs
    assert builder.flops_per_sample(params) / seq == \
        pytest.approx(per_token, rel=1e-4)
    assert builder.first_loss(params) == \
        pytest.approx(math.log(30522) + math.log(2))


def test_flash_costs_only_where_the_kernels_are_on_the_path():
    builder, params = load("bert-base-uncased", seq_len=128, batch=128)
    assert builder.kernel_costs(params) is None
    assert builder.expects_in_hlo(params) == []
    builder, params = load("bert-base-uncased-attndrop0", seq_len=512,
                           batch=32)
    assert builder.expects_in_hlo(params) == ["tpu_custom_call"]
    costs = builder.kernel_costs(params)
    calls = 32 * 12 * 12                       # sequences x heads x layers
    assert costs["flops"] == calls * 14 * 512 * 512 * 64
    assert costs["bytes"] == calls * (12 * 512 * 64 * 2 + 4 * 512 * 4) \
        + 32 * 12 * 2 * 512 * 512 * 4


def test_the_two_bert_configurations_differ_in_one_listed_key():
    a = spec.load_json(os.path.join(CONFIGS, "bert-base-uncased.json"))
    b = spec.load_json(os.path.join(CONFIGS,
                                    "bert-base-uncased-attndrop0.json"))
    differ = {k for k in a if a[k] != b[k]}
    assert differ == {"name", "attention_probs_dropout_prob", "reduced",
                      "changed"}
    assert a["reduced"] == []
    assert b["reduced"] == ["attention_probs_dropout_prob"]
