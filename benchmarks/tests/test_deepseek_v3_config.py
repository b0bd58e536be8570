"""The Moonlight configuration's arithmetic worked by hand, its file
against the catalog row's keys, and the two new cells' files."""

import math
import os

import pytest

from harness import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")
BENCH = spec.load_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))

# the catalog row's ``config`` (architectures.jsonl, Moonlight-16B-A3B)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}


@pytest.fixture(scope="module")
def loaded():
    params = spec.load_json(os.path.join(
        CONFIGS, "moonlight-16b-a3b-ep8share.json"))
    params.update(spec.load_json(os.path.join(
        spec.BENCH_DIR, "traffic", "s4096_b1_loader.json")))
    builder = spec.load_module(os.path.join(CONFIGS, params["builder"]))
    return builder, params


def test_only_the_listed_keys_differ_from_the_published_config(loaded):
    _, params = loaded
    differ = {k for k, v in PUBLISHED.items() if params.get(k, "absent") != v}
    assert differ == {"num_hidden_layers", "vocab_size"}
    assert params["reduced"] == ["num_hidden_layers", "n_routed_experts_held",
                                 "vocab_size"]
    assert (params["num_hidden_layers"], params["n_routed_experts_held"],
            params["vocab_size"]) == (5, 8, 20480)
    entry, = [c for c in BENCH["configs"] if c["name"] == params["name"]]
    assert entry["reduced"] == params["reduced"]
    assert entry["source"] == params["source"]
    # the guide's floors: four layers after the dense one, 8 experts, an
    # eighth of the vocabulary; no width is touched
    assert params["vocab_size"] * 8 == PUBLISHED["vocab_size"]


def test_flops_by_hand(loaded):
    builder, params = loaded
    mla = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    scores = 4096 * (192 + 128) * 16 // 2          # the causal half
    assert mla == 13762560 and scores == 10485760
    shared, routed = 3 * 2048 * 2816, 0.75 * 3 * 2048 * 1408
    per_token = 5 * (mla + scores) + 4 * (shared + routed + 2048 * 64) \
        + 3 * 2048 * 11264 + 2048 * 20480
    assert builder.expected_rows_per_token(params) == 0.75
    assert builder.forward_macs(params) == 4096 * per_token
    assert per_token == pytest.approx(328.0e6, rel=2e-3)
    assert builder.flops_per_sample(params) == 6 * 4096 * per_token
    assert builder.flops_per_sample(params) == pytest.approx(8.06e12,
                                                             rel=2e-3)


def test_first_loss_counts_the_logits_variance(loaded):
    builder, params = loaded
    assert builder.first_loss(params) == pytest.approx(
        math.log(20480) + 2048 * 0.02 ** 2 / 2)
    assert builder.first_loss(params) == pytest.approx(10.337, abs=1e-3)


def test_latent_attention_kernel_costs_by_hand(loaded):
    builder, params = loaded
    costs = builder.kernel_costs(params)
    square = 4096 * 4096
    per_head = square * (192 + 128) + square * (3 * 192 + 2 * 128)
    assert costs["flops"] == 5 * 16 * per_head
    # forward: q 192, k 128, v 128 read, o 128 written; backward: those and
    # dO read, dq 192, dk 128, dv 128 written; rows: lse and delta
    head_bytes = (192 + 128 + 128 + 128) * 4096 * 2 \
        + (192 + 128 + 128 + 128 + 192 + 128 + 128) * 4096 * 2 + 4 * 4096 * 4
    seq_bytes = 3 * 64 * 4096 * 2       # k_pe read twice, dk_pe written
    assert costs["bytes"] == 5 * (16 * head_bytes + seq_bytes)
    # compute binds: 7.85 ms at 197 TFLOP/s against 1.30 ms at 819 GB/s
    assert costs["flops"] / 197e12 == pytest.approx(7.85e-3, rel=1e-2)
    assert costs["bytes"] / 819e9 == pytest.approx(1.296e-3, rel=1e-2)
    assert builder.expects_in_hlo(params) == ["tpu_custom_call"]


def test_the_batch_is_ids_and_their_shift(loaded):
    import numpy as np
    builder, params = loaded
    batch = builder.make_batch(np.random.default_rng(2 ** 31 + 5), params)
    assert batch["ids"].shape == batch["labels"].shape == (1, 4096, 1)
    assert batch["ids"].dtype == np.int64
    np.testing.assert_array_equal(batch["ids"][:, 1:], batch["labels"][:, :-1])
    assert 0 <= batch["ids"].min() and batch["ids"].max() < 20480


@pytest.mark.parametrize("name,kind,cls", [
    ("layers.3.mlp.experts.gate", "mlp.experts.gate", "routed"),
    ("layers.1.mlp.experts.router", "mlp.experts.router", "routed"),
    ("layers.1.mlp.shared_experts.up_proj", "mlp.shared_experts.up_proj",
     "dense"),
    ("layers.0.self_attn.kv_a_layernorm", "self_attn.kv_a_layernorm",
     "dense"),
    ("lm_head", "lm_head", "dense"),
])
def test_every_leaf_has_a_kind_and_a_limit(loaded, name, kind, cls):
    builder, _ = loaded
    assert builder.leaf_kind(name) == (kind, cls)
    assert 0.00302 < builder.LOAD_LIMIT < 0.00583   # the two readings
    assert 0 < builder.CHANGE_LIMITS[cls] < 1     # 1 = state left unchanged


@pytest.mark.parametrize("left,reads", [
    ((), (0, 0, 0)), (("p",), (1, 0, 0)), (("m", "v"), (0, 1, 1))])
def test_a_state_left_unchanged_reads_one(loaded, left, reads):
    """``off_expected_change`` on one Adam step made by hand: the expected
    step reads 0, and whatever of parameter / moment1 / moment2 the step
    left as it was reads exactly 1."""
    import numpy as np
    builder, _ = loaded
    rng = np.random.default_rng(3)
    g, p0, m0 = (rng.normal(size=(8, 16)).astype(np.float32)
                 for _ in range(3))
    v0 = np.square(rng.normal(size=(8, 16))).astype(np.float32)
    rate, b1, b2, eps = np.float32(2e-3), 0.9, 0.999, 1e-8
    after = {"m": b1 * m0 + (1 - b1) * g, "v": b2 * v0 + (1 - b2) * g * g}
    after["p"] = p0 - rate * after["m"] / (np.sqrt(after["v"]) + eps)
    for name, was in zip("pmv", (p0, m0, v0)):
        if name in left:
            after[name] = was
    got = builder.off_expected_change(g, p0, m0, v0, after["p"], after["m"],
                                      after["v"], rate, b1, b2, eps)
    np.testing.assert_allclose(got, reads, atol=2e-4)


@pytest.mark.parametrize("cell,config,traffic,batch,seq", [
    ("moonlight_ep8share_s4096_train", "moonlight-16b-a3b-ep8share",
     "s4096_b1_loader", 1, 4096),
    ("bert_base_s512_dropout", "bert-base-uncased", "s512_b16_loader", 16,
     512),
])
def test_the_new_cells_are_entries_and_traffic_files(cell, config, traffic,
                                                     batch, seq):
    entry, = [w for w in BENCH["workloads"] if w["name"] == cell]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        (config, traffic, 1)
    assert len(entry["why"]) <= 200
    params = spec.load_cell(cell).params()
    assert (params["batch"], params["seq_len"], params["wrap"],
            params["loader_capacity"]) == (batch, seq, "none", 2)


def test_new_layer_metrics_read_nothing_without_a_trace(loaded):
    builder, params = loaded
    ctx = {"trace": None, "peaks": None, "builder": builder,
           "params": params}
    for name in ("moe_experts_ms_per_step", "moe_route_dispatch_ms_per_step",
                 "mla_attn_ms_per_step", "mla_attn_roofline"):
        entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["moonlight_ep8share_s4096_train"]
        module = spec.load_module(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".py"))
        assert module.read(ctx) is None
