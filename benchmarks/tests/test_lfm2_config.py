"""The LFM2 configuration's arithmetic worked by hand, its file against the
catalog row's keys, the new cell's files and readers, and the comparison
that decides ``correct`` against two planted faults."""

import contextlib
import math
import os
from unittest import mock

import numpy as np
import pytest

from harness import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")
BENCH = spec.load_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
CELL = "lfm2_ep4share_s8192_train"

# the catalog row's ``config`` (architectures.jsonl, LFM2-8B-A1B)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}


@pytest.fixture(scope="module")
def loaded():
    params = spec.load_json(os.path.join(CONFIGS,
                                         "lfm2-8b-a1b-ep4share.json"))
    params.update(spec.load_json(os.path.join(
        spec.BENCH_DIR, "traffic", "s8192_b1_loader.json")))
    builder = spec.load_module(os.path.join(CONFIGS, params["builder"]))
    return builder, params


def test_only_the_listed_keys_differ_from_the_published_config(loaded):
    _, params = loaded
    differ = {k for k, v in PUBLISHED.items() if params.get(k, "absent") != v}
    assert differ == {"num_hidden_layers", "layer_types", "num_dense_layers",
                      "vocab_size"}
    assert params["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_dense_layers", "num_experts_held",
                                 "vocab_size"]
    assert set(params["changed"]) == set(params["reduced"])
    # published layers 0, 2, 3, 4, 5: one leading dense layer and a whole
    # period A c c c of the layers that follow
    kept = [PUBLISHED["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert params["layer_types"] == kept == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (params["num_hidden_layers"], params["num_dense_layers"],
            params["num_experts_held"], params["vocab_size"]) == \
        (5, 1, 8, 16384)
    entry, = [c for c in BENCH["configs"] if c["name"] == params["name"]]
    assert entry["reduced"] == params["reduced"]
    assert entry["source"] == params["source"]
    # the guide's floors: four layers after the dense one, 8 experts, an
    # eighth of the vocabulary or more; no width is touched
    assert params["vocab_size"] * 4 == PUBLISHED["vocab_size"]
    for key in ("tie_embedding", "initializer_range", "bias_update_speed",
                "optimizer", "amp"):
        assert key in params["assumed"] and key in params
    assert params["deployment"].startswith("One chip's share of a 4-way")


def test_flops_by_hand(loaded):
    builder, params = loaded
    conv = 2048 * 6144 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    scores = 8192 * (64 + 64) * 32 // 2            # the causal half
    assert (conv, attn, scores) == (16777216, 10485760, 16777216)
    dense, routed = 3 * 2048 * 7168, 1.0 * 3 * 2048 * 1792
    per_token = 4 * conv + (attn + scores) + dense \
        + 4 * (routed + 2048 * 32) + 2048 * 16384
    assert builder.expected_rows_per_token(params) == 1.0
    assert builder.forward_macs(params) == 8192 * per_token
    assert per_token == 216268800
    assert builder.flops_per_sample(params) == 6 * 8192 * per_token
    assert builder.flops_per_sample(params) == pytest.approx(10.63e12,
                                                             rel=1e-3)


def test_first_loss_counts_the_logits_variance(loaded):
    builder, params = loaded
    assert builder.first_loss(params) == pytest.approx(
        math.log(16384) + 2048 * 0.02 ** 2 / 2)
    assert builder.first_loss(params) == pytest.approx(10.114, abs=1e-3)


def test_grouped_attention_kernel_costs_by_hand(loaded):
    builder, params = loaded
    costs = builder.kernel_costs(params)
    square = 8192 * 8192
    # per query head: forward 2 products, backward 5, of 2 * (S*S/2) * 64
    assert costs["flops"] == 1 * 32 * square * 7 * 64
    # per query head: q read twice, o written and read, dO read, dq
    # written, and the two float32 rows written and read; per KEY/VALUE
    # head (8, not 32): k and v read twice, dk and dv written
    head_bytes = 6 * 8192 * 64 * 2 + 4 * 8192 * 4
    kv_head_bytes = 6 * 8192 * 64 * 2
    assert costs["bytes"] == 32 * head_bytes + 8 * kv_head_bytes
    # a lowering that repeats K and V to 32 heads moves 3.9 times the K/V
    # bytes counted here
    # compute binds: 4.88 ms at 197 TFLOP/s against 0.31 ms at 819 GB/s
    assert costs["flops"] / 197e12 == pytest.approx(4.883e-3, rel=1e-3)
    assert costs["bytes"] / 819e9 == pytest.approx(0.313e-3, rel=1e-2)
    assert builder.expects_in_hlo(params) == ["tpu_custom_call"]


def test_the_batch_is_ids_and_their_shift(loaded):
    builder, params = loaded
    batch = builder.make_batch(np.random.default_rng(2 ** 31 + 5), params)
    assert batch["ids"].shape == batch["labels"].shape == (1, 8192, 1)
    assert batch["ids"].dtype == np.int64
    np.testing.assert_array_equal(batch["ids"][:, 1:], batch["labels"][:, :-1])
    assert 0 <= batch["ids"].min() and batch["ids"].max() < 16384


@pytest.mark.parametrize("name,kind,cls", [
    ("layers.3.feed_forward.experts.gate", "feed_forward.experts.gate",
     "routed"),
    ("layers.1.feed_forward.experts.router", "feed_forward.experts.router",
     "routed"),
    ("layers.0.feed_forward.up_proj", "feed_forward.up_proj", "dense"),
    ("layers.2.conv.conv", "conv.conv", "dense"),
    ("layers.1.self_attn.k_layernorm", "self_attn.k_layernorm", "dense"),
    ("embed_tokens", "embed_tokens", "dense"),
])
def test_every_leaf_has_a_kind_and_a_limit(loaded, name, kind, cls):
    builder, _ = loaded
    assert builder.leaf_kind(name) == (kind, cls)
    assert 0 < builder.CHANGE_LIMITS[cls] < 1     # 1 = state left unchanged
    assert 0.00190 < builder.LOAD_LIMIT < 0.00499   # the two readings
    assert 8.4e-3 < builder.TOKEN_LOSS_LIMIT < 5.43e-2    # the two readings
    assert 3.1e-4 < builder.LOSS_LIMIT < 1.99e-2


def test_the_new_cell_is_an_entry_and_a_traffic_file():
    entry, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("lfm2-8b-a1b-ep4share", "s8192_b1_loader", 1)
    assert len(entry["why"]) <= 200
    params = spec.load_cell(CELL).params()
    assert (params["batch"], params["seq_len"], params["wrap"],
            params["loader_capacity"], params["pool"], params["feed"]) == \
        (1, 8192, "none", 2, 4, "loader")
    assert entry in BENCH["workloads"]


def test_new_layer_metrics_read_nothing_without_a_trace(loaded):
    """The three new readers and the five the cell was appended to: with no
    trace (an untraced run, or a program without the scopes, as the parent
    is) each returns None and raises nothing."""
    builder, params = loaded
    ctx = {"trace": None, "peaks": None, "builder": builder,
           "params": params}
    for name in ("short_conv_ms_per_step", "gqa_attn_ms_per_step",
                 "gqa_attn_roofline"):
        entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"]
        assert entry["moves"] == "samples_per_s_per_chip"
    for name in ("short_conv_ms_per_step", "gqa_attn_ms_per_step",
                 "gqa_attn_roofline", "flash_fwd_ms_per_step",
                 "flash_dq_ms_per_step", "flash_dkv_ms_per_step",
                 "moe_experts_ms_per_step", "moe_route_dispatch_ms_per_step"):
        entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in entry["workloads"]
        module = spec.load_module(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".py"))
        assert module.read(ctx) is None


# -- the comparison against planted faults ---------------------------------------

def _readings(builder, params, plant=None):
    """The comparison's readings at a small size on the CPU, float32 on
    both sides: the program's forward and backward (through ``Executor``,
    learning rate 0, so the state stays) against the reference's, with
    ``plant`` applied to the PROGRAM's lowering."""
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import lfm2_moe

    small = {**params, **params["tiny"], "seq_len": 128, "batch": 2}
    cfg = lfm2_moe.Lfm2MoeConfig(max_seq_len=128, **{
        k: small[k] for k in builder.MODEL_KEYS})
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = lfm2_moe.build_train(
            cfg, optimizer=fluid.optimizer.SGD(learning_rate=0.0))
    batch = builder.make_batch(np.random.default_rng(5), small)
    names = [p.name for p in main.global_block().all_parameters()]
    fetch = [handles["loss"], handles["token_loss"]] + \
        [main._grad_name_map.get(n, n + "@GRAD") for n in names] + \
        handles["expert_loads"]
    with fluid.scope_guard(fluid.Scope()):
        scope = fluid.global_scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = {n: jnp.asarray(np.array(scope.find_var(n)))
                   for n in names}
        for i, bias in enumerate(handles["select_biases"],
                                 cfg.num_dense_layers):
            weights["select_bias.%d" % i] = jnp.asarray(
                np.array(scope.find_var(bias.name)))
        with (plant() if plant else contextlib.nullcontext()):
            got = exe.run(main, feed=batch, fetch_list=fetch)
    rcfg = {k: v for k, v in small.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}
    rcfg["layer_types"] = small["layer_types"]
    want_loss, want_tokens, want_grads, want_loads = \
        builder._reference().loss_and_grads(
            weights, jnp.asarray(batch["ids"][..., 0]),
            jnp.asarray(batch["labels"][..., 0]), rcfg)
    grads = dict(zip(names, got[2:2 + len(names)]))
    assignments = small["batch"] * 128 * small["num_experts_per_tok"]
    moved = [builder.moved_share(load, want, assignments)
             for load, want in zip(got[2 + len(names):], want_loads)]
    # a gradient off its reference, as a step of SGD would carry it: the
    # same ratio ``off_expected_change`` reads for a parameter
    off = {n: [float(np.linalg.norm(grads[n] - np.asarray(want_grads[n])) /
                     max(float(np.linalg.norm(want_grads[n])), 1e-30))] * 3
           for n in names}
    want_tokens = np.asarray(want_tokens)
    return {"loss_err": abs(float(got[0][0]) - float(want_loss)) /
            float(want_loss),
            "token_loss_err": float(
                np.linalg.norm(got[1][..., 0] - want_tokens) /
                np.linalg.norm(want_tokens)),
            "moved": sum(moved) / len(moved), "off": off}


def _heads_paired_by_remainder():
    """Query head ``h`` reads key/value head ``h % H_kv`` instead of ``h //
    G``: the pairing a wrong index map gives."""
    import jax.numpy as jnp
    from paddle_tpu.fluid.ops import pallas_ops

    real = pallas_ops.flash_attention_lse

    def wrong(q, k, v, *rest):
        n, n_kv = q.shape[0] // 2, k.shape[0] // 2       # a batch of 2
        rows = jnp.asarray([(i // n) * n_kv + (i % n) % n_kv
                            for i in range(q.shape[0])])
        return real(q, k[rows], v[rows], *rest)
    return mock.patch.object(pallas_ops, "flash_attention_lse", wrong)


def _taps_reversed():
    from paddle_tpu.fluid.ops import decoder_ops

    real = decoder_ops.gated_short_conv
    return mock.patch.object(decoder_ops, "gated_short_conv",
                             lambda bcx, w: real(bcx, w[:, ::-1]))


def test_the_comparison_passes_the_program(loaded):
    builder, params = loaded
    readings = _readings(builder, params)
    assert builder.held_to_limits(readings) == []
    assert readings["token_loss_err"] < 1e-5 and readings["moved"] == 0


@pytest.mark.parametrize("plant,named", [
    (_heads_paired_by_remainder, "self_attn"), (_taps_reversed, "conv.conv")],
    ids=["kv_heads_paired_h_mod_8", "taps_reversed"])
def test_a_planted_fault_fails_a_named_limit(loaded, plant, named):
    """Each fault moves the loss by less than a percent on untrained
    weights; it is the leaves' change that names it: the planted layer's
    own tensors are off their expected change by more than the limit."""
    builder, params = loaded
    faults = builder.held_to_limits(_readings(builder, params, plant))
    assert faults, "the comparison let the planted fault pass"
    assert any(named in f and "expected change" in f for f in faults), faults
