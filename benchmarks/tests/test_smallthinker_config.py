"""The SmallThinker configuration's arithmetic worked by hand, its file
against the catalog row's keys, the cell's files and readers, and the
comparison that decides ``correct`` against planted faults.

``BENCHMARK.json`` lists the configuration, the cell
``smallthinker_ep8share_s16384_train`` and the three new readers.  Every
assertion about an entry looks it up by name (``_entries``), never by
position or count, so that a later added cell breaks nothing here."""

import contextlib
import math
import os
from unittest import mock

import numpy as np
import pytest

from harness import spec

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")
BENCH = spec.load_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
CELL = "smallthinker_ep8share_s16384_train"
NEW_READERS = ("window_attn_ms_per_step", "window_attn_roofline",
               "recompute_ms_per_step")
SHARED_READERS = ("flash_fwd_ms_per_step", "flash_dq_ms_per_step",
                  "flash_dkv_ms_per_step", "gqa_attn_ms_per_step",
                  "gqa_attn_roofline", "moe_experts_ms_per_step",
                  "moe_route_dispatch_ms_per_step")

# the catalog row's ``config`` (architectures.jsonl,
# SmallThinker-21BA3B-Instruct)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}


def _entries(section, name):
    """The entries of ``BENCHMARK.json``'s ``section`` called ``name``:
    at most one."""
    found = [e for e in BENCH[section] if e["name"] == name]
    assert len(found) <= 1
    return found


@pytest.fixture(scope="module")
def loaded():
    params = spec.load_json(os.path.join(
        CONFIGS, "smallthinker-21b-a3b-ep8share.json"))
    params.update(spec.load_json(os.path.join(
        spec.BENCH_DIR, "traffic", "s16384_b1_loader.json")))
    builder = spec.load_module(os.path.join(CONFIGS, params["builder"]))
    return builder, params


def test_only_the_listed_keys_differ_from_the_published_config(loaded):
    _, params = loaded
    differ = {k for k, v in PUBLISHED.items() if params.get(k, "absent") != v}
    assert differ == {"num_hidden_layers", "rope_layout",
                      "sliding_window_layout", "vocab_size"}
    assert params["reduced"] == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout",
        "moe_num_primary_experts_held", "vocab_size"]
    assert set(params["changed"]) == set(params["reduced"])
    for key in ("num_hidden_layers", "rope_layout", "sliding_window_layout",
                "vocab_size"):
        assert params["published"][key] == PUBLISHED[key]
    assert params["published"]["moe_num_primary_experts"] == 64
    # published layers 0-3: one whole period, full / window / window / window
    assert params["rope_layout"] == PUBLISHED["rope_layout"][:4] == \
        params["sliding_window_layout"] == [0, 1, 1, 1]
    assert (params["num_hidden_layers"],
            params["moe_num_primary_experts_held"], params["vocab_size"]) == \
        (4, 8, 18992)
    for entry in _entries("configs", params["name"]):
        assert entry["reduced"] == params["reduced"]
        assert entry["source"] == params["source"]
        assert entry["file"] == \
            "benchmarks/configs/smallthinker-21b-a3b-ep8share.json"
    # the guide's floors: one whole period and four layers, 8 experts, an
    # eighth of the vocabulary; no width is touched
    assert params["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("moe_enable_early_router", "hidden_act", "initializer_range",
                "embedding_initializer_range", "optimizer", "amp",
                "recompute"):
        assert key in params["assumed"] and key in params
    # the recipe: 0.02 for every matrix and Adam at a constant rate, as the
    # issue has them; the two values that hold the routers still over a run
    # (the table's own deviation, the rate) each say in ``assumed`` that no
    # source of the model gives them and that they depart from the issue
    assert (params["initializer_range"],
            params["embedding_initializer_range"], params["learning_rate"],
            params["optimizer"]) == (0.02, 1.0, 1e-6, "adam")
    assert "constant" in params["assumed"]["optimizer"]
    for key in ("embedding_initializer_range", "optimizer"):
        assert "NO source of the model gives" in params["assumed"][key]
        assert "ISSUE 39" in params["assumed"][key]
    assert not {"residual_init_layers", "warmup_steps"} & set(params)
    for key in ("attention_bias", "auxiliary_loss", "positions"):
        assert key in params["assumed"]
    assert params["deployment"].startswith("One chip's share of an 8-way")
    assert "idle" in params["changed"]["num_hidden_layers"]
    assert params["parameters"] == 4 * 68326400 + 2 * 48619520 + 2560 == \
        370547200


def test_the_pairs_of_a_full_and_of_a_window_layer(loaded):
    builder, params = loaded
    assert builder.attended_pairs(16384) == 134225920
    assert builder.attended_pairs(16384, 4096) == 58722304
    assert builder.attended_pairs(4096, 4096) == \
        builder.attended_pairs(4096) == 4096 * 4097 // 2
    # by count: query i sees min(i + 1, W) keys
    assert builder.attended_pairs(100, 7) == \
        sum(min(i + 1, 7) for i in range(100))
    # in tiles of 512: 528 of the full triangle, 252 of the band
    tiles = sum(1 for q in range(32) for k in range(32)
                if k <= q and (k + 1) * 512 - 1 > q * 512 - 4096)
    assert (32 * 33 // 2, tiles) == (528, 252)


def test_flops_by_hand(loaded):
    builder, params = loaded
    projections = 2 * 2560 * 3584 + 2 * 2560 * 512
    routed = 0.75 * 3 * 2560 * 768 + 2560 * 64
    assert builder.expected_rows_per_token(params) == 0.75
    per_token = 4 * (projections + routed) + 2560 * 18992
    pairs = 134225920 + 3 * 58722304
    assert builder.forward_macs(params) == \
        16384 * per_token + pairs * 2 * 128 * 28
    assert builder.flops_per_sample(params) == \
        6 * builder.forward_macs(params)
    assert builder.flops_per_sample(params) == pytest.approx(28.18e12,
                                                             rel=1e-3)


def test_first_loss_counts_the_logits_variance(loaded):
    builder, params = loaded
    assert builder.first_loss(params) == pytest.approx(
        math.log(18992) + 2560 * 0.02 ** 2 / 2)
    assert builder.first_loss(params) == pytest.approx(10.364, abs=1e-3)


def test_attention_kernel_costs_by_hand(loaded):
    builder, params = loaded
    costs = builder.kernel_costs(params)
    # per query head 14 * D FLOPs a pair: forward 2 products, backward 5
    assert costs["flops"] == 28 * 14 * 128 * (134225920 + 3 * 58722304)
    assert costs["window"]["flops"] == 28 * 14 * 128 * 3 * 58722304
    # per query head: q read twice, o written and read, dO read, dq
    # written, and the two float32 rows written and read; per KEY/VALUE
    # head (4, not 28): k and v read twice, dk and dv written
    head_bytes = 6 * 16384 * 128 * 2 + 4 * 16384 * 4
    kv_head_bytes = 6 * 16384 * 128 * 2
    layer = 28 * head_bytes + 4 * kv_head_bytes
    assert costs["bytes"] == 4 * layer and \
        costs["window"]["bytes"] == 3 * layer
    # compute binds: 79.0 ms at 197 TFLOP/s against 4.0 ms at 819 GB/s
    assert costs["flops"] / 197e12 == pytest.approx(79.04e-3, rel=1e-3)
    assert costs["window"]["flops"] / 197e12 == pytest.approx(44.86e-3,
                                                              rel=1e-3)
    assert costs["bytes"] / 819e9 == pytest.approx(4.0e-3, rel=2e-2)
    assert builder.expects_in_hlo(params) == ["tpu_custom_call"]


def test_the_batch_is_ids_and_their_shift(loaded):
    builder, params = loaded
    batch = builder.make_batch(np.random.default_rng(2 ** 31 + 5), params)
    assert batch["ids"].shape == batch["labels"].shape == (1, 16384, 1)
    assert batch["ids"].dtype == np.int64
    np.testing.assert_array_equal(batch["ids"][:, 1:], batch["labels"][:, :-1])
    assert 0 <= batch["ids"].min() and batch["ids"].max() < 18992


@pytest.mark.parametrize("name,kind,cls", [
    ("layers.3.block_sparse_moe.experts.gate",
     "block_sparse_moe.experts.gate", "routed"),
    ("layers.1.block_sparse_moe.experts.router",
     "block_sparse_moe.experts.router", "routed"),
    ("layers.0.self_attn.q_proj", "self_attn.q_proj", "dense"),
    ("layers.2.input_layernorm", "input_layernorm", "dense"),
    ("embed_tokens", "embed_tokens", "dense"), ("lm_head", "lm_head",
                                                "dense"),
])
def test_every_leaf_has_a_kind_and_a_limit(loaded, name, kind, cls):
    builder, _ = loaded
    assert builder.leaf_kind(name) == (kind, cls)
    # each limit between its two readings on the chip (PERF.md section 6)
    # (the program's largest reading, the window off's on its worst leaf)
    assert {"routed": 0.058, "dense": 0.048}[cls] < \
        builder.CHANGE_LIMITS[cls] < \
        {"routed": 0.327, "dense": 0.70}[cls]     # 1 = state left unchanged
    assert 0 < builder.FIRST_LAYER_LOAD_LIMIT < 0.00166
    assert 0.00029 < builder.LOAD_LIMIT < 0.00185
    assert 4.12e-4 < builder.TOKEN_LOSS_LIMIT < 8.54e-3
    assert 5.80e-6 < builder.LOSS_LIMIT == 1e-3
    assert list(builder.PASSES)[0] == "float32" and \
        set(builder.PASSES) == {"float32", "bfloat16", "bfloat16_router",
                                "window_off"}


def test_the_cell_is_an_entry_over_its_traffic_file(loaded):
    _, params = loaded
    assert (params["batch"], params["seq_len"], params["wrap"],
            params["loader_capacity"], params["pool"], params["feed"]) == \
        (1, 16384, "none", 2, 4, "loader")
    assert params["seq_len"] == params["max_position_embeddings"]
    assert _entries("configs", params["name"])
    assert _entries("workloads", CELL)
    for entry in _entries("workloads", CELL):
        assert (entry["config"], entry["traffic"], entry["chips"]) == \
            ("smallthinker-21b-a3b-ep8share", "s16384_b1_loader", 1)
        assert len(entry["why"]) <= 200
        assert "12288" in entry["why"] and "1536" in entry["why"]
        assert spec.load_cell(CELL).params()["seq_len"] == 16384


def test_new_layer_metrics_read_nothing_without_a_trace(loaded):
    """The three new readers and the seven the cell shares: with
    no trace (an untraced run) each returns None and raises nothing; and
    from a traced program without the scopes (the parent: no
    ``attn_window``, no ``rematted_computation`` in any ``op_name``) the
    new ones find no operation and return None too."""
    from harness import program_spans

    builder, params = loaded
    ctx = {"trace": None, "peaks": None, "builder": builder,
           "params": params}
    for name in NEW_READERS + SHARED_READERS:
        module = spec.load_module(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".py"))
        assert module.read(ctx) is None
    listed = bool(_entries("workloads", CELL))
    for name in SHARED_READERS:
        entry, = _entries("per_layer", name)
        assert (CELL in entry["workloads"]) == listed
    for name in NEW_READERS:
        assert len(_entries("per_layer", name)) == listed
        for entry in _entries("per_layer", name):
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "samples_per_s_per_chip"

    class Traced:
        steps = 2

        def ops(self):
            return [("fluid_fused_attention", "flash_fwd.1", 0.0, 1.0,
                     "tpu_custom_call"),
                    ("fluid_mul", "fusion.7", 1.0, 2.0, "")]
    scopes = {"flash_fwd.1": "jit(step)/role_fwd/fluid_fused_attention/"
                             "flash_fwd/pallas_call",
              "fusion.7": "jit(step)/role_bwd/fluid_mul_grad/dot_general"}
    traced = dict(ctx, trace=Traced(),
                  peaks={"bf16_flops_per_s": 197e12,
                         "hbm_bytes_per_s": 819e9})
    with mock.patch.object(program_spans, "step_scopes", lambda: scopes):
        for name in NEW_READERS:
            module = spec.load_module(os.path.join(
                spec.BENCH_DIR, "layer_metrics", name + ".py"))
            assert module.read(traced) is None
    # and with them: the windowed kernel alone, the replayed operations alone
    scopes = {"flash_fwd.1": "jit(step)/role_bwd/"
                             "checkpoint/rematted_computation/"
                             "fluid_fused_attention/attn_window/flash_fwd/"
                             "pallas_call",
              "fusion.7": "jit(step)/role_bwd/"
                          "checkpoint/fluid_mul/transpose"}
    with mock.patch.object(program_spans, "step_scopes", lambda: scopes):
        reads = {name: spec.load_module(os.path.join(
            spec.BENCH_DIR, "layer_metrics", name + ".py")).read(traced)
            for name in NEW_READERS}
    assert reads["window_attn_ms_per_step"] == 500.0 == \
        reads["recompute_ms_per_step"]
    costs = builder.kernel_costs(params)["window"]
    assert reads["window_attn_roofline"] == pytest.approx(
        100 * 1e3 * costs["flops"] / 197e12 / 500.0)


# -- the comparison against planted faults ---------------------------------------

def _readings(builder, params, plant=None):
    """The comparison's readings at a small size on the CPU, float32 on
    both sides: the program's forward and backward (through ``Executor``,
    learning rate 0, so the state stays; the ``recompute`` spans on)
    against the reference's, with ``plant`` applied to the PROGRAM's
    lowering."""
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import smallthinker

    small = {**params, **params["tiny"], "seq_len": 128, "batch": 2}
    cfg = smallthinker.SmallThinkerConfig(max_seq_len=128, **{
        k: small[k] for k in builder.MODEL_KEYS})
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = smallthinker.build_train(
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
        with (plant() if plant else contextlib.nullcontext()):
            got = exe.run(main, feed=batch, fetch_list=fetch)
    rcfg = {k: small[k] for k in builder.MODEL_KEYS}
    want_loss, want_tokens, want_grads, want_loads = \
        builder._reference().loss_and_grads(
            weights, jnp.asarray(batch["ids"][..., 0]),
            jnp.asarray(batch["labels"][..., 0]), rcfg)
    grads = dict(zip(names, got[2:2 + len(names)]))
    assignments = small["batch"] * 128 * \
        small["moe_num_active_primary_experts"]
    moved = [builder.moved_share(load, want, assignments)
             for load, want in zip(got[2 + len(names):], want_loads)]
    # a gradient off its reference, as a step of SGD would carry it: the
    # same ratio ``off_expected_change`` reads for a parameter
    off = {n: [float(np.linalg.norm(grads[n] - np.asarray(want_grads[n])) /
                     max(float(np.linalg.norm(want_grads[n])), 1e-30))] * 3
           for n in names}
    want_tokens = np.asarray(want_tokens)
    late = small["sliding_window_size"]

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))
    return {"loss_err": abs(float(got[0][0]) - float(want_loss)) /
            float(want_loss),
            "token_loss_err": rel(got[1][..., 0], want_tokens),
            "late_loss_err": rel(got[1][:, late:, 0], want_tokens[:, late:]),
            "moved": sum(moved) / len(moved), "moved_first": moved[0],
            "off": off}


def _window_off():
    """Every layer plain causal: the window's attribute lost on the way."""
    from paddle_tpu.fluid.ops import pallas_ops
    return mock.patch.object(pallas_ops, "_band", lambda *a: 0)


def _router_fed_late():
    """The router reads the experts' own input: ``RouterX`` dropped."""
    from paddle_tpu.fluid.ops import decoder_ops

    real = decoder_ops._held_part

    def late(*args, router_x=None, **kw):
        return real(*args, **kw)
    return mock.patch.object(decoder_ops, "_held_part", late)


def test_the_comparison_passes_the_program(loaded):
    builder, params = loaded
    readings = _readings(builder, params)
    assert builder.held_to_limits(readings) == []
    assert readings["token_loss_err"] < 1e-5 and readings["moved"] == 0


@pytest.mark.parametrize("plant,named", [
    (_window_off, ("self_attn", "expected change")),
    (_router_fed_late, ("assignments differ",))],
    ids=["window_off", "router_fed_from_the_experts_input"])
def test_a_planted_fault_fails_a_named_limit(loaded, plant, named):
    """Each fault moves the loss by little on untrained weights.  The
    window off is named by the leaves' change: the windowed layers' own
    attention projections are off their expected change by more than the
    limit.  The router fed from the experts' input (which differs from the
    attention's by the attention's small output) is named by the
    assignments it moves."""
    builder, params = loaded
    faults = builder.held_to_limits(_readings(builder, params, plant))
    assert faults, "the comparison let the planted fault pass"
    assert any(all(word in f for word in named) for f in faults), faults


def test_a_first_layer_that_moves_assignments_is_a_router_in_low_precision(
        loaded):
    """The limit that refuses a precision: the first layer's router reads
    float32 in program and reference, so what the all-bfloat16 control
    read there on the chip (0.00166 at the least) is a fault of its own
    whatever the four layers' average says, and what the program read on
    every seed (0) is none."""
    builder, _ = loaded
    sound = {"loss_err": 0.0, "token_loss_err": 0.0, "late_loss_err": 0.0,
             "moved": 0.00029, "moved_first": 0.0, "off": {}}
    assert builder.held_to_limits(sound) == []
    fault, = builder.held_to_limits(dict(sound, moved_first=0.00166))
    assert "first expert layer" in fault and "float32" in fault


def test_the_two_copies_of_the_reference_are_one():
    with open(os.path.join(spec.REPO_DIR, "paddle_tpu", "models",
                           "smallthinker_reference.py")) as f:
        program_side = f.read()
    with open(os.path.join(CONFIGS, "smallthinker_reference.py")) as f:
        assert f.read() == program_side
