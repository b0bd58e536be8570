"""The Ouro configuration's arithmetic worked by hand, its file against the
catalog row's keys, its cell's files, and the three readers of the loop's
scopes on a synthetic trace."""

import math
import os

import pytest

from harness import program_spans, spec, trace

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")
BENCH = spec.load_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
CELL = "ouro_pp8stage_s4096_loop4_train"

# the catalog row's ``config`` (architectures.jsonl, Ouro-2.6B)
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


@pytest.fixture(scope="module")
def loaded():
    cell = spec.load_cell(CELL)
    return cell.builder, cell.params()


def test_only_the_listed_key_differs_from_the_published_config(loaded):
    _, params = loaded
    differ = {k for k, v in PUBLISHED.items() if params.get(k, "absent") != v}
    assert differ == {"num_hidden_layers"}
    assert params["reduced"] == ["num_hidden_layers"]
    assert params["num_hidden_layers"] * 8 == PUBLISHED["num_hidden_layers"]
    entry, = [c for c in BENCH["configs"] if c["name"] == params["name"]]
    assert entry["reduced"] == params["reduced"]
    assert entry["source"] == params["source"]
    assert entry["file"] == "benchmarks/configs/%s.json" % params["name"]
    assert set(params["changed"]) == set(params["reduced"])
    for key in ("assumed", "deployment", "source_detail", "tiny"):
        assert params[key], key


def test_the_parameters_by_hand(loaded):
    _, params = loaded
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    total = 6 * layer + 2 * 49152 * 2048 + 2048 + 2049
    assert layer == 51388416 and total == 509661185
    assert params["num_hidden_layers"] * layer == 308330496


def test_flops_by_hand(loaded):
    builder, params = loaded
    application = 51380224 + 4096 * 2048          # matmuls + the causal half
    assert 4 * 2048 * 2048 + 3 * 2048 * 5632 == 51380224
    per_token = 24 * application + 4 * 2048 * 49152
    assert builder.forward_macs(params) == 4096 * per_token
    assert builder.flops_per_sample(params) == 6 * 4096 * per_token
    assert 6 * per_token == pytest.approx(11.02e9, rel=1e-3)
    assert builder.flops_per_sample(params) == pytest.approx(45.1e12,
                                                             rel=2e-3)


def test_first_loss_counts_the_logits_variance_and_the_gates_entropy(loaded):
    builder, params = loaded
    entropy = 0.5 * math.log(2) + 0.25 * math.log(4) + 2 * 0.125 * math.log(8)
    assert builder.untrained_exit_entropy(params) == pytest.approx(entropy)
    assert builder.first_loss(params) == pytest.approx(
        math.log(49152) + 2048 * 0.02 ** 2 / 2 - 0.05 * entropy)
    assert builder.first_loss(params) == pytest.approx(11.1518, abs=1e-3)


def test_attention_kernel_costs_by_hand(loaded):
    builder, params = loaded
    costs = builder.kernel_costs(params)
    square = 4096 * 4096
    # forward two products, backward five, over the causal half: 7 * D
    assert costs["flops"] == 24 * 16 * square * 7 * 128
    # forward: q, k, v read, o written; backward: q, k, v, dO read, dq,
    # dk, dv written; rows: lse and delta written and read
    head_bytes = (4 + 7) * 4096 * 128 * 2 + 4 * 4096 * 4
    assert costs["bytes"] == 24 * 16 * head_bytes
    # compute binds: 29.3 ms at 197 TFLOP/s against 5.4 ms at 819 GB/s
    assert costs["flops"] / 197e12 == pytest.approx(29.3e-3, rel=1e-2)
    assert costs["bytes"] / 819e9 == pytest.approx(5.44e-3, rel=1e-2)
    assert builder.expects_in_hlo(params) == ["tpu_custom_call"]


def test_the_batch_is_ids_and_their_shift(loaded):
    import numpy as np
    builder, params = loaded
    batch = builder.make_batch(np.random.default_rng(2 ** 31 + 5), params)
    assert batch["ids"].shape == batch["labels"].shape == (1, 4096, 1)
    assert batch["ids"].dtype == np.int64
    np.testing.assert_array_equal(batch["ids"][:, 1:], batch["labels"][:, :-1])
    assert 0 <= batch["ids"].min() and batch["ids"].max() < 49152


@pytest.mark.parametrize("name,kind,cls", [
    ("layers.3.self_attn.q_proj", "self_attn.q_proj", "layers"),
    ("layers.5.mlp.down_proj", "mlp.down_proj", "layers"),
    ("layers.0.input_layernorm_2", "input_layernorm_2", "layers"),
    ("norm", "norm", "layers"), ("lm_head", "lm_head", "layers"),
    ("embed_tokens", "embed_tokens", "layers"),
    ("early_exit_gate.w", "early_exit_gate.w", "gate"),
    ("early_exit_gate.b", "early_exit_gate.b", "gate"),
])
def test_every_leaf_has_a_kind_and_a_limit(loaded, name, kind, cls):
    builder, _ = loaded
    assert builder.leaf_kind(name) == (kind, cls)
    # between the largest reading of its class and 1 = a state left
    # unchanged; the precision's limit between the program's ratio and 1 =
    # an all-bfloat16 run
    assert 0.035 < builder.CHANGE_LIMITS["layers"] < 1
    assert 0.083 < builder.CHANGE_LIMITS["gate"] < 1
    assert 0.745 < builder.PRECISION_LIMIT < 1


def test_a_tokens_cross_entropy_error_is_read_per_token():
    """``token_ce_error``: noise that a mean over the tokens hides."""
    import numpy as np
    builder = spec.load_cell(CELL).builder
    want = np.full((4, 1, 1000), 10.0)
    noise = np.tile([1e-2, -1e-2], 500)
    assert builder.token_ce_error(want + noise, want) == pytest.approx(1e-3)
    assert abs((want + noise).mean() / want.mean() - 1) < 1e-12
    assert builder.token_ce_error(want, want) == 0


@pytest.mark.parametrize("left,reads", [
    ((), (0, 0, 0)), (("p",), (1, 0, 0)), (("m", "v"), (0, 1, 1))])
def test_a_state_left_unchanged_reads_one(loaded, left, reads):
    """``off_expected_change`` on one Adam step made by hand: the expected
    step reads 0, and whatever of parameter / moment1 / moment2 the step
    left as it was reads exactly 1; the fourth number is ||g - m0||."""
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
    np.testing.assert_allclose(got[:3], reads, atol=2e-4)
    assert got[3] == pytest.approx(np.linalg.norm(g - m0), rel=1e-5)


def test_the_cell_is_an_entry_over_the_traffic_file_that_is_there():
    entry, = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("ouro-2.6b-pp8stage", "s4096_b1_loader", 1)
    assert len(entry["why"]) <= 200
    params = spec.load_cell(CELL).params()
    assert (params["batch"], params["seq_len"], params["wrap"], params["pool"],
            params["loader_capacity"]) == (1, 4096, "none", 4, 2)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) == 7


# -- the readers of the loop's scopes ------------------------------------------

NEW = ("ut_loop_ms_per_step", "ut_remat_ms_per_step",
       "exit_heads_ms_per_step")
FLASH = ("flash_fwd_ms_per_step", "flash_dq_ms_per_step",
         "flash_dkv_ms_per_step", "flash_attn_ms_per_step",
         "flash_attn_roofline")


def reader(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "layer_metrics",
                                         name + ".py"))


def test_the_metrics_list_the_cell():
    for name in NEW:
        entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["source"], entry["layer"],
                entry["moves"]) == ("ms", "device_trace", "lowering",
                                    "samples_per_s_per_chip")
    for name in FLASH:
        entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert entry["workloads"][-1] == CELL


def test_the_readers_read_nothing_without_a_trace(loaded, monkeypatch):
    builder, params = loaded
    ctx = {"trace": None, "peaks": None, "builder": builder,
           "params": params}
    for name in NEW:
        assert reader(name).read(ctx) is None
    # a traced run of a program that names no instruction (the parent)
    ctx["trace"] = synthetic()
    monkeypatch.setattr(program_spans, "step_scopes", lambda: None)
    for name in NEW:
        assert reader(name).read(ctx) is None
    # ... and of one whose step holds no loop
    monkeypatch.setattr(program_spans, "step_scopes",
                        lambda: {"fusion.1": "jit(fn)/role_fwd/fluid_mul/dot"})
    for name in NEW:
        assert reader(name).read(ctx) is None


PRE = "jit(fn)/role_%s/while/body/closed_call/"
NAMES = {
    "fusion.1": PRE % "fwd" + "ut_loop/role_fwd/fluid_mul/dot_general",
    "flash_fwd.2": PRE % "fwd" + "ut_loop/role_fwd/fluid_fused_attention/"
                   "flash_fwd/pallas_call",
    "fusion.3": PRE % "fwd" + "ut_loop/role_fwd/exit_head/fluid_mul/"
                "dot_general",
    "fusion.4": PRE % "bwd" + "ut_remat/jvp(ut_loop)/role_fwd/fluid_mul/"
                "dot_general",
    "flash_fwd.5": PRE % "bwd" + "ut_remat/jvp(ut_loop)/role_fwd/"
                   "fluid_fused_attention/flash_fwd/pallas_call",
    "fusion.6": PRE % "bwd" + "ut_remat/jvp(ut_loop)/role_fwd/exit_head/"
                "fluid_softmax_with_cross_entropy/exp",
    "fusion.7": PRE % "bwd" + "transpose(jvp(ut_loop))/role_fwd/fluid_mul/"
                "dot_general",
    "flash_dq.8": PRE % "bwd" + "transpose(ut_remat)/jvp(ut_loop)/role_fwd/"
                  "fluid_fused_attention/flash_dq/pallas_call",
    "fusion.9": PRE % "bwd" + "transpose(jvp(ut_loop))/role_fwd/exit_head/"
                "fluid_mul/dot_general",
    "fusion.10": "jit(fn)/role_opt/fluid_adam/mul",
    "fusion.11": "jit(fn)/role_fwd/fluid_lookup_table/gather",
}
MS = {"fusion.1": 10, "flash_fwd.2": 3, "fusion.3": 4, "fusion.4": 11,
      "flash_fwd.5": 3, "fusion.6": 5, "fusion.7": 20, "flash_dq.8": 6,
      "fusion.9": 8, "fusion.10": 7, "fusion.11": 1}


def synthetic(steps=2):
    """``steps`` steps of the operations above, one after the other."""
    reduced, at, ops = trace.Reduced(steps), 0.0, []
    for _ in range(steps):
        for name, ms in MS.items():
            target = "tpu_custom_call" if name.startswith("flash_") else ""
            ops.append(("label", name, at, at + ms * 1e-3, target))
            at += ms * 1e-3
    reduced.devices[0] = {"ops": ops, "begin": 0.0, "end": at,
                          "window_s": at, "busy_s": at, "async_ops": []}
    return reduced


def test_the_readers_on_a_synthetic_trace(loaded, monkeypatch):
    builder, params = loaded
    ctx = {"trace": synthetic(), "peaks": None, "builder": builder,
           "params": params}
    monkeypatch.setattr(program_spans, "step_scopes", lambda: NAMES)
    # every operation of both scans' bodies: all but Adam and the embedding
    assert reader("ut_loop_ms_per_step").read(ctx) == pytest.approx(
        sum(MS.values()) - 7 - 1)
    # the rematerialised forward, its exit head and flash_fwd among it,
    # and none of the transposes (the dq kernel carries transpose(ut_remat))
    assert reader("ut_remat_ms_per_step").read(ctx) == pytest.approx(
        11 + 3 + 5)
    # the exit head: forward, rematerialised and transposed
    assert reader("exit_heads_ms_per_step").read(ctx) == pytest.approx(
        4 + 5 + 8)
    assert reader("flash_fwd_ms_per_step").read(ctx) == pytest.approx(6)
    assert reader("flash_dq_ms_per_step").read(ctx) == pytest.approx(6)


def test_the_comparison_sees_a_dropped_pass(monkeypatch, capsys):
    """The planted fault, through the cell's own comparison at the tiny
    sizes: a gradient that lost ONE pass's contribution to the layers'
    tied leaves (planted on the reference's side, which the comparison
    cannot tell from the program's) puts those leaves over the limit; the
    sound run puts none.  (The limits are the chip's: off it the
    comparison only says which it would have refused.)"""
    import time

    from harness import loop

    cell = spec.load_cell(CELL)          # its builder is a module of its own
    builder = cell.builder
    reference = builder._reference()
    whole = reference.loss_and_grads
    refused = "not held to the chip's limit here: layers."

    def run():
        loop.run_cell(cell, seed=2 ** 31 + 11, seconds=0.5,
                      trace=False, t_start=time.perf_counter(), tiny=True)
        out = capsys.readouterr().out
        lost, = [ln for ln in out.splitlines() if "lost a pass" in ln]
        return [ln for ln in out.splitlines() if ln.startswith(refused)], \
            [float(x) for x in lost.split(", ")[-1].split(" (")[0].split()]

    sound, would_read = run()
    assert not sound
    # what the comparison says a lost pass would read is over the limit
    assert min(would_read) > builder.CHANGE_LIMITS["layers"]

    def dropping(*args, take, **kwargs):
        def lossy(name, grad):
            lose = name.startswith("layers.") and name.endswith("@1")
            return take(name, grad * 0 if lose else grad)
        return whole(*args, take=lossy, **kwargs)

    monkeypatch.setattr(reference, "loss_and_grads", dropping)
    faulty, _ = run()
    assert len(faulty) == 22, faulty     # every tied leaf of the two layers
