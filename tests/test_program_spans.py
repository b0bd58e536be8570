"""The program's own measurement (PR 26): ``telemetry.span`` regions as
``fluid.*`` events of a ``jax.profiler`` trace with no flag set, the compile
counters fed by the executor's ``jax.monitoring`` listener, and the names the
lowering leaves in the compiled step (op role scopes, Pallas kernel names).
"""

import collections
import glob
import itertools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import profiler, telemetry
from paddle_tpu.fluid.ops import pallas_ops

SPANS = ("fluid.step", "fluid.feed_wait", "fluid.feed_stage",
         "fluid.dispatch", "fluid.enqueue", "fluid.compile")


def _train_program(with_loader):
    main, startup = fluid.Program(), fluid.Program()
    loader = None
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        if with_loader:
            loader = fluid.DataLoader.from_generator(
                feed_list=[x, y], capacity=2, iterable=False)
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.01).minimize(loss)
    return main, startup, loss, loader


def _batches(n):
    rng = np.random.default_rng(0)
    return [{"x": rng.standard_normal((4, 8), dtype=np.float32),
             "y": rng.standard_normal((4, 1), dtype=np.float32)}
            for _ in range(n)]


def _fluid_events(trace_dir):
    """``[(line index, name, start ns, end ns, labels)]`` of the trace's
    ``fluid.*`` events; a line of the host plane is a thread."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            out += [(i, e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for e in line.events if e.name.startswith("fluid.")]
    return out


@pytest.fixture(scope="module")
def loader_trace(tmp_path_factory):
    """Three loader-fed steps of a tiny training program under
    ``jax.profiler``, with no flag of ours set."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    main, startup, loss, loader = _train_program(with_loader=True)
    pool = _batches(2)
    loader.set_batch_generator(lambda: itertools.cycle(pool))
    telemetry.reset_step_events()
    compiles = telemetry.registry().counter("xla_backend_compiles_total")
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        before = compiles.value(why="dispatch")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            loader.start()
            for _ in range(3):
                out = exe.run(main, fetch_list=[loss], return_numpy=False)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
            loader.reset()
    return {"events": _fluid_events(trace_dir),
            "ring": telemetry.step_events(),
            "dispatch_compiles": compiles.value(why="dispatch") - before}


def _named(events, name):
    return sorted((e for e in events if e[1] == name), key=lambda e: e[2])


def _inside(inner, outer):
    return inner[0] == outer[0] and outer[2] <= inner[2] and \
        inner[3] <= outer[3]


def test_step_spans_nest_on_the_consumer_thread(loader_trace):
    events = loader_trace["events"]
    steps = _named(events, "fluid.step")
    assert len(steps) == 3
    assert [s[4]["step_num"] for s in steps] == \
        [steps[0][4]["step_num"] + i for i in range(3)]
    for name in ("fluid.feed_wait", "fluid.dispatch", "fluid.enqueue"):
        spans = _named(events, name)
        assert len(spans) == 3, name
        for step, span in zip(steps, spans):
            assert _inside(span, step), (name, span, step)
            assert span[4]["step"] == step[4]["step_num"]
    for dispatch, enqueue in zip(_named(events, "fluid.dispatch"),
                                 _named(events, "fluid.enqueue")):
        assert _inside(enqueue, dispatch)
    assert [w[4]["batch"] for w in _named(events, "fluid.feed_wait")] == \
        [0, 1, 2]


def test_feed_stage_is_on_the_worker_thread_and_shares_a_batch(loader_trace):
    events = loader_trace["events"]
    stages = _named(events, "fluid.feed_stage")
    consumer = _named(events, "fluid.step")[0][0]
    assert stages and all(s[0] != consumer for s in stages)
    waited = {w[4]["batch"] for w in _named(events, "fluid.feed_wait")}
    assert waited <= {s[4]["batch"] for s in stages}
    assert all(s[4]["bytes"] == 4 * 8 * 4 + 4 * 4 for s in stages
               if s[4]["batch"] in waited)


def test_a_fresh_executable_shows_one_dispatch_compile(loader_trace):
    compiles = _named(loader_trace["events"], "fluid.compile")
    assert len(compiles) == 1
    assert compiles[0][4]["why"] == "dispatch"
    assert re.fullmatch(r"[0-9a-f]+:k1", compiles[0][4]["sig"])
    first = _named(loader_trace["events"], "fluid.enqueue")[0]
    assert _inside(compiles[0], first)
    fresh = [d[4]["fresh"] for d in
             _named(loader_trace["events"], "fluid.dispatch")]
    assert [bool(f) for f in fresh] == [True, False, False]
    assert loader_trace["dispatch_compiles"] >= 1


def test_no_span_record_in_the_ring_with_span_records_off(loader_trace):
    assert not telemetry.spans_enabled()
    assert loader_trace["ring"]      # the dispatch records are there
    assert not [e for e in loader_trace["ring"] if e.get("kind") == "span"]


def test_span_records_carry_labels_and_thread_when_on():
    telemetry.reset_step_events()
    telemetry.enable_spans()
    try:
        with telemetry.span("feed_stage", batch=3) as staging:
            staging.label(bytes=17)
    finally:
        telemetry.enable_spans(False)
    (rec,) = telemetry.step_events()
    assert rec["kind"] == "span" and rec["span"] == "feed_stage"
    assert rec["batch"] == 3 and rec["bytes"] == 17 and rec["k"] == 0
    assert rec["dur_ns"] >= 0 and rec["wall_ns"] > 0 and rec["tid"]
    telemetry.reset_step_events()


def test_recompile_of_a_step_that_has_run_is_counted():
    """jit compiles the same step again when a feed arrives committed to
    the device: ``exe.compile_count()`` does not see it (one Fluid-level
    build), ``xla_backend_compiles_total{why=recompile}`` does."""
    main, startup, loss, _ = _train_program(with_loader=False)
    compiles = telemetry.registry().counter("xla_backend_compiles_total")
    seconds = telemetry.registry().counter("xla_compile_seconds_total")
    (batch,) = _batches(1)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        before = {w: compiles.value(why=w)
                  for w in ("dispatch", "recompile", "introspection")}
        trace_s = seconds.value(phase="trace", why="dispatch")
        exe.run(main, feed=batch, fetch_list=[loss])
        built = exe.compile_count()
        assert compiles.value(why="dispatch") == before["dispatch"] + 1
        assert seconds.value(phase="trace", why="dispatch") > trace_s
        exe.run(main, feed=batch, fetch_list=[loss])
        assert compiles.value(why="recompile") == before["recompile"]
        committed = {k: jax.device_put(v, jax.devices("cpu")[0])
                     for k, v in batch.items()}
        exe.run(main, feed=committed, fetch_list=[loss])
        assert compiles.value(why="recompile") == before["recompile"] + 1
        assert exe.compile_count() == built
        exe.compiled_hlo(main, feed=batch, fetch_list=[loss])
        assert compiles.value(why="introspection") == \
            before["introspection"] + 1


def test_the_compile_cache_keys_on_scopes_and_not_on_the_callers_stack():
    """Scope names are metadata; a cache keyed without them serves another
    checkout's names, and one keyed on whole Python stacks misses whenever
    the same step is lowered from another call site (PERF.md, PR 26)."""
    fluid.Executor(fluid.CPUPlace())
    assert jax.config.jax_compilation_cache_include_metadata_in_key

    def fresh_step():       # a new function each time: traced anew
        def step(x):
            with jax.named_scope("role_fwd"):
                return jnp.tanh(x) * 2
        return step

    def from_here():
        return jax.jit(fresh_step()).lower(jnp.ones(4)).as_text(
            debug_info=True)

    def from_deeper():
        return (lambda: from_here())()

    # what the key is computed from: the module with its locations
    assert from_here() == from_deeper()
    assert "role_fwd" in from_here()
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 10)
    try:
        assert from_here() != from_deeper()     # JAX's default: the stack
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)


FLUID_SCOPE = re.compile(r"fluid_[A-Za-z0-9_]+")


def test_every_instruction_of_a_step_sits_under_its_ops_role():
    main, startup, loss, _ = _train_program(with_loader=False)
    (batch,) = _batches(1)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        hlo = exe.compiled_hlo(main, feed=batch, fetch_list=[loss])
    scopes = profiler.step_scopes()
    assert scopes and all("%" + name in hlo or name in hlo
                          for name in scopes)
    seen = set()
    for op_name in scopes.values():
        # an op_name may join several ops' names with ';'
        for part in op_name.split(";"):
            op = FLUID_SCOPE.search(part)
            if op is None:
                continue
            role = re.search(r"role_(fwd|bwd|opt)", part)
            assert role, part
            # the role is the scope right outside the op's
            assert "/%s/%s" % (role.group(0), op.group(0)) in part, part
            want = "role_bwd" if op.group(0).endswith("_grad") else \
                "role_opt" if op.group(0) == "fluid_sgd" else "role_fwd"
            assert role.group(0) == want, part
            seen.add(role.group(0))
    assert seen == {"role_fwd", "role_bwd", "role_opt"}
    # what the benchmark and costmodel.op_attribution read is what it was:
    # the first fluid_ match of an op_name is the op's own scope
    first = {FLUID_SCOPE.search(v).group(0) for v in scopes.values()
             if FLUID_SCOPE.search(v)}
    assert {"fluid_mul", "fluid_mul_grad", "fluid_sgd",
            "fluid_mean"} <= first
    assert not [f for f in first if f.startswith("fluid_role")]


def test_role_scope_of_every_op_role():
    from paddle_tpu.fluid.framework import OpRole
    from paddle_tpu.fluid.lowering import role_scope

    assert role_scope(OpRole.Forward) == "role_fwd"
    assert role_scope(OpRole.Forward | OpRole.Loss) == "role_fwd"
    assert role_scope(OpRole.Backward) == "role_bwd"
    assert role_scope(OpRole.Backward | OpRole.Loss) == "role_bwd"
    assert role_scope(OpRole.Optimize) == "role_opt"
    assert role_scope(OpRole.LRSched) == "role_opt"
    assert role_scope(OpRole.Optimize | OpRole.LRSched) == "role_opt"


def _flash_loss(q, k, v):
    return pallas_ops.flash_attention(q, k, v, None, 0.125).sum()


def _flash_args(sharding=None, seq=256):
    shape = jax.ShapeDtypeStruct((2, seq, 64), jnp.bfloat16,
                                 sharding=sharding)
    return shape, shape, shape


# a head of 256 rows is one tile (one backward kernel), one of 1024 two tiles
# a side (the dQ pass, then the dK/dV pass)
BACKWARD_KERNELS = {256: ["flash_bwd"], 1024: ["flash_dkv", "flash_dq"]}


@pytest.mark.parametrize("seq", sorted(BACKWARD_KERNELS))
def test_flash_kernels_are_named_in_op_names(seq):
    text = jax.jit(jax.grad(_flash_loss, argnums=(0, 1, 2))).lower(
        *_flash_args(seq=seq)).as_text(debug_info=True)
    for name in ["flash_fwd"] + BACKWARD_KERNELS[seq]:
        assert "/%s/" % name in text, name


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("seq", sorted(BACKWARD_KERNELS))
def test_flash_kernels_name_their_tpu_instructions(one_chip, seq):
    """Compiled for a v5e (no chip needed): XLA:TPU names each Mosaic
    custom call's instruction after the kernel, which is what a device
    trace's ``XLA Ops`` events show."""
    text = jax.jit(jax.grad(_flash_loss, argnums=(0, 1, 2))).lower(
        *_flash_args(one_chip, seq)).compile().as_text()
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert sorted(calls) == BACKWARD_KERNELS[seq] + ["flash_fwd"], calls


def test_grouped_flash_kernels_compile_at_published_widths(one_chip):
    """32 query heads over 8 key/value heads of 64 at S=8192, causal, bf16
    (the grouped-query cell's attention layer), compiled for a v5e: Mosaic
    takes the three kernels at the tiles the chooser picks, K and V enter
    them at 8 heads, and dK / dV leave the dK/dV pass as float32 parts a
    query head, summed outside it."""
    def loss(q, k, v):
        return pallas_ops.flash_attention(q, k, v, None, 0.125, True) \
            .astype(jnp.float32).sum()

    def arg(heads):
        return jax.ShapeDtypeStruct((heads, 8192, 64), jnp.bfloat16,
                                    sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(32), arg(8), arg(8)).compile()
    text = compiled.as_text()
    calls = {name: line for name, line in re.findall(
        r"%(\w+?)(?:\.\d+)? = ([^\n]*custom_call_target="
        r'"tpu_custom_call"[^\n]*)', text)}
    assert sorted(calls) == ["flash_dkv", "flash_dq", "flash_fwd"]
    for name, line in calls.items():
        layouts = line.split("operand_layout_constraints={")[1]
        assert layouts.count("bf16[8,8192,64]") == 2, (name, layouts[:400])
    assert calls["flash_dkv"].startswith(
        "(f32[32,8192,64]") and calls["flash_dkv"].count(
            "f32[32,8192,64]") >= 2
    assert [tuple(o.shape) for o in jax.tree.leaves(compiled.out_info)] == \
        [(32, 8192, 64), (8, 8192, 64), (8, 8192, 64)]


# -- the flash residual path, compiled for a v5e (no chip needed) -----------

LAYERS, BATCH, SEQ, HEADS = 3, 8, 512, 4


def _bert_stack(with_lse):
    """A small BERT pretraining step at the flash cell's attention shapes
    per head (S=512, D=64, pure-bf16 AMP, attention dropout off).
    ``with_lse=False`` strips the op's ``LSE`` slot before the backward is
    appended: the program as it was built before the slot existed, whose
    grad ops replay the forward."""
    from paddle_tpu import models
    from paddle_tpu.fluid.layers import rnn

    cfg = models.bert.BertConfig(
        vocab_size=512, hidden_size=64 * HEADS, num_layers=LAYERS,
        num_heads=HEADS, ffn_size=512, max_position=SEQ, type_vocab_size=2,
        hidden_dropout=0.0, attn_dropout=0.0, max_seq_len=SEQ)
    real = rnn.fused_attention

    def without_lse(*args, **kwargs):
        out = real(*args, **kwargs)
        del out.block.ops[-1].outputs["LSE"]
        return out

    main, startup = fluid.Program(), fluid.Program()
    fluid.layers.fused_attention = real if with_lse else without_lse
    try:
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            opt = fluid.contrib.mixed_precision.decorate(
                fluid.optimizer.SGD(0.01), use_pure_bf16=True)
            loss = models.bert.build_pretrain(cfg, optimizer=opt,
                                              max_pred_per_seq=4)["loss"]
    finally:
        fluid.layers.fused_attention = real
    rng = np.random.default_rng(0)
    feed = {
        "src_ids": rng.integers(0, 512, (BATCH, SEQ, 1), dtype=np.int64),
        "pos_ids": np.tile(np.arange(SEQ, dtype=np.int64)[None, :, None],
                           (BATCH, 1, 1)),
        "sent_ids": np.zeros((BATCH, SEQ, 1), np.int64),
        "input_mask": np.ones((BATCH, SEQ, 1), np.float32),
        "mask_pos": (rng.integers(0, SEQ, (BATCH, 4))
                     + np.arange(BATCH)[:, None] * SEQ)
        .reshape(-1, 1).astype(np.int32),
        "mask_label": rng.integers(0, 512, (BATCH * 4, 1), dtype=np.int64),
        "nsp_label": rng.integers(0, 2, (BATCH, 1), dtype=np.int64),
    }
    return main, startup, loss, feed


def _compile_step_for(sharding, main, startup, loss, feed, dump=None):
    """The executor's jitted step of ``main``, lowered on shapes placed
    on the described chip: XLA:TPU and Mosaic compile it here (``dump``: a
    directory for XLA's dump of it)."""
    from paddle_tpu.fluid import executor

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        compiled, feed_vals = exe._resolve_compiled(main, feed, [loss],
                                                    scope, None)
        args = (executor._scope_state(scope, compiled.state_mut),
                executor._scope_state(scope, compiled.state_ro),
                tuple(feed_vals), np.int32(0))
        shapes = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=sharding), args)
        return compiled._jitted.lower(*shapes).compile(
            compiler_options=None if dump is None else {"xla_dump_to": dump})


def _hbm_reserve(dump):
    """The bytes of the step's ``preallocated-temp`` allocations in HBM in
    XLA's buffer assignment under ``dump``: what the chip reserves for its
    temporaries (``tools/step_memory.py``)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "step_memory", os.path.join(os.path.dirname(__file__), "..",
                                    "tools", "step_memory.py"))
    step_memory = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(step_memory)
    path = max(glob.glob(os.path.join(
        dump, "*after_optimizations-buffer-assignment.txt")),
        key=os.path.getsize)
    return sum(size for size, _ in step_memory.temp_buffers(path).values())


def _mosaic_calls(executable):
    return sorted(re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*"
                             r'custom_call_target="tpu_custom_call"',
                             executable.as_text()))


def _tiles_counted():
    """``{(kernel, block_q, block_k): calls traced}`` so far, summed over
    the counter's other labels (``stats``, ``bias``)."""
    values = telemetry.registry().snapshot()["flash_tiles_total"]["values"]
    counted = collections.Counter()
    for v in values:
        counted[tuple(v["labels"][n]
                      for n in ("kernel", "block_q", "block_k"))] += v["value"]
    return counted


@pytest.fixture(scope="module")
def bert_stack_steps(one_chip):
    """The compiled step with and without the ``LSE`` slot, and under
    ``"tiles"`` what ``flash_tiles_total`` counted while the first was
    traced."""
    before = _tiles_counted()
    steps = {True: _compile_step_for(one_chip, *_bert_stack(True))}
    steps["tiles"] = _tiles_counted() - before
    steps[False] = _compile_step_for(one_chip, *_bert_stack(False))
    return steps


def test_training_step_holds_one_flash_forward_per_layer(bert_stack_steps):
    """N attention layers compile to N ``flash_fwd`` and N ``flash_bwd``
    Mosaic calls (S=512: a head is one tile, dQ, dK and dV come from one
    kernel); the replay compiled 2N / N, because XLA cannot merge two
    custom calls as it merges replayed HLO."""
    kernels = ["flash_bwd", "flash_fwd"]
    assert _mosaic_calls(bert_stack_steps[True]) == sorted(kernels * LAYERS)
    assert _mosaic_calls(bert_stack_steps[False]) == sorted(
        kernels * LAYERS + ["flash_fwd"] * LAYERS)


def test_flash_cell_shape_compiles_with_one_tile_a_head(bert_stack_steps):
    """At the flash cell's attention shape (S=512, D=64, bf16, the padding
    mask as a bias) the chooser gives every kernel ONE 512 x 512 tile a
    head, so the backward is the fused kernel and neither pass of the
    pair, and the STEP compiles for the v5e with them (inside a step XLA
    may park a kernel's operand in VMEM, so a kernel that compiles alone
    proves nothing): one traced call a layer and kernel."""
    assert bert_stack_steps["tiles"] == {
        (kernel, 512, 512): LAYERS for kernel in ("fwd", "bwd")}


def test_flash_step_reads_the_projections_outputs_in_place(bert_stack_steps):
    """The small flash step compiled by XLA:TPU and Mosaic: every
    ``flash_fwd`` and ``flash_bwd`` custom call takes Q, K and V as ``[B,
    S, H * D]`` (Mosaic accepts the blocks of 128 lanes, a pair of heads of
    64 a cell, and the lane slices inside) and writes its outputs the same
    way; no instruction sits under a ``fluid_transpose2`` scope and none
    has the shape of split heads."""
    text = bert_stack_steps[True].as_text()
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = ([^\n]*?) custom-call\("
                       r'[^\n]*custom_call_target="tpu_custom_call", '
                       r"operand_layout_constraints=\{([^\n]*?)\}, "
                       r"frontend_attributes", text)
    assert sorted(c[0] for c in calls) == sorted(
        ["flash_bwd", "flash_fwd"] * LAYERS)
    in_place = "[%d,%d,%d]" % (BATCH, SEQ, HEADS * 64)
    for name, result, operands in calls:
        assert operands.count(in_place) == (3 if name == "flash_fwd" else 4)
        assert result.count(in_place) == (1 if name == "flash_fwd" else 3)
    assert "fluid_transpose2" not in text
    for split in ("[%d,%d,%d,64]" % (BATCH, HEADS, SEQ),
                  "[%d,%d,%d,64]" % (BATCH, SEQ, HEADS),
                  "[%d,%d,64]" % (BATCH * HEADS, SEQ)):
        assert split not in text, split


def test_lse_residual_costs_its_own_bytes_and_no_more(bert_stack_steps):
    """Handing the LSE from the forward op to the grad op costs the step's
    temporaries the statistic's own bytes a layer.  At this shape (a head
    is one tile) the kernels write and read it as a lane-dense ``[BH, 1,
    S]`` row, the size of its numbers.  A multi-pass shape's ``[BH, S, 1]``
    column XLA:TPU holds with the size-1 dimension padded to 128 lanes
    (128 times the numbers), and would keep THAT buffer alive from forward
    to backward if the lowering did not hand on a lane-dense ``[BH, S]``
    behind ``optimization_barrier``s (pallas_ops
    ``_forward_keeping_lse``)."""
    new, replay = (bert_stack_steps[w].memory_analysis().temp_size_in_bytes
                   for w in (True, False))
    # [B, H, S] float32 in the (8, 128) tiling: H rounds up to 8 sublanes
    lse_bytes = BATCH * -(-HEADS // 8) * 8 * SEQ * 4
    # two schedules of one step differ by a few hundred KB whatever they
    # hold (here 0.34 MB, and 0.06-0.42 MB at the flash cell's size); kept
    # naively every layer holds BATCH * HEADS * SEQ * 128 * 4 = 8.4 MB
    slack = 1 << 20
    assert new - replay <= LAYERS * lse_bytes + slack, (new, replay)


# -- the routed experts' rungs, compiled for a v5e (no chip needed) ----------

EXPERT_LAYERS = 2


def _expert_stack(with_kept):
    """A decoder of one dense and two expert layers at a share's shapes in
    small (2048 tokens choosing 6 of 64 experts, 8 held: row buffers of
    3072 | 12288 rows), pure-bf16 AMP.  ``with_kept=False`` strips the
    op's ``Kept`` slot before the backward is appended: the program as it
    was built before the slot existed, whose grad ops replay the
    forward."""
    from paddle_tpu import models
    from paddle_tpu.fluid.layers import rnn

    cfg = models.deepseek_v3.DeepseekV3Config(
        vocab_size=512, hidden_size=512, num_hidden_layers=1 + EXPERT_LAYERS,
        num_attention_heads=2, kv_lora_rank=64, intermediate_size=512,
        moe_intermediate_size=256, n_routed_experts=64,
        num_experts_per_tok=6, n_routed_experts_held=8, max_seq_len=2048)
    real = rnn.routed_experts

    def without_kept(*args, **kwargs):
        outs = real(*args, **kwargs)
        del outs[0].block.ops[-1].outputs["Kept"]
        return outs

    main, startup = fluid.Program(), fluid.Program()
    fluid.layers.routed_experts = real if with_kept else without_kept
    try:
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            opt = fluid.contrib.mixed_precision.decorate(
                fluid.optimizer.SGD(0.01), use_pure_bf16=True)
            loss = models.deepseek_v3.build_train(cfg, optimizer=opt)["loss"]
    finally:
        fluid.layers.routed_experts = real
    ids = np.random.default_rng(0).integers(0, 512, (1, 2048 + 1))
    feed = {"ids": ids[:, :-1, None].astype(np.int64),
            "labels": ids[:, 1:, None].astype(np.int64)}
    return main, startup, loss, feed


def _conditionals(executable):
    return len(re.findall(r" conditional\(", executable.as_text()))


@pytest.fixture(scope="module")
def monkeypatch_module():
    patch = pytest.MonkeyPatch()
    yield patch
    patch.undo()


@pytest.fixture(scope="module")
def expert_stack_steps(one_chip, monkeypatch_module, tmp_path_factory):
    """The compiled step with the ``Kept`` slot, without it, and (``"one
    rung"``) with the rungs taken away: the layer as it was before it had
    any; ``"dumps"``: XLA's dumps of the first and the last."""
    from paddle_tpu.fluid.ops import decoder_ops

    dumps = {w: str(tmp_path_factory.mktemp("expert_stack"))
             for w in (True, "one rung")}
    steps = {True: _compile_step_for(one_chip, *_expert_stack(True),
                                     dump=dumps[True]),
             False: _compile_step_for(one_chip, *_expert_stack(False))}
    monkeypatch_module.setattr(
        decoder_ops, "_rungs", lambda T, top_k, n_held, E: (T * top_k,))
    steps["one rung"] = _compile_step_for(one_chip, *_expert_stack(True),
                                          dump=dumps["one rung"])
    steps["dumps"] = dumps
    return steps


def test_training_step_holds_one_conditional_a_layer_and_pass(
        expert_stack_steps):
    """An expert layer with two rungs compiles to ONE forward and ONE
    backward conditional: the forward op hands its first rung's rows to
    the grad op through ``Kept``.  A grad op that replays the forward
    compiles the forward conditional a second time (XLA merges replayed
    HLO, never two conditionals); one rung compiles none."""
    assert _conditionals(expert_stack_steps[True]) == 2 * EXPERT_LAYERS
    assert _conditionals(expert_stack_steps[False]) == 3 * EXPERT_LAYERS
    assert _conditionals(expert_stack_steps["one rung"]) == 0


def test_rungs_cost_the_step_no_temporaries(expert_stack_steps):
    """The step's temporaries with two rungs are not above those of the
    layer with worst-case buffers alone.  What is kept from forward to
    backward falls to a quarter (3072 rows of 12288); the last rung's own
    buffers still have their place in the allocation, whichever rung
    runs, so two expert layers gain little and four gain a quarter of the
    step (the Moonlight cell: 4.9 GB against 6.5).  Read as the chip
    reserves them, the HBM ``preallocated-temp`` allocation of XLA's buffer
    assignment: ``temp_size_in_bytes`` counts a buffer that crosses into a
    conditional on both sides (PERF.md section 6), and since the sums by
    token became a Pallas kernel the two-rung step's 204.3 MB reserve
    reads 246.7 MB there against the one-rung step's 220.6 and 239.6
    (before: 196.6 / 233.4 against 257.3 / 276.5)."""
    dumps = expert_stack_steps["dumps"]
    new, old = (_hbm_reserve(dumps[w]) for w in (True, "one rung"))
    assert new <= old, (new, old)


# -- latent attention's kernels at the cell's shapes, compiled for a v5e ------

def test_latent_attention_kernels_fit_the_v5e_at_s4096(one_chip):
    """One sequence of the Moonlight cell's attention (16 heads, S=4096,
    nope 128 + a shared rotary key head of 64, V 128, bf16, causal):
    forward, dQ and dK/dV keep a whole sequence of the other side in VMEM,
    walk it in 512 x 512 tiles and compile as three named Mosaic calls.
    The dK/dV pass's estimate (15.5 MiB and an eighth) is past the
    compiler's 16 MiB scoped default, so that call alone carries a
    ``vmem_limit_bytes``, computed from the estimate."""
    heads, seq = 16, 4096

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v, qr, kr):
        return pallas_ops.flash_attention(
            q, k, v, None, 192 ** -0.5, True, (qr, kr)) \
            .astype(jnp.float32).sum()

    before = _tiles_counted()
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shape(heads, seq, 128), shape(heads, seq, 128),
        shape(heads, seq, 128), shape(heads, seq, 64),
        shape(1, seq, 64))
    assert _tiles_counted() - before == {
        (kernel, 512, 512): 1 for kernel in ("fwd", "dq", "dkv")}
    key = (seq, seq, 128, 128, 64, False, True, 2)
    limits = [pallas_ops._vmem_limit(pallas_ops._vmem_bytes(
        kernel, 512, 512, *key)) for kernel in ("fwd", "dq", "dkv")]
    assert limits[:2] == [None, None] and limits[2] > 16 << 20
    config = lowered.as_text().replace("\\22", '"')
    assert config.count('"scoped_memory_configs"') == 1
    assert '"size": %d' % limits[2] in config
    step = lowered.compile()
    assert _mosaic_calls(step) == ["flash_dkv", "flash_dq", "flash_fwd"]
    # no per-head copy of the shared rotary keys: nothing of [16, 4096, 64]
    # is built from the [1, 4096, 64] operand
    assert "bf16[16,4096,192]" not in step.as_text()
