"""HLO-property regression tests (VERDICT r4 item 7): perf-shaped
invariants asserted on the OPTIMIZED compiled HLO over the 8-device CPU
mesh, so collective layouts and fusion behavior are testable without a
TPU.  Substrate: ``Executor.compiled_hlo`` (executor.py), which resolves
the exact executable ``run()`` would use.

Pinned counts are measurements on the repo's fixed jax/XLA build; a
change means the partitioner laid out the composition differently —
justify and re-pin, don't loosen.  (Reference analogue: the transpiler
structure assertions of test_dist_transpiler.py, moved down to the HLO
where TPU perf is actually decided.)
"""

import re

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.transpiler import (ExpertParallelTranspiler,
                                         SequenceParallelTranspiler,
                                         TensorParallelTranspiler)

COLLECTIVES = ("all-reduce", "all-to-all", "collective-permute",
               "all-gather", "reduce-scatter")


def _counts(hlo):
    c = {p: len(re.findall(r"%s\(" % p, hlo)) for p in COLLECTIVES}
    c["convolution"] = len(re.findall(r"convolution\(", hlo))
    return c


def _assert_no_host_transfers(hlo):
    """The step must be device-resident end to end: no infeed/outfeed,
    no host sends/recvs (a host round-trip inside the step stalls
    the device on the host every step)."""
    for bad in ("infeed(", "outfeed(", " send(", " recv(", "send-done(",
                "recv-done("):
        assert bad not in hlo, "host transfer %r inside the step" % bad


def _compile_hlo(build, transpile=None, feed=None, fetch=None):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = build()
    if transpile is not None:
        transpile(main, startup)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        hlo = exe.compiled_hlo(main, feed=feed,
                               fetch_list=[fetch or handles])
    return hlo


def _mlp_build(opt_wrap=None):
    x = fluid.layers.data(name="x", shape=[32], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    h = fluid.layers.fc(x, size=64, act="gelu")
    out = fluid.layers.fc(h, size=32)
    logits = fluid.layers.fc(x + out, size=8)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    opt = fluid.optimizer.SGDOptimizer(0.1)
    if opt_wrap is not None:
        opt = opt_wrap(opt, out)
    opt.minimize(loss)
    return loss


_MLP_FEED = {"x": np.zeros((8, 32), np.float32),
             "label": np.zeros((8, 1), np.int64)}


def test_megatron_pair_exactly_two_allreduces():
    """One Megatron column/row pair at mp=2: EXACTLY one all-reduce in
    the forward (row-parallel partial outputs) and one in the backward
    (column-parallel input grad) — nothing else.  More means GSPMD
    stopped recognizing the pair and fell back to resharding."""
    hlo = _compile_hlo(
        _mlp_build, TensorParallelTranspiler(2).transpile, _MLP_FEED)
    c = _counts(hlo)
    assert c["all-reduce"] == 2, c
    assert c["all-to-all"] == 0 and c["collective-permute"] == 0, c
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0, c
    _assert_no_host_transfers(hlo)


B, S, H, D = 8, 16, 8, 4
DM = H * D


def _attn_build():
    x = fluid.layers.data(name="x", shape=[S, DM], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")

    def heads(t):
        t = fluid.layers.reshape(t, [0, S, H, D])
        return fluid.layers.transpose(t, [0, 2, 1, 3])

    def proj(i, s):
        return fluid.layers.fc(i, size=s, num_flatten_dims=2)

    q, k, v = heads(proj(x, DM)), heads(proj(x, DM)), heads(proj(x, DM))
    c = fluid.layers.fused_attention(q, k, v, scale=D ** -0.5)
    c = fluid.layers.reshape(fluid.layers.transpose(c, [0, 2, 1, 3]),
                             [0, S, DM])
    pooled = fluid.layers.reduce_mean(x + c, dim=1)
    logits = fluid.layers.fc(pooled, size=8)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    return loss


_ATTN_FEED = {"x": np.zeros((B, S, DM), np.float32),
              "label": np.zeros((B, 1), np.int64)}


def test_sp_ring_is_permute_only():
    """Ring SP at sp=4: the sequence exchange is collective-permute
    steps (12 = fwd ring 3 + bwd replay 3 + grad ring accumulation 6 on
    this build) — NO all-to-all, and exactly the boundary all-gathers
    of the loss reduction (4).  An all-to-all appearing here means the
    ring island degraded to a reshard."""
    hlo = _compile_hlo(
        _attn_build, SequenceParallelTranspiler(4, mode="ring").transpile,
        _ATTN_FEED)
    c = _counts(hlo)
    assert c["collective-permute"] == 12, c
    assert c["all-to-all"] == 0, c
    assert c["all-gather"] == 4, c
    _assert_no_host_transfers(hlo)


def test_sp_ulysses_is_all_to_all_only():
    """Ulysses SP at sp=4: head exchange is all-to-alls (8 = 2 fwd +
    replay + grad on this build) — no ring permutes."""
    hlo = _compile_hlo(
        _attn_build,
        SequenceParallelTranspiler(4, mode="ulysses").transpile,
        _ATTN_FEED)
    c = _counts(hlo)
    assert c["all-to-all"] == 8, c
    assert c["collective-permute"] == 0, c
    _assert_no_host_transfers(hlo)


def _moe_build():
    x = fluid.layers.data(name="x", shape=[4, 16], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    moe_out, aux = fluid.layers.switch_moe(x, num_experts=8, ffn_dim=32)
    pooled = fluid.layers.reduce_mean(moe_out, dim=1)
    logits = fluid.layers.fc(pooled, size=8)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label)) + 0.01 * aux
    fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    return loss


_MOE_FEED = {"x": np.zeros((8, 4, 16), np.float32),
             "label": np.zeros((8, 1), np.int64)}


def test_moe_ep_collective_layout():
    """Framework MoE (dense-global einsum formulation) under dp4 x ep2:
    GSPMD lays the dispatch/combine out as all-gather + all-reduce —
    comm volume scales with GLOBAL token count (known gap vs GShard
    all-to-alls, tracked for the shard_map island; the raw kernel path
    in parallel/expert_parallel.py already does a2a, see
    test_expert_parallel.test_moe_uses_all_to_all).  Pin the layout so
    a partitioner regression (e.g. resharding per einsum) is caught."""
    hlo = _compile_hlo(
        _moe_build, ExpertParallelTranspiler(2).transpile, _MOE_FEED)
    c = _counts(hlo)
    assert c["all-reduce"] == 8, c
    assert c["all-gather"] == 7, c
    assert c["collective-permute"] == 0, c
    _assert_no_host_transfers(hlo)


def test_bn_relu_conv_single_pass_and_no_host_transfers():
    """conv + BN(relu) training step: the conv appears exactly twice
    (forward + weight grad; the input is a feed, so no data grad) and
    the channel-statistics reduces number at most 5 (BN fwd sum/sumsq
    2, BN bwd 2, conv bias grad 1) — the r3 two-pass-BN regression
    recomputed centered moments in a second sweep, pushing this to 6+."""
    def build():
        img = fluid.layers.data(name="img", shape=[8, 16, 16],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c = fluid.layers.conv2d(img, num_filters=16, filter_size=3,
                                padding=1)
        b = fluid.layers.batch_norm(c, act="relu")
        pooled = fluid.layers.reduce_mean(b, dim=[2, 3])
        logits = fluid.layers.fc(pooled, size=8)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, label))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        return loss

    feed = {"img": np.zeros((4, 8, 16, 16), np.float32),
            "label": np.zeros((4, 1), np.int64)}
    hlo = _compile_hlo(build, None, feed)
    c = _counts(hlo)
    assert c["convolution"] == 2, c
    stat_reduces = len(re.findall(r"f32\[16\]\{0\} reduce\(", hlo))
    assert stat_reduces <= 5, (stat_reduces, c)
    _assert_no_host_transfers(hlo)


def test_plain_train_step_no_collectives_no_host_transfers():
    """An untranspiled single-device step contains no collectives at all
    and no host transfers (everything else is noise on top of this)."""
    hlo = _compile_hlo(_mlp_build, None, _MLP_FEED)
    c = _counts(hlo)
    assert all(c[p] == 0 for p in COLLECTIVES), c
    _assert_no_host_transfers(hlo)


def _stack_feed(feed, K):
    return {k: np.stack([v] * K) for k, v in feed.items()}


def _compile_window_hlo(build, transpile, feed, K):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = build()
    if transpile is not None:
        transpile(main, startup)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        hlo = exe.compiled_hlo(main, feed=_stack_feed(feed, K),
                               fetch_list=[loss], steps_per_run=K)
    return hlo


def _count_whiles(hlo):
    """while INSTRUCTIONS (each carries condition=/body= operands) —
    computation definitions and metadata lines don't match."""
    return len(re.findall(r"\bwhile\(.*body=", hlo))


def test_window_adds_exactly_one_while_loop_no_host_transfers():
    """A K=16 steps_per_run window lowers to EXACTLY ONE while loop on
    top of the K=1 step (the lax.scan over inner steps — more means the
    scan split or unrolled per step; same count means it constant-folded
    and K stopped amortizing anything), with no host transfers: all K
    steps run device-resident off one dispatch.  Counted RELATIVE to
    the same program's K=1 HLO so loops already inside the step (gather
    lowerings etc.) don't pollute the pin."""
    base = _compile_hlo(_mlp_build, None, _MLP_FEED)
    hlo = _compile_window_hlo(_mlp_build, None, _MLP_FEED, 16)
    assert _count_whiles(hlo) == _count_whiles(base) + 1, \
        (_count_whiles(base), _count_whiles(hlo))
    _assert_no_host_transfers(hlo)
    c = _counts(hlo)
    assert all(c[p] == 0 for p in COLLECTIVES), c


def test_window_mp_collectives_match_k1():
    """Megatron mp=2 under the outer window scan: the scan body is the
    K=1 step, so the HLO carries the SAME collective species and counts
    — the composition pays zero extra communication, it only amortizes
    dispatch — plus exactly the one scan while loop."""
    t = TensorParallelTranspiler(2).transpile
    base_hlo = _compile_hlo(_mlp_build, t, _MLP_FEED)
    hlo = _compile_window_hlo(_mlp_build, t, _MLP_FEED, 16)
    k1, ck = _counts(base_hlo), _counts(hlo)
    del k1["convolution"], ck["convolution"]
    assert ck == k1, (k1, ck)
    assert _count_whiles(hlo) == _count_whiles(base_hlo) + 1
    _assert_no_host_transfers(hlo)


def test_window_ep_collectives_match_k1():
    """Expert parallelism (dense-global einsum MoE, dp4 x ep2 GSPMD
    layout: all-gathers + all-reduces) composes inside the window scan
    with the K=1 step's collective species: nothing becomes an
    all-to-all or a permute, nothing is dropped.  How MANY all-reduces
    XLA:CPU leaves after combining them is its own business inside a
    while body (9 against the flat step's 8 on jaxlib 0.9) and is not
    pinned."""
    t = ExpertParallelTranspiler(2).transpile
    base_hlo = _compile_hlo(_moe_build, t, _MOE_FEED)
    hlo = _compile_window_hlo(_moe_build, t, _MOE_FEED, 8)
    k1, ck = _counts(base_hlo), _counts(hlo)
    species = {p for p in COLLECTIVES if k1[p]}
    assert species == {"all-gather", "all-reduce"}, k1
    assert {p for p in COLLECTIVES if ck[p]} == species, (k1, ck)
    assert _count_whiles(hlo) == _count_whiles(base_hlo) + 1
    _assert_no_host_transfers(hlo)


@pytest.mark.parametrize("path", ["gspmd", "collective"])
def test_dp_loader_feeds_arrive_sharded_zero_reshard(path):
    """Data parallel + program-bound DataLoader, through GSPMD
    (CompiledProgram) and through the program's own collectives
    (GradAllReduce): after the first dispatch binds the plan's feed
    shardings back to the loader, the producer thread stages batches
    ALREADY SHARDED across the 8-device mesh — steady-state dispatches
    perform zero implicit device-to-device reshard transfers (pinned with
    jax's transfer guard, which trips on exactly the
    whole-batch-on-one-device-then-resharded layout this removes)."""
    import jax
    from jax.sharding import NamedSharding
    from paddle_tpu.fluid.transpiler import GradAllReduce

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, size=8, act="relu"))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        loader = fluid.DataLoader.from_generator(feed_list=[x], capacity=4,
                                                 iterable=False)

    rng = np.random.RandomState(0)

    def gen():
        for _ in range(64):
            yield {"x": rng.normal(0, 1, (16, 16)).astype(np.float32)}

    loader.set_batch_generator(gen)
    if path == "gspmd":
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
    else:
        GradAllReduce().transpile(startup_program=startup, main_program=main,
                                  rank=0, endpoints=[], nranks=8)
        compiled = main
    from paddle_tpu.fluid import telemetry
    reputs = telemetry.registry().counter("executor_feed_reputs_total")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        loader.start()
        try:
            # first pull compiles the dp plan and binds its feed
            # shardings back to the loader
            exe.run(compiled, fetch_list=[loss], return_numpy=False)
            sh = loader._consumer_shardings
            assert sh and isinstance(sh["x"], NamedSharding), sh
            assert "dp" in sh["x"].spec
            # drain batches staged BEFORE the binding (ring depth +
            # worker queue + in-hand lookahead <= 8); these may need
            # the dispatch-time placement fixup, counted below
            for _ in range(10):
                exe.run(compiled, fetch_list=[loss], return_numpy=False)
            # steady state: the staged feed is already laid out
            feed = loader.next_feed()
            arr = feed["x"]
            assert isinstance(arr, jax.Array)
            assert not arr.sharding.is_fully_replicated
            assert len(arr.sharding.device_set) == 8, arr.sharding
            # the pin, both halves: dispatching a pre-sharded feed
            # needs zero corrective re-puts AND zero implicit
            # device-to-device transfers (the guard trips on exactly
            # the replicated-then-resharded layout this fix removes)
            r0 = reputs.value()
            with jax.transfer_guard_device_to_device("disallow"):
                for _ in range(3):
                    exe.run(compiled, feed=loader.next_feed(),
                            fetch_list=[loss], return_numpy=False)
            assert reputs.value() == r0, "steady-state feeds resharded"
            # introspection reads the executable that ran, whichever
            # kind of program it was handed
            hlo = exe.compiled_hlo(compiled, feed=feed, fetch_list=[loss])
            assert _counts(hlo)["all-reduce"] >= 1
        finally:
            loader.reset()


def test_train_step_flop_budget_and_remat_control():
    """Chip-free FLOP accounting (Executor.compiled_cost): the counted
    step FLOPs must sit in the classic fwd+bwd band (~3x the analytic
    forward matmul FLOPs — 3.29x measured on this build with
    elementwise noise); a recompute/double-backward regression lands
    >= 5x and is caught here.  Positive control: RecomputeOptimizer
    must RAISE counted FLOPs (it replays the forward by design, +30%
    measured) while the math stays identical."""
    def wrap_remat(opt, out):
        opt = fluid.optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints([out])
        return opt

    def cost(recompute):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 1
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            loss = _mlp_build(opt_wrap=wrap_remat if recompute else None)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            return exe.compiled_cost(main, feed=_MLP_FEED,
                                     fetch_list=[loss])

    B = 8
    fwd_matmul_flops = 2 * (32 * 64 + 64 * 32 + 32 * 8) * B
    plain = cost(recompute=False)
    assert 2.8 * fwd_matmul_flops <= plain["flops"] <= \
        4.0 * fwd_matmul_flops, plain["flops"]
    remat = cost(recompute=True)
    assert remat["flops"] >= 1.1 * plain["flops"], \
        (plain["flops"], remat["flops"])


# ---------------------------------------------------------------------------
# Quantized-collective wire pins (explicit-collective dp path)
# ---------------------------------------------------------------------------

def _grad_allreduce_hlo(precision, K=None):
    """Compiled HLO of a GradAllReduce-transpiled dp train step at the
    given wire precision (one coalesced bucket; the explicit-collective
    shard_map path — introspectable since the ensure_built hook)."""
    from paddle_tpu.fluid.transpiler import GradAllReduce

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[64], dtype="float32")
        pred = fluid.layers.fc(x, size=64)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    GradAllReduce(allreduce_precision=precision).transpile(
        startup_program=startup, main_program=main, rank=0,
        endpoints=[], nranks=0)
    feed = {"x": np.zeros((16, 64), np.float32),
            "y": np.zeros((16, 64), np.float32)}
    if K is not None:
        feed = _stack_feed(feed, K)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        return exe.compiled_hlo(main, feed=feed, fetch_list=[loss],
                                steps_per_run=K)


def _collective_lines(hlo, species):
    return [ln for ln in hlo.splitlines() if ("%s(" % species) in ln]


def test_allreduce_precision_hlo_species_and_payload_dtypes():
    """Pin collective species AND payload element types per precision
    mode:

    - fp32: the gradient sum is all-reduce(s) on f32 — no s8/bf16
      payloads anywhere, no all-to-all;
    - bf16: the payload VALUES are bf16-rounded (the convert pair
      feeding the all-reduce survives) — note this CPU XLA build
      PROMOTES the reduction wire itself back to f32 (reduce-type
      promotion), which is exactly the EQuARX argument for int8's
      explicit exchange: pure data-movement collectives don't get
      promoted;
    - int8: the sum is gone — replaced by the two-phase quantized
      exchange: all-to-all + all-gather with s8 payloads (+ their f32
      scale companions), and NO f32/bf16 all-reduce of gradient size.
    """
    fp32 = _grad_allreduce_hlo("fp32")
    assert _collective_lines(fp32, "all-reduce"), "no gradient all-reduce"
    assert "s8[" not in fp32
    assert "bf16[" not in fp32
    assert not _collective_lines(fp32, "all-to-all")

    bf16 = _grad_allreduce_hlo("bf16")
    assert "bf16[" in bf16, "bf16 mode lost its payload rounding"
    assert "s8[" not in bf16

    int8 = _grad_allreduce_hlo("int8")
    a2a = _collective_lines(int8, "all-to-all")
    ag = _collective_lines(int8, "all-gather")
    assert any("s8[" in ln for ln in a2a), \
        "int8 mode lost its s8 all-to-all payload: %r" % (a2a,)
    assert any("s8[" in ln for ln in ag), \
        "int8 mode lost its s8 all-gather payload: %r" % (ag,)
    # the gradient-sized f32 all-reduce must be GONE (the partial sums
    # happen post-dequant on the 1/N shard, not on the wire); small f32
    # scale companions ride the a2a/all-gather instead
    assert not any("f32[4160]" in ln or "f32[4352]" in ln
                   for ln in _collective_lines(int8, "all-reduce")), int8


def test_int8_window_collective_counts_match_k1():
    """K-window collective-count parity vs K=1 for the int8 quantized
    exchange (the PR 4 pin pattern, now on the explicit-collective
    path): the window scan body traces once, so species and counts are
    identical, plus exactly one extra while loop."""
    base = _grad_allreduce_hlo("int8")
    win = _grad_allreduce_hlo("int8", K=4)
    k1, ck = _counts(base), _counts(win)
    del k1["convolution"], ck["convolution"]
    assert ck == k1, (k1, ck)
    assert _count_whiles(win) == _count_whiles(base) + 1, \
        (_count_whiles(base), _count_whiles(win))
    _assert_no_host_transfers(win)


# ---------------------------------------------------------------------------
# Weight-update sharding pins (reduce-scatter → sharded update → all-gather)
# ---------------------------------------------------------------------------

_WUS_HLO_MEMO = {}


def _wus_hlo(precision, n_buckets=2):
    """Compiled HLO of a weight-update-sharded dp train step: a 3-layer
    MLP with a small fuse limit, so the grads coalesce into
    ``n_buckets`` independent buckets.  Memoized — two tests read the
    fp32 text and an XLA compile is the expensive part."""
    from paddle_tpu.fluid.transpiler import GradAllReduce

    if precision in _WUS_HLO_MEMO:
        return _WUS_HLO_MEMO[precision]

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=64, act="relu")
        h2 = fluid.layers.fc(h, size=32, act="relu")
        pred = fluid.layers.fc(h2, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    # 0.02 MB ≈ 21 KB: the 16 KB fc_0 weight closes bucket 0, the rest
    # coalesce into bucket 1
    GradAllReduce(weight_update_sharding=True, fuse_grad_size_mb=0.02,
                  allreduce_precision=precision).transpile(
        startup_program=startup, main_program=main, rank=0,
        endpoints=[], nranks=8)
    rs_ops = sum(1 for op in main.global_block().ops
                 if op.type == "c_reducescatter")
    assert rs_ops == n_buckets, rs_ops
    feed = {"x": np.zeros((16, 64), np.float32),
            "y": np.zeros((16, 1), np.float32)}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        hlo = exe.compiled_hlo(main, feed=feed, fetch_list=[loss])
    _WUS_HLO_MEMO[precision] = hlo
    return hlo


def test_wus_hlo_species_and_payload_dtypes():
    """Weight-update sharding pins: per-bucket reduce-scatter +
    all-gather replace the gradient all-reduce (the only surviving
    all-reduces are the __dp_mean__ world-size scalars, f32[]), and in
    int8 mode the RS becomes the s8 a2a exchange while the delta
    all-gather keeps its s8 payload."""
    fp32 = _wus_hlo("fp32")
    c = _counts(fp32)
    assert c["reduce-scatter"] == 2, c
    assert c["all-gather"] == 2, c
    assert c["all-to-all"] == 0, c
    # every remaining all-reduce is the dp-mean size scalar — no
    # gradient-sized reduction survives
    for ln in _collective_lines(fp32, "all-reduce"):
        assert " f32[] all-reduce(" in ln, ln
    assert "s8[" not in fp32
    _assert_no_host_transfers(fp32)

    int8 = _wus_hlo("int8")
    c8 = _counts(int8)
    # quantized RS = a2a of (q, scales) per bucket; quantized delta-AG
    # = all-gather of (q, scales) per bucket
    assert c8["all-to-all"] == 4, c8
    assert c8["all-gather"] == 4, c8
    assert c8["reduce-scatter"] == 0, c8
    assert any("s8[" in ln
               for ln in _collective_lines(int8, "all-to-all")), int8
    assert any("s8[" in ln
               for ln in _collective_lines(int8, "all-gather")), int8
    for ln in _collective_lines(int8, "all-reduce"):
        assert " f32[] all-reduce(" in ln, ln


def _hlo_def_use(hlo):
    """name → direct operand names over every instruction line."""
    graph = {}
    for ln in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.-]+)\s*=\s*\S+\s+"
                     r"([\w-]+)\((.*)", ln)
        if not m:
            continue
        name, opcode, rest = m.groups()
        graph[name] = (opcode, re.findall(r"%([\w.-]+)", rest))
    return graph


def _reaches(graph, src, dst):
    """True when ``dst`` is in ``src``'s transitive operand cone (i.e.
    src DEPENDS ON dst)."""
    seen, stack = set(), [src]
    while stack:
        cur = stack.pop()
        if cur == dst:
            return True
        if cur in seen:
            continue
        seen.add(cur)
        stack.extend(graph.get(cur, (None, ()))[1])
    return False


def test_wus_bucket_collectives_schedulable_independently():
    """No serializing dependence chain between buckets: no bucket's
    reduce-scatter depends on any all-gather (an artificial RS→AG→RS
    chain would force the exchanges to run back-to-back), and no
    reduce-scatter depends on another — each bucket's exchange hangs
    only off its own backward producers, so XLA's latency-hiding
    scheduler is free to interleave collective-start/done with the
    remaining backward compute."""
    hlo = _wus_hlo("fp32")
    graph = _hlo_def_use(hlo)
    rs = [n for n, (op, _) in graph.items() if op == "reduce-scatter"]
    ag = [n for n, (op, _) in graph.items() if op == "all-gather"]
    assert len(rs) == 2 and len(ag) == 2, (rs, ag)
    for r in rs:
        for a in ag:
            assert not _reaches(graph, r, a), \
                "reduce-scatter %s serialized behind all-gather %s" % (r, a)
    assert not _reaches(graph, rs[0], rs[1])
    assert not _reaches(graph, rs[1], rs[0])
    # sanity: the graph is not vacuous — each AG DOES depend on a RS
    # (grad shard → sharded update → gathered params)
    for a in ag:
        assert any(_reaches(graph, a, r) for r in rs), a


def test_quantized_allreduce_byte_accounting_pinned():
    """Byte-count pin per precision mode: the shared two-phase
    accounting (quantized_collectives.allreduce_wire_bytes) must give
    int8 ≈ 1/4 fp32 bytes + scale overhead — and stay ≤ 0.30x, the
    acceptance ceiling (block scales included)."""
    from paddle_tpu.fluid.quantized_collectives import (
        DEFAULT_BLOCK_SIZE, allreduce_wire_bytes, block_count)

    numel = 128 * 128 + 128
    fp32 = allreduce_wire_bytes(numel, "fp32")
    bf16 = allreduce_wire_bytes(numel, "bf16")
    int8 = allreduce_wire_bytes(numel, "int8", world_size=8)
    assert fp32 == 2 * 4 * numel
    assert bf16 == fp32 / 2
    # the accounting includes the REAL ring padding quantized_psum
    # transmits: 65 blocks pad to 72 on an 8-ring
    blocks = block_count(numel, DEFAULT_BLOCK_SIZE, world_size=8)
    assert blocks == 72
    assert int8 == 2 * (blocks * DEFAULT_BLOCK_SIZE + 4 * blocks)
    assert int8 / fp32 <= 0.30, int8 / fp32
    # a SMALL bucket on a big ring pays real padding — the honest ratio
    # exceeds the ceiling there (use bigger buckets / fuse_grad_size_mb)
    small = allreduce_wire_bytes(4160, "int8", world_size=8) / \
        allreduce_wire_bytes(4160, "fp32")
    assert small > 0.30, small
    # the ratio approaches 0.25 + 1/block_size as padding amortizes
    big = allreduce_wire_bytes(1 << 20, "int8", world_size=8) / \
        allreduce_wire_bytes(1 << 20, "fp32")
    assert abs(big - (0.25 + 1.0 / DEFAULT_BLOCK_SIZE)) < 1e-3, big
