"""Benchmark: ResNet-50 training throughput (images/sec/chip) and BERT-base
pretraining throughput (tokens/sec) on the attached TPU — the BASELINE.json
headline metrics.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device",
...extras}.  The reference publishes no training numbers (BASELINE.md), so
vs_baseline is the framework/bare-JAX-control throughput ratio on the same
chip & batch (1.0 == the framework's emitted HLO costs nothing over
hand-written JAX); with --no-control it is the MFU estimate against the
chip's bf16 peak (costmodel.DEVICE_PEAKS).

The chip modes (the default training run and --infer) need a TPU: on any
other backend they end with a JSON error line and exit 3, never a CPU
number.  A section that fails raises: the run ends with a JSON error line
and a non-zero exit.  --hot-path / --serving / --multihost measure host-side
work of the executor and run on the default backend, CPU included.

Measurement protocol (fluid/timing.py): feeds are device-resident jax arrays
rotated across a few prefetched batches — what the DataLoader's background
device_put delivers in a real input pipeline (fluid/reader.py) — the loss is
fetched as a device array per step (return_numpy=False, async dispatch), and
one host read of the last loss fences the window.
"""

import json
import sys

import numpy as np

# training FLOPs estimates (fwd+bwd ~= 3x fwd)
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.09e9
BERT_BASE_PARAMS = 110e6
BERT_TRAIN_FLOPS_PER_TOKEN = 6 * BERT_BASE_PARAMS


def bench_resnet(batch, steps, amp):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            img = fluid.layers.data(name="img", shape=[3, 224, 224],
                                    dtype="float32")
            label = fluid.layers.data(name="label", shape=[1], dtype="int64")
            logits = models.resnet.resnet(img, class_dim=1000, depth=50)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, label))
            opt = fluid.optimizer.MomentumOptimizer(
                learning_rate=0.1, momentum=0.9,
                regularization=fluid.regularizer.L2Decay(1e-4))
            if amp:
                # pure-bf16 activations: no fp32 round trip of the
                # activations through HBM
                opt = fluid.contrib.mixed_precision.decorate(
                    opt, use_pure_bf16=True)
            opt.minimize(loss)

    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feeds = []
        for _ in range(4):  # rotate device-resident batches (≈ prefetch)
            feeds.append({
                "img": jax.device_put(
                    rng.normal(0, 1, (batch, 3, 224, 224)).astype(np.float32),
                    exe._device),
                "label": jax.device_put(
                    rng.randint(0, 1000, (batch, 1)).astype(np.int64),
                    exe._device),
            })
        def step(i):
            return exe.run(main_prog, feed=feeds[i % len(feeds)],
                           fetch_list=[loss], return_numpy=False)

        dt, final_loss = _timed_steps(step, steps, warmup=2)
    assert np.isfinite(final_loss), "non-finite loss in bench"
    img_s = batch * steps / dt
    mfu = img_s * RESNET50_TRAIN_FLOPS_PER_IMG / _peak_bf16_flops()
    return img_s, mfu


def bench_control_resnet(batch, steps):
    """Bare-JAX ResNet-50 v1.5 train step — the control experiment VERDICT
    r2 asked for: same chip, same batch, same architecture/optimizer as
    bench_resnet (models/resnet.py), but hand-written JAX with zero
    framework machinery.  Splits "XLA conv ceiling" from "overhead in the
    framework's emitted HLO".  Mirrors the framework's pure-bf16 mode:
    activations + conv weights bf16, BN statistics/params/optimizer fp32.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    bf16 = jnp.bfloat16
    rs = np.random.RandomState(0)
    params, mom, stats = {}, {}, {}

    def add_conv_bn(name, cin, cout, k):
        fan = cin * k * k
        params[name + ".w"] = rs.normal(
            0, np.sqrt(2.0 / fan), (cout, cin, k, k)).astype(np.float32)
        params[name + ".g"] = np.ones((cout,), np.float32)
        params[name + ".b"] = np.zeros((cout,), np.float32)
        stats[name + ".mu"] = np.zeros((cout,), np.float32)
        stats[name + ".var"] = np.ones((cout,), np.float32)

    # mirror models/resnet.py DEPTH_CFG[50]: stem + 4 stages of bottlenecks
    counts, filters = [3, 4, 6, 3], [64, 128, 256, 512]
    add_conv_bn("stem", 3, 64, 7)
    cin = 64
    for st, count in enumerate(counts):
        for i in range(count):
            nf, base = filters[st], "s%d.%d" % (st, i)
            add_conv_bn(base + ".c0", cin, nf, 1)
            add_conv_bn(base + ".c1", nf, nf, 3)
            add_conv_bn(base + ".c2", nf, nf * 4, 1)
            if cin != nf * 4 or (i == 0 and st > 0):
                add_conv_bn(base + ".sc", cin, nf * 4, 1)
            cin = nf * 4
    params["fc.w"] = rs.uniform(-0.01, 0.01, (cin, 1000)).astype(np.float32)
    params["fc.b"] = np.zeros((1000,), np.float32)
    mom = {k: np.zeros_like(v) for k, v in params.items()}

    def conv_bn(p, s, x, name, stride, act, new_stats):
        w = p[name + ".w"].astype(bf16)
        k = w.shape[2]
        pad = (k - 1) // 2
        y = lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        yf = y.astype(jnp.float32)
        mean = jnp.mean(yf, axis=(0, 2, 3))
        var = jnp.mean(jnp.square(yf), axis=(0, 2, 3)) - jnp.square(mean)
        new_stats[name + ".mu"] = 0.9 * s[name + ".mu"] + 0.1 * mean
        new_stats[name + ".var"] = 0.9 * s[name + ".var"] + 0.1 * var
        scale = p[name + ".g"] * lax.rsqrt(var + 1e-5)
        shift = p[name + ".b"] - mean * scale
        out = y * scale[None, :, None, None].astype(bf16) \
            + shift[None, :, None, None].astype(bf16)
        return jnp.maximum(out, 0) if act else out

    def forward(p, s, img, label):
        new_stats = {}
        x = conv_bn(p, s, img.astype(bf16), "stem", 2, True, new_stats)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
        cin = 64
        for st, count in enumerate(counts):
            for i in range(count):
                nf, base = filters[st], "s%d.%d" % (st, i)
                stride = 2 if i == 0 and st > 0 else 1
                y = conv_bn(p, s, x, base + ".c0", 1, True, new_stats)
                y = conv_bn(p, s, y, base + ".c1", stride, True, new_stats)
                y = conv_bn(p, s, y, base + ".c2", 1, False, new_stats)
                if (base + ".sc.w") in p:
                    sc = conv_bn(p, s, x, base + ".sc", stride, False,
                                 new_stats)
                else:
                    sc = x
                x = jnp.maximum(sc + y, 0)
                cin = nf * 4
        x = jnp.mean(x.astype(jnp.float32), axis=(2, 3))
        logits = x @ p["fc.w"] + p["fc.b"]
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, label, axis=1))
        return loss, new_stats

    def train_step(p, m, s, img, label):
        (loss, new_stats), grads = jax.value_and_grad(
            forward, has_aux=True)(p, s, img, label)
        new_p, new_m = {}, {}
        for k in p:
            v = 0.9 * m[k] + (grads[k] + 1e-4 * p[k])
            new_m[k] = v
            new_p[k] = p[k] - 0.1 * v
        return new_p, new_m, new_stats, loss

    dev = jax.devices()[0]
    p = jax.device_put({k: jnp.asarray(v) for k, v in params.items()}, dev)
    m = jax.device_put({k: jnp.asarray(v) for k, v in mom.items()}, dev)
    s = jax.device_put({k: jnp.asarray(v) for k, v in stats.items()}, dev)
    step_fn = jax.jit(train_step, donate_argnums=(0, 1, 2))
    feeds = []
    for _ in range(2):
        feeds.append((
            jax.device_put(rs.normal(0, 1, (batch, 3, 224, 224))
                           .astype(np.float32), dev),
            jax.device_put(rs.randint(0, 1000, (batch, 1))
                           .astype(np.int64), dev)))

    state = {"p": p, "m": m, "s": s, "loss": None}

    def step(i):
        img, label = feeds[i % len(feeds)]
        state["p"], state["m"], state["s"], loss = step_fn(
            state["p"], state["m"], state["s"], img, label)
        return [loss]

    dt, final_loss = _timed_steps(step, steps, warmup=2)
    assert np.isfinite(final_loss), "non-finite control loss"
    img_s = batch * steps / dt
    mfu = img_s * RESNET50_TRAIN_FLOPS_PER_IMG / _peak_bf16_flops()
    return img_s, mfu


def _pctl(sorted_vals, q):
    """Nearest-rank percentile (q in 0..100) over an already-sorted
    list — one definition shared by every bench section (the same
    convention as tools/metrics_report.percentile)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


def _telemetry_counters():
    """Raw cumulative telemetry reading (process-global registry)."""
    from paddle_tpu.fluid import telemetry
    reg = telemetry.registry()
    plan = reg.counter("executor_plan_lookups_total")
    disp = reg.histogram("executor_dispatch_host_seconds").value()
    return {
        "plan_hits": int(plan.value(result="hit")),
        "plan_misses": int(plan.value(result="miss")),
        "compiles": int(reg.counter("executor_compiles_total").value()),
        "host_syncs": int(reg.counter("host_syncs_total").value()),
        "step_events": telemetry.step_events_recorded(),
        "dispatch_host_seconds_sum": disp["sum"],
        "dispatch_count": disp["count"],
        # self-healing runtime (must stay zero in a healthy bench run)
        "preemptions": int(
            reg.counter("preemption_stops_total").value()),
        "rollbacks": int(reg.counter("rollback_total").value()),
        "storage_retries": int(
            reg.counter("storage_retry_total").value()),
        # input pipeline (absolute gauges — None until a feed ring ran)
        "feed_ring_occupancy": reg.gauge("feed_ring_occupancy").value(),
        "h2d_overlap_frac": reg.gauge("h2d_overlap_frac").value(),
        # optimizer memory + backward/collective overlap (absolute
        # gauges — None until a training dispatch with optimizer state /
        # gradient collectives ran; weight-update sharding drops the
        # bytes ~1/N and bucketed eager emission raises the overlap
        # bound toward 1 - 1/buckets)
        "optimizer_state_bytes":
            reg.gauge("optimizer_state_bytes").value(),
        "comm_bucket_overlap_frac":
            reg.gauge("comm_bucket_overlap_frac").value(),
    }


# absolute gauge keys of _telemetry_counters: reported as-is, never as a
# delta over the section baseline (a gauge difference means nothing)
_GAUGE_KEYS = ("feed_ring_occupancy", "h2d_overlap_frac",
               "optimizer_state_bytes", "comm_bucket_overlap_frac")


def _telemetry_metrics(since=None):
    """Condensed runtime-telemetry summary for the hot-path JSON line
    (tests/test_bench_protocol.py pins these keys).  ``since`` is a
    `_telemetry_counters()` reading taken when the bench section started:
    the emitted values are DELTAS over that baseline, so they speak for
    this section alone (the registry is process-global and cumulative —
    raw values would fold in whatever ran earlier in the process) and
    prove the measured loop ran on the cached-plan path with zero host
    syncs."""
    cur = _telemetry_counters()
    if since is not None:
        cur = {k: cur[k] if k in _GAUGE_KEYS
               else cur[k] - since.get(k, 0) for k in cur}
    cur["dispatch_host_seconds_sum"] = round(
        cur["dispatch_host_seconds_sum"], 6)
    return cur


def _device_fingerprint():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "n_devices": jax.device_count(),
            "jax_version": jax.__version__}


def _peak_bf16_flops():
    """bf16 peak of the attached chip (an unknown device_kind raises)."""
    import jax
    from paddle_tpu.fluid import costmodel
    return costmodel.device_peaks(
        jax.devices()[0].device_kind)["bf16_flops"]


def _timed_steps(step, steps, warmup=2):
    """Shared fence protocol (paddle_tpu/fluid/timing.py)."""
    from paddle_tpu.fluid.timing import timed_steps
    return timed_steps(step, steps, warmup=warmup)


def bench_bert(batch, steps):
    """BERT-base pretraining tokens/sec.  Matmul precision is governed by
    FLAGS_matmul_precision (default: XLA's fastest, bf16 MXU passes), so the
    MFU estimate is against the bf16 peak; --fp32 does not apply here."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    cfg = models.bert.base_config()
    S = cfg.max_seq_len
    n_pred = 20
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            handles = models.bert.build_pretrain(cfg, lr=1e-4,
                                                 max_pred_per_seq=n_pred)
    loss = handles["loss"]
    # bf16 MXU ops with bf16-resident activations (loss math stays fp32
    # inside the CE lowering; params/optimizer state stay fp32)
    main_prog._amp_dtype = "bfloat16"
    main_prog._amp_keep = True

    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feeds = []
        for _ in range(2):
            ids = rng.randint(0, cfg.vocab_size, (batch, S, 1))
            pos = np.tile(np.arange(S)[None, :, None], (batch, 1, 1))
            mask_pos = (rng.randint(0, S, (batch, n_pred))
                        + np.arange(batch)[:, None] * S)
            feeds.append({k: jax.device_put(v, exe._device) for k, v in {
                "src_ids": ids.astype(np.int64),
                "pos_ids": pos.astype(np.int64),
                "sent_ids": np.zeros((batch, S, 1), np.int64),
                "input_mask": np.ones((batch, S, 1), np.float32),
                "mask_pos": mask_pos.reshape(-1, 1).astype(np.int32),
                "mask_label": rng.randint(
                    0, cfg.vocab_size, (batch * n_pred, 1)).astype(np.int64),
                "nsp_label": rng.randint(0, 2, (batch, 1)).astype(np.int64),
            }.items()})
        def step(i):
            return exe.run(main_prog, feed=feeds[i % len(feeds)],
                           fetch_list=[loss], return_numpy=False)

        dt, final_loss = _timed_steps(step, steps, warmup=2)
    assert np.isfinite(final_loss), "non-finite BERT loss in bench"
    tok_s = batch * S * steps / dt
    mfu = tok_s * BERT_TRAIN_FLOPS_PER_TOKEN / _peak_bf16_flops()
    return tok_s, mfu


def bench_nmt(batch, steps):
    """Transformer-NMT (base config: h512/L6+6/ffn2048, S=256) training
    tokens/sec — BASELINE.json config 4.  Tokens counted as sentence-pair
    tokens (src and trg both length S); the MFU estimate uses the exact
    6*N*tokens matmul-parameter decomposition (encoder params touch src
    tokens, decoder+proj params touch trg tokens, both length S, so
    6*B*S*N_total is exact for equal-length pairs; embedding lookups are
    excluded — they are gathers, not MXU work)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    cfg = models.transformer.base_config()
    S = cfg.max_len
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            handles = models.transformer.build_train(cfg, lr=2.0,
                                                     warmup_steps=4000)
    loss = handles["loss"]
    main_prog._amp_dtype = "bfloat16"
    main_prog._amp_keep = True

    h, f = cfg.hidden_size, cfg.ffn_size
    n_matmul = (cfg.num_layers * (4 * h * h + 2 * h * f)      # encoder
                + cfg.num_layers * (8 * h * h + 2 * h * f)    # decoder
                + h * cfg.trg_vocab_size)                     # pre-softmax
    flops_per_tok = 6 * n_matmul

    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feeds = []
        for _ in range(2):
            feeds.append({k: jax.device_put(v, exe._device) for k, v in {
                "src_ids": rng.randint(0, cfg.src_vocab_size,
                                       (batch, S, 1)).astype(np.int64),
                "src_mask": np.ones((batch, S, 1), np.float32),
                "trg_ids": rng.randint(0, cfg.trg_vocab_size,
                                       (batch, S, 1)).astype(np.int64),
                "trg_mask": np.ones((batch, S, 1), np.float32),
                "label": rng.randint(0, cfg.trg_vocab_size,
                                     (batch, S, 1)).astype(np.int64),
            }.items()})

        def step(i):
            return exe.run(main_prog, feed=feeds[i % len(feeds)],
                           fetch_list=[loss], return_numpy=False)

        dt, final_loss = _timed_steps(step, steps, warmup=2)
    assert np.isfinite(final_loss), "non-finite NMT loss in bench"
    tok_s = batch * S * steps / dt
    mfu = tok_s * flops_per_tok / _peak_bf16_flops()
    return tok_s, mfu


def bench_deepfm(batch, steps):
    """DeepFM CTR (base config: 26 fields x 1M-row sparse table, E=10,
    400x3 tower) training examples/sec — BASELINE.json config 5.  This
    workload is embedding-gather-bound, so the dense-tower MFU estimate is
    expected to be tiny; the number that matters is examples/sec."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    cfg = models.deepfm.base_config()
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            handles = models.deepfm.build_train(cfg, lr=1e-3)
    loss = handles["loss"]

    widths = [cfg.num_fields * cfg.embedding_size + cfg.dense_dim]
    widths += list(cfg.layer_sizes) + [1]
    tower_macs = sum(a * b for a, b in zip(widths[:-1], widths[1:]))
    flops_per_ex = 3 * 2 * tower_macs

    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feeds = []
        for _ in range(2):
            feeds.append({k: jax.device_put(v, exe._device) for k, v in {
                "sparse_ids": rng.randint(
                    0, cfg.sparse_feature_dim,
                    (batch, cfg.num_fields, 1)).astype(np.int64),
                "dense_value": rng.rand(
                    batch, cfg.dense_dim).astype(np.float32),
                "label": rng.randint(0, 2, (batch, 1)).astype(np.int64),
            }.items()})

        def step(i):
            return exe.run(main_prog, feed=feeds[i % len(feeds)],
                           fetch_list=[loss], return_numpy=False)

        dt, final_loss = _timed_steps(step, steps, warmup=2)
    assert np.isfinite(final_loss), "non-finite DeepFM loss in bench"
    ex_s = batch * steps / dt
    mfu = ex_s * flops_per_ex / _peak_bf16_flops()
    return ex_s, mfu


def bench_lenet(batch, steps):
    """MNIST LeNet images/sec — BASELINE.json config 1.  Dispatch-bound at
    any reasonable batch (the whole model is <2 MFLOP/img), included so the
    driver artifact covers every BASELINE config."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            handles = models.lenet.build_train(lr=1e-3)
    loss = handles["loss"]

    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        feeds = []
        for _ in range(2):
            feeds.append({
                "img": jax.device_put(rng.normal(
                    0, 1, (batch, 1, 28, 28)).astype(np.float32),
                    exe._device),
                "label": jax.device_put(rng.randint(
                    0, 10, (batch, 1)).astype(np.int64), exe._device),
            })

        def step(i):
            return exe.run(main_prog, feed=feeds[i % len(feeds)],
                           fetch_list=[loss], return_numpy=False)

        dt, final_loss = _timed_steps(step, steps, warmup=2)
    assert np.isfinite(final_loss), "non-finite LeNet loss in bench"
    return batch * steps / dt


def bench_hot_path(steps=2000):
    """Host overhead per cached-hit ``run()`` step (``--hot-path``).

    Times two per-step paths on ONE compiled tiny train step (fc +
    mean + SGD, device-resident feed, async fetches):

    * ``bare_jit``   — the jitted callable invoked directly with
      pre-resolved state (the floor: zero executor involvement);
    * ``plan``       — ``exe.run`` via the cached dispatch plan.

    ``host_overhead_us_per_step`` = plan − bare_jit.  The computation is
    deliberately tiny so the host, not the device, is the bottleneck —
    this measures dispatch, not FLOPs."""
    import time as _time
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.fluid.executor import _scope_state

    tele0 = _telemetry_counters()   # delta baseline for this section

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[64], dtype="float32")
            y = fluid.layers.fc(x, size=64, act="relu")
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)

    rng = np.random.RandomState(0)
    scope = fluid.Scope()
    out = {}
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        xdev = jax.device_put(rng.normal(0, 1, (32, 64)).astype(np.float32),
                              exe._device)
        feed = {"x": xdev}

        def fence(o):
            return float(np.asarray(o[0]).reshape(-1)[0])

        def window(step_fn):
            o = step_fn(0)
            fence(o)                       # drain compile + pipeline
            t0 = _time.perf_counter()
            for i in range(steps):
                o = step_fn(i + 1)
            fence(o)                       # one sync at the end
            return (_time.perf_counter() - t0) / steps

        def run_step(i):
            return exe.run(main_prog, feed=feed, fetch_list=[loss],
                           return_numpy=False)

        # compile + warm every path once; everything below is cached-hit
        window(run_step)
        assert exe._compile_count == 2, \
            "hot-path bench recompiled mid-loop (%d)" % exe._compile_count

        # bare jitted call: the same executable with state threaded
        # through the scope exactly like _dispatch does — the floor the
        # dispatch plan chases (zero key/coerce/plan work, same buffer
        # lifecycle).  (The startup program's block is also in the cache;
        # it fetches nothing.)
        compiled = next(c for c in exe._cache.values() if c.fetch_names)
        ro = _scope_state(scope, compiled.state_ro)

        def bare_step(i):
            fetches, new_state = compiled.fn(
                _scope_state(scope, compiled.state_mut), ro,
                (xdev,), np.int32(i))
            for n, v in zip(compiled.state_out, new_state):
                scope.set_var(n, v)
            return fetches

        # interleave the two paths round-robin and keep per-path minima:
        # the shared host is noisy and this measures HOST work — sampling
        # all paths across the same noise windows makes the deltas honest
        paths = {"bare": bare_step, "plan": run_step}
        best = {k: float("inf") for k in paths}
        for _ in range(5):
            for name, fn in paths.items():
                best[name] = min(best[name], window(fn))
        bare_s, plan_s = best["bare"], best["plan"]

        out = {
            "metric": "executor_hot_path",
            "unit": "us/step (host)",
            "steps": steps,
            "steps_per_sec": round(1.0 / plan_s, 1),
            "bare_jit_us_per_step": round(bare_s * 1e6, 2),
            "plan_us_per_step": round(plan_s * 1e6, 2),
            "host_overhead_us_per_step": round((plan_s - bare_s) * 1e6, 2),
            "value": round((plan_s - bare_s) * 1e6, 2),
            "vs_baseline": round(plan_s / bare_s, 2),
            "vs_baseline_kind": "plan_over_bare_jit_step_time",
            "metrics": _telemetry_metrics(since=tele0),
        }
        # device-cost ledger record of the hot-path step (AFTER the
        # metrics delta so the capture's own compile/events don't skew
        # the hot-path counters): static FLOPs/bytes plus the roofline
        # estimated_step_s — what the step WOULD cost on a device at the
        # configured peak rates, vs the measured host-bound time above
        rec = exe.cost_record(main_prog, feed=feed, fetch_list=[loss],
                              tag="bench:hot_path")
        out["cost"] = None if rec is None else {
            "sig": rec["sig"],
            "flops_per_step": rec["flops"],
            "transcendentals": rec["transcendentals"],
            "bytes_per_step": rec["bytes_accessed"],
            "peak_bytes": rec["peak_bytes"],
            "argument_bytes": rec["argument_bytes"],
            "output_bytes": rec["output_bytes"],
            "temp_bytes": rec["temp_bytes"],
            "instructions": rec["instructions"],
            "fusions": rec["fusions"],
            "collectives": rec["collectives"],
            "estimated_step_s": rec["estimated_step_s"],
            "roofline_peak_flops":
                float(_flags.get_flag("roofline_peak_flops")),
            "roofline_peak_bytes_per_s":
                float(_flags.get_flag("roofline_peak_bytes_per_s")),
        }
    # wire-compression section: gradient-allreduce / a2a bytes by
    # precision (the quantized-collectives acceptance numbers)
    out["comm"] = bench_comm()
    return out


def bench_comm(steps=3):
    """Gradient-allreduce (and MoE-style a2a) wire bytes by precision —
    the ``comm`` section of ``--hot-path``.

    For each ``allreduce_precision`` mode a small dp program (fc
    128→128, grads coalesced into one ~16.5k-element bucket — big
    enough that the ring-padding of the int8 block count, which the
    accounting includes, amortizes) is transpiled with
    ``GradAllReduce`` and stepped on the local mesh; the per-step bytes
    come from the ``collective_bytes_total{species,precision}`` counter
    the executor stamps per dispatch (trace-time exact shapes, the
    two-phase accounting of quantized_collectives.allreduce_wire_bytes
    — block scales included).  The headline ratio is the acceptance
    number: int8 must sit at ≤ 0.30x the fp32 payload."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.fluid.transpiler import GradAllReduce
    from paddle_tpu.fluid.quantized_collectives import (DEFAULT_BLOCK_SIZE,
                                                        PRECISIONS)

    ctr = telemetry.registry().counter("collective_bytes_total")
    ndev = jax.device_count()
    rng = np.random.RandomState(0)
    xs = rng.normal(0, 1, (8 * ndev, 128)).astype(np.float32)
    ys = rng.normal(0, 1, (8 * ndev, 128)).astype(np.float32)

    def _train_fc_model(optimizer, **grad_allreduce_kwargs):
        """Build + transpile + step the ONE fc-128 dp model both the
        allreduce and weight-update-sharding modes measure — the
        equal-wire comparison (wus_fp32_vs_allreduce) is only valid
        while both move byte-identical gradient sets."""
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[128],
                                      dtype="float32")
                y = fluid.layers.data(name="y", shape=[128],
                                      dtype="float32")
                pred = fluid.layers.fc(x, size=128)
                loss = fluid.layers.mean(
                    fluid.layers.square_error_cost(pred, y))
                optimizer.minimize(loss)
        GradAllReduce(**grad_allreduce_kwargs).transpile(
            startup_program=startup, main_program=main, rank=0,
            endpoints=[], nranks=0)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            out = None
            for _ in range(steps):
                out = exe.run(main, feed={"x": xs, "y": ys},
                              fetch_list=[loss], return_numpy=False)
            assert np.isfinite(np.asarray(out[0])).all()

    def allreduce_mode(precision):
        before = ctr.value(species="allreduce", precision=precision)
        _train_fc_model(fluid.optimizer.SGDOptimizer(0.05),
                        allreduce_precision=precision)
        return (ctr.value(species="allreduce", precision=precision)
                - before) / steps

    def a2a_mode(precision):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                block = main.global_block()
                x = fluid.layers.data(name="x", shape=[64],
                                      dtype="float32")
                out = block.create_var(name="a2a_out")
                block.append_op("c_alltoall", inputs={"X": [x]},
                                outputs={"Out": [out]},
                                attrs={"ring_id": 0,
                                       "precision": precision})
        main._use_collective = True
        main._collective_rings = {0: "dp"}
        before = ctr.value(species="a2a", precision=precision)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor()
            for _ in range(steps):
                exe.run(main, feed={"x": xs}, fetch_list=[out],
                        return_numpy=False)
        return (ctr.value(species="a2a", precision=precision)
                - before) / steps

    def wus_mode(precision):
        """Weight-update sharding A/B: the same fc-128 model with Adam,
        the bucket's allreduce replaced by RS + sharded update + AG —
        reports the per-step RS+AG wire bytes (fp32 must equal the
        allreduce's own two-phase movement) and leaves the per-device
        optimizer-state bytes gauge at ~1/N of the replicated Adam
        moments."""
        rs = ctr.value(species="reducescatter", precision=precision)
        ag = ctr.value(species="allgather", precision=precision)
        _train_fc_model(fluid.optimizer.AdamOptimizer(1e-3),
                        allreduce_precision=precision,
                        weight_update_sharding=True)
        return (ctr.value(species="reducescatter", precision=precision)
                - rs
                + ctr.value(species="allgather", precision=precision)
                - ag) / steps

    ar = {p: allreduce_mode(p) for p in PRECISIONS}
    a2a = {p: a2a_mode(p) for p in PRECISIONS}
    # fp32 pins the equal-wire claim; the int8 RS/AG byte composition is
    # pinned analytically (phase_wire_bytes) and by the HLO s8 payload
    # tests — measuring it here would just re-pay two XLA compiles
    wus = {"fp32": wus_mode("fp32")}
    reg = telemetry.registry()
    return {
        "steps": steps,
        "devices": ndev,
        "grad_numel": 128 * 128 + 128,
        "quant_block_size": DEFAULT_BLOCK_SIZE,
        "allreduce_bytes_per_step": ar,
        "a2a_bytes_per_step": a2a,
        # the acceptance ratios: block scales are inside the int8 bytes
        "int8_vs_fp32": round(ar["int8"] / ar["fp32"], 4)
        if ar["fp32"] else None,
        "bf16_vs_fp32": round(ar["bf16"] / ar["fp32"], 4)
        if ar["fp32"] else None,
        "a2a_int8_vs_fp32": round(a2a["int8"] / a2a["fp32"], 4)
        if a2a["fp32"] else None,
        # weight-update sharding: RS+AG wire bytes/step by precision
        # (fp32 == the allreduce's own two phases → ratio 1.0), plus the
        # per-device optimizer-state bytes of the sharded Adam step
        "wus_bytes_per_step": wus,
        "wus_fp32_vs_allreduce": round(wus["fp32"] / ar["fp32"], 4)
        if ar["fp32"] else None,
        "wus_optimizer_state_bytes":
            reg.gauge("optimizer_state_bytes").value(),
        "wus_overlap_frac":
            reg.gauge("comm_bucket_overlap_frac").value(),
    }


def _ring_parity(main_prog, startup, loss, rng, K=4, windows=3):
    """Bit-exact loss parity, ring on vs off: the SAME host batch stream
    trained through the feed ring (depth 2) and through the synchronous
    depth-0 path must produce identical per-step losses under threefry —
    the ring only moves staging off the critical path, it must never
    change what is fed."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.fluid.dataset import stack_batch_windows
    from paddle_tpu.fluid.executor import prefetch_ahead

    feeds_np = [rng.normal(0, 1, (32, 64)).astype(np.float32)
                for _ in range(K * windows)]
    prev_impl = _flags.get_flag("prng_impl")
    _flags.set_flag("prng_impl", "threefry")
    try:
        def run(depth):
            losses = []
            with fluid.scope_guard(fluid.Scope()):
                exe = fluid.Executor()
                exe.run(startup)
                src = prefetch_ahead(
                    lambda d: {k: jax.device_put(v, exe._device)
                               for k, v in d.items()},
                    stack_batch_windows(({"x": f} for f in feeds_np), K),
                    depth=depth)
                for feed in src:
                    out = exe.run_window(main_prog, feed=feed,
                                         fetch_list=[loss], steps_per_run=K,
                                         return_numpy=False)
                    losses.append(np.asarray(out[0]).ravel())
            return np.concatenate(losses)

        return bool(np.array_equal(run(0), run(2)))
    finally:
        _flags.set_flag("prng_impl", prev_impl)


def bench_hot_path_window(inner_steps=2048, ks=(1, 4, 16, 64),
                          focus_k=None):
    """Host overhead per inner step of the multi-step fused training
    loop (``--hot-path --steps-per-run [K]``).

    For each window size K the SAME tiny train step (fc + mean + SGD,
    device-resident feeds) runs ``inner_steps`` inner steps as
    ``inner_steps/K`` fused ``run_window`` dispatches; the floor is the
    bare jitted call of that K's window executable with pre-resolved
    state (zero executor involvement).  ``host_overhead_us_per_step(K)
    = (run_window − bare) / K`` — the executor's per-dispatch work
    amortizes over K inner steps, so the curve must fall ~1/K
    (TF iterations_per_loop; the MLPerf TPU-pod submissions' in-loop
    training).  K=1 runs through run_window too, so the A/B isolates
    the window size, not the code path.

    Also proves the fusion is SEMANTICALLY free: a fresh K=1 run and a
    fresh fused K=16 run of the same program under
    ``FLAGS_prng_impl=threefry`` must produce bit-identical per-step
    losses (``parity_bit_exact``)."""
    import time as _time
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import flags as _flags
    from paddle_tpu.fluid.executor import _scope_state

    ks = sorted(set(ks) | ({int(focus_k)} if focus_k else set()))
    tele0 = _telemetry_counters()   # delta baseline for this section

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 5
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[64], dtype="float32")
            y = fluid.layers.fc(x, size=64, act="relu")
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)

    rng = np.random.RandomState(0)
    xstep = rng.normal(0, 1, (32, 64)).astype(np.float32)

    def fence(o):
        return float(np.asarray(o[0]).reshape(-1)[-1])

    per_k = {}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        for K in ks:
            xK = jax.device_put(np.stack([xstep] * K), exe._device)
            feed = {"x": xK}
            windows = max(1, inner_steps // K)

            def win_step(i):
                return exe.run_window(main_prog, feed=feed,
                                      fetch_list=[loss], steps_per_run=K,
                                      return_numpy=False)

            def window(step_fn):
                o = step_fn(0)
                fence(o)                   # drain compile + pipeline
                t0 = _time.perf_counter()
                for i in range(windows):
                    o = step_fn(i + 1)
                fence(o)                   # one sync at the end
                return (_time.perf_counter() - t0) / windows

            window(win_step)               # compile + warm
            compiled = next(c for c in exe._cache.values()
                            if c.fetch_names and c.steps_per_run == K)
            ro = _scope_state(scope, compiled.state_ro)

            def bare_step(i):
                fetches, new_state = compiled.fn(
                    _scope_state(scope, compiled.state_mut), ro,
                    (xK,), np.int32(i * K))
                for n, v in zip(compiled.state_out, new_state):
                    scope.set_var(n, v)
                return fetches

            # PAIRED rounds (bare then window back to back) so shared-
            # host drift cancels in the difference; the median pair is
            # the overhead estimate, clamped at 0 — at large K the
            # per-step overhead falls below timer resolution
            best = {"bare": float("inf"), "window": float("inf")}
            diffs = []
            for _ in range(5):
                b = window(bare_step)
                w = window(win_step)
                best["bare"] = min(best["bare"], b)
                best["window"] = min(best["window"], w)
                diffs.append(w - b)
            med = sorted(diffs)[len(diffs) // 2]
            per_k[K] = {
                "windows": windows,
                "window_us": round(best["window"] * 1e6, 2),
                "bare_jit_window_us": round(best["bare"] * 1e6, 2),
                "us_per_step": round(best["window"] / K * 1e6, 2),
                "host_overhead_us_per_step": round(
                    max(med, 0.0) / K * 1e6, 3),
            }

    # -- input-pipeline host cost: feed ring vs synchronous staging -------
    # The per_k sweep above uses PRE-STAGED device feeds, so it measures
    # pure dispatch overhead.  Real training feeds come from a host
    # pipeline: K batches stacked + device_put per window.  This section
    # measures what that pipeline adds per inner step with the staging
    # on the consumer's critical path (FLAGS_feed_ring_depth=0, the
    # PR-4 behavior) vs streamed through the async feed ring (depth 2,
    # the default) — the ring figure must sit well below the sync one
    # (stacking + H2D hidden under compute).  A bigger feed (32x1024
    # fp32, 128KB/step) makes the staging cost visible above timer
    # noise on a CPU CI host.
    pipeline = {}
    pipe_prog, pipe_start = fluid.Program(), fluid.Program()
    pipe_prog.random_seed = pipe_start.random_seed = 7
    with fluid.program_guard(pipe_prog, pipe_start):
        with fluid.unique_name.guard():
            px = fluid.layers.data(name="x", shape=[1024], dtype="float32")
            ploss = fluid.layers.mean(
                fluid.layers.fc(px, size=64, act="relu"))
            fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(ploss)
    src_bufs = [rng.normal(0, 1, (32, 1024)).astype(np.float32)
                for _ in range(8)]
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(pipe_start)
        from paddle_tpu.fluid.dataset import stack_batch_windows
        from paddle_tpu.fluid.executor import prefetch_ahead

        def hot_batches(n):
            for i in range(n):
                yield {"x": src_bufs[i % len(src_bufs)]}

        def run_pipe(K, W, depth):
            """Wall seconds per inner step consuming W windows of K
            host batches through the staging pipeline at ring depth
            ``depth`` (None = pre-staged device feeds, the floor)."""
            if depth is None:
                xdev = jax.device_put(np.stack([src_bufs[0]] * K),
                                      exe._device)
                feeds = [{"x": xdev}] * W
            else:
                feeds = prefetch_ahead(
                    lambda d: {k: jax.device_put(v, exe._device)
                               for k, v in d.items()},
                    stack_batch_windows(hot_batches(W * K), K),
                    depth=depth)
            out = None
            t0 = _time.perf_counter()
            for feed in feeds:
                out = exe.run_window(pipe_prog, feed=feed,
                                     fetch_list=[ploss], steps_per_run=K,
                                     return_numpy=False)
            fence(out)
            dt = _time.perf_counter() - t0
            if hasattr(feeds, "close"):
                feeds.close()
            return dt / (W * K)

        for K in [k for k in (16, 64) if k in ks]:
            W = max(4, 512 // K)
            run_pipe(K, 2, 0)      # compile + warm every path
            best = {"prestaged": float("inf"), "sync": float("inf"),
                    "ring": float("inf")}
            for _ in range(3):     # interleaved rounds: shared-host noise
                best["prestaged"] = min(best["prestaged"],
                                        run_pipe(K, W, None))
                best["sync"] = min(best["sync"], run_pipe(K, W, 0))
                best["ring"] = min(best["ring"], run_pipe(K, W, 2))
            sync_oh = max(best["sync"] - best["prestaged"], 0.0) * 1e6
            ring_oh = max(best["ring"] - best["prestaged"], 0.0) * 1e6
            pipeline[str(K)] = {
                "windows": W,
                "prestaged_us_per_step": round(best["prestaged"] * 1e6, 2),
                "sync_us_per_step": round(best["sync"] * 1e6, 2),
                "ring_us_per_step": round(best["ring"] * 1e6, 2),
                "sync_staging_overhead_us_per_step": round(sync_oh, 3),
                "ring_staging_overhead_us_per_step": round(ring_oh, 3),
                # resolution floor as in the dispatch sweep: below
                # ~0.5us/step the difference is timer noise
                "ring_vs_sync": round(sync_oh / max(ring_oh, 0.5), 2),
            }

    # -- ring on/off loss parity (bit-exact, threefry) --------------------
    ring_parity = _ring_parity(main_prog, startup, loss, rng)

    # -- per-step loss parity: K=1 vs fused K=16 (bit-exact, threefry) ----
    parity_k = 16 if 16 in ks else max(ks)
    prev_impl = _flags.get_flag("prng_impl")
    _flags.set_flag("prng_impl", "threefry")
    try:
        pfeeds = [rng.normal(0, 1, (32, 64)).astype(np.float32)
                  for _ in range(parity_k)]
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            l1 = np.concatenate([np.ravel(np.asarray(exe.run(
                main_prog, feed={"x": f}, fetch_list=[loss],
                return_numpy=False)[0])) for f in pfeeds])
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            out = exe.run_window(main_prog, feed={"x": np.stack(pfeeds)},
                                 fetch_list=[loss],
                                 steps_per_run=parity_k)
            lk = np.asarray(out[0]).ravel()
    finally:
        _flags.set_flag("prng_impl", prev_impl)

    focus = int(focus_k) if focus_k else 16
    focus = focus if focus in per_k else max(per_k)
    ov1 = per_k[1]["host_overhead_us_per_step"]
    # resolution floor: below ~0.5us/step the paired-difference estimate
    # is timer noise, so the ratio is a LOWER bound there
    ovk = max(per_k[focus]["host_overhead_us_per_step"], 0.5)
    result = {
        "metric": "executor_hot_path_window",
        "unit": "us/step (host)",
        "inner_steps": inner_steps,
        "per_k": {str(k): v for k, v in per_k.items()},
        "pipeline": pipeline,
        "ring_parity_bit_exact": ring_parity,
        "parity_k": parity_k,
        "parity_bit_exact": bool(np.array_equal(l1, lk)),
        "parity_max_abs_diff": float(np.max(np.abs(l1 - lk)))
        if l1.shape == lk.shape else None,
        "value": per_k[focus]["host_overhead_us_per_step"],
        "vs_baseline": round(ov1 / ovk, 2),
        "vs_baseline_kind":
            "k1_over_k%d_host_overhead_per_step_lower_bound" % focus,
        "metrics": _telemetry_metrics(since=tele0),
    }
    return result


def bench_feed_bound(windows=24, K=8, delay_s=0.002):
    """``--hot-path --feed-bound``: the input pipeline is made the
    bottleneck ON PURPOSE (a synthetic generator sleeping ``delay_s``
    per batch) to exercise and measure the starvation instrumentation —
    the consumer must spend most of the wall waiting (``wait_frac``
    high, ``h2d_overlap_frac`` meaningfully below 1, ring occupancy
    pinned near 0), and the step-events must carry the per-dispatch
    ``data_wait_s`` that tools/metrics_report.py turns into p50/p99
    starvation.  A feed-bound job is the one case the ring cannot
    speed up (the producer IS the critical path) — this mode proves the
    diagnosis story, the ``--steps-per-run`` pipeline section proves
    the speedup story."""
    import time as _time
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.fluid.dataset import stack_batch_windows
    from paddle_tpu.fluid.executor import prefetch_ahead

    tele0 = _telemetry_counters()   # delta baseline for this section

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[64], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=64, act="relu"))
            fluid.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)

    rng = np.random.RandomState(0)
    batch_np = rng.normal(0, 1, (32, 64)).astype(np.float32)

    def slow_batches(n):
        for _ in range(n):
            _time.sleep(delay_s)
            yield {"x": batch_np}

    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor()
        exe.run(startup)
        # warm the window executable OUTSIDE the measured/counted
        # region (compile stalls are not starvation) with a DEVICE
        # feed, twice: the ring stages committed device arrays, and
        # jax's jit cache keys on input committedness — a numpy warm
        # would leave the first ring dispatches paying a re-lowering
        for _ in range(2):
            warm = exe.run_window(
                main_prog,
                feed={"x": jax.device_put(np.stack([batch_np] * K),
                                          exe._device)},
                fetch_list=[loss], steps_per_run=K, return_numpy=False)
            float(np.asarray(warm[0]).reshape(-1)[-1])
        wait0 = telemetry.registry().histogram("data_wait_seconds").value()
        events0 = telemetry.step_events_recorded()
        rings0 = int(telemetry.registry()
                     .counter("feed_ring_windows_total").value())
        src = prefetch_ahead(
            lambda d: {k: jax.device_put(v, exe._device)
                       for k, v in d.items()},
            stack_batch_windows(slow_batches(windows * K), K), depth=2)
        out = None
        t0 = _time.perf_counter()
        for feed in src:
            out = exe.run_window(main_prog, feed=feed, fetch_list=[loss],
                                 steps_per_run=K, return_numpy=False)
        float(np.asarray(out[0]).reshape(-1)[-1])       # final fence
        wall_s = _time.perf_counter() - t0
        src.close()

    wait1 = telemetry.registry().histogram("data_wait_seconds").value()
    wait_s = wait1["sum"] - wait0["sum"]
    # per-dispatch starvation distribution from the new step-events
    n_new = telemetry.step_events_recorded() - events0
    recent = telemetry.step_events()[-n_new:] if n_new > 0 else []
    waits_us = sorted(
        e["data_wait_s"] * 1e6 for e in recent
        if not e.get("kind") and e.get("data_wait_s") is not None)
    reg = telemetry.registry()

    return {
        "metric": "executor_feed_bound",
        "unit": "wait fraction of wall",
        "windows": windows,
        "k": K,
        "depth": 2,
        "generator_delay_s": delay_s,
        "wall_s": round(wall_s, 4),
        "wait_s": round(wait_s, 4),
        "value": round(wait_s / wall_s, 3) if wall_s else 0.0,
        "wait_frac": round(wait_s / wall_s, 3) if wall_s else 0.0,
        "data_wait_p50_us": round(_pctl(waits_us, 50), 1),
        "data_wait_p99_us": round(_pctl(waits_us, 99), 1),
        "h2d_overlap_frac": reg.gauge("h2d_overlap_frac").value(),
        "feed_ring_occupancy": reg.gauge("feed_ring_occupancy").value(),
        "ring_windows": int(
            reg.counter("feed_ring_windows_total").value()) - rings0,
        "metrics": _telemetry_metrics(since=tele0),
    }


# The ONLY absolute performance numbers the reference publishes
# (BASELINE.md, paddle/contrib/float16/README.md): fp16 inference
# latency ms/minibatch on a V100.  --infer measures the same sweep here.
REF_V100_FP16_MS = {
    "vgg16": {1: 3.32, 2: 4.11, 4: 5.88, 8: 9.41, 16: 16.54, 32: 30.47,
              64: 60.23},
    "resnet50": {1: 6.13, 2: 6.32, 4: 6.24, 8: 7.40, 16: 10.90, 32: 18.18,
                 64: 33.20, 128: 64.52},
}


def bench_infer(model="resnet50", batches=(1, 8, 32, 128), steps=50):
    """Inference latency ms/minibatch, bf16 activations — the reference's
    float16 benchmark protocol (avg over many batches, single device).
    Returns {batch: ms} plus speedup vs the published V100 fp16 table."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            img = fluid.layers.data(name="img", shape=[3, 224, 224],
                                    dtype="float32")
            if model == "vgg16":
                logits = models.vgg.vgg(img, class_dim=1000, depth=16)
            else:
                logits = models.resnet.resnet(img, class_dim=1000, depth=50)
            # scalar fence: fetching full logits would time the D2H copy
            fence = fluid.layers.mean(logits)
    infer = main.clone(for_test=True)
    infer._amp_dtype = "bfloat16"
    infer._amp_keep = True

    out = {}
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        for b in batches:
            feed = {"img": jax.device_put(
                rng.normal(0, 1, (b, 3, 224, 224)).astype(np.float32),
                exe._device)}

            def step(i):
                return exe.run(infer, feed=feed, fetch_list=[fence],
                               return_numpy=False)

            dt, _ = _timed_steps(step, steps, warmup=2)
            ms = dt / steps * 1e3
            ref = REF_V100_FP16_MS.get(model, {}).get(b)
            out[b] = {"ms": round(ms, 3)}
            if ref:
                out[b]["ref_v100_fp16_ms"] = ref
                out[b]["speedup_vs_ref"] = round(ref / ms, 2)
    return out


def bench_serving(requests=240, qps_levels=(500.0, 4000.0, 50000.0),
                  max_batch=16, max_wait_ms=2.0, seed=0):
    """``--serving``: continuous-batching serving throughput/latency vs
    the naive one-request-per-dispatch baseline, on synthetic open-loop
    Poisson traffic (arrival times are drawn up front and honored
    regardless of completion — the closed-loop trap would let a slow
    server throttle its own offered load).

    Both modes run the SAME ServingExecutor machinery over the same
    tiny fc model; the baseline's bucket ladder is pinned to ``(1,)``,
    so every request is dispatched alone — the pre-batching serving
    story.  Host-side measurable on the 1-core CPU CI: the win is
    per-dispatch host overhead amortized over bucket rows, exactly the
    hot-path numbers ``--hot-path`` pins, seen from the request side.
    The headline ``vs_baseline`` is batched/naive requests-per-second
    at the top offered QPS; per-level rows carry p50/p99 latency,
    occupancy, and recompile counts (the steady-state contract:
    0 after warmup)."""
    import time

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import serving

    since = _telemetry_counters()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            h = fluid.layers.fc(x, size=64, act="relu")
            out = fluid.layers.softmax(fluid.layers.fc(h, size=10))
    infer = main.clone(for_test=True)
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)
    rng = np.random.RandomState(seed)
    xs = rng.randn(requests, 1, 16).astype(np.float32)

    def drive(buckets, qps):
        sv = serving.ServingExecutor(
            infer, feed_specs={"x": ((16,), "float32")},
            fetch_list=[out], scope=scope,
            max_batch=max_batch, buckets=buckets,
            max_wait_ms=max_wait_ms, max_queue=10 * requests)
        warm = sv.warmup()
        arrivals = np.cumsum(rng.exponential(1.0 / qps, size=requests))
        lat = [None] * requests
        done_at = [None] * requests
        futs = []
        t_start = time.perf_counter()
        for i in range(requests):
            tgt = t_start + arrivals[i]
            now = time.perf_counter()
            if tgt > now:
                time.sleep(tgt - now)
            t_sub = time.perf_counter()
            fut = sv.submit({"x": xs[i]})

            def cb(fut, i=i, t_sub=t_sub):
                done_at[i] = time.perf_counter()
                lat[i] = done_at[i] - t_sub

            fut.add_done_callback(cb)     # fires on the completion thread
            futs.append(fut)
        for f in futs:
            f.result(timeout=300)
        # result() can return before the done-callback has run (waiters
        # are notified first) — wait for every callback's timestamp
        deadline = time.perf_counter() + 60
        while any(v is None for v in done_at) and \
                time.perf_counter() < deadline:
            time.sleep(0.001)
        assert not any(v is None for v in done_at), "callbacks missing"
        wall = max(done_at) - t_start
        sv.close()
        st = sv.stats()
        ms = sorted(v * 1e3 for v in lat)
        return {"offered_qps": qps,
                "achieved_rps": round(requests / wall, 1),
                "wall_s": round(wall, 4),
                "p50_ms": round(_pctl(ms, 50), 3),
                "p99_ms": round(_pctl(ms, 99), 3),
                "occupancy": st["occupancy_mean"],
                "batches": st["batches"],
                "recompiles": st["recompiles"],
                "rejects": st["rejects"],
                "warmup_s": round(sum(warm.values()), 3)}

    levels = [drive(None, qps) for qps in qps_levels]
    naive = drive((1,), qps_levels[-1])
    top = levels[-1]
    speedup = round(top["achieved_rps"] / naive["achieved_rps"], 3) \
        if naive["achieved_rps"] else 0.0
    return {
        "metric": "serving_throughput",
        "unit": "requests/sec",
        "value": top["achieved_rps"],
        "vs_baseline": speedup,
        "vs_baseline_kind": "continuous_batching_vs_per_request_dispatch",
        "requests": requests,
        "max_batch": max_batch,
        "buckets": serving.bucket_ladder(max_batch),
        "max_wait_ms": max_wait_ms,
        "levels": levels,
        "naive": naive,
        "speedup_vs_naive": speedup,
        "zero_steady_state_recompiles": all(
            lv["recompiles"] == 0 for lv in levels + [naive]),
        "batch_occupancy_frac": top["occupancy"],
        "metrics": _telemetry_metrics(since),
    }


# keys every --hot-path --multihost artifact carries (pinned in
# tests/test_bench_protocol.py so the harness/driver can rely on them)
MULTIHOST_RESULT_KEYS = (
    "metric", "unit", "value", "processes", "steps", "steps_per_run",
    "per_process_us_per_step", "per_process_allreduce_bytes",
    "allreduce_bytes_total", "plan_hit_rate")


def bench_multihost(nproc=2, steps=60, K=4, timeout=300):
    """``--hot-path --multihost N``: per-process host overhead and
    cross-process allreduce wire bytes of a REAL N-process
    ``jax.distributed`` CPU run (``distributed/launch.py
    --coordinator``, gloo collectives, one device per process — the
    same entrypoint CI's 2-process SPMD parity tests use).

    Spawns the launcher with bench.py itself as the worker
    (``--multihost-worker``): each process trains the hot-path dp
    program through the explicit-collective path — per-step dispatches
    plus fused K-step windows, every dispatch through the shared
    dispatch-plan cache — and reports its own timing/byte counters;
    the artifact carries the per-process vectors plus totals.  The
    parent never touches JAX: the children own the devices."""
    import os
    import subprocess
    import sys as _sys
    import tempfile

    out = {"metric": "multihost_hot_path", "unit": "us/step (host)",
           "processes": int(nproc), "steps": int(steps),
           "steps_per_run": int(K), "value": None,
           "per_process_us_per_step": [],
           "per_process_allreduce_bytes": [],
           "allreduce_bytes_total": 0, "plan_hit_rate": None}
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ)
        env.update({"BENCH_MH_OUT": td, "BENCH_MH_STEPS": str(steps),
                    "BENCH_MH_K": str(K)})
        port = 27000 + (os.getpid() % 1500)
        proc = subprocess.run(
            [_sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--coordinator", "--nproc_per_node", str(nproc),
             "--started_port", str(port), "--log_dir", td,
             os.path.abspath(__file__), "--multihost-worker"],
            env=env, timeout=timeout, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError("multihost pack failed (rc=%d): %s"
                               % (proc.returncode,
                                  proc.stdout[-500:] + proc.stderr[-500:]))
        ranks = []
        for r in range(nproc):
            with open(os.path.join(td, "bench_mh_r%d.json" % r)) as f:
                ranks.append(json.load(f))
    out["per_process_us_per_step"] = [r["us_per_step"] for r in ranks]
    out["per_process_allreduce_bytes"] = [r["allreduce_bytes"]
                                          for r in ranks]
    out["allreduce_bytes_total"] = int(sum(
        r["allreduce_bytes"] for r in ranks))
    out["plan_hit_rate"] = round(min(r["plan_hit_rate"] for r in ranks), 4)
    # headline: the SLOWEST process's host overhead — the pod runs at
    # the straggler's pace
    out["value"] = round(max(r["us_per_step"] for r in ranks), 2)
    return out


def _multihost_worker():
    """One process of the ``--multihost`` pack (spawned by the
    launcher; identity via PADDLE_* env → fluid.distributed.init)."""
    import os
    import time as _time

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import distributed as dist
    from paddle_tpu.fluid import telemetry
    from paddle_tpu.fluid.transpiler import GradAllReduce

    rank, nproc = dist.init()
    steps = int(os.environ.get("BENCH_MH_STEPS", "60"))
    K = int(os.environ.get("BENCH_MH_K", "4"))

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[64], dtype="float32")
            y = fluid.layers.fc(x, size=64, act="relu")
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGDOptimizer(0.01).minimize(loss)
    GradAllReduce().transpile(startup_program=startup,
                              main_program=main_prog, rank=rank,
                              endpoints=[], nranks=nproc)
    rng = np.random.RandomState(rank)
    feed = {"x": rng.normal(0, 1, (8, 64)).astype(np.float32)}
    wfeed = {"x": np.stack([feed["x"]] * K)}
    m = telemetry.counter("collective_bytes_total")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    # warm both executables, then measure cached-hit dispatch only
    exe.run(main_prog, feed=feed, fetch_list=[loss], return_numpy=False)
    exe.run_window(main_prog, feed=wfeed, fetch_list=[loss],
                   steps_per_run=K, return_numpy=False)
    b0 = int(m.value(species="allreduce", precision="fp32"))
    hits0 = exe._plan_hits
    t0 = _time.perf_counter()
    for _ in range(steps):
        out = exe.run(main_prog, feed=feed, fetch_list=[loss],
                      return_numpy=False)
    np.asarray(out[0])                      # one trailing fence
    per_step = (_time.perf_counter() - t0) / steps
    for _ in range(max(1, steps // K)):
        out = exe.run_window(main_prog, feed=wfeed, fetch_list=[loss],
                             steps_per_run=K, return_numpy=False)
    np.asarray(out[0])
    dispatches = steps + max(1, steps // K)
    result = {
        "rank": rank,
        "us_per_step": round(per_step * 1e6, 2),
        "allreduce_bytes": int(m.value(species="allreduce",
                                       precision="fp32")) - b0,
        "plan_hit_rate": (exe._plan_hits - hits0) / float(dispatches),
    }
    path = os.path.join(os.environ["BENCH_MH_OUT"],
                        "bench_mh_r%d.json" % rank)
    with open(path + ".tmp", "w") as f:
        json.dump(result, f)
    os.replace(path + ".tmp", path)
    print("bench multihost rank %d done" % rank, flush=True)


def _emit_error_json(message):
    """The harness parses bench stdout's LAST line as JSON — every
    failure path must still end with one parseable line
    (``{"error": ..., "metric": null}``), never a bare text message."""
    print(json.dumps({"error": str(message), "metric": None,
                      "value": None}))
    sys.stdout.flush()


def _require_tpu():
    """The chip modes measure the chip: on any other backend end with a
    JSON error line and exit 3 instead of timing a CPU."""
    fp = _device_fingerprint()
    if fp["platform"] == "tpu":
        return fp
    msg = ("this mode needs a TPU; JAX found platform=%r device_kind=%r "
           "(%d device(s))" % (fp["platform"], fp["device_kind"],
                               fp["n_devices"]))
    print("bench: " + msg, file=sys.stderr)
    _emit_error_json(msg)
    sys.exit(3)


def main():
    try:
        _main()
    except SystemExit:
        raise
    except BaseException as e:
        # keep the traceback on stderr for humans, but the last stdout
        # line stays machine-parseable for the harness
        import traceback
        traceback.print_exc()
        _emit_error_json("%s: %s" % (type(e).__name__, e))
        sys.exit(1)


def _main():
    if "--multihost-worker" in sys.argv:
        # one process of the --multihost pack (launcher-spawned; CPU
        # backend pinned by launch.py --coordinator)
        _multihost_worker()
        return
    if "--hot-path" in sys.argv and "--multihost" in sys.argv:
        # pod-scale host-overhead bench: spawn a REAL N-process
        # jax.distributed CPU pack and report per-process dispatch
        # overhead + cross-process allreduce bytes
        idx = sys.argv.index("--multihost")
        nproc = 2
        if idx + 1 < len(sys.argv) and not sys.argv[idx + 1].startswith("--"):
            nproc = int(sys.argv[idx + 1])
        print(json.dumps(bench_multihost(nproc=nproc)))
        return
    if "--serving" in sys.argv:
        # continuous-batching serving executor vs one-request-per-
        # dispatch, open-loop Poisson traffic (host-side measurable)
        print(json.dumps(bench_serving()))
        return
    if "--hot-path" in sys.argv:
        if "--watchdog" in sys.argv:
            # A/B pin for the hang-detection PR: arm the watchdog
            # (default 60s — far above any bench stall, so it never
            # fires) and re-measure the same hot path; the artifact is
            # comparable key-for-key against the watchdog-off run, and
            # host_overhead_us_per_step must sit within noise of it
            # (the FLAGS_watchdog_timeout_s=0 zero-overhead contract)
            from paddle_tpu.fluid import watchdog as _watchdog
            _watchdog.arm(timeout_s=60.0, abort=False)
        if "--feed-bound" in sys.argv:
            # deliberately input-bound run: measures the starvation /
            # H2D-overlap instrumentation, not throughput
            print(json.dumps(bench_feed_bound()))
            return
        if "--steps-per-run" in sys.argv:
            # multi-step fused window sweep: host overhead per INNER
            # step at K ∈ {1, 4, 16, 64} must fall ~1/K, with per-step
            # loss parity between K=1 and fused runs
            idx = sys.argv.index("--steps-per-run")
            focus = None
            if idx + 1 < len(sys.argv) and not \
                    sys.argv[idx + 1].startswith("--"):
                focus = int(sys.argv[idx + 1])
            result = bench_hot_path_window(focus_k=focus)
        else:
            # host-overhead microbenchmark: dispatch-plan run() vs the
            # bare jitted call —
            # measures the executor, not the chip (valid on any
            # backend, incl. CPU CI)
            result = bench_hot_path()
        if "--watchdog" in sys.argv:
            result["watchdog_armed"] = True
        print(json.dumps(result))
        return
    device = _require_tpu()
    if "--infer" in sys.argv:
        # reference-table comparison mode: the one benchmark the
        # reference actually publishes (BASELINE.md)
        result = {"metric": "inference_latency_ms", "unit": "ms/minibatch",
                  "reference": "V100 fp16, contrib/float16/README.md",
                  "device": device}
        for model in ("resnet50", "vgg16"):
            result[model] = bench_infer(model)
        sp = [v["speedup_vs_ref"] for m in ("resnet50", "vgg16")
              for v in result[m].values() if "speedup_vs_ref" in v]
        result["value"] = round(float(np.mean(sp)), 3) if sp else 0.0
        result["vs_baseline"] = result["value"]
        print(json.dumps(result))
        return
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    batch = int(args[0]) if args else 256
    steps = int(args[1]) if len(args) > 1 else 30
    amp = "--fp32" not in sys.argv

    img_s, resnet_mfu = bench_resnet(batch, steps, amp)
    result = {
        "metric": "resnet50_train_throughput",
        "value": round(img_s, 2),
        "unit": "images/sec/chip",
        "device": device,
        # anchor when the control below is skipped: MFU vs bf16 peak
        "vs_baseline": round(resnet_mfu, 4),
        "vs_baseline_kind": "mfu_est",
        "resnet50_mfu_est": round(resnet_mfu, 4),
    }
    if "--no-control" not in sys.argv:
        # bare-JAX control on the same chip/batch: separates the XLA conv
        # ceiling from framework-emitted-HLO overhead
        ctrl_img_s, ctrl_mfu = bench_control_resnet(batch, steps)
        result["control_bare_jax_img_s"] = round(ctrl_img_s, 2)
        result["control_bare_jax_mfu_est"] = round(ctrl_mfu, 4)
        result["framework_vs_control"] = round(img_s / ctrl_img_s, 3)
        # primary anchor: framework vs the bare-JAX control — 1.0 means
        # zero framework overhead
        result["vs_baseline"] = result["framework_vs_control"]
        result["vs_baseline_kind"] = "framework_vs_bare_jax_control"
    if "--resnet-only" not in sys.argv:
        # the non-resnet BASELINE.json configs — one artifact that speaks
        # for all five reference configs
        sub_steps = max(10, steps // 3)
        for fn, kwargs, keys in (
                (bench_bert, dict(batch=64, steps=sub_steps),
                 (("bert_base_tokens_per_sec", 1), ("bert_base_mfu_est", 4))),
                (bench_nmt, dict(batch=32, steps=sub_steps),
                 (("transformer_nmt_tokens_per_sec", 1),
                  ("transformer_nmt_mfu_est", 4))),
                (bench_deepfm, dict(batch=4096, steps=sub_steps),
                 (("deepfm_examples_per_sec", 1), ("deepfm_mfu_est", 6))),
                (bench_lenet, dict(batch=1024, steps=sub_steps),
                 (("lenet_images_per_sec", 1),))):
            out = fn(**kwargs)
            vals = out if isinstance(out, tuple) else (out,)
            for (key, digits), val in zip(keys, vals):
                result[key] = round(val, digits)

    print(json.dumps(result))


if __name__ == "__main__":
    main()
