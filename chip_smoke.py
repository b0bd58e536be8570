#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once — ``fluid.Program`` -> ``fluid.Executor(
fluid.TPUPlace())`` -> one jitted XLA step per dispatch — at the full width
of ResNet-50 and BERT-base, with random weights made from a seed, and checks
what comes out by the repo's own means.  ONE process, no child: a chip
belongs to one process at a time.

    python chip_smoke.py                 one chip: phases 1-5
    python chip_smoke.py --chips 4       one process driving four chips
    python chip_smoke.py --dry-run-cpu   the same phases at tiny sizes with
                                         interpreted kernels (pre-flight and
                                         tier-1 handle; NOT a chip result)

Phases (any failure raises through to a non-zero exit):

1. device   versions, ``jax.devices()``; anything but a TPU fails here,
            before any work, naming what was found; so does a native
            runtime that did not build.
2. resnet50 bench.py's ResNet-50 program (batch 256, Momentum + L2,
            pure-bf16 AMP), 8 steps fed as host numpy batches through a
            program-bound ``fluid.DataLoader.from_generator``.
3. bert     BERT-base pretrain twice: (a) S=128, batch 64, dropout 0.1
            (attention runs the XLA composition: at S=128 the kernels
            would hold more than it, ``pallas_ops._drop_in_kernels``); (b)
            S=512, batch 16, ``attn_dropout=0`` (the Pallas flash kernels,
            forward and backward, are inside the step — proven from its
            compiled HLO).
4. kernels  every Pallas kernel alone against its ``jnp`` reference.
5. flash_dropout  attention dropout drawn inside the in-place kernels from
            the core's generator: its keep fraction over a [32, 512, 12 *
            64] draw, forward and backward from one seed against the
            composition fed the kernels' own mask, two seeds' masks apart.

``--chips 4`` runs phase 1 and then phase 2's program at global batch 1024
twice: through ``CompiledProgram.with_data_parallel`` (GSPMD) and through
``GradAllReduce().transpile`` (the program's own collectives).

Per training run: loss finite at every step and, on a repeated batch, below
the first step's at a later one; every persistable a ``jax.Array`` on the
executor's device(s); no compile after the first training step; in steady
state the loader hands every batch over already on the device — one shard
per chip — and the dispatch moves none of it again.

The timings printed are informational: this script records no metric.  When
every phase has passed, a ``summary:`` line carries the per-phase outcome and
those timings, and the last line of stdout is the result, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reports it.  A dry run prints ``dry-run summary:`` and no
result line.
"""

import argparse
import functools
import json
import os
import time

import numpy as np

# bf16 carries 8 significant bits (eps 2^-8 ~ 4e-3); the kernels round the
# probabilities and ds to bf16 before their second matmul, so errors of a
# few eps relative to the largest reference element are the dtype's own.
FWD_TOL = 2e-2
BWD_TOL = 4e-2
MOSAIC_CALL = "tpu_custom_call"

# A program-bound loader starts staging before any executor exists: its
# first LOADER_CAPACITY + 2 batches reach the executor as host arrays and
# the next one on a single device (counted on the CPU).  From the first
# dispatch on the loader stages onto the executor's device with the compiled
# plan's shardings; WARM_STEPS consumes the early batches with two to spare,
# and the STEADY_STEPS after them are inspected and timed.
LOADER_CAPACITY = 1
WARM_STEPS = 6
STEADY_STEPS = 2


def log(msg):
    print(msg, flush=True)


# Every XLA compile jit performs, a persistent-cache hit included.
# ``exe.compile_count()`` counts the executables the executor built; jit
# compiles the same one again whenever an argument turns from uncommitted
# to committed or changes sharding, which only this count sees.
_xla_compiles = []


def count_xla_compiles():
    import jax.monitoring

    if not _xla_compiles:
        _xla_compiles.append(0)

        def on_duration(event, duration_secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _xla_compiles[0] += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
    return _xla_compiles[0]


def require(ok, msg, *args):
    """A check that fails the smoke (not an ``assert``: ``python -O`` would
    strip those and pass everything)."""
    if not ok:
        raise AssertionError(msg % args)


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def phase_device(dry_run, chips):
    """Returns the device fingerprint as JAX reports it and the devices
    the run must use: ``chips`` TPUs (CPU devices in a dry run)."""
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "absent"
    log("jax %s  jaxlib %s  libtpu %s" % (jax.__version__,
                                          jaxlib.__version__, libtpu))
    devs = jax.devices()
    for d in devs:
        log("  device %d: platform=%s kind=%s" % (d.id, d.platform,
                                                  d.device_kind))
    d0 = devs[0]
    if dry_run:
        log("DRY RUN on platform=%s: tiny sizes, interpreted kernels — "
            "not a chip result" % d0.platform)
        pool = jax.devices("cpu")
    elif d0.platform != "tpu":
        raise SystemExit(
            "chip_smoke: needs a TPU, but JAX found platform=%r "
            "(device_kind=%r, %d device(s)); --dry-run-cpu runs the "
            "phases at tiny sizes on the CPU"
            % (d0.platform, d0.device_kind, len(devs)))
    else:
        pool = devs
    # several chips: ONE process drives every local chip (the GSPMD mesh
    # spans them all), so the count must match
    if len(pool) < chips or (chips > 1 and len(pool) != chips):
        raise SystemExit(
            "chip_smoke: --chips %d needs %s %d %s devices, JAX found %d%s"
            % (chips, "exactly" if chips > 1 else "at least", chips,
               pool[0].platform, len(pool),
               " (dry run: set XLA_FLAGS=--xla_force_host_platform_device_"
               "count=%d)" % chips if dry_run else ""))
    # a g++ that failed would leave the pure-Python fallback: not the
    # system this smoke vouches for
    from paddle_tpu import native
    require(native.available(), "the native runtime (paddle_tpu/native/"
            "native.cc) did not build or load")
    log("native runtime loaded: True")
    return ({"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devs)}, pool[:chips])


# ---------------------------------------------------------------------------
# phases 2-3: training through Executor(place)
# ---------------------------------------------------------------------------

def _amp(opt):
    import paddle_tpu.fluid as fluid
    return fluid.contrib.mixed_precision.decorate(opt, use_pure_bf16=True)


def build_resnet(depth, class_dim, image):
    """The program of bench.py bench_resnet."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    img = fluid.layers.data(name="img", shape=[3, image, image],
                            dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    logits = models.resnet.resnet(img, class_dim=class_dim, depth=depth)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    _amp(fluid.optimizer.MomentumOptimizer(
        learning_rate=0.1, momentum=0.9,
        regularization=fluid.regularizer.L2Decay(1e-4))).minimize(loss)
    return [img, label], loss


def resnet_batch(rng, batch, class_dim, image):
    return {
        "img": rng.normal(0, 1, (batch, 3, image, image)).astype(np.float32),
        "label": rng.randint(0, class_dim, (batch, 1)).astype(np.int64),
    }


N_PRED = 20


def build_bert(cfg):
    """The program of bench.py bench_bert, AMP set the way a user sets it."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import models

    handles = models.bert.build_pretrain(
        cfg, max_pred_per_seq=N_PRED,
        optimizer=_amp(fluid.optimizer.AdamOptimizer(learning_rate=1e-4)))
    block = fluid.default_main_program().global_block()
    feeds = [block.var(n) for n in ("src_ids", "pos_ids", "sent_ids",
                                    "input_mask", "mask_pos", "mask_label",
                                    "nsp_label")]
    return feeds, handles["loss"]


def bert_batch(rng, cfg, batch):
    S = cfg.max_seq_len
    mask_pos = rng.randint(0, S, (batch, N_PRED)) \
        + np.arange(batch)[:, None] * S
    return {
        "src_ids": rng.randint(0, cfg.vocab_size,
                               (batch, S, 1)).astype(np.int64),
        "pos_ids": np.tile(np.arange(S)[None, :, None],
                           (batch, 1, 1)).astype(np.int64),
        "sent_ids": np.zeros((batch, S, 1), np.int64),
        "input_mask": np.ones((batch, S, 1), np.float32),
        "mask_pos": mask_pos.reshape(-1, 1).astype(np.int32),
        "mask_label": rng.randint(0, cfg.vocab_size,
                                  (batch * N_PRED, 1)).astype(np.int64),
        "nsp_label": rng.randint(0, 2, (batch, 1)).astype(np.int64),
    }


def check_state_on(scope, devices, names=None):
    """Every persistable in the scope (or just ``names``) is a jax.Array
    living on exactly ``devices``."""
    import jax

    names = names or scope.var_names()
    require(names, "scope holds no state")
    for n in names:
        v = scope.find_var(n)
        require(isinstance(v, jax.Array), "state %r is %s, not a jax.Array",
                n, type(v).__name__)
        require(v.sharding.device_set == set(devices),
                "state %r lives on %s, wanted %s", n,
                sorted(map(str, v.sharding.device_set)),
                sorted(map(str, devices)))
    return len(names)


def check_feed_on(name, feed, devices, rows):
    """What the loader handed over: every feed array (``rows`` samples) is
    already a jax.Array split along dim 0 into one shard per device —
    nothing left for the dispatch to move."""
    import jax

    n = len(devices)
    for k, v in feed.items():
        require(isinstance(v, jax.Array), "%s: the loader handed feed %r "
                "over as %s", name, k, type(v).__name__)
        shards = v.addressable_shards
        require({s.device for s in shards} == set(devices) and
                len(shards) == n and
                all(s.data.shape[0] == v.shape[0] // n for s in shards) and
                v.shape[0] % rows == 0,
                "%s: feed %r %s is not %d shard(s) on %s: %s", name, k,
                v.shape, n, sorted(map(str, devices)),
                [(str(s.device), s.data.shape) for s in shards])


def check_memory_spread(name, devices):
    used = [d.memory_stats()["bytes_in_use"] for d in devices]
    log("%s: bytes_in_use per device %s" % (name, used))
    require(min(used) > 0 and max(used) <= 1.5 * min(used),
            "%s: device memory is lopsided: %s", name, used)


def check_losses(name, losses):
    """Finite at every step, and below the first step's at a later one.
    Not "the last below the first": at bench.py's learning rate (0.1,
    momentum 0.9, no warm-up) the ResNet loss on a repeated batch swings
    back above its start within a few steps, fed plain numpy arrays too,
    which says nothing about the system."""
    require(all(np.isfinite(losses)), "%s: non-finite loss %s", name, losses)
    require(min(losses[1:]) < losses[0],
            "%s: loss never fell on a repeated batch: %s", name, losses)


def train(name, place, devices, build, batch, wrap=None, check_hlo=None):
    """Build the program the way a user does, feed ``batch`` (host numpy)
    through a program-bound DataLoader whose feed ring stages it onto the
    executor's device(s), take WARM_STEPS + STEADY_STEPS steps and check
    the run.  ``devices``: the devices the state and the steady-state
    feeds must live on.  ``wrap(main, startup, loss)`` returns what to
    run when that is not ``main`` itself; ``check_hlo(hlo)`` inspects the
    compiled step.  Returns the first loss and informational timings."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import telemetry

    rows = len(next(iter(batch.values())))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, loss = build()
        loader = fluid.DataLoader.from_generator(
            feed_list=feeds, capacity=LOADER_CAPACITY, iterable=False)
    loader.set_batch_generator(
        lambda: (batch for _ in range(WARM_STEPS + STEADY_STEPS + 8)))
    prog = wrap(main, startup, loss) if wrap else main
    reputs = telemetry.registry().counter("executor_feed_reputs_total")
    losses, step_s = [], []

    def step(**kw):
        t0 = time.perf_counter()
        out = exe.run(prog, fetch_list=[loss], return_numpy=False, **kw)
        jax.block_until_ready(out)
        step_s.append(time.perf_counter() - t0)
        # the explicit-collective path fetches one loss per replica
        losses.append(float(np.mean(np.asarray(out[0]))))

    scope = fluid.Scope()
    count_xla_compiles()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        exe.run(startup)
        loader.start()
        try:
            step()      # the executor pulls the batch from the loader
            compiles_after_first = exe.compile_count(), count_xla_compiles()
            for _ in range(WARM_STEPS - 1):
                step()
            # steady state: pull the batch by hand to see what the loader
            # hands over, then dispatch it
            reputs_before = reputs.value()
            for _ in range(STEADY_STEPS):
                feed = loader.next_feed()
                check_feed_on(name, feed, devices, rows)
                step(feed=feed)
        finally:
            loader.reset()
        require(reputs.value() == reputs_before,
                "%s: %d staged feed(s) were moved again at dispatch", name,
                reputs.value() - reputs_before)
        check_losses(name, losses)
        compiles = exe.compile_count(), count_xla_compiles()
        recompiles = compiles[1] - compiles_after_first[1]
        # one device: no compile of any kind after the first training step.
        # Several: the executor builds nothing more, but jit compiles the
        # step again for the arguments' changed shardings (open, PERF.md
        # section 7) — counted and reported, not passed over in silence
        require(compiles[0] == compiles_after_first[0] and
                (recompiles == 0 or len(devices) > 1),
                "%s: recompiled after the first training step (executor "
                "%d -> %d, XLA compiles +%d)", name, compiles_after_first[0],
                compiles[0], recompiles)
        # several devices: the parameters (read-only state such as the
        # learning rate is placed per dispatch and stays where the startup
        # program put it)
        n_state = check_state_on(
            scope, devices, None if len(devices) == 1 else
            [p.name for p in main.global_block().all_parameters()])
        if len(devices) > 1 and devices[0].platform == "tpu":
            check_memory_spread(name, devices)
        if check_hlo:
            check_hlo(exe.compiled_hlo(prog, feed=feed, fetch_list=[loss]))
    # informational: the first step carries trace + compile; the steady
    # steps are fenced one by one, so each includes a host round trip
    info = {"compile_s": round(step_s[0], 2),
            "steady_ms_per_step":
                round(float(np.median(step_s[WARM_STEPS:])) * 1e3, 2),
            "xla_compiles_after_first_step": recompiles}
    log("%s: loss %s; %d persistables on %s; %d XLA compile(s) after the "
        "first step; first step (trace+compile) %.1f s, steady %.1f ms/step "
        "[informational]"
        % (name, " ".join("%.3f" % x for x in losses), n_state,
           ", ".join(map(str, devices)), recompiles, info["compile_s"],
           info["steady_ms_per_step"]))
    return losses[0], info


def phase_resnet(place, devices, dry_run):
    depth, class_dim, image, batch = (18, 10, 32, 4) if dry_run \
        else (50, 1000, 224, 256)
    rng = np.random.RandomState(0)
    return train("resnet%d_b%d" % (depth, batch), place, devices,
                 lambda: build_resnet(depth, class_dim, image),
                 resnet_batch(rng, batch, class_dim, image))[1]


def _require_mosaic(hlo):
    # compiled, not interpreted and not replaced by the reference
    require(MOSAIC_CALL in hlo, "no Mosaic custom call in the compiled step")


def phase_bert(place, devices, dry_run):
    """(a) the default config at S=128 — attention is the XLA composition
    (with dropout the kernels draw it only where a head's scores outnumber
    what they keep: S=512, ``phase_flash_dropout``); (b) ``attn_dropout=0``
    — the Pallas flash kernels are inside the step.  The dry run takes (b)
    only: on the CPU the two differ by one attribute of one op."""
    from paddle_tpu import models

    if dry_run:
        make = functools.partial(models.bert.tiny_config, num_layers=1)
        batch_a, batch_b, s_a, s_b = 4, 2, 32, 128
    else:
        make = models.bert.base_config
        batch_a, batch_b, s_a, s_b = 64, 16, 128, 512
    out = {}
    rng = np.random.RandomState(0)
    if not dry_run:
        cfg = make(max_seq_len=s_a, max_position=512)
        require(cfg.attn_dropout == 0.1 and cfg.use_fused_attention,
                "bert (a) is not the default config")
        out["a"] = train("bert_a_S%d_b%d" % (s_a, batch_a), place, devices,
                         lambda: build_bert(cfg),
                         bert_batch(rng, cfg, batch_a))[1]
    cfg_b = make(max_seq_len=s_b, max_position=512, attn_dropout=0.0)
    out["b"] = train("bert_b_S%d_b%d_flash" % (s_b, batch_b), place, devices,
                     lambda: build_bert(cfg_b),
                     bert_batch(rng, cfg_b, batch_b),
                     check_hlo=None if dry_run else _require_mosaic)[1]
    return out


# ---------------------------------------------------------------------------
# phase 4: every Pallas kernel against its jnp reference
# ---------------------------------------------------------------------------

def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, "kernel output shape %s, wanted %s",
            got.shape, want.shape)
    require(np.all(np.isfinite(got)), "non-finite kernel output")
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6))


def _check_lowering(fn, args, on_tpu, what):
    import jax
    has = MOSAIC_CALL in jax.jit(fn).lower(*args).as_text()
    require(has == on_tpu, "%s: Mosaic custom call %s the lowering", what,
            "missing from" if on_tpu else "found in")


def phase_kernels(device, dry_run):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid.ops import pallas_ops as po

    on_tpu = device.platform == "tpu"
    bf16, f32 = jnp.bfloat16, jnp.float32
    rng = np.random.RandomState(0)

    def arr(shape, dtype=bf16, scale=1.0):
        return jax.device_put(
            jnp.asarray(rng.normal(0, scale, shape), dtype), device)

    BH, D = (1, 64) if dry_run else (16 * 12, 64)
    # 128 and 512 are one tile a head (the fused backward, flash_bwd);
    # 1024 is two tiles a side (the dQ pass and the dK/dV pass)
    seqs = (128,) if dry_run else (128, 512, 1024)
    # the interpreter is slow: the dry run takes the one case that reaches
    # every kernel (a bias brings in dbias) under the causal mask
    cases = [(True, True)] if dry_run else \
        [(b, c) for b in (False, True) for c in (False, True)]
    scale = 1.0 / np.sqrt(D)
    worst = {"fwd": 0.0, "bwd": 0.0}
    def kern(causal, q, k, v, b=None):
        return po.flash_attention(q, k, v, b, scale, causal)

    def ref(causal, q, k, v, b=None):
        up = [x.astype(f32) for x in (q, k, v)]
        return po._reference_attention(
            *up, None if b is None else b.astype(f32), scale, causal=causal)

    def grads(f, causal, n_args):
        # all four backward outputs: dq, dk, dv and (with a bias) dbias
        def loss(g, *args):
            return jnp.sum(f(causal, *args).astype(f32) * g.astype(f32))
        return jax.jit(jax.grad(loss, argnums=tuple(range(1, n_args + 1))))

    for S in seqs:
        q, k, v, g = (arr((BH, S, D)) for _ in range(4))
        bias = arr((BH, S, S))
        # arrays go in as arguments: a closed-over array becomes a constant
        # of the executable (100 MB of bias in every cache entry)
        for with_bias, causal in cases:
            args = (q, k, v, bias) if with_bias else (q, k, v)
            what = "flash S=%d bias=%s causal=%s" % (S, with_bias, causal)
            fk = functools.partial(kern, causal)
            _check_lowering(fk, args, on_tpu, what)
            e = _rel_err(jax.jit(fk)(*args),
                         jax.jit(functools.partial(ref, causal))(*args))
            require(e <= FWD_TOL, "%s: fwd err %.4f", what, e)
            worst["fwd"] = max(worst["fwd"], e)
            got = grads(kern, causal, len(args))(g, *args)
            want = grads(ref, causal, len(args))(g, *args)
            for nm, a, w in zip(("dq", "dk", "dv", "dbias"), got, want):
                e = _rel_err(a, w)
                require(e <= BWD_TOL, "%s: %s err %.4f", what, nm, e)
                worst["bwd"] = max(worst["bwd"], e)
    log("flash_attention: fwd + dq/dk/dv/dbias match the reference at "
        "BH=%d S=%s D=%d bf16 (worst rel err fwd %.4f <= %.2g, bwd %.4f "
        "<= %.2g)" % (BH, seqs, D, worst["fwd"], FWD_TOL, worst["bwd"],
                      BWD_TOL))

    # grouped key/value heads: 32 query heads read 8 key/value heads where
    # they lie (block row i // 4), dK and dV summed over each group; against
    # the float32 composition on K and V repeated to 32 heads
    heads, kv_heads, S = (4, 2, 128) if dry_run else (32, 8, 1024)
    q, g = arr((heads, S, D)), arr((heads, S, D))
    k, v = arr((kv_heads, S, D)), arr((kv_heads, S, D))

    def ref_grouped(causal, q, k, v):
        return ref(causal, q, *(jnp.repeat(x, heads // kv_heads, axis=0)
                                for x in (k, v)))
    what = "flash H=%d H_kv=%d S=%d causal" % (heads, kv_heads, S)
    _check_lowering(functools.partial(kern, True), (q, k, v), on_tpu, what)
    e_gqa = _rel_err(jax.jit(functools.partial(kern, True))(q, k, v),
                     jax.jit(functools.partial(ref_grouped, True))(q, k, v))
    require(e_gqa <= FWD_TOL, "%s: fwd err %.4f", what, e_gqa)
    for nm, a, w in zip(("dq", "dk", "dv"), grads(kern, True, 3)(g, q, k, v),
                        grads(ref_grouped, True, 3)(g, q, k, v)):
        require(a.shape == w.shape, "%s: %s is %s", what, nm, a.shape)
        e = _rel_err(a, w)
        require(e <= BWD_TOL, "%s: %s err %.4f", what, nm, e)
        e_gqa = max(e_gqa, e)
    log("%s D=%d bf16: fwd + dq/dk/dv match the composition on repeated "
        "K/V (worst rel err %.4f)" % (what, D, e_gqa))

    # a sliding window: the looped kernels at the long-context cell's shape,
    # one head group of it (7 query heads of 128 over one key/value head,
    # 16384 tokens), with a window of 4096 keys and with none, against the
    # float32 composition whose band is a boolean mask, a head and 2048
    # query rows at a time (one head's scores are 1 GB)
    group, S, Dw, rows = (3, 256, 64, 128) if dry_run \
        else (7, 16384, 128, 2048)
    scale_w = 1.0 / np.sqrt(Dw)
    q, g = arr((group, S, Dw)), arr((group, S, Dw))
    k, v = arr((1, S, Dw)), arr((1, S, Dw))

    def band(window, q, k, v):
        return po.flash_attention(q, k, v, None, scale_w, True, None, window)

    def ref_band(window, q, k, v):
        kf, vf = k[0].astype(f32), v[0].astype(f32)

        @jax.checkpoint
        def piece(qs, first):
            s_ = (qs @ kf.T) * scale_w
            allowed = po._in_band(first + jnp.arange(rows)[:, None],
                                  jnp.arange(S)[None, :], window)
            return jax.nn.softmax(jnp.where(allowed, s_, -jnp.inf),
                                  axis=-1) @ vf

        def head(qh):
            return jax.lax.map(lambda a: piece(*a), (
                qh.reshape(S // rows, rows, Dw),
                jnp.arange(0, S, rows))).reshape(S, Dw)
        return jax.lax.map(head, q.astype(f32))
    e_window = 0.0
    for window in (S // 4, 0):
        what = "flash H=%d H_kv=1 S=%d window=%d" % (group, S, window)
        fk = functools.partial(band, window)
        _check_lowering(fk, (q, k, v), on_tpu, what)
        e = _rel_err(jax.jit(fk)(q, k, v),
                     jax.jit(functools.partial(ref_band, window))(q, k, v))
        require(e <= FWD_TOL, "%s: fwd err %.4f", what, e)
        e_window = max(e_window, e)
        for nm, a, w in zip(("dq", "dk", "dv"),
                            grads(band, window, 3)(g, q, k, v),
                            grads(ref_band, window, 3)(g, q, k, v)):
            require(a.shape == w.shape, "%s: %s is %s", what, nm, a.shape)
            e = _rel_err(a, w)
            require(e <= BWD_TOL, "%s: %s err %.4f", what, nm, e)
            e_window = max(e_window, e)
    log("flash H=%d H_kv=1 S=%d D=%d bf16, a window of %d and none: fwd + "
        "dq/dk/dv match the masked composition (worst rel err %.4f)"
        % (group, S, Dw, S // 4, e_window))

    # operands as the projections leave them, [B, S, H * D], read and
    # written in place, a pair of heads of 64 a grid cell (the lane slices
    # at offset 64 are Mosaic's to get right: the interpreter cannot tell);
    # float32 as the BERT step hands them over, and bfloat16
    B_, heads, S = (2, 2, 128) if dry_run else (16, 12, 512)
    e_in_place = 0.0
    for dtype in (f32, bf16):
        q, k, v, g = (arr((B_, S, heads * D), dtype) for _ in range(4))
        mask = arr((B_, S, S), dtype)

        def in_place(causal, q, k, v, b, g=None):
            # the calls a training step makes: the forward keeping its
            # logsumexp, then ONE flash_bwd on it (no bias gradient)
            out, lse = po._flash_fwd_in_place(q, k, v, b, scale, heads,
                                              causal)
            if g is None:
                return out
            return po._backward_in_place(q, k, v, b, scale, causal, heads,
                                         lse, g)

        def ref_in_place(causal, q, k, v, b):
            split = [po._flat(po._heads_major(x, heads)) for x in (q, k, v)]
            out = ref(causal, *split, b)
            return po._heads_minor(out.reshape(B_, heads, S, D))
        for causal in (False, True):
            what = "flash in place B=%d H=%d S=%d %s causal=%s" % (
                B_, heads, S, jnp.dtype(dtype).name, causal)
            fk = functools.partial(in_place, causal)
            _check_lowering(fk, (q, k, v, mask, g), on_tpu, what)
            e = _rel_err(jax.jit(fk)(q, k, v, mask), jax.jit(
                functools.partial(ref_in_place, causal))(q, k, v, mask))
            require(e <= FWD_TOL, "%s: fwd err %.4f", what, e)
            e_in_place = max(e_in_place, e)
            got = jax.jit(fk)(q, k, v, mask, g)
            want = grads(ref_in_place, causal, 3)(g, q, k, v, mask)
            for nm, a, w in zip(("dq", "dk", "dv"), got, want):
                require(a.shape == q.shape, "%s: %s is %s", what, nm, a.shape)
                e = _rel_err(a, w)
                require(e <= BWD_TOL, "%s: %s err %.4f", what, nm, e)
                e_in_place = max(e_in_place, e)
    log("flash in place [B=%d, S=%d, %d x %d]: fwd + dq/dk/dv match the "
        "reference on split heads (worst rel err %.4f)"
        % (B_, S, heads, D, e_in_place))

    M, H = (64, 128) if dry_run else (64 * 128, 768)
    x, sc, sh = arr((M, H)), arr((H,), f32), arr((H,), f32)

    def ln(x, sc, sh):
        return po.fused_layer_norm(x, sc, sh, 1e-5)
    _check_lowering(ln, (x, sc, sh), on_tpu, "fused_layer_norm")
    e_ln = _rel_err(jax.jit(ln)(x, sc, sh),
                    po._reference_layer_norm(x, sc, sh, 1e-5))
    require(e_ln <= FWD_TOL, "fused_layer_norm err %.4f", e_ln)
    log("fused_layer_norm [%d, %d]: rel err %.4f" % (M, H, e_ln))

    return {"flash_fwd_err": round(worst["fwd"], 5),
            "flash_bwd_err": round(worst["bwd"], 5),
            "flash_grouped_err": round(e_gqa, 5),
            "flash_window_err": round(e_window, 5),
            "flash_in_place_err": round(e_in_place, 5),
            "layer_norm_err": round(e_ln, 5)}


def phase_flash_dropout(device, dry_run):
    """Attention dropout drawn inside ``flash_fwd`` / ``flash_bwd`` in
    place (PR 40), at the BERT cells' widths: the keep fraction of the
    core's generator over a ``[32, 512, 12 * 64]`` draw (read back by
    ``_drawn_mask``, a kernel of the same grid drawing as they do) within
    5 sigma of 1 - rate; forward and backward from one seed against the
    composition (``_attn_core``) handed that mask as its ``bernoulli``, in
    float32 and bfloat16; and the seeds of two steps drawing masks that
    agree on (1 - rate)^2 + rate^2 of their elements, as two independent
    ones do.  On the CPU the kernels draw the interpreter's counter hash."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid.ops import pallas_ops as po

    rate, D = 0.1, 64
    scale = 1.0 / np.sqrt(D)
    rng = np.random.RandomState(1)

    def put(x):
        return jax.device_put(x, device)
    seeds = [put(jnp.array([s], jnp.int32)) for s in (1234567, 1234568)]
    B, heads, S = (2, 2, 128) if dry_run else (32, 12, 512)
    drawn = jax.jit(po._drawn_mask, static_argnums=(1, 2, 3, 4, 5, 6))
    first = drawn(seeds[0], B, heads, D, S, S, rate)
    keep = float(jnp.mean(first.astype(jnp.float32)))
    sigma = np.sqrt(rate * (1 - rate) / first.size)
    require(abs(keep - (1 - rate)) <= 5 * sigma,
            "keep fraction %.6f over %d draws, wanted %.2f +- %.6f", keep,
            first.size, 1 - rate, 5 * sigma)
    agree = float(jnp.mean((first == drawn(seeds[1], B, heads, D, S, S,
                                           rate)).astype(jnp.float32)))
    want = (1 - rate) ** 2 + rate ** 2
    require(abs(agree - want) <= 0.01, "two seeds' masks agree on %.4f, "
            "wanted %.4f", agree, want)
    log("flash dropout [%d, %d, %d x %d]: keep %.6f (5 sigma %.6f); two "
        "seeds agree on %.4f" % (B, S, heads, D, keep, 5 * sigma, agree))
    del first

    B_, heads, S = (2, 2, 128) if dry_run else (2, 12, 512)
    kept = drawn(seeds[0], B_, heads, D, S, S, rate).reshape(
        B_, heads, S, S).astype(bool)
    err = 0.0
    for dtype in (jnp.float32, jnp.bfloat16):
        q, k, v, g = (put(jnp.asarray(rng.normal(0, 1, (B_, S, heads * D)),
                                      dtype)) for _ in range(4))
        mask = put(jnp.asarray(rng.normal(0, 1, (B_, S, S)), dtype))

        def kernels(q, k, v, b, g):
            out, lse = po._flash_fwd_in_place(q, k, v, b, scale, heads,
                                              False, True, rate, seeds[0])
            return (out,) + po._backward_in_place(
                q, k, v, b, scale, False, heads, lse, g, rate, seeds[0])

        def composition(q, k, v, b, g):
            def core(q, k, v):
                split = [po._heads_major(x.astype(jnp.float32), heads)
                         for x in (q, k, v)]
                with mock.patch.object(jax.random, "bernoulli",
                                       lambda key, p, shape: kept):
                    out = po._attn_core(*split, b[:, None].astype(
                        jnp.float32), scale, False, 0, rate,
                        jax.random.PRNGKey(0))
                return po._heads_minor(out)
            out, vjp = jax.vjp(core, q, k, v)
            return (out,) + vjp(g.astype(jnp.float32))
        got = jax.jit(kernels)(q, k, v, mask, g)
        want = jax.jit(composition)(q, k, v, mask, g)
        for nm, a, w, tol in zip(("out", "dq", "dk", "dv"), got, want,
                                 (FWD_TOL,) + (BWD_TOL,) * 3):
            e = _rel_err(a, w)
            require(e <= tol, "flash dropout %s %s err %.4f",
                    jnp.dtype(dtype).name, nm, e)
            err = max(err, e)
    log("flash dropout in place [B=%d, S=%d, %d x %d]: fwd + dq/dk/dv match "
        "the composition fed the kernels' mask (worst rel err %.4f)"
        % (B_, S, heads, D, err))
    return {"flash_dropout_err": round(err, 5),
            "flash_dropout_keep": round(keep, 7)}


# ---------------------------------------------------------------------------
# --chips 4: one process driving every local chip, both data-parallel paths
# ---------------------------------------------------------------------------

def phase_multichip(place, devices, dry_run):
    """ResNet-50 at global batch 256 x chips in ONE process, once through
    GSPMD (CompiledProgram.with_data_parallel) and once through the
    program's own collectives (GradAllReduce -> c_allreduce_sum -> psum
    under shard_map)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.transpiler import GradAllReduce

    n = len(devices)
    depth, class_dim, image, per = (18, 10, 32, 4) if dry_run \
        else (50, 1000, 224, 256)
    batch = resnet_batch(np.random.RandomState(0), per * n, class_dim, image)

    def gspmd(main, startup, loss):
        return fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)

    def collective(main, startup, loss):
        GradAllReduce().transpile(startup_program=startup, main_program=main,
                                  rank=0, endpoints=[], nranks=n)
        return main

    def require_all_reduce(hlo):
        n_ar = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
        require(n_ar > 0, "no all-reduce in the compiled step")
        log("%d all-reduce(s) in the compiled step" % n_ar)

    first, timings = {}, {}
    for wrap in (gspmd, collective):
        path = wrap.__name__
        first[path], timings[path] = train(
            "resnet%d_b%d_%s" % (depth, per * n, path), place, devices,
            lambda: build_resnet(depth, class_dim, image), batch, wrap=wrap,
            check_hlo=require_all_reduce)
    # same seed, same batch: the two paths start from the same weights, and
    # differ only in batch-norm statistics (global batch under GSPMD, each
    # replica's share under shard_map)
    gap = abs(first["gspmd"] - first["collective"]) / abs(first["gspmd"])
    require(gap <= FWD_TOL, "first-step losses disagree: %s", first)
    log("first-step loss gspmd %.4f vs collective %.4f (rel gap %.4f)"
        % (first["gspmd"], first["collective"], gap))
    return timings


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--dry-run-cpu", action="store_true")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device, devices = phase_device(args.dry_run_cpu, args.chips)
    phases = {"device": "pass"}

    import jax
    import paddle_tpu.fluid as fluid

    place = fluid.CPUPlace() if args.dry_run_cpu else fluid.TPUPlace()
    timings = {}
    dry = args.dry_run_cpu
    if args.chips > 1:
        todo = [("multichip", lambda: phase_multichip(place, devices, dry))]
    else:
        todo = [("resnet50", lambda: phase_resnet(place, devices, dry)),
                ("bert", lambda: phase_bert(place, devices, dry)),
                ("kernels", lambda: phase_kernels(devices[0], dry)),
                ("flash_dropout",
                 lambda: phase_flash_dropout(devices[0], dry))]
    for name, run in todo:
        log("== phase %s" % name)
        t0 = time.perf_counter()
        timings[name] = run()
        phases[name] = "pass"
        log("== phase %s passed in %.0f s" % (name,
                                              time.perf_counter() - t0))
    log("compile cache: JAX_COMPILATION_CACHE_DIR %s; "
        "jax_compilation_cache_dir=%r"
        % ("set" if "JAX_COMPILATION_CACHE_DIR" in os.environ else "unset",
           jax.config.jax_compilation_cache_dir))
    summary = {"device": device, "native": True, "phases": phases,
               "informational": timings,
               "wall_s": round(time.perf_counter() - t_start, 1)}
    if args.dry_run_cpu:
        # no result line: a result comes only from a chip
        log("dry-run summary: " + json.dumps(summary))
        return
    log("summary: " + json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
