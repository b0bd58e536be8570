#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once — ``fluid.Program`` -> ``fluid.Executor(
fluid.TPUPlace())`` -> one jitted XLA step per dispatch — at the full width
of ResNet-50 and BERT-base, with random weights made from a seed, and checks
what comes out by the repo's own means.  ONE process, no child: a chip
belongs to one process at a time.

    python chip_smoke.py                 one chip: phases 1-4
    python chip_smoke.py --chips 4       one process driving four chips
    python chip_smoke.py --dry-run-cpu   the same phases at tiny sizes with
                                         interpreted kernels (pre-flight and
                                         tier-1 handle; NOT a chip result)

Phases (any failure raises through to a non-zero exit):

1. device   versions, ``jax.devices()``; anything but a TPU fails here,
            before any work, naming what was found.
2. resnet50 bench.py's ResNet-50 program (batch 256, Momentum + L2,
            pure-bf16 AMP), >= 6 steps fed as host numpy batches through
            ``fluid.DataLoader.from_generator``.
3. bert     BERT-base pretrain twice: (a) S=128, batch 64, dropout 0.1
            (attention runs the XLA composition); (b) S=512, batch 16,
            ``attn_dropout=0`` (the Pallas flash kernels, forward and
            backward, are inside the step — proven from its compiled HLO).
4. kernels  every Pallas kernel alone against its ``jnp`` reference.

Per training run: loss finite at every step and lower at the last than the
first on a repeated batch; every persistable a ``jax.Array`` on the
executor's platform; no compile after the first training step.

The timings printed are informational: this script records no metric.  The
last line of stdout is one JSON object, printed only when every phase
passed.
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

TRAIN_STEPS = 8        # >= 6; the dry run takes 6


def log(msg):
    print(msg, flush=True)


def require(ok, msg, *args):
    """A check that fails the smoke (not an ``assert``: ``python -O`` would
    strip those and pass everything)."""
    if not ok:
        raise AssertionError(msg % args)


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def phase_device(dry_run, min_chips):
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
    else:
        if d0.platform != "tpu":
            raise SystemExit(
                "chip_smoke: needs a TPU, but JAX found platform=%r "
                "(device_kind=%r, %d device(s)); --dry-run-cpu runs the "
                "phases at tiny sizes on the CPU"
                % (d0.platform, d0.device_kind, len(devs)))
        if len(devs) < min_chips:
            raise SystemExit("chip_smoke: --chips %d needs %d TPU devices, "
                             "JAX found %d" % (min_chips, min_chips,
                                               len(devs)))
    from paddle_tpu import native
    log("native runtime loaded: %s" % native.available())
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


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


def check_state_on(scope, platform, n_devices=1, names=None):
    """Every persistable in the scope (or just ``names``) is a jax.Array
    living on ``n_devices`` devices of ``platform``."""
    import jax

    names = names or scope.var_names()
    require(names, "scope holds no state")
    for n in names:
        v = scope.find_var(n)
        require(isinstance(v, jax.Array), "state %r is %s, not a jax.Array",
                n, type(v).__name__)
        devs = v.sharding.device_set
        require(len(devs) == n_devices and
                all(d.platform == platform for d in devs),
                "state %r lives on %s, wanted %d %s device(s)",
                n, sorted(str(d) for d in devs), n_devices, platform)
    return len(names)


def check_losses(name, losses):
    require(all(np.isfinite(losses)), "%s: non-finite loss %s", name, losses)
    require(losses[-1] < losses[0],
            "%s: loss did not fall on a repeated batch: %s", name, losses)


def _timings(step_s):
    """Informational: the first step carries trace + compile; the rest are
    fenced one by one, so each includes a host round trip."""
    return {"compile_s": round(step_s[0], 2),
            "steady_ms_per_step":
                round(float(np.median(step_s[2:])) * 1e3, 2)}


def train(name, place, build, batch, steps, expect_mosaic=False):
    """Build the program the way a user does, feed ``batch`` (host numpy)
    ``steps`` times through an iterable DataLoader whose feed ring stages
    it onto ``place``, and check the run.  ``expect_mosaic``: the compiled
    step's HLO must hold the Mosaic custom call.  Returns informational
    timings."""
    import jax
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, loss = build()
        loader = fluid.DataLoader.from_generator(feed_list=feeds, capacity=4,
                                                 iterable=True)
    loader.set_batch_generator(lambda: (batch for _ in range(steps)),
                               places=place)
    scope = fluid.Scope()
    losses, step_s = [], []
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        platform = exe._device.platform
        exe.run(startup)
        compiles_after_first = None
        for feed in loader():
            for k, v in feed.items():
                require(isinstance(v, jax.Array) and
                        v.devices() == {exe._device},
                        "%s: feed %r was not staged onto %s", name, k,
                        exe._device)
            t0 = time.perf_counter()
            out = exe.run(main, feed=feed, fetch_list=[loss],
                          return_numpy=False)
            jax.block_until_ready(out)
            step_s.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
            if compiles_after_first is None:
                compiles_after_first = exe.compile_count()
        require(len(losses) == steps, "%s: %d of %d steps ran", name,
                len(losses), steps)
        check_losses(name, losses)
        require(exe.compile_count() == compiles_after_first,
                "%s: recompiled after the first training step (%d -> %d)",
                name, compiles_after_first, exe.compile_count())
        n_state = check_state_on(scope, platform)
        if expect_mosaic:
            # compiled, not interpreted and not replaced by the reference
            require(MOSAIC_CALL in exe.compiled_hlo(main, feed=feed,
                                                    fetch_list=[loss]),
                    "%s: no Mosaic custom call in the compiled step", name)
    timings = _timings(step_s)
    log("%s: loss %.4f -> %.4f over %d steps; %d persistables on %s; "
        "first step (trace+compile) %.1f s, steady %.1f ms/step "
        "[informational]"
        % (name, losses[0], losses[-1], steps, n_state, platform,
           timings["compile_s"], timings["steady_ms_per_step"]))
    return timings


def phase_resnet(place, dry_run):
    depth, class_dim, image, batch, steps = (18, 10, 32, 4, 6) if dry_run \
        else (50, 1000, 224, 256, TRAIN_STEPS)
    rng = np.random.RandomState(0)
    return train("resnet%d_b%d" % (depth, batch), place,
                 lambda: build_resnet(depth, class_dim, image),
                 resnet_batch(rng, batch, class_dim, image), steps)


def phase_bert(place, dry_run):
    from paddle_tpu import models

    if dry_run:
        def make(**kw):
            return models.bert.tiny_config(num_layers=1, **kw)
        batch_a, batch_b, s_a, s_b, steps = 4, 2, 32, 128, 6
    else:
        make = models.bert.base_config
        batch_a, batch_b, s_a, s_b, steps = 64, 16, 128, 512, TRAIN_STEPS
    out = {}
    rng = np.random.RandomState(0)
    cfg = make(max_seq_len=s_a, max_position=512)
    require(cfg.attn_dropout == 0.1 and cfg.use_fused_attention,
            "bert (a) is not the default config")
    out["a"] = train("bert_a_S%d_b%d" % (s_a, batch_a), place,
                     lambda: build_bert(cfg), bert_batch(rng, cfg, batch_a),
                     steps)
    cfg_b = make(max_seq_len=s_b, max_position=512, attn_dropout=0.0)
    out["b"] = train("bert_b_S%d_b%d_flash" % (s_b, batch_b), place,
                     lambda: build_bert(cfg_b),
                     bert_batch(rng, cfg_b, batch_b), steps,
                     expect_mosaic=not dry_run)
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
    from paddle_tpu.fluid.ops.conv_pallas import conv3x3_bn_relu

    on_tpu = device.platform == "tpu"
    bf16, f32 = jnp.bfloat16, jnp.float32
    rng = np.random.RandomState(0)

    def arr(shape, dtype=bf16, scale=1.0):
        return jax.device_put(
            jnp.asarray(rng.normal(0, scale, shape), dtype), device)

    BH, D = (1, 64) if dry_run else (16 * 12, 64)
    seqs = (128,) if dry_run else (128, 512)
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
        for args in ((q, k, v), (q, k, v, bias)):
            for causal in (False, True):
                what = "flash S=%d bias=%s causal=%s" % (S, len(args) == 4,
                                                         causal)
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

    M, H = (64, 128) if dry_run else (64 * 128, 768)
    x, sc, sh = arr((M, H)), arr((H,), f32), arr((H,), f32)

    def ln(x, sc, sh):
        return po.fused_layer_norm(x, sc, sh, 1e-5)
    _check_lowering(ln, (x, sc, sh), on_tpu, "fused_layer_norm")
    e_ln = _rel_err(jax.jit(ln)(x, sc, sh),
                    po._reference_layer_norm(x, sc, sh, 1e-5))
    require(e_ln <= FWD_TOL, "fused_layer_norm err %.4f", e_ln)
    log("fused_layer_norm [%d, %d]: rel err %.4f" % (M, H, e_ln))

    N, HW, C = (2, 8, 64) if dry_run else (32, 56, 64)
    x = arr((N, HW, HW, C))
    w = arr((3, 3, C, C), scale=1.0 / np.sqrt(9 * C))
    sc, sh = arr((C,), f32), arr((C,), f32)

    def conv_ref(x, w, sc, sh):
        y = jax.lax.conv_general_dilated(
            x.astype(f32), w.astype(f32), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.maximum(y * sc + sh, 0.0)
    _check_lowering(conv3x3_bn_relu, (x, w, sc, sh), on_tpu,
                    "conv3x3_bn_relu")
    e_conv = _rel_err(jax.jit(conv3x3_bn_relu)(x, w, sc, sh),
                      jax.jit(conv_ref)(x, w, sc, sh))
    require(e_conv <= FWD_TOL, "conv3x3_bn_relu err %.4f", e_conv)
    log("conv3x3_bn_relu [%d,%d,%d,%d]->%d: rel err %.4f"
        % (N, HW, HW, C, C, e_conv))
    return {"flash_fwd_err": round(worst["fwd"], 5),
            "flash_bwd_err": round(worst["bwd"], 5),
            "layer_norm_err": round(e_ln, 5), "conv3x3_err": round(e_conv, 5)}


# ---------------------------------------------------------------------------
# --chips 4: one process driving every local chip, both data-parallel paths
# ---------------------------------------------------------------------------

def _all_reduces(hlo):
    return hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")


def _check_feed_shards(name, feed, n, batch):
    for k, v in feed.items():
        shards = v.addressable_shards
        require(len(shards) == n and
                len({s.device for s in shards}) == n and
                all(s.data.shape[0] == batch // n for s in shards),
                "%s: feed %r is not %d shards of %d rows on %d devices: %s",
                name, k, n, batch // n, n,
                [(str(s.device), s.data.shape) for s in shards])


def _check_memory_spread(name, devices):
    used = [d.memory_stats()["bytes_in_use"] for d in devices]
    log("%s: bytes_in_use per device %s" % (name, used))
    require(min(used) > 0 and max(used) <= 3 * min(used),
            "%s: device memory is lopsided: %s", name, used)


def phase_multichip(place, dry_run, n):
    """ResNet-50 at global batch 256*n in ONE process, once through GSPMD
    (CompiledProgram.with_data_parallel) and once through the program's own
    collectives (GradAllReduce -> c_allreduce_sum -> psum under
    shard_map)."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import _scope_state
    from paddle_tpu.fluid.transpiler import GradAllReduce

    depth, class_dim, image, per = (18, 10, 32, 4) if dry_run \
        else (50, 1000, 224, 256)
    batch = per * n
    steps = 6
    host_batch = resnet_batch(np.random.RandomState(0), batch, class_dim,
                              image)
    first, timings = {}, {}
    for path in ("gspmd", "collective"):
        name = "resnet%d_b%d_%s" % (depth, batch, path)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 1
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            feeds, loss = build_resnet(depth, class_dim, image)
            loader = fluid.DataLoader.from_generator(
                feed_list=feeds, capacity=4, iterable=False)
        loader.set_batch_generator(
            lambda: (host_batch for _ in range(steps + 8)))
        if path == "gspmd":
            prog = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
        else:
            GradAllReduce().transpile(startup_program=startup,
                                      main_program=main, rank=0,
                                      endpoints=[], nranks=n)
            prog = main
        scope = fluid.Scope()
        losses, step_s = [], []
        with fluid.scope_guard(scope):
            exe = fluid.Executor(place)
            platform = exe._device.platform
            devices = jax.devices(platform)[:n]
            exe.run(startup)
            loader.start()
            try:
                for _ in range(steps):
                    t0 = time.perf_counter()
                    out = exe.run(prog, fetch_list=[loss],
                                  return_numpy=False)
                    jax.block_until_ready(out)
                    step_s.append(time.perf_counter() - t0)
                    # the collective path fetches one loss per replica
                    losses.append(float(np.mean(np.asarray(out[0]))))
                compiled = exe._last_compiled
                # one more batch, placed the way every dispatch of this
                # executable places its feeds, inspected, then dispatched
                staged = loader.next_feed()
                feed = dict(zip(compiled.feed_names,
                                compiled.fix_feed_placements(
                                    [staged[k] for k in
                                     compiled.feed_names])))
                _check_feed_shards(name, feed, n, batch)
                jax.block_until_ready(exe.run(prog, feed=feed,
                                              fetch_list=[loss],
                                              return_numpy=False))
            finally:
                loader.reset()
            check_losses(name, losses)
            # parameters: read-only state (the learning rate) is re-placed
            # at each dispatch and stays where the startup program put it
            n_state = check_state_on(
                scope, platform, n_devices=n,
                names=[p.name for p in main.global_block().all_parameters()])
            if not dry_run:
                _check_memory_spread(name, devices)
            if path == "gspmd":
                # Executor.compiled_hlo takes raw programs only: lower the
                # data-parallel executable behind the last dispatch
                hlo = compiled._jitted.lower(
                    _scope_state(scope, compiled.state_mut),
                    _scope_state(scope, compiled.state_ro),
                    tuple(feed[k] for k in compiled.feed_names),
                    np.int32(scope.step_counter)).compile().as_text()
            else:
                hlo = exe.compiled_hlo(main, feed=feed, fetch_list=[loss])
            n_ar = _all_reduces(hlo)
            require(n_ar > 0, "%s: no all-reduce in the compiled step", name)
        first[path] = losses[0]
        timings[path] = _timings(step_s)
        log("%s: loss %.4f -> %.4f; %d parameters on %d %s devices; %d "
            "all-reduce(s) in the HLO; first step %.1f s, steady %.1f "
            "ms/step [informational]"
            % (name, losses[0], losses[-1], n_state, n, platform, n_ar,
               step_s[0], timings[path]["steady_ms_per_step"]))
    # same seed, same batch: the two paths start from the same weights, and
    # differ only in batch-norm statistics (global batch under GSPMD, each
    # replica's quarter under shard_map)
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
    device = phase_device(args.dry_run_cpu, args.chips)
    phases = {"device": "pass"}

    import jax
    import paddle_tpu.fluid as fluid

    place = fluid.CPUPlace() if args.dry_run_cpu else fluid.TPUPlace()
    timings = {}
    if args.chips > 1:
        todo = [("multichip", lambda: phase_multichip(
            place, args.dry_run_cpu, args.chips))]
    else:
        todo = [("resnet50", lambda: phase_resnet(place, args.dry_run_cpu)),
                ("bert", lambda: phase_bert(place, args.dry_run_cpu)),
                ("kernels", lambda: phase_kernels(jax.devices()[0],
                                                  args.dry_run_cpu))]
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
    result = {"ok": True, "device": device, "phases": phases,
              "informational": timings,
              "wall_s": round(time.perf_counter() - t_start, 1)}
    if args.dry_run_cpu:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
