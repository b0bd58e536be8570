"""BERT pretraining convergence acceptance (VERDICT r3 item 6).

The book tests cover small models; the north star names BERT.  This file
is the bounded pretraining acceptance: a synthetic corpus with LEARNABLE
structure (first-order Markov chains — a masked token is predictable
from its left neighbor), a few hundred optimizer steps, and three
assertions:

1. the MLM+NSP loss CONVERGES (falls well below the random-prediction
   entropy, not just "decreases");
2. the same pretraining program is dp=8-parity-exact on the CPU mesh
   (the reference's test_dist_base.py:362 oracle, SPMD form);
3. the flagship width runs: hidden 768 / 12 heads / vocab 30522 (the
   real BERT-base embedding + attention geometry, depth-trimmed for CPU
   time), finite and decreasing.

On-chip BERT-base at full width is chip_smoke.py's and bench.py's job.
"""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu import models

MASK_ID = 0          # reserved mask token in the synthetic vocabulary


def _corpus_batch(rng, chain, batch, S, n_pred, vocab):
    """Markov sentences + BERT masking: returns a feed dict.

    ``chain`` [vocab] maps token t -> its deterministic successor; each
    sentence is a random-start chain walk, so P(token | left neighbor)
    is a delta — an attention model can drive MLM loss toward 0.
    """
    starts = rng.randint(1, vocab, batch)
    seq = np.empty((batch, S), np.int64)
    seq[:, 0] = starts
    for i in range(1, S):
        seq[:, i] = chain[seq[:, i - 1]]
    # mask n_pred positions per sentence (never position 0: its
    # predecessor is unseen, keeping the task fully learnable)
    mask_pos = np.stack([rng.choice(np.arange(1, S), n_pred, replace=False)
                         for _ in range(batch)])
    mask_label = np.take_along_axis(seq, mask_pos, 1).reshape(-1, 1)
    masked = seq.copy()
    np.put_along_axis(masked, mask_pos, MASK_ID, 1)
    flat_pos = (mask_pos + np.arange(batch)[:, None] * S).reshape(-1, 1)
    return {
        "src_ids": masked[:, :, None],
        "pos_ids": np.tile(np.arange(S)[None, :, None], (batch, 1, 1))
        .astype(np.int64),
        "sent_ids": np.zeros((batch, S, 1), np.int64),
        "input_mask": np.ones((batch, S, 1), np.float32),
        "mask_pos": flat_pos.astype(np.int32),
        "mask_label": mask_label.astype(np.int64),
        "nsp_label": rng.randint(0, 2, (batch, 1)).astype(np.int64),
    }


def _build(cfg, lr, n_pred):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        handles = models.bert.build_pretrain(cfg, lr=lr,
                                             max_pred_per_seq=n_pred)
    return main, startup, handles


def test_bert_pretrain_converges():
    """800 steps on the Markov corpus: MLM+NSP loss must fall from the
    random-prediction level (ln V + ln 2 ~ 6.9 at V=512) well toward the
    NSP floor (NSP labels are random, so ln 2 ~ 0.69 is irreducible).

    Config tuned on the CPU mesh (r4 sweep): 2 layers / hidden 64 at
    Adam lr 3e-3 descends 6.9 -> ~2.4 in 800 steps and is still
    falling; deeper post-LN stacks need the noam warmup the flagship
    recipe uses (models/transformer.py:161) — covered by the width
    smoke below."""
    vocab, S, B, n_pred = 512, 32, 32, 8
    cfg = models.bert.tiny_config(
        hidden_size=64, num_layers=2, num_heads=4, max_seq_len=S,
        vocab_size=vocab, max_position=2 * S)
    main, startup, handles = _build(cfg, lr=3e-3, n_pred=n_pred)
    rng = np.random.RandomState(0)
    chain = rng.permutation(vocab).astype(np.int64)
    chain[chain == MASK_ID] = rng.randint(1, vocab)   # never emit MASK
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for step in range(800):
            feed = _corpus_batch(rng, chain, B, S, n_pred, vocab)
            lv, = exe.run(main, feed=feed,
                          fetch_list=[handles["loss"]],
                          return_numpy=(step % 50 == 49))
            if step % 50 == 49:
                losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.all(np.isfinite(losses)), losses
    # random MLM over 512 tokens + random NSP: ~6.9 nats.  Converged:
    # MLM -> small (deterministic chain), NSP floor ln2 ~ 0.69.
    assert losses[0] < 7.4, losses
    assert losses[-1] < 2.9, ("BERT pretraining did not converge on the "
                              "Markov corpus: %s" % losses)
    assert losses[-1] < 0.45 * losses[0], losses


def test_bert_pretrain_dp8_parity():
    """The SAME pretraining program, dp=8 CompiledProgram vs single
    device: per-step losses equal (test_dist_base oracle)."""
    vocab, S, B, n_pred = 512, 32, 16, 4
    cfg = models.bert.tiny_config(
        hidden_size=64, num_layers=2, num_heads=4, max_seq_len=S,
        vocab_size=vocab, max_position=2 * S)
    rng0 = np.random.RandomState(1)
    chain = rng0.permutation(vocab).astype(np.int64)
    chain[chain == MASK_ID] = rng0.randint(1, vocab)
    feeds = []
    for _ in range(5):
        feeds.append(_corpus_batch(rng0, chain, B, S, n_pred, vocab))

    def run(data_parallel):
        main, startup, handles = _build(cfg, lr=1e-3, n_pred=n_pred)
        out = []
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            prog = main
            if data_parallel:
                prog = fluid.CompiledProgram(main).with_data_parallel(
                    loss_name=handles["loss"].name)
            for feed in feeds:
                lv, = exe.run(prog, feed=feed,
                              fetch_list=[handles["loss"]])
                out.append(float(np.asarray(lv).reshape(-1)[0]))
        return out

    ref = run(False)
    dp = run(True)
    np.testing.assert_allclose(ref, dp, rtol=2e-5, atol=2e-5)


def test_bert_flagship_width_smoke():
    """Real BERT-base geometry where it matters for lowering coverage:
    hidden 768, 12 heads, vocab 30522, S=128 (depth trimmed to 2 layers
    for CPU time).  Three steps: finite and moving."""
    vocab, S, B, n_pred = 30522, 128, 4, 8
    cfg = models.bert.base_config(num_layers=2, max_seq_len=S)
    assert cfg.hidden_size == 768 and cfg.num_heads == 12
    assert cfg.vocab_size == vocab
    main, startup, handles = _build(cfg, lr=1e-4, n_pred=n_pred)
    rng = np.random.RandomState(2)
    chain = rng.permutation(vocab).astype(np.int64)
    chain[chain == MASK_ID] = rng.randint(1, vocab)
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for _ in range(3):
            feed = _corpus_batch(rng, chain, B, S, n_pred, vocab)
            lv, = exe.run(main, feed=feed, fetch_list=[handles["loss"]])
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] != losses[0]


def test_bert_pretrain_sp4_parity():
    """BERT pretraining under SEQUENCE PARALLELISM (sp=4, ring): the
    flagship integration of the r4 SP feature — the encoder's padding
    -mask attention rides the ring path (bias q-row-sharded, kv window
    sliced per step), embeddings/FFN stay sequence-sharded by GSPMD.
    Per-step loss parity vs the single-device program."""
    from paddle_tpu.fluid.transpiler import SequenceParallelTranspiler

    vocab, S, B, n_pred = 512, 32, 8, 4
    # attn_dropout=0 engages the fused_attention op (the SP target);
    # hidden_dropout off keeps the parity oracle exact
    cfg = models.bert.tiny_config(
        hidden_size=64, num_layers=2, num_heads=4, max_seq_len=S,
        vocab_size=vocab, max_position=2 * S, attn_dropout=0.0,
        hidden_dropout=0.0)
    rng0 = np.random.RandomState(5)
    chain = rng0.permutation(vocab).astype(np.int64)
    chain[chain == MASK_ID] = rng0.randint(1, vocab)
    feeds = [_corpus_batch(rng0, chain, B, S, n_pred, vocab)
             for _ in range(4)]

    def run(sp):
        main, startup, handles = _build(cfg, lr=1e-3, n_pred=n_pred)
        if sp > 1:
            stamped = SequenceParallelTranspiler(sp, mode="ring") \
                .transpile(main, startup)
            assert stamped
            assert main._sp_feed_dims.get("src_ids") == 1
        out = []
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for feed in feeds:
                lv, = exe.run(main, feed=feed,
                              fetch_list=[handles["loss"]])
                out.append(float(np.asarray(lv).reshape(-1)[0]))
        return out

    ref = run(1)
    sp = run(4)
    np.testing.assert_allclose(ref, sp, rtol=3e-5, atol=3e-5)
