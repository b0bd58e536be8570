"""Sequence (context) parallel transpiler: long-sequence sharding as a
program→program annotation pass.

The reference (Fluid 1.5) predates sequence parallelism entirely
(SURVEY.md §2.5: SP/CP absent — long sequences were LoD ragged batches);
this is the TPU re-founding's long-context tier promoted to a framework
feature, following the same strategy→annotation shape as
``transpiler/tensor_parallel.py`` (reference structural precedent:
``transpiler/collective.py:36``).

Mechanism (TPU-first, no communication ops inserted):

* every ``fused_attention`` op is stamped with ``sp_axis``/``sp_mode``
  attrs; at lowering time the op becomes a ``shard_map`` island over the
  'sp' mesh axis running **ring attention** (K/V blocks rotate via
  ``ppermute``, online-softmax merge — Liu et al., arXiv:2310.01889) or
  **Ulysses** (all-to-all head exchange, full-sequence local flash —
  arXiv:2309.14509), so the [S, S] score matrix and the full-sequence
  K/V never materialize on one device;
* activations stay sequence-sharded everywhere else by GSPMD
  propagation: the transpiler records which feed vars carry the sequence
  dim (``program._sp_feed_dims``) and the executor shards those feeds
  P('dp', 'sp'); position-wise ops (matmul/layernorm/gelu) partition for
  free;
* attention ops with an additive BiasQK (padding masks) ride the same
  path: the bias is q-row-sharded over 'sp' with full kv columns local
  (the natural layout of a padding mask) — the ring slices the arriving
  block's column window per step, Ulysses reshards it with the head
  exchange.

Usage::

    t = SequenceParallelTranspiler(sp_degree=4, mode="ring")
    t.transpile(main_program)          # stamps attention ops + feeds
    # or via fleet: DistributedStrategy(sp_degree=4, sp_mode="ulysses")

then run through plain ``Executor.run`` (mesh (dp, sp) built
automatically) or ``CompiledProgram(...).with_data_parallel(...)``.
"""

class SequenceParallelTranspiler:
    """Stamp a program's attention ops + sequence feeds for sequence
    parallelism over ``sp_degree`` mesh partitions."""

    def __init__(self, sp_degree, mode="ring", mesh_axis="sp"):
        if sp_degree < 1:
            raise ValueError("sp_degree must be >= 1")
        if mode not in ("ring", "ulysses"):
            raise ValueError("mode must be 'ring' or 'ulysses', got %r"
                             % (mode,))
        self.sp_degree = sp_degree
        self.mode = mode
        self.mesh_axis = mesh_axis

    def shard_feed(self, program, feed_name, dim=1):
        """Explicitly mark feed ``feed_name`` as carrying the sequence on
        ``dim`` (auto-detection covers feeds whose dim 1 equals the
        attention sequence length)."""
        var = program.global_block()._find_var_recursive(feed_name)
        if var is None:
            raise ValueError("no variable %r in program" % feed_name)
        shape = var.shape or ()
        if len(shape) <= dim:
            raise ValueError("cannot seq-shard %r (shape %s) on dim %d"
                             % (feed_name, shape, dim))
        dims = getattr(program, "_sp_feed_dims", None)
        if dims is None:
            dims = program._sp_feed_dims = {}
        dims[feed_name] = dim

    def transpile(self, main_program, startup_program=None):
        """Stamp every self-attention op; auto-detect sequence feeds.
        Returns the list of stamped attention op indices."""
        program = main_program
        sp = self.sp_degree
        stamped = []
        seq_lens = set()
        bias_names = set()
        block = program.global_block()
        for blk in program.blocks:
            for op in blk.ops:
                if op.type not in ("fused_attention",
                                   "fused_attention_grad"):
                    continue
                qnames = (op.inputs.get("Q") or
                          (op.attrs.get("__fwd_inputs__") or {}).get("Q")
                          or [])
                qv = blk._find_var_recursive(qnames[0]) if qnames else None
                # heads in the minor dimension (``num_heads``): [B, S, H*D]
                heads = op.attrs.get("num_heads")
                if qv is None or not qv.shape or \
                        len(qv.shape) != (3 if heads else 4):
                    continue
                S, H = (qv.shape[1], heads) if heads else \
                    (qv.shape[2], qv.shape[1])
                if S is None or S % sp:
                    raise ValueError(
                        "sequence length %s of attention input %r is not "
                        "divisible by sp_degree=%d — pad/bucket the "
                        "sequence" % (S, qnames[0], sp))
                if self.mode == "ulysses" and H % sp:
                    raise ValueError(
                        "ulysses needs heads %% sp_degree == 0 "
                        "(H=%d, sp=%d); use mode='ring'" % (H, sp))
                # biased attention (padding masks) routes through the
                # ring/ulysses path too: the bias is q-row-sharded and
                # its kv window sliced per ring step (r4)
                op.attrs["sp_axis"] = self.mesh_axis
                op.attrs["sp_mode"] = self.mode
                stamped.append((blk.idx, op.type))
                seq_lens.add(S)
                # cross-attention memory lengths count as sequence dims
                # too: a kv feed left replicated would make the gather
                # island pay an all-gather for data GSPMD must first
                # slice — shard it at the feed instead (only when
                # divisible; feed_spec re-checks divisibility anyway)
                knames = (op.inputs.get("K") or
                          (op.attrs.get("__fwd_inputs__") or {}).get("K")
                          or [])
                kv = blk._find_var_recursive(knames[0]) if knames else None
                if kv is not None and kv.shape and \
                        len(kv.shape) == (3 if heads else 4):
                    S_kv = kv.shape[1 if heads else 2]
                    if S_kv and S_kv > 0 and S_kv % sp == 0:
                        seq_lens.add(S_kv)
                bias_names.update(
                    op.inputs.get("BiasQK") or
                    (op.attrs.get("__fwd_inputs__") or {})
                    .get("BiasQK") or [])
        if not stamped:
            raise ValueError(
                "SequenceParallelTranspiler found no fused_attention op "
                "to shard — build the model with "
                "fluid.layers.fused_attention (models/transformer.py and "
                "models/bert.py do whenever use_fused_attention is on; "
                "attention dropout and cross-attention are supported)")
        # feeds carrying the sequence dim: any unfed-by-ops data var whose
        # dim 1 matches an attention sequence length
        produced = set()
        for blk in program.blocks:
            for op in blk.ops:
                for names in op.outputs.values():
                    produced.update(names)
        dims = getattr(program, "_sp_feed_dims", None) or {}
        auto_detected = []
        for v in block.vars.values():
            if getattr(v, "persistable", False) or v.name in produced:
                continue
            shape = v.shape or ()
            if v.name in bias_names:
                # an attention-bias feed [B, 1|H, S_q, S_kv] is q-ROW
                # sharded (dim 2) — exactly the shard_map layout of
                # _sp_attention — never dim-1 (that's the head dim,
                # which may coincidentally equal S)
                if len(shape) == 4 and shape[2] in seq_lens:
                    dims.setdefault(v.name, 2)
                continue
            if len(shape) >= 2 and shape[1] in seq_lens:
                if v.name not in dims:
                    dims[v.name] = 1
                    auto_detected.append(v.name)
        program._sp_feed_dims = dims
        if auto_detected:
            # shape coincidence is not intent (VERDICT r4 item 6c): a
            # [B, S]-shaped NON-sequence feed whose dim 1 happens to
            # equal an attention sequence length would be silently
            # seq-sharded — say what was auto-detected and how to
            # override it
            import warnings
            warnings.warn(
                "sequence-parallel auto-detection will shard feeds %s on "
                "dim 1 (dim matches an attention sequence length %s); if "
                "any of these is NOT a sequence tensor, override it with "
                "SequenceParallelTranspiler.shard_feed(program, name, "
                "dim) before compiling" % (sorted(auto_detected),
                                           sorted(seq_lens)), stacklevel=2)
        program._sp_degree = sp
        program._sp_mode = self.mode
        if startup_program is not None:
            startup_program._sp_degree = sp
            startup_program._sp_mode = self.mode
        return stamped
