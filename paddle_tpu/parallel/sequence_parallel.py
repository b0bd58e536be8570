"""Ring attention + Ulysses (all-to-all) sequence parallelism.

Long-context attention over a sequence-sharded batch: every device holds
``T_local = T / P`` of the sequence (P = size of the ``sp`` mesh axis).

- ``ring_attention``: K/V blocks rotate around the ring via ``lax.ppermute``
  (one ICI hop per step) while each device's Q stays resident; softmax is
  accumulated online (running max / denominator — the flash-attention
  recurrence), so the full ``T×T`` score matrix never materializes.  Compute
  and the next block's transfer overlap (XLA schedules the ppermute DMA
  against the einsum).  Reverse-mode differentiable: jax transposes the
  ppermutes automatically.
- ``ulysses_attention``: DeepSpeed-Ulysses layout swap — ``all_to_all``
  turning the sequence shard into a head shard ([B, T/P, H, D] →
  [B, T, H/P, D]), full-sequence attention on local heads, then the inverse
  all_to_all.  Two collectives per layer; needs H % P == 0.

Both match ``local_attention`` (the single-device oracle) exactly — tests
assert value and gradient parity on a virtual 8-device CPU mesh.

These primitives do not exist in the reference (SURVEY.md §2.5 — Fluid 1.5
predates sequence parallelism); they are the long-context design the TPU
rebuild adds as first-class, following the public blockwise/ring-attention
recipe (PAPERS.md).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax


NEG_INF = -1e30


def _scores(q, k, scale):
    # [B, Tq, H, D] x [B, Tk, H, D] -> [B, H, Tq, Tk]; bf16-friendly MXU
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def local_attention(q, k, v, causal=False, scale=None, q_offset=0,
                    k_offset=0, bias=None):
    """Single-device softmax attention oracle ([B, T, H, D] layout).

    q_offset/k_offset: global positions of the local blocks, for causal
    masking under sequence sharding.  bias: additive [B, 1|H, Tq, Tk]."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    s = _scores(q, k, scale)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])
        kpos = k_offset + jnp.arange(k.shape[1])
        allowed = qpos[:, None] >= kpos[None, :]
        s = jnp.where(allowed[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _flash_block(q, kb, vb, scale):
    """One ring step through the pallas flash kernel: returns the block's
    normalized output AND its logsumexp so steps merge exactly.
    [B, Tl, H, D] layout in/out."""
    from paddle_tpu.fluid.ops.pallas_ops import _flash_forward

    B, Tl, H, D = q.shape
    qf = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, Tl, D)
    kf = jnp.transpose(kb, (0, 2, 1, 3)).reshape(B * H, Tl, D)
    vf = jnp.transpose(vb, (0, 2, 1, 3)).reshape(B * H, Tl, D)
    o, lse = _flash_forward(qf, kf, vf, None, scale, with_lse=True)
    o = jnp.transpose(o.reshape(B, H, Tl, D), (0, 2, 1, 3))
    return o.astype(jnp.float32), lse.reshape(B, H, Tl)


def _ring_flash_fwd_impl(q, k, v, axis_name, scale):
    P = lax.axis_size(axis_name)
    B, Tl, H, D = q.shape
    perm = [(j, (j + 1) % P) for j in range(P)]
    kb, vb = k, v
    o = jnp.zeros((B, Tl, H, D), jnp.float32)
    lse = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
    for step in range(P):
        o_s, lse_s = _flash_block(q, kb, vb, scale)
        new_lse = jnp.logaddexp(lse, lse_s)
        w_old = jnp.exp(lse - new_lse)
        w_new = jnp.exp(lse_s - new_lse)
        wo = jnp.transpose(w_old, (0, 2, 1))[..., None]   # [B,Tl,H,1]
        wn = jnp.transpose(w_new, (0, 2, 1))[..., None]
        o = o * wo + o_s * wn
        lse = new_lse
        if step < P - 1:
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)
    return o.astype(q.dtype), lse


def _bhsd(x):
    """[B, Tl, H, D] -> [B*H, Tl, D] (the pallas kernels' layout)."""
    B, Tl, H, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, Tl, D)


def _bshd(x, B, H):
    BH, Tl, D = x.shape
    return jnp.transpose(x.reshape(B, H, Tl, D), (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_attention_flash(q, k, v, axis_name, scale):
    """Non-causal ring attention where each step's local block runs the
    pallas flash kernel — even the [Tl, Tl] per-step score block never
    reaches HBM.  Steps merge by logsumexp re-weighting (exact).

    Backward is tiled too: with the GLOBAL logsumexp saved from forward,
    p recomputes blockwise per ring step (FlashAttention-2 decomposition
    holds across blocks), dQ accumulates locally, and dK/dV accumulators
    rotate around the ring WITH their K/V blocks, arriving home after a
    full revolution."""
    return _ring_flash_fwd_impl(q, k, v, axis_name, scale)[0]


def _ring_flash_fwd(q, k, v, axis_name, scale):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, scale)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, scale, res, g):
    from paddle_tpu.fluid.ops.pallas_ops import _flash_backward, _row_delta

    q, k, v, out, lse = res
    P = lax.axis_size(axis_name)
    B, Tl, H, D = q.shape
    perm = [(j, (j + 1) % P) for j in range(P)]
    qf, gf = _bhsd(q), _bhsd(g.astype(q.dtype))
    # delta belongs to the GLOBAL row: formed here from the merged output,
    # never by a backward kernel over one ring step's K/V shard
    delta = _row_delta(gf, _bhsd(out))
    lsef = lse.reshape(B * H, Tl)
    kb, vb = k, v
    dq = jnp.zeros((B * H, Tl, D), jnp.float32)
    dkb = jnp.zeros_like(k, dtype=jnp.float32)
    dvb = jnp.zeros_like(v, dtype=jnp.float32)
    for step in range(P):
        dq_s, dk_s, dv_s, _ = _flash_backward(
            qf, _bhsd(kb), _bhsd(vb), None, scale, lsef, gf, delta=delta)
        dq = dq + dq_s.astype(jnp.float32)
        dkb = dkb + _bshd(dk_s, B, H).astype(jnp.float32)
        dvb = dvb + _bshd(dv_s, B, H).astype(jnp.float32)
        # rotate after EVERY step (P total = identity): the accumulators
        # travel with their blocks and are home when the loop ends
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        dkb = lax.ppermute(dkb, axis_name, perm)
        dvb = lax.ppermute(dvb, axis_name, perm)
    return (_bshd(dq, B, H).astype(q.dtype), dkb.astype(k.dtype),
            dvb.astype(v.dtype))


_ring_attention_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None,
                   use_flash=None, bias=None):
    """Blockwise ring attention over the ``axis_name`` mesh axis.

    q, k, v: [B, T_local, H, D] — this device's sequence shard.
    Returns [B, T_local, H, D], exact (not approximate) attention over the
    full sequence.

    bias: additive [B, 1|H, T_local, T_global] — this device's q rows,
    ALL kv columns (a padding mask is q-row-sharded, kv-full); each ring
    step slices the arriving block's column window.  Bias forces the
    masked-einsum path.

    use_flash: run each step's block attention through the pallas flash
    kernel (ops/pallas_ops.py) so the per-step [Tl, Tl] score block stays
    in VMEM.  Default: on for non-causal, bias-free tileable shards.
    Causal ring attention keeps the masked-einsum path (the block mask
    depends on the traced ring position, which a static pallas grid
    cannot consume).
    """
    B, Tl, H, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    if use_flash and causal:
        raise ValueError(
            "use_flash=True is not available for causal ring attention "
            "(the block mask depends on the traced ring position, which "
            "a static pallas grid cannot consume) — omit use_flash")
    if use_flash and bias is not None:
        raise ValueError(
            "use_flash=True is not available for biased ring attention "
            "(the bias column window depends on the traced ring "
            "position) — omit use_flash")
    tileable = Tl % min(128, Tl) == 0
    # scale rides custom_vjp nondiff_argnums on the flash path, so it
    # must be a static Python number there
    static_scale = None
    try:
        static_scale = float(scale)
    except Exception:
        pass
    if use_flash:
        if not tileable:
            raise ValueError(
                "use_flash=True needs the local shard length (%d) to be "
                "a multiple of the 128 block size — pad/bucket the "
                "sequence or omit use_flash" % Tl)
        if static_scale is None:
            raise ValueError(
                "use_flash=True needs a static (Python float) scale, "
                "got a traced value — omit use_flash or pass a constant")
    def einsum(q, k, v):
        return _ring_attention_einsum(q, k, v, axis_name, causal, scale,
                                      bias=bias)

    def flash(q, k, v):
        return _ring_attention_flash(q, k, v, axis_name, static_scale)

    if use_flash is None and (not causal) and bias is None and tileable \
            and static_scale is not None:
        # default: the kernel where it pays — a computation lowered for
        # a TPU (interpret-mode pallas anywhere else is strictly slower
        # emulation).  The lowering's target platform decides, not the
        # process's default backend.
        return lax.platform_dependent(q, k, v, tpu=flash, default=einsum)
    return flash(q, k, v) if use_flash else einsum(q, k, v)


def _ring_attention_einsum(q, k, v, axis_name, causal, scale, bias=None):
    """The masked-einsum ring (blockwise online softmax); also the
    autodiff path behind the flash forward."""
    P = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tl, H, D = q.shape
    q32 = q.astype(jnp.float32)
    m = jnp.full((B, H, Tl), NEG_INF, jnp.float32)     # running max
    l = jnp.zeros((B, H, Tl), jnp.float32)             # running denom
    acc = jnp.zeros((B, Tl, H, D), jnp.float32)        # running numerator

    perm = [(j, (j + 1) % P) for j in range(P)]
    kb, vb = k, v
    qpos = my * Tl + jnp.arange(Tl)

    def ring_step(q32, kb, vb, m, l, acc, src, bias_full):
        s = _scores(q32, kb.astype(jnp.float32), scale)  # [B,H,Tl,Tl]
        if bias_full is not None:
            # this ring step sees the src block's column window of the
            # q-row-sharded, kv-full bias [B, 1|H, Tl, T] — slice FIRST,
            # cast the [Tl, Tl] window (a pre-slice cast would re-run
            # over the full bias in every checkpoint region)
            bb = lax.dynamic_slice_in_dim(bias_full, src * Tl, Tl,
                                          axis=3).astype(jnp.float32)
            s = s + bb
        if causal:
            kpos = src * Tl + jnp.arange(Tl)
            allowed = qpos[:, None] >= kpos[None, :]
            s = jnp.where(allowed[None, None], s, NEG_INF)
        blk_max = s.max(axis=-1)                         # [B,H,Tl]
        m_new = jnp.maximum(m, blk_max)
        # guard fully-masked-so-far rows (m_new still -inf)
        live = m_new > NEG_INF / 2
        corr = jnp.where(live, jnp.exp(m - m_new), 0.0)
        p = jnp.where(live[..., None], jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vb.astype(jnp.float32))
        acc_new = acc * jnp.transpose(corr, (0, 2, 1))[..., :, None] + pv
        return m_new, l_new, acc_new

    # remat per ring step: without it, backward keeps every step's
    # [Tl, Tl] score/prob blocks — O(S^2/sp * H) residual bytes per
    # device, which silently forfeits the long-context memory property
    # on the einsum path (causal/biased rings).  With it, each region
    # saves only its INPUTS — across all P steps that is the rotating
    # K/V blocks plus carry snapshots, O(S * D) per device (the same
    # scale flash keeps) — and backward recomputes the score blocks.
    ring_step = jax.checkpoint(ring_step)

    for step in range(P):
        src = (my - step) % P            # whose block we hold this step
        m, l, acc = ring_step(q32, kb, vb, m, l, acc, src, bias)
        if step < P - 1:
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)

    denom = jnp.transpose(jnp.maximum(l, 1e-20), (0, 2, 1))[..., None]
    return (acc / denom).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name="sp", causal=False, scale=None,
                      attn_fn=None, bias=None):
    """DeepSpeed-Ulysses sequence parallelism: all-to-all swaps the
    sequence shard for a head shard, attends over the full sequence
    locally, and swaps back.  Heads must divide the axis size.

    bias: additive [B, 1|H, T_local, T_global] (this device's q rows,
    all kv columns).  A per-head bias rides the same all-to-all as q (head
    shard in, q rows gathered); a broadcast (HB=1) bias is all-gathered
    on the q dim."""
    P = lax.axis_size(axis_name)
    H = q.shape[2]
    if H % P:
        raise ValueError("ulysses needs heads %% axis size == 0 "
                         "(H=%d, P=%d)" % (H, P))
    if bias is not None:
        if bias.shape[1] == 1:
            # broadcast over heads: gather full q rows, keep 1-head dim
            bias = lax.all_gather(bias, axis_name, axis=2, tiled=True)
        else:
            # per-head: shard heads, gather q rows — same swap as q
            bias = lax.all_to_all(bias, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def fwd(x):   # [B, T/P, H, D] -> [B, T, H/P, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def rev(x):   # [B, T, H/P, D] -> [B, T/P, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qf, kf, vf = fwd(q), fwd(k), fwd(v)
    attn = attn_fn
    T = qf.shape[1]
    static_scale = None
    try:
        static_scale = float(scale) if scale is not None else \
            1.0 / (q.shape[-1] ** 0.5)
    except Exception:
        pass
    flash_ok = static_scale is not None and T % min(128, T) == 0

    def flash_attn(q_, k_, v_, causal=False, scale=None, bias=None):
        # full-sequence local attention through the flash kernel
        # (causal works in-kernel — the whole sequence is local after
        # the all-to-all, so block indices are static)
        from paddle_tpu.fluid.ops.pallas_ops import flash_attention
        B_, Hl = q_.shape[0], q_.shape[2]
        bf = None
        if bias is not None:
            T_ = q_.shape[1]
            bf = jnp.broadcast_to(
                bias, (B_, Hl, T_, T_)).reshape(B_ * Hl, T_, T_) \
                .astype(q_.dtype)
        return _bshd(flash_attention(_bhsd(q_), _bhsd(k_), _bhsd(v_),
                                     bf, static_scale, causal),
                     B_, Hl).astype(q_.dtype)

    if attn == "flash":            # explicit request (tests use this to
        if not flash_ok:           # cover the path in interpret mode)
            raise ValueError("flash ulysses needs a static scale and a "
                             "128-tileable full sequence")
        attn = flash_attn
    kw = {"bias": bias} if bias is not None else {}

    def run(attn):
        return lambda q_, k_, v_: attn(q_, k_, v_, causal=causal,
                                       scale=scale, **kw)

    if attn is None and flash_ok:
        # default: the flash kernel when lowered for a TPU, the einsum
        # oracle anywhere else (same rule as ring_attention)
        out = lax.platform_dependent(qf, kf, vf, tpu=run(flash_attn),
                                     default=run(local_attention))
    else:
        out = run(attn or local_attention)(qf, kf, vf)
    return rev(out)
