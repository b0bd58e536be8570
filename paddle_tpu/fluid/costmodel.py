"""Device-cost ledger: normalized per-executable XLA cost records.

Every compiled executable — plain step, K-window, explicit-collective,
multihost, serving bucket — can be reduced to one normalized record:
FLOPs, transcendentals, bytes accessed, argument/output/temp memory,
instruction + fusion counts, static collective bytes by species/axis, and
a roofline ``estimated_step_s``.  Records are keyed by the executable
signature (program fingerprint prefix + window size) and stamped into
telemetry as ``hlo_*`` gauges plus a ``kind="compile"`` ledger record in
the metrics JSONL (docs/observability.md "Device-cost ledger"); the memory
of an executable is the ``step_memory_bytes`` / ``step_resident_bytes``
gauges, stamped by whatever introspection call produced it.

Two capture depths, by cost:

- **dispatch stamp** (``stamp_compile_event``, executor ``_dispatch``):
  host scalars already in hand on a fresh executable — signature,
  compile seconds, trace-time collective bytes.  No extra compile, no
  host sync; safe on the hot path whenever ``FLAGS_cost_ledger`` is on.
- **full capture** (``Executor.cost_record``, ``tools/cost_ledger.py``,
  ``bench.py --hot-path``, serving ``warmup(ledger=True)``): runs XLA's
  static cost/memory analyses over the AOT-lowered executable and parses
  the optimized HLO for instruction/fusion/collective counts and per-
  Fluid-op attribution.  Costs one ahead-of-time compile per executable
  (cached thereafter), so it is on-demand, never automatic.

Normalization contract: XLA's cost analysis visits a ``while``/``scan``
body ONCE — trip counts are not folded in — so a ``steps_per_run=K``
window's figures are already per-inner-step, NOT K-times inflated.
``describe()`` keeps that per-step meaning, records ``k`` explicitly,
and derives window totals as ``per_step * k`` where a total is wanted.
Pinned by tests/test_cost_ledger.py against K=1.
"""

import re

from . import flags
from . import telemetry

_m_flops = telemetry.gauge(
    "hlo_flops_total",
    "static XLA FLOP count of a compiled executable, per inner step, "
    "by signature")
_m_step_memory = telemetry.gauge(
    "step_memory_bytes",
    "what XLA's memory analysis says a compiled executable takes on one "
    "device, by signature and kind (argument|output|alias|temp|code, and "
    "peak where the backend fills it)")
_m_step_resident = telemetry.gauge(
    "step_resident_bytes",
    "bytes on one device of the values a compiled step is called with, "
    "by signature and kind (parameter|optimizer_state|other_state|feed)")
_m_fusion = telemetry.gauge(
    "hlo_fusion_count",
    "fusion instruction count in a compiled executable's optimized HLO, "
    "by signature")
_m_records = telemetry.counter(
    "cost_ledger_records_total",
    "device-cost ledger records stamped, by source (dispatch|full)")


def enabled():
    """Is the device-cost ledger on?  ``FLAGS_cost_ledger=0`` disables
    every stamp and makes ``capture``/``cost_record`` return None — the
    off path is bit-exact with zero added host syncs (pinned in tests)."""
    return bool(flags.get_flag("cost_ledger"))


def signature(fingerprint, k=1):
    """Ledger key of one executable: program-fingerprint prefix plus the
    window size, e.g. ``"7854f8031c07:k16"``.  Short enough for a metric
    label, stable across processes for the same ProgramDesc."""
    fp = (fingerprint or "anon")[:12]
    return "%s:k%d" % (fp, max(1, int(k or 1)))


# ---------------------------------------------------------------------------
# HLO text analytics
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# "f32[16,64]{1,0}" / "pred[]" — dtype + dims of one shape literal.
_SHAPE_RE = re.compile(r"\b(pred|[a-z]\d+)\[([0-9,]*)\]")
# "  %name = f32[16,64]{1,0} opcode(" — one instruction line.  ``%`` is
# optional: newer HLO dumps drop the sigil.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(\(?[a-z][\w\[\]{},\s]*?)\s"
    r"([a-z][a-z0-9-]*)\(")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
# First fluid_* path segment of an op_name (jax.named_scope from
# lowering.dispatch): "jit(f)/jit(main)/fluid_relu/max" -> "fluid_relu".
_FLUID_RE = re.compile(r"(?:^|/)(fluid_[A-Za-z0-9_.]+)")

COLLECTIVE_OPCODES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def _shape_bytes(text):
    """Total byte size of every shape literal in ``text`` (a result-shape
    token, possibly a tuple)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def instruction_stats(hlo_text):
    """Instruction/fusion/collective counts from optimized HLO text.

    Counts every instruction line across all computations (fused
    computations included — deterministic for a given compile), fusions
    by opcode, and collectives by species.  Returns
    ``{"instructions": int, "fusions": int, "collectives": {species: n}}``.
    """
    instructions = 0
    fusions = 0
    collectives = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        instructions += 1
        opcode = m.group(2)
        if opcode == "fusion":
            fusions += 1
        elif opcode in COLLECTIVE_OPCODES or (
                opcode.endswith("-start") and
                opcode[:-len("-start")] in COLLECTIVE_OPCODES):
            species = opcode[:-len("-start")] if opcode.endswith(
                "-start") else opcode
            collectives[species] = collectives.get(species, 0) + 1
    return {"instructions": instructions, "fusions": fusions,
            "collectives": collectives}


def op_attribution(hlo_text):
    """Per-Fluid-op cost attribution from HLO instruction metadata.

    Groups instructions by the first ``fluid_<type>`` named-scope segment
    of their ``op_name`` metadata (written by lowering.dispatch).  Per op:
    instruction count, output bytes (result-shape sizes — a proxy for
    bytes written), and an estimated FLOP count for contraction opcodes
    (dot/convolution/matmul custom-calls: ``2 * out_numel *
    contracted_dim``).  Estimates rank "where do the FLOPs/bytes go";
    exact totals come from ``cost_analysis`` in the record itself.
    Instructions with no fluid scope (feed plumbing, optimizer glue that
    XLA hoisted out of any scope) land under ``"(unattributed)"``.
    """
    ops = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        shape_tok, opcode = m.group(1), m.group(2)
        name_m = _OPNAME_RE.search(line)
        fluid_m = _FLUID_RE.search(name_m.group(1)) if name_m else None
        key = fluid_m.group(1) if fluid_m else "(unattributed)"
        ent = ops.setdefault(
            key, {"instructions": 0, "bytes": 0, "flops_est": 0})
        ent["instructions"] += 1
        out_bytes = _shape_bytes(shape_tok)
        ent["bytes"] += out_bytes
        if opcode in ("dot", "convolution") or (
                opcode == "custom-call" and
                re.search(r"matmul|conv", line, re.IGNORECASE)):
            ent["flops_est"] += _contraction_flops(line, shape_tok)
    return ops


def _contraction_flops(line, shape_tok):
    """2 * out_numel * contracted-dim estimate for a dot/conv line."""
    out_numel = 0
    shapes = _SHAPE_RE.findall(shape_tok)
    if shapes:
        out_numel = 1
        for d in shapes[0][1].split(","):
            if d:
                out_numel *= int(d)
    # Operand shapes appear inside the call parens; the contracted dim is
    # the lhs dim named by lhs_contracting_dims when present, else the
    # lhs's last dim (the common row-major matmul case).
    paren = line[line.find("("):]
    operands = _SHAPE_RE.findall(paren)
    if not operands:
        return 2 * out_numel
    lhs_dims = [int(d) for d in operands[0][1].split(",") if d]
    if not lhs_dims:
        return 2 * out_numel
    contracted = lhs_dims[-1]
    cm = re.search(r"lhs_contracting_dims=\{(\d+)", line)
    if cm:
        idx = int(cm.group(1))
        if 0 <= idx < len(lhs_dims):
            contracted = lhs_dims[idx]
    return 2 * out_numel * contracted


def top_ops(attribution, n=6):
    """The n heaviest ops of an ``op_attribution`` table, ranked by
    estimated FLOPs then bytes — the ledger's "name the responsible
    Fluid ops" payload."""
    ranked = sorted(
        attribution.items(),
        key=lambda kv: (kv[1]["flops_est"], kv[1]["bytes"]),
        reverse=True)
    return [
        {"op": k, "flops_est": v["flops_est"], "bytes": v["bytes"],
         "instructions": v["instructions"]}
        for k, v in ranked[:n]]


# ---------------------------------------------------------------------------
# Device peaks
# ---------------------------------------------------------------------------

# Published per-chip peaks keyed by ``jax.Device.device_kind`` (Google
# Cloud documentation, "TPU v5e"; the key is the string the chip reports).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def device_peaks(device_kind):
    """Peaks of one chip of ``device_kind``.  A device that is not in the
    table is an error, never a default: a utilization computed against
    another chip's peak is not a measurement."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            "no published peaks for device_kind %r (known: %s) — add its "
            "row to costmodel.DEVICE_PEAKS with the source"
            % (device_kind, sorted(DEVICE_PEAKS))) from None


# ---------------------------------------------------------------------------
# Record building
# ---------------------------------------------------------------------------

def roofline_seconds(flops, bytes_accessed):
    """Roofline step-time estimate: the executable is bound by whichever
    of compute (``flops / FLAGS_roofline_peak_flops``) and memory
    (``bytes / FLAGS_roofline_peak_bytes_per_s``) takes longer.  Static
    lower bound — no overlap modeling, no collective latency."""
    peak_flops = float(flags.get_flag("roofline_peak_flops")) or 1.0
    peak_bw = float(flags.get_flag("roofline_peak_bytes_per_s")) or 1.0
    return max(float(flops) / peak_flops, float(bytes_accessed) / peak_bw)


def normalize_cost(raw):
    """Unwrap a backend ``cost_analysis()`` result to one flat dict.

    jax returns a single-element list of properties on this backend
    (one per partition); older builds return the dict directly.  Keys of
    interest: ``flops``, ``transcendentals``, ``bytes accessed``."""
    c = raw
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return dict(c) if c else {}


# kind of ``step_memory_bytes`` -> the field of XLA's analysis it reads
MEMORY_KINDS = {
    "argument": "argument_size_in_bytes",
    "output": "output_size_in_bytes",
    "alias": "alias_size_in_bytes",
    "temp": "temp_size_in_bytes",
    "code": "generated_code_size_in_bytes",
    "peak": "peak_memory_in_bytes",
}


def memory_record(analysis):
    """``{kind: bytes}`` of one ``memory_analysis()`` result (per device):
    the ONE reader of XLA's analysis, for ``describe`` and for the
    executor's stamp.  ``alias`` is the part of the arguments the outputs
    are written over (donated state), so what a step holds beside its
    arguments is ``output - alias + temp``.  ``peak`` is left out where
    the backend does not fill it (XLA:CPU and XLA:TPU of jaxlib 0.9.0
    give 0).  Attribute reads only: never the serialized buffer
    assignment, which is as large as the step."""
    rec = {kind: int(getattr(analysis, field, 0) or 0)
           for kind, field in MEMORY_KINDS.items()}
    if not rec["peak"]:
        del rec["peak"]
    return rec


def stamp_step_memory(sig, memory, resident=None):
    """Publish one executable's memory record as ``step_memory_bytes{sig,
    kind}`` and, where the caller holds the values the step takes,
    ``step_resident_bytes{sig, kind}``."""
    for kind, nbytes in memory.items():
        _m_step_memory.set(nbytes, sig=sig, kind=kind)
    for kind, nbytes in (resident or {}).items():
        _m_step_resident.set(nbytes, sig=sig, kind=kind)


def describe(executable, k=1, sig=None, comm=None, tag=None, memory=None):
    """Normalized ledger record for one jax AOT-compiled executable.

    ``k`` is the steps_per_run window size; per the module contract the
    cost figures are already per-inner-step (XLA visits the scan body
    once), so they are recorded as-is with ``k`` alongside and a
    ``window_flops`` total derived as ``flops * k``.  ``comm`` is the
    trace-time ``{(species, precision, axis): bytes_per_step}`` map from
    ``_CompiledBlock.comm_bytes_by_axis()`` — static collective bytes,
    cross-checkable against the runtime ``collective_bytes_total{axis}``
    counters.  ``memory`` is the executable's ``memory_record`` where the
    caller has it already.
    """
    k = max(1, int(k or 1))
    ca = normalize_cost(executable.cost_analysis())
    if memory is None:
        memory = memory_record(executable.memory_analysis())
    hlo = executable.as_text()
    stats = instruction_stats(hlo)
    arg, out, tmp = memory["argument"], memory["output"], memory["temp"]
    flops = float(ca.get("flops", 0.0) or 0.0)
    bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
    rec = {
        "sig": sig or "?",
        "k": k,
        "flops": flops,
        "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
        "bytes_accessed": bytes_accessed,
        "window_flops": flops * k,
        "argument_bytes": arg,
        "output_bytes": out,
        "temp_bytes": tmp,
        "generated_code_bytes": memory["code"],
        # no peak: donated state is in the arguments and in the outputs.
        # The key stays as tools/cost_ledger.py's baseline pins it
        "peak_bytes": arg + out + tmp,
        "instructions": stats["instructions"],
        "fusions": stats["fusions"],
        "collectives": stats["collectives"],
        "estimated_step_s": roofline_seconds(flops, bytes_accessed),
    }
    if comm:
        rec["collective_bytes"] = {
            "%s_%s@%s" % key: int(v) for key, v in sorted(comm.items())}
        rec["collective_bytes_per_step"] = int(sum(comm.values()))
    if tag:
        rec["tag"] = tag
    return rec


def stamp(rec, source="full"):
    """Publish one ledger record: ``hlo_*`` gauges labeled by signature
    (visible in prometheus_text/dump_prometheus and the /aggregate
    endpoint) plus a ``kind="compile"`` lifecycle record in the step-
    event ring / metrics JSONL for tools/metrics_report.py.  The
    executable's memory is not published here: ``step_memory_bytes`` was
    stamped where the executor produced the executable the record
    describes (``stamp_step_memory``)."""
    sig = rec.get("sig") or "?"
    if "flops" in rec:
        _m_flops.set(float(rec["flops"]), sig=sig)
    if "fusions" in rec:
        _m_fusion.set(float(rec["fusions"]), sig=sig)
    _m_records.inc(source=source)
    telemetry.record_lifecycle_event(kind="compile", source=source, **rec)


def stamp_compile_event(sig, k=1, compile_s=None, comm=None,
                        feed_bytes=None, fetch_count=None, window=False):
    """Dispatch-time lightweight stamp: the host scalars a fresh
    executable's first dispatch already has, with no second compile and
    no device sync.  Full HLO analytics ride ``Executor.cost_record()``
    / ``tools/cost_ledger.py`` instead."""
    rec = {"sig": sig, "k": max(1, int(k or 1)), "window": bool(window)}
    if compile_s is not None:
        rec["compile_s"] = float(compile_s)
    if comm:
        rec["collective_bytes"] = {
            "%s_%s@%s" % key: int(v) for key, v in sorted(comm.items())}
        rec["collective_bytes_per_step"] = int(sum(comm.values()))
    if feed_bytes is not None:
        rec["feed_bytes"] = int(feed_bytes)
    if fetch_count is not None:
        rec["fetch_count"] = int(fetch_count)
    _m_records.inc(source="dispatch")
    telemetry.record_lifecycle_event(kind="compile", source="dispatch",
                                     **rec)
