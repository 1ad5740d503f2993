"""HLO compute audit: realized FLOPs vs the model's FLOPs, before any run.

The communication side of the lowered tier (:mod:`hlo_audit`, X-codes)
diffs the realized collective schedule against the strategy's plan; this
module is its COMPUTE counterpart.  XLA's own count for the ResNet-50
step is ~1.95x the model FLOPs — recompute, duplicated fusions and
batch-stats overhead that no jaxpr-tier pass can see, because they only
exist after lowering.  In the Checkmate spirit of static tensor-
rematerialization accounting (arxiv 1910.02653) and the mixed-precision
master-weight recipe (arxiv 1710.03740), this pass parses the step's
StableHLO text — the shared walker :func:`hlo_audit.walk_module_ops`,
loop-trip multiplicities included — into a per-region compute table and
prices the MFU ceiling statically:

  F000 INFO    compute audit skipped (no lowered module available)
  F001 ERROR   realized contraction FLOPs exceed the model FLOPs
               (``cost_model.jaxpr_flops`` on the same trace) beyond
               FLOPS_TOL, with a per-signature attribution table
  F002 WARNING duplicated expensive-op signature (recompute): remat
               multiplicity + the HBM-saved-vs-FLOPs-paid estimate
  F003 WARNING f32 contractions eligible for bf16 under a master-weight
               policy (params/moments stay f32; the MXU runs 2x on bf16)
  F004 WARNING donation declared but not realized at lowering: the
               donated arg produced no ``input_output_alias``-eligible
               attribute, or no type-compatible output exists for its
               deferred ``jax.buffer_donor`` — a full-buffer copy per step
               the D-codes (jaxpr tier) cannot see
  F005 WARNING batch-stats/elementwise share of the realized work above
               threshold (the BN-stats 8.8ms-of-30ms failure mode)
  F006 INFO    machine-readable compute table (``Finding.data``):
               model/realized FLOPs, per-class + per-region attribution,
               recompute groups, f32-contraction volume, and the
               predicted MFU ceiling from the calibrated cost model —
               consumed by ``tools/telemetry_report.py --compute`` and
               AutoStrategy's ``predicted_mfu_ceiling`` gauges
  F007 INFO    machine-readable HBM-traffic table (``Finding.data``):
               fusion-aware per-region bytes
               (``cost_model.hbm_traffic_from_ops``), arithmetic
               intensity, the roofline step time
               ``max(flops/peak, bytes/bw)`` and its verdict word, and
               the roofline-capped MFU ceiling — the byte view F006's
               FLOP view cannot price
  F008 WARNING memory-bound step: the roofline's HBM term dominates the
               compute term beyond MEMORY_BOUND_RATIO at real traffic
               volume, naming the top HBM-traffic sites (the measured
               ResNet-50 83.4 GB/99.8 ms failure mode) — remediated by
               the fused-norm / GroupNorm knob (``--suggest``)

FLOP accounting is single-source: every per-op count routes through
``cost_model.dot_flops`` / ``conv_flops`` / ``elementwise_flops`` — the
same rules ``jaxpr_flops`` applies to the jaxpr — so the realized-vs-
model ratio compares like with like (``tools/lint.py`` AD03 enforces the
single-sourcing).  Both sides count remat recompute (``jaxpr_flops``
descends into remat sub-jaxprs), so F001 fires only on LOWERING-ADDED
work; recompute itself is F002's job, detected as textually duplicated
expensive-op signatures (a scan-rolled op appears once with a trip
multiplicity — only genuine re-materialization, or repeated identical
unrolled blocks, duplicates a signature).

Region attribution is a textual heuristic (the lowering is topologically
ordered): the first contraction with a given operand/result shape
multiset is ``fwd``; later contractions sharing the multiset are its
``bwd`` transposes (or recompute); elementwise work after the last
contraction is the optimizer ``update``; anything inside a ``while``
(scan) body is ``in-scan``.
"""
import dataclasses
import re
from collections import Counter
from typing import List, Optional, Tuple

from autodist_tpu.analysis.hlo_audit import (_TENSOR_RE, _fmt_bytes,
                                             _tensor_bytes, lowered_text_for,
                                             walk_module_ops)
from autodist_tpu.analysis.report import Finding, Severity

# realized contraction FLOPs may exceed the jaxpr count by fusion
# duplication and lowering-added epilogues; beyond this relative
# tolerance F001 fires (same number as the wire-byte tolerance — the
# acceptance contract in docs/analysis.md uses both)
FLOPS_TOL = 0.25
# absolute slack under which F001 never fires: elementwise-only programs
# (the records sweep's quadratic synthetic loss) count ~0 on both sides
FLOPS_ABS_SLACK = 1e4
# a duplicated signature must pay at least this many extra FLOPs per
# step before F002 reports it (scalar/tiny duplicates are fusion noise)
RECOMPUTE_MIN_FLOPS = 1e5
# f32-contraction volume below this is not worth a precision migration
BF16_MIN_FLOPS = 1e5
# elementwise share of the realized work beyond which F005 fires
ELEMENTWISE_SHARE_TOL = 0.25
ELEMENTWISE_MIN_FLOPS = 1e5
# F008 (memory-bound step) fires when the roofline's HBM term exceeds
# the compute term by this factor AND the step moves real traffic —
# the floor keeps the records sweep's tiny synthetic steps (a few kB)
# from tripping a verdict that only means something at HBM scale
MEMORY_BOUND_RATIO = 1.5
MEMORY_BOUND_MIN_BYTES = 1e9

CONTRACTION_KINDS = ("dot_general", "dot", "convolution")
# the pretty-printer's single-line ``: tensor<...>`` ops (no regions);
# the share they carry approximates the BN-stats / optimizer-epilogue
# work the MXU never sees.  Reductions and data movement are excluded:
# this is a share heuristic, not a cycle count.
ELEMENTWISE_KINDS = (
    "add", "subtract", "multiply", "divide", "negate", "power",
    "tanh", "logistic", "exponential_minus_one", "exponential",
    "log_plus_one", "log", "rsqrt", "sqrt", "abs", "sign",
    "maximum", "minimum", "select", "compare", "floor", "ceil",
    "cosine", "sine", "and", "or", "xor", "not", "remainder",
)

_COMPUTE_RE = re.compile(
    r'"?stablehlo\.(' + "|".join(CONTRACTION_KINDS + ELEMENTWISE_KINDS)
    + r')"?[\s(]')
# the BYTE view additionally walks reductions (BN batch-stats, loss
# means, optimizer norms): they move every operand byte through HBM even
# though the FLOP-share heuristic above deliberately excludes them.
# Kept as a separate regex so the F005/F006 FLOP tables stay pinned.
_TRAFFIC_RE = re.compile(
    r'"?stablehlo\.('
    + "|".join(CONTRACTION_KINDS + ("reduce",) + ELEMENTWISE_KINDS)
    + r')"?[\s(]')
# ``contracting_dims = [1] x [0]`` (pretty) / ``lhs_contracting_dimensions
# = [1]`` (generic #stablehlo.dot attribute)
_CDIMS_RE = re.compile(r"contracting_dims\s*=\s*\[([\d,\s]*)\]\s*x")
_CDIMS_GENERIC_RE = re.compile(r"lhs_contracting_dimensions\s*=\s*\[([\d,\s]*)\]")
# the ``[b, 0, 1, f]x[0, 1, i, o]->[b, 0, 1, f]`` core both conv forms share
_CONV_DNUMS_RE = re.compile(r"\[([^\]]*)\]x\[([^\]]*)\]->\[([^\]]*)\]")
_MAIN_RE = re.compile(r"func\.func\s+public\s+@main\(")


@dataclasses.dataclass
class ComputeOp:
    """One realized compute op from the lowered module."""

    kind: str
    flops: float              # per execution (single-source cost_model rules)
    out_bytes: float = 0.0
    dtype: str = ""           # contraction operand dtype
    signature: str = ""       # exact dedup key (shapes + dims + dtypes)
    shape_key: str = ""       # operand/result shape multiset (fwd/bwd pairing)
    function: str = ""
    in_loop: bool = False
    count: float = 1.0        # static multiplicity (call sites x trips)
    region: str = "fwd"
    in_bytes: float = 0.0     # operand bytes per execution (byte view)
    in_types: tuple = ()      # operand tensor types (fused-region dedup key)
    out_type: str = ""        # result tensor type

    @property
    def is_contraction(self):
        return self.kind in CONTRACTION_KINDS

    @property
    def total_flops(self):
        return self.flops * max(1.0, self.count)


def _fmt_flops(f):
    for unit, div in (("TFLOP", 1e12), ("GFLOP", 1e9), ("MFLOP", 1e6),
                      ("kFLOP", 1e3)):
        if f >= div:
            return f"{f / div:.2f} {unit}"
    return f"{f:.0f} FLOP"


def _dims_of(ty: str) -> Tuple[List[int], str]:
    """``"2x64xf32"`` -> ([2, 64], "f32"); scalars -> ([], dtype)."""
    parts = ty.split("x")
    dims = []
    for p in parts[:-1]:
        if not p.isdigit():
            return [], parts[-1]
        dims.append(int(p))
    return dims, parts[-1]


def _split_types(trailer: str):
    """Operand/result tensor types from an op's trailing function type
    (``... : (tensor<A>, tensor<B>) -> tensor<C>``), or ``(None, None)``
    when the trailer has no arrowed form."""
    idx = trailer.rfind(" : (")
    if idx < 0:
        return None, None
    seg = trailer[idx + len(" : ("):]
    arrow = seg.find(") -> ")
    if arrow < 0:
        return None, None
    return _TENSOR_RE.findall(seg[:arrow]), _TENSOR_RE.findall(seg[arrow:])


def _parse_contraction(raw) -> Optional[ComputeOp]:
    from autodist_tpu.simulator.cost_model import conv_flops, dot_flops

    ins, outs = _split_types(raw.trailer)
    if not ins or not outs:
        return None
    out_dims, out_dt = _dims_of(outs[0])
    lhs_dims, lhs_dt = _dims_of(ins[0])
    dims_note = ""
    if raw.kind == "convolution":
        rhs_dims, _ = _dims_of(ins[1]) if len(ins) > 1 else ([], "")
        m = _CONV_DNUMS_RE.search(raw.text)
        in_ch, spatial = 1, []
        if m and rhs_dims:
            rhs_spec = [t.strip() for t in m.group(2).split(",")]
            for i, tok in enumerate(rhs_spec[:len(rhs_dims)]):
                if tok == "i":
                    in_ch = rhs_dims[i]
                elif tok.isdigit():
                    spatial.append(rhs_dims[i])
            dims_note = m.group(2)
        elif rhs_dims:     # no dim_numbers parsed: assume HWIO-style tail
            in_ch, spatial = rhs_dims[-2] if len(rhs_dims) >= 2 else 1, \
                rhs_dims[:-2]
        flops = conv_flops(out_dims, in_ch, spatial)
    else:
        m = _CDIMS_RE.search(raw.text) or _CDIMS_GENERIC_RE.search(raw.text)
        if m is not None:
            cdims = [int(t) for t in m.group(1).replace(" ", "").split(",")
                     if t]
            dims_note = ",".join(str(d) for d in cdims)
        elif raw.kind == "dot":
            cdims = [len(lhs_dims) - 1] if lhs_dims else []
            dims_note = "dot"
        else:
            cdims = []
        contract = 1
        for d in cdims:
            if 0 <= d < len(lhs_dims):
                contract *= lhs_dims[d]
        flops = dot_flops(out_dims, contract)
    out_bytes, _ = _tensor_bytes(outs[0])
    in_bytes = sum(_tensor_bytes(t)[0] for t in ins)
    sig = f"{raw.kind} ({', '.join(ins)}) -> {outs[0]} [{dims_note}]"
    shapes = sorted(list(ins) + [outs[0]])
    return ComputeOp(
        kind=raw.kind, flops=flops, out_bytes=out_bytes, dtype=lhs_dt,
        signature=sig, shape_key="|".join(shapes), function=raw.function,
        in_loop=raw.in_loop, count=raw.count, in_bytes=in_bytes,
        in_types=tuple(ins), out_type=outs[0])


def _parse_elementwise(raw) -> Optional[ComputeOp]:
    from autodist_tpu.simulator.cost_model import elementwise_flops

    ins, outs = _split_types(raw.trailer)
    ty = outs[0] if outs else None
    if ty is None:
        types = _TENSOR_RE.findall(raw.trailer)
        if not types:
            return None
        ty = types[-1]     # ``%1 = stablehlo.tanh %0 : tensor<8x32xf32>``
        # shorthand trailer elides operand types (all equal to the
        # result); operand COUNT is the SSA uses on the op line minus
        # the result binding
        n_in = max(1, raw.text.count("%") - 1)
        ins = (ty,) * n_in
    dims, dt = _dims_of(ty)
    out_bytes, _ = _tensor_bytes(ty)
    return ComputeOp(
        kind="elementwise", flops=elementwise_flops(dims), dtype=dt,
        signature=f"{raw.kind} {ty}", shape_key=ty, function=raw.function,
        in_loop=raw.in_loop, count=raw.count, out_bytes=out_bytes,
        in_bytes=sum(_tensor_bytes(t)[0] for t in ins),
        in_types=tuple(ins), out_type=ty)


def extract_compute_ops(text: str) -> List[ComputeOp]:
    """Parse every compute op (contractions + the elementwise share) out
    of a lowered StableHLO module, with loop-trip/call-site
    multiplicities from the shared walker, and attribute each op to a
    program region (module docstring heuristic)."""
    ops = []
    for raw in walk_module_ops(text, _COMPUTE_RE,
                               single_line_kinds=frozenset(ELEMENTWISE_KINDS)):
        op = (_parse_contraction(raw) if raw.kind in CONTRACTION_KINDS
              else _parse_elementwise(raw))
        if op is not None:
            ops.append(op)
    _classify_regions(ops)
    return ops


def _parse_reduce(raw) -> Optional[ComputeOp]:
    """A ``stablehlo.reduce``: one combiner application per input
    element (the elementwise FLOP rule on the INPUT dims), and — the
    part the byte view exists for — the full operand read plus the
    reduced-result write."""
    from autodist_tpu.simulator.cost_model import elementwise_flops

    ins, outs = _split_types(raw.trailer)
    if not ins or not outs:
        return None
    data_ins = [t for t in ins if "x" in t] or ins[:1]   # drop scalar inits
    dims, dt = _dims_of(data_ins[0])
    out_bytes = sum(_tensor_bytes(t)[0] for t in outs)
    return ComputeOp(
        kind="reduce", flops=elementwise_flops(dims), dtype=dt,
        signature=f"reduce {data_ins[0]} -> {outs[0]}",
        shape_key=data_ins[0], function=raw.function, in_loop=raw.in_loop,
        count=raw.count, out_bytes=out_bytes,
        in_bytes=sum(_tensor_bytes(t)[0] for t in data_ins),
        in_types=tuple(data_ins), out_type=outs[0])


def extract_traffic_ops(text: str) -> List[ComputeOp]:
    """Parse the BYTE view of a lowered module: every
    dot/conv/elementwise/reduce op with operand+result tensor types and
    bytes filled in, through the same shared walker (scan-trip
    multiplicities included).  Feeds
    ``cost_model.hbm_traffic_from_ops`` and :func:`audit_traffic`; kept
    separate from :func:`extract_compute_ops` so the pinned F005/F006
    FLOP totals never shift when the byte walker grows new op kinds."""
    ops = []
    for raw in walk_module_ops(text, _TRAFFIC_RE,
                               single_line_kinds=frozenset(ELEMENTWISE_KINDS)):
        if raw.kind in CONTRACTION_KINDS:
            op = _parse_contraction(raw)
        elif raw.kind == "reduce":
            op = _parse_reduce(raw)
        else:
            op = _parse_elementwise(raw)
        if op is not None:
            ops.append(op)
    _classify_regions(ops)
    return ops


def _classify_regions(ops):
    last_contraction = max(
        (i for i, op in enumerate(ops) if op.is_contraction), default=-1)
    seen_shapes = set()
    first_bwd = None
    for i, op in enumerate(ops):
        if op.is_contraction:
            if op.shape_key in seen_shapes:
                op.region = "bwd"       # transpose partner or recompute
                first_bwd = i if first_bwd is None else first_bwd
            else:
                op.region = "fwd"
                seen_shapes.add(op.shape_key)
        else:
            if last_contraction >= 0 and i > last_contraction:
                op.region = "update"    # optimizer epilogue: dots are done
            elif first_bwd is not None and i > first_bwd:
                op.region = "bwd"
            else:
                op.region = "fwd"
        if op.in_loop:
            op.region = "in-scan"


def audit_compute(ops: List[ComputeOp], *, model_flops=None,
                  source="lowered module", mxu_eff=None) -> List[Finding]:
    """Diff the realized compute table against the model FLOPs and emit
    the F-code findings (F001/F002/F003/F005 + the F006 table)."""
    from autodist_tpu.simulator.cost_model import (DEFAULT_MXU_EFF,
                                                   predicted_mfu_ceiling)

    eff = DEFAULT_MXU_EFF if mxu_eff is None else mxu_eff
    findings = []
    contractions = [op for op in ops if op.is_contraction]
    realized = sum(op.total_flops for op in contractions)
    elementwise = sum(op.total_flops for op in ops if not op.is_contraction)

    per_class = {}
    per_region = {}
    for op in ops:
        cls = "dot" if op.kind in ("dot", "dot_general") else \
            ("convolution" if op.kind == "convolution" else "elementwise")
        per_class[cls] = per_class.get(cls, 0.0) + op.total_flops
        per_region[op.region] = per_region.get(op.region, 0.0) + op.total_flops

    # F001: the lowering added contraction work the model never asked for
    # (both sides count recompute, so this is pure lowering overhead)
    ratio = (realized / model_flops) if model_flops else None
    if model_flops and \
            realized > model_flops * (1.0 + FLOPS_TOL) + FLOPS_ABS_SLACK:
        top = sorted(contractions, key=lambda o: -o.total_flops)[:5]
        table = "; ".join(
            f"{_fmt_flops(op.total_flops)} {op.signature}"
            f"{' [in-scan]' if op.in_loop else ''}" for op in top)
        findings.append(_f(
            Severity.ERROR, "F001",
            f"realized contraction FLOPs ({_fmt_flops(realized)}) exceed "
            f"the model FLOPs ({_fmt_flops(model_flops)}) by "
            f"{(ratio - 1) * 100:.0f}% (tolerance {FLOPS_TOL:.0%}) in the "
            f"{source}: the lowering added compute the cost model never "
            f"priced — top contributors: {table}", "flops"))

    # F002: duplicated expensive-op signatures = recompute (remat or
    # repeated identical unrolled blocks — both pay the FLOPs again)
    recompute = []
    groups = {}
    for op in contractions:
        groups.setdefault(op.signature, []).append(op)
    for sig, grp in groups.items():
        if len(grp) < 2:
            continue
        extra = grp[1:]
        flops_paid = sum(op.total_flops for op in extra)
        if flops_paid < RECOMPUTE_MIN_FLOPS:
            continue
        hbm_saved = sum(op.out_bytes * max(1.0, op.count) for op in extra)
        recompute.append({"signature": sig, "multiplicity": len(grp),
                          "flops_paid": round(flops_paid, 1),
                          "hbm_saved_bytes": round(hbm_saved, 1)})
        findings.append(_f(
            Severity.WARNING, "F002",
            f"duplicated expensive op (recompute) x{len(grp)}: {sig} — "
            f"pays {_fmt_flops(flops_paid)} extra per step to save "
            f"~{_fmt_bytes(hbm_saved)} of HBM residuals (remat "
            f"multiplicity, or repeated identical unrolled blocks)", sig))

    # F003: f32 contractions a master-weight policy would run on bf16.
    # Precision-aware counting: every contraction lands in exactly ONE
    # dtype bucket (a bf16-master lowering's bf16 dots are counted as
    # bf16, never double-counted back into the f32 volume), so the
    # by-dtype totals reconcile with ``realized`` exactly — the ``make
    # audit`` reconciliation line asserts this on every record.
    by_dtype = {}
    for op in contractions:
        dt = op.dtype or "unknown"
        by_dtype[dt] = by_dtype.get(dt, 0.0) + op.total_flops
    f32_ops = [op for op in contractions if op.dtype == "f32"]
    f32_flops = by_dtype.get("f32", 0.0)
    f32_frac = (f32_flops / realized) if realized else 0.0
    if f32_flops >= BF16_MIN_FLOPS:
        findings.append(_f(
            Severity.WARNING, "F003",
            f"{len(f32_ops)} f32 contraction(s) totaling "
            f"{_fmt_flops(f32_flops)} are bf16-eligible under a "
            f"master-weight policy (keep f32 params/moments, cast the "
            f"matmul operands): the MXU runs ~2x on bf16", "precision"))

    # F005: batch-stats / elementwise share of the realized work
    total = realized + elementwise
    share = (elementwise / total) if total > 0 else 0.0
    if realized > 0 and share > ELEMENTWISE_SHARE_TOL \
            and elementwise >= ELEMENTWISE_MIN_FLOPS:
        findings.append(_f(
            Severity.WARNING, "F005",
            f"elementwise/batch-stats work is {share:.0%} of the realized "
            f"FLOPs ({_fmt_flops(elementwise)} of {_fmt_flops(total)}; "
            f"threshold {ELEMENTWISE_SHARE_TOL:.0%}): normalization "
            f"statistics and optimizer epilogues are HBM-bound and the "
            f"MXU idles through them", "elementwise"))

    ceiling = predicted_mfu_ceiling(model_flops or realized, realized,
                                    mxu_eff=eff)
    # the precision-aware ceiling additionally prices the MXU's f32
    # contraction slowdown (cost_model.F32_CONTRACTION_SLOWDOWN): an
    # all-f32 lowering halves its ceiling, a bf16-master lowering keeps
    # it — the ``--suggest`` F003 remediation quantifies the gap.  The
    # plain ``predicted_mfu_ceiling`` key stays frac-free so blessed
    # baselines and the R004 gate keep their meaning across records.
    ceiling_prec = predicted_mfu_ceiling(model_flops or realized, realized,
                                         mxu_eff=eff,
                                         f32_contraction_frac=f32_frac)
    data = {
        "model_flops": round(float(model_flops), 1) if model_flops else None,
        "realized_flops": round(realized, 1),
        "flop_ratio": round(ratio, 4) if ratio else None,
        "elementwise_flops": round(elementwise, 1),
        "elementwise_share": round(share, 4),
        "f32_contraction_flops": round(f32_flops, 1),
        "f32_contraction_frac": round(f32_frac, 4),
        "contraction_flops_by_dtype": {
            k: round(v, 1) for k, v in sorted(by_dtype.items())},
        "per_class": {k: round(v, 1) for k, v in sorted(per_class.items())},
        "per_region": {k: round(v, 1) for k, v in sorted(per_region.items())},
        "recompute": recompute,
        "predicted_mfu_ceiling": round(ceiling, 4),
        "predicted_mfu_ceiling_precision": round(ceiling_prec, 4),
        "mxu_eff": eff,
        "n_ops": len(ops),
        "n_contractions": len(contractions),
        "source": source,
    }
    findings.append(Finding(
        Severity.INFO, "F006", "compute-audit",
        f"compute table ({len(contractions)} contraction(s), {source}): "
        f"realized {_fmt_flops(realized)}"
        + (f" vs model {_fmt_flops(model_flops)} (ratio {ratio:.2f})"
           if model_flops else "")
        + f"; elementwise {_fmt_flops(elementwise)} ({share:.0%})"
        + f"; predicted MFU ceiling {ceiling:.3f} (mxu_eff {eff})",
        "summary", data=data))
    return findings


def audit_traffic(ops: List[ComputeOp], *, model_flops=None,
                  source="lowered module", peak_flops=None,
                  hbm_gbps=None) -> List[Finding]:
    """The BYTE view (F007/F008): price the module's static HBM traffic
    through ``cost_model.hbm_traffic_from_ops``, put it on the roofline
    against the realized FLOPs, and flag a memory-bound step.

    ``ops`` is :func:`extract_traffic_ops` output.  All byte/second
    arithmetic routes through the cost model's single-source rules
    (``hbm_traffic_from_ops`` / ``roofline_s`` / ``roofline_bound`` /
    ``predicted_mfu_ceiling`` — lint AD13 enforces the confinement)."""
    from autodist_tpu.simulator.cost_model import (DEFAULT_HBM_GBPS,
                                                   DEFAULT_PEAK_FLOPS,
                                                   hbm_traffic_from_ops,
                                                   predicted_mfu_ceiling,
                                                   roofline_bound, roofline_s)

    peak = DEFAULT_PEAK_FLOPS if peak_flops is None else peak_flops
    bw = DEFAULT_HBM_GBPS if hbm_gbps is None else hbm_gbps
    traffic = hbm_traffic_from_ops(ops)
    total = traffic["total_bytes"]
    realized = sum(op.total_flops for op in ops if op.is_contraction)
    per_region = {}
    for r in traffic["regions"]:
        per_region[r["region"]] = per_region.get(r["region"], 0.0) \
            + r["bytes"]
    compute_s = (realized / peak) if peak else 0.0
    hbm_s = total / (bw * 1e9) if bw else 0.0
    rl = roofline_s(realized, total, peak_flops=peak, hbm_gbps=bw)
    bound = roofline_bound(realized, total, peak_flops=peak, hbm_gbps=bw)
    ceiling_rl = predicted_mfu_ceiling(
        model_flops or realized, realized, hbm_bytes=total,
        peak_flops=peak, hbm_gbps=bw)
    top = traffic["regions"][:5]
    data = {
        "hbm_bytes": round(total, 1),
        "by_class": traffic["by_class"],
        "per_region": {k: round(v, 1) for k, v in sorted(per_region.items())},
        "arithmetic_intensity": round(realized / total, 3) if total else None,
        "compute_s": compute_s,
        "hbm_s": hbm_s,
        "roofline_s": rl,
        "roofline_bound": bound,
        "peak_flops": peak,
        "hbm_gbps": bw,
        "predicted_mfu_ceiling_roofline": round(ceiling_rl, 4),
        "top_sites": top,
        "n_regions": len(traffic["regions"]),
        "n_ops": traffic["n_ops"],
        "source": source,
    }
    findings = [Finding(
        Severity.INFO, "F007", "compute-audit",
        f"HBM-traffic table ({len(traffic['regions'])} fused region(s), "
        f"{source}): {_fmt_bytes(total)}/step, arithmetic intensity "
        + (f"{realized / total:.1f} FLOP/B" if total else "n/a")
        + f", roofline {rl * 1e3:.2f} ms ({bound}-bound), "
        f"roofline MFU ceiling {ceiling_rl:.3f}",
        "traffic", data=data)]
    if total >= MEMORY_BOUND_MIN_BYTES \
            and hbm_s > compute_s * MEMORY_BOUND_RATIO:
        sites = "; ".join(
            f"{_fmt_bytes(r['bytes'])} {r['site']}"
            f"{' [in-scan]' if r['in_loop'] else ''}" for r in top[:3])
        findings.append(_f(
            Severity.WARNING, "F008",
            f"memory-bound step: HBM traffic {_fmt_bytes(total)} needs "
            f"{hbm_s * 1e3:.2f} ms at {bw:.0f} GB/s vs "
            f"{compute_s * 1e3:.2f} ms of MXU time "
            f"({_fmt_flops(realized)}) — the roofline is "
            f"{hbm_s / max(compute_s, 1e-12):.1f}x bytes-dominated (threshold "
            f"{MEMORY_BOUND_RATIO}x); top HBM-traffic sites: {sites}",
            "roofline"))
    return findings


# ---------------------------------------------------------------------------
# lowered-level donation check (F004)
# ---------------------------------------------------------------------------


def parse_main_signature(text: str):
    """``(args, outs)`` of the module's public ``@main``: ``args`` is a
    list of ``(tensor_type, attr_text)`` per argument, ``outs`` the
    result tensor types.  ``(None, None)`` when no main is found."""
    for line in text.splitlines():
        if not _MAIN_RE.search(line) or " -> " not in line:
            continue
        left, right = line.split(" -> ", 1)
        args_str = left[left.index("@main(") + len("@main("):]
        args = []
        for seg in args_str.split("%arg")[1:]:
            tys = _TENSOR_RE.findall(seg)
            if tys:
                args.append((tys[0], seg))
        return args, _TENSOR_RE.findall(right)
    return None, None


def audit_donation(args, outs, donated_mask,
                   source="lowered module") -> List[Finding]:
    """F004: a donation the trace declared (``donated_mask`` — the
    AnalysisContext's first-n-state-leaves convention) that the lowering
    did not realize.  Two rules:

    1. the donated arg carries NEITHER ``tf.aliasing_output`` (the
       single-program path pins aliases at lowering) NOR
       ``jax.buffer_donor`` (the SPMD path defers them to compile) —
       the donation vanished;
    2. a deferred ``jax.buffer_donor`` arg whose tensor type has no
       remaining type-compatible output: XLA's input_output_alias needs
       matching shape+dtype, so the alias can never materialize and the
       "donated" buffer is a full copy per step.
    """
    findings = []
    if not args or donated_mask is None or len(args) != len(donated_mask):
        return findings
    out_counts = Counter(outs or [])
    deferred = Counter()
    for i, ((ty, attrs), donated) in enumerate(zip(args, donated_mask)):
        if not donated:
            continue
        pinned = "tf.aliasing_output" in attrs
        donor = "jax.buffer_donor" in attrs
        if not pinned and not donor:
            findings.append(_f(
                Severity.WARNING, "F004",
                f"donation declared for arg {i} (tensor<{ty}>) but the "
                f"{source} carries no input_output_alias attribute for it "
                f"— the donation was dropped at lowering and the buffer "
                f"is copied in full every step", f"arg{i}"))
        elif donor and not pinned:
            deferred[ty] += 1
    for ty, n in deferred.items():
        avail = out_counts.get(ty, 0)
        if n > avail:
            findings.append(_f(
                Severity.WARNING, "F004",
                f"{n - avail} donated buffer(s) of tensor<{ty}> can never "
                f"realize an input_output_alias: only {avail} output(s) of "
                f"that type exist in the {source} (aliases need matching "
                f"shape+dtype — e.g. stats updated in a different "
                f"precision than their state slot), so the donation is a "
                f"full copy per step", ty))
    return findings


def _f(sev, code, msg, subject=""):
    return Finding(Severity(sev), code, "compute-audit", msg, subject)


# ---------------------------------------------------------------------------
# the registered pass
# ---------------------------------------------------------------------------


def compute_audit_pass(ctx):
    """PASS_REGISTRY entry (the lowered tier): build the realized compute
    table, diff it against the jaxpr's model FLOPs, and check the
    declared donations realized."""
    text, source = lowered_text_for(ctx)
    if text is None:
        return [_f(Severity.INFO, "F000",
                   "compute audit skipped: no lowered module (trace the "
                   "step or enable AUTODIST_DUMP_HLO dumps) — realized "
                   "FLOPs were not checked")]
    ops = extract_compute_ops(text)
    model = None
    if getattr(ctx, "jaxpr", None) is not None:
        from autodist_tpu.simulator.cost_model import jaxpr_flops

        model = jaxpr_flops(ctx.jaxpr)
    findings = audit_compute(ops, model_flops=model, source=source)
    findings.extend(audit_traffic(
        extract_traffic_ops(text), model_flops=model, source=source))
    args, outs = parse_main_signature(text)
    findings.extend(audit_donation(
        args, outs, getattr(ctx, "donated_invars", None), source))
    ctx.compute_summary = next(
        (f.data for f in findings if f.code == "F006"), None)
    ctx.traffic_summary = next(
        (f.data for f in findings if f.code == "F007"), None)
    return findings
