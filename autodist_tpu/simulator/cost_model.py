"""Strategy cost simulator.

The reference ships an empty ``autodist/simulator/`` plus the AutoSync
dataset format (NeurIPS 2020) of measured (graph_item, resource_spec,
strategy, runtime) tuples; the learned cost model itself is out-of-repo
(``simulator/dataset/README.md``).  Here we provide a working *analytic*
cost model for TPU meshes — enough to rank strategies per model — plus the
dataset-record plumbing so measured runs can be exported in AutoSync spirit.

Model (per step, seconds):
  compute    ~ 3 * flops_per_example * batch / (chips * peak_flops * mxu_eff)
               (fwd 1x + bwd 2x)
  allreduce  ~ 2 * (R-1)/R * bytes / ici_bw        (ring over the slice)
  ps         ~ reduce-scatter + all-gather = same wire volume as allreduce,
               but param all-gather adds param_bytes * (R-1)/R each step
  sharded    ~ adds param all-gather on use (forward) as well
  sparse     ~ all-gather of touched rows only: batch * row_bytes * R factor
  update     ~ opt_bytes_factor * update_bytes / hbm_bw — the optimizer
               phase is HBM-traffic-bound (param + grad + moment reads,
               param + moment writes).  Replicated placements touch the
               FULL parameter set on every chip; weight-update-sharded
               placements touch 1/R.  On a TPU mesh the wire volumes of
               ring-AR and reduce-scatter+all-gather are IDENTICAL (that
               equivalence is how the engine realizes PS), so this term
               is what genuinely separates the dense strategies.
  two-level  ~ AR vars under ``Hierarchy.TWO_LEVEL`` (or AUTO on a
               replica_dcn x replica_ici factored mesh) price per hop:
               reduce-scatter + all-gather of the full volume INSIDE the
               slice at ICI bandwidth, plus a ring allreduce of only the
               1/R_ici shard (scaled by the DCN-hop codec's wire factor)
               across slices at DCN bandwidth — replacing the flat
               min(ici, dcn) ring that ships the whole gradient over DCN.
  sharded    ~ AR vars under ``ShardedUpdate.SHARDED`` (ZeRO-style) swap
   update      the allreduce ring's two phases for a gradient
               reduce-scatter (codec-scaled) + a FRESH-PARAM all-gather
               (native dtype): same wire volume at NoneCompressor, less
               under a gradient codec (the codec never applies to the
               param leg), and the ``update`` term drops to 1/R — the
               optimizer touches only the local shard, with opt state
               permanently sharded (the HBM counterpart lives in
               :func:`hbm_footprint`).  Under TWO_LEVEL the DCN hop pays
               scatter+gather one-way instead of the shard ring.
  overlap    ~ strategies with ``schedule="overlap"`` price comm and
               compute as max(comm, compute) + exposed-tail instead of
               the serialized hi + 0.7*lo: the per-bucket collectives
               pipeline behind remaining backward FLOPs under XLA's
               latency-hiding scheduler, except the topologically last
               bucket whose reduce has nothing left to hide behind.  The
               overlapped total is clamped to never exceed the serialized
               one (tests/test_overlap_sync.py pins this).
"""
import dataclasses
import json

from autodist_tpu.kernel.partitioner import (Placement, SyncKind,
                                             build_var_plans,
                                             master_shard_storage,
                                             plan_sharded_update)

# v5e-class defaults; override per ResourceSpec bandwidths when present.
DEFAULT_PEAK_FLOPS = 394e12        # bf16 FLOPs/s per chip (v5e ~394 TFLOPs)
DEFAULT_MXU_EFF = 0.45
DEFAULT_ICI_GBPS = 1600.0          # per-chip ICI bi-dir, Gbit/s
DEFAULT_DCN_GBPS = 100.0
DEFAULT_HBM_GBPS = 819.0           # v5e HBM bandwidth, GByte/s
# optimizer-phase bytes touched per parameter byte: param + grad + two
# moments read, param + two moments written (adam-class; sgd touches less
# but the RANKING only needs the placement-relative factor)
DEFAULT_OPT_BYTES_FACTOR = 7.0
# f32 contractions run the MXU at half the bf16 issue rate on TPU —
# the F003 lever's compute term: bf16-master strategies shed this
# slowdown on the fraction of contraction work their vars cover
F32_CONTRACTION_SLOWDOWN = 2.0


@dataclasses.dataclass
class CostEstimate:
    compute_s: float
    comm_s: float
    breakdown: dict
    # AllReduceSynchronizer.Schedule of the strategy's dense AR family:
    # "overlap" prices the per-bucket pipelined schedule (max(comm,
    # compute) + exposed tail), "barrier" the serialized one
    schedule: str = "barrier"

    @property
    def serialized_s(self):
        """Barrier-schedule step time: collectives overlap with compute
        only incidentally; assume the larger dominates with 30% credit."""
        lo, hi = sorted((self.compute_s, self.comm_s))
        return hi + 0.7 * lo

    @property
    def overlapped_s(self):
        """Overlap-schedule step time: per-bucket collectives pipeline
        behind remaining backward FLOPs under the latency-hiding
        scheduler, so comm and compute cost ``max(comm, compute)`` instead
        of ``comm + compute`` — plus the EXPOSED tail: the topologically
        last bucket (the first layers' gradients) finalizes when no
        backward compute remains to hide behind, so one bucket's worth of
        comm always serializes.  Clamped by ``serialized_s``: pipelining
        can never cost more than not pipelining."""
        exposed = self.breakdown.get("overlap_exposed_s", 0.0)
        return min(self.serialized_s,
                   max(self.compute_s, self.comm_s) + exposed)

    @property
    def total_s(self):
        if self.schedule == "overlap":
            return self.overlapped_s
        return self.serialized_s

    def calibrated_total(self, calibration):
        """Measured-data-corrected step time: the analytic terms scaled by
        coefficients fit from RuntimeRecords (see :func:`calibrate`)."""
        return (calibration["compute_scale"] * self.compute_s
                + calibration["comm_scale"] * self.comm_s
                + calibration.get("overhead_s", 0.0))

    def to_json(self):
        return {"compute_s": self.compute_s, "comm_s": self.comm_s,
                "total_s": self.total_s, "schedule": self.schedule,
                "serialized_s": self.serialized_s,
                "overlapped_s": self.overlapped_s, **self.breakdown}


def calibrate(pairs):
    """Fit correction coefficients from measured runs (the AutoSync loop:
    measured (strategy, runtime) tuples ground the analytic model).

    ``pairs``: list of ``(CostEstimate, measured_step_s)``.  Least-squares
    fit of ``measured ~= a*compute_s + b*comm_s + c``; returns the
    calibration dict :meth:`CostEstimate.calibrated_total` consumes.  With
    fewer than 3 pairs (one per coefficient) the system is underdetermined
    — lstsq's min-norm answer would be arbitrary — so the identity
    calibration is returned instead.
    """
    import numpy as np

    if len(pairs) < 3:
        return {"compute_scale": 1.0, "comm_scale": 1.0, "overhead_s": 0.0}
    A = np.array([[e.compute_s, e.comm_s, 1.0] for e, _ in pairs])
    y = np.array([m for _, m in pairs])
    coef, *_ = np.linalg.lstsq(A, y, rcond=None)
    a, b, c = coef
    return {"compute_scale": float(max(a, 0.0)),
            "comm_scale": float(max(b, 0.0)),
            "overhead_s": float(max(c, 0.0))}


def _jaxpr_of(j):
    return j.jaxpr if hasattr(j, "jaxpr") and not hasattr(j, "eqns") else j


# -- single-source FLOP accounting -----------------------------------------
# Every FLOP number in the engine routes through these three rules
# (tools/lint.py AD03 rejects ad-hoc shape-product FLOP arithmetic
# elsewhere): the jaxpr counter below and the HLO-level counter
# (analysis/compute_audit.py) share them, which is what makes their
# realized-vs-model comparison meaningful.


def dot_flops(out_shape, contract_size):
    """Matmul rule: ``2 * prod(out) * K`` multiply-accumulates for a
    contraction of size ``K`` (batch dims ride in ``out_shape``)."""
    n = 1.0
    for d in out_shape:
        n *= int(d)
    return 2.0 * n * float(max(1, contract_size))


def conv_flops(out_shape, in_channels, kernel_spatial):
    """Convolution rule: ``2 * prod(out) * C_in_per_group * prod(kernel)``
    (``in_channels`` is the rhs 'i' dim — already per feature group)."""
    k = 1.0
    for d in kernel_spatial:
        k *= int(d)
    n = 1.0
    for d in out_shape:
        n *= int(d)
    return 2.0 * n * float(max(1, in_channels)) * k


def elementwise_flops(out_shape):
    """One op per output element — the F005 batch-stats/elementwise
    share's unit (NOT part of the model-FLOPs MFU numerator)."""
    n = 1.0
    for d in out_shape:
        n *= int(d)
    return n


# -- single-source HBM-byte accounting --------------------------------------
# Every per-op HBM-traffic number routes through these rules, mirroring
# the FLOP single-sourcing above (tools/lint.py AD13 rejects ad-hoc
# itemsize/byte-product arithmetic in hbm/roofline/traffic contexts
# elsewhere): the lowered-tier byte walker (analysis/compute_audit.py)
# and the roofline terms below share them.


def hbm_traffic_from_ops(ops):
    """Fusion-aware static HBM-traffic model over a lowered module's
    compute ops (``compute_audit.extract_traffic_ops`` — the shared
    :func:`analysis.hlo_audit.walk_module_ops` walker with scan-trip
    multiplicities).

    Accounting rules:

    - contractions (dot/conv) materialize their operands and results
      individually: ``in_bytes + out_bytes`` per execution — MXU ops
      anchor their own fusions;
    - maximal runs of consecutive NON-contraction ops (elementwise +
      reduce) in the same function/loop placement form one FUSED region:
      XLA's fusion pass keeps the intermediate chain in
      registers/VMEM, so the region bills each distinct external operand
      buffer ONCE (deduped by tensor type within the region) plus one
      materialized result write — never the per-op round-trips;
    - every term scales by the op's static multiplicity (call sites x
      scan trips, from the walker).

    Returns ``{"total_bytes", "by_class": {"contraction", "fused"},
    "regions": [...], "n_ops"}`` — ``regions`` entries carry ``bytes``,
    ``kind``, ``site`` (a representative signature), ``function``,
    ``in_loop``, ``count``, ``region`` (fwd/bwd/update/in-scan) and
    ``n_ops``, sorted by descending bytes so F008 can name the top
    HBM-traffic sites."""
    regions = []
    by_class = {"contraction": 0.0, "fused": 0.0}
    run = None     # accumulating fused region

    def flush():
        nonlocal run
        if run is None:
            return
        seen = set()
        in_bytes = 0.0
        for t, b in run["ins"]:
            if t in seen:
                continue
            seen.add(t)
            in_bytes += b
        total = (in_bytes + run["out_bytes"]) * run["count"]
        by_class["fused"] += total
        regions.append({
            "kind": "fused", "bytes": round(total, 1),
            "site": run["site"], "function": run["function"],
            "in_loop": run["in_loop"], "count": run["count"],
            "region": run["region"], "n_ops": run["n_ops"]})
        run = None

    for op in ops:
        count = max(1.0, float(getattr(op, "count", 1.0)))
        if getattr(op, "is_contraction", False):
            flush()
            total = (float(op.in_bytes) + float(op.out_bytes)) * count
            by_class["contraction"] += total
            regions.append({
                "kind": op.kind, "bytes": round(total, 1),
                "site": op.signature, "function": op.function,
                "in_loop": op.in_loop, "count": count,
                "region": op.region, "n_ops": 1})
            continue
        key = (op.function, op.in_loop, count, op.region)
        if run is not None and run["key"] != key:
            flush()
        if run is None:
            run = {"key": key, "ins": [], "out_bytes": 0.0,
                   "site": op.signature, "best": -1.0,
                   "function": op.function, "in_loop": op.in_loop,
                   "count": count, "region": op.region, "n_ops": 0}
        in_types = getattr(op, "in_types", ()) or \
            ((op.out_type,) if getattr(op, "out_type", "") else ())
        for t in in_types:
            run["ins"].append((t, float(op.in_bytes) / max(1, len(in_types))))
        # the region's materialized write: its LAST op's result (earlier
        # results are the chain's VMEM temporaries)
        run["out_bytes"] = float(op.out_bytes)
        if float(op.out_bytes) > run["best"]:
            run["best"] = float(op.out_bytes)
            run["site"] = op.signature
        run["n_ops"] += 1
    flush()
    regions.sort(key=lambda r: -r["bytes"])
    total = by_class["contraction"] + by_class["fused"]
    return {"total_bytes": round(total, 1),
            "by_class": {k: round(v, 1) for k, v in by_class.items()},
            "regions": regions, "n_ops": len(ops)}


def hbm_traffic(text):
    """Static per-op HBM-traffic model of a lowered StableHLO module:
    parse every dot/conv/elementwise/reduce op through the shared
    ``analysis/hlo_audit.py`` walker and apply the fusion-aware byte
    rules of :func:`hbm_traffic_from_ops`."""
    from autodist_tpu.analysis.compute_audit import extract_traffic_ops

    return hbm_traffic_from_ops(extract_traffic_ops(text))


def roofline_s(flops, hbm_bytes, *, peak_flops=DEFAULT_PEAK_FLOPS,
               hbm_gbps=DEFAULT_HBM_GBPS):
    """Static roofline step time: ``max(flops / peak_flops,
    bytes / hbm_bw)`` — the chip can never finish a step before it has
    both issued the FLOPs and moved the bytes, so whichever term wins
    names the bound.  ``flops`` should be the REALIZED count (the work
    the chip actually executes), ``hbm_bytes`` the step's HBM traffic
    (:func:`hbm_traffic`, or a measured number)."""
    compute_s = float(flops) / float(peak_flops) if peak_flops else 0.0
    hbm_s = float(hbm_bytes) / (float(hbm_gbps) * 1e9) if hbm_gbps else 0.0
    return max(compute_s, hbm_s)


def roofline_bound(flops, hbm_bytes, *, peak_flops=DEFAULT_PEAK_FLOPS,
                   hbm_gbps=DEFAULT_HBM_GBPS):
    """``"memory"`` when the HBM term of :func:`roofline_s` dominates the
    compute term, else ``"compute"`` — the F007/F008 verdict word."""
    compute_s = float(flops) / float(peak_flops) if peak_flops else 0.0
    hbm_s = float(hbm_bytes) / (float(hbm_gbps) * 1e9) if hbm_gbps else 0.0
    return "memory" if hbm_s > compute_s else "compute"


def predicted_mfu_ceiling(model_flops, realized_flops,
                          mxu_eff=DEFAULT_MXU_EFF,
                          f32_contraction_frac=0.0, *, hbm_bytes=None,
                          peak_flops=DEFAULT_PEAK_FLOPS,
                          hbm_gbps=DEFAULT_HBM_GBPS):
    """Best MFU the lowered program can reach: the calibrated MXU
    efficiency discounted by the lowering's FLOP overhead — MFU counts
    MODEL flops, the chip executes REALIZED flops, so
    ``ceiling = mxu_eff * model / realized``.  With no contraction work
    (or no model count) the ceiling is the raw efficiency.

    ``f32_contraction_frac`` is the share of contraction FLOPs executing
    at f32 (the F003 finding's ``f32_flops / total``): those run the MXU
    at ``1/F32_CONTRACTION_SLOWDOWN`` of the bf16 issue rate, so the
    ceiling (measured against bf16 peak) divides by the blended slowdown
    — the term a bf16-master strategy sheds.

    ``hbm_bytes`` (the step's HBM traffic, :func:`hbm_traffic` or a
    measured number) adds the ROOFLINE ceiling: the step can never run
    faster than ``roofline_s``, so the reachable MFU is also capped at
    ``model_flops / (roofline_s * peak_flops)`` and the returned ceiling
    is the min of the compute and roofline ceilings — a memory-bound
    model finally reports an honest number instead of the MXU story.
    Without ``hbm_bytes`` the pre-roofline behavior is unchanged (the
    committed perf-gate baselines pin it)."""
    if not model_flops or not realized_flops or realized_flops <= 0:
        base = float(mxu_eff)
    else:
        base = float(mxu_eff) * min(
            1.0, float(model_flops) / float(realized_flops))
    f = min(1.0, max(0.0, float(f32_contraction_frac)))
    ceiling = base / (1.0 + f * (F32_CONTRACTION_SLOWDOWN - 1.0))
    if hbm_bytes:
        mf = float(model_flops or realized_flops or 0.0)
        rl = roofline_s(float(realized_flops or model_flops or 0.0),
                        hbm_bytes, peak_flops=peak_flops,
                        hbm_gbps=hbm_gbps)
        if mf > 0.0 and rl > 0.0 and peak_flops:
            ceiling = min(ceiling, mf / (rl * float(peak_flops)))
    return ceiling


def jaxpr_flops(jaxpr):
    """Conservative FLOP count of a (closed) jaxpr: matmul + convolution
    math, control flow folded in structurally (``scan`` multiplies by its
    trip count, ``cond`` takes the max branch, ``while`` counts its body
    once — trip counts are data-dependent).  Elementwise ops are ignored:
    this is the MODEL-FLOPs numerator an MFU wants (the convention of
    ``benchmark/harness/flops.py``), not XLA's emitted-op count.

    Counted on the jaxpr the engine traces, the ``shard_map`` body
    carries per-device shapes — so the returned count is per-device work
    per step (forward + backward both appear in a grad-traced program).
    """
    j = _jaxpr_of(jaxpr)
    total = 0.0
    for eqn in j.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lc, _rc), (lb, _rb) = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            out = eqn.outvars[0].aval.shape
            contract = 1
            for d in lc:
                contract *= lhs[d]
            total += dot_flops(out, contract)
        elif name == "conv_general_dilated":
            rhs = eqn.invars[1].aval.shape
            out = eqn.outvars[0].aval.shape
            dn = eqn.params["dimension_numbers"]
            rhs_spec = getattr(dn, "rhs_spec", None)
            if rhs_spec is not None:
                in_ch = rhs[rhs_spec[1]]
                spatial = [rhs[d] for d in rhs_spec[2:]]
            else:  # fallback: assume OIHW-style (out, in, *spatial)
                in_ch, spatial = rhs[1], rhs[2:]
            total += conv_flops(out, in_ch, spatial)
        elif name == "scan":
            total += float(eqn.params.get("length", 1)) * \
                jaxpr_flops(eqn.params["jaxpr"])
        elif name == "while":
            total += jaxpr_flops(eqn.params["body_jaxpr"])
        elif name == "cond":
            branches = eqn.params.get("branches", ())
            total += max((jaxpr_flops(b) for b in branches), default=0.0)
        else:
            from autodist_tpu.analysis.jaxpr_utils import subjaxprs

            for sub in subjaxprs(eqn):
                total += jaxpr_flops(sub)
    return total


def traced_step_flops(transformer, batch_shapes):
    """Per-device FLOPs of one train step, counted on the abstract trace
    (:meth:`GraphTransformer.trace_step` — no devices touched, nothing
    compiled).  The telemetry layer's achieved-MFU numerator."""
    traced = transformer.trace_step(batch_shapes, donate=False)
    return jaxpr_flops(traced.jaxpr)


def _ring_time(bytes_, n, bw_bytes_per_s):
    """Full allreduce (reduce-scatter + all-gather) ring cost."""
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) / n * bytes_ / bw_bytes_per_s


def _gather_time(bytes_, n, bw_bytes_per_s):
    """Single all-gather (or reduce-scatter) phase: half the ring cost."""
    if n <= 1:
        return 0.0
    return (n - 1) / n * bytes_ / bw_bytes_per_s


def _hier_factors(strategy, resource_spec, R):
    """``(R_dcn, R_ici)`` of the two-level factorization, from a mesh that
    actually DECLARES the sub-axes: the strategy's ``graph_config.mesh``
    (the two-level builders write the host-boundary factorization there)
    or the spec's ``mesh:`` request.  ``(1, R)`` otherwise — the engine
    realizes FLAT on an unfactored mesh, so the model must price it flat
    too (an AUTO strategy on a plain multi-node spec stays a flat ring)."""
    from autodist_tpu.const import AXIS_REPLICA_DCN, AXIS_REPLICA_ICI

    for sizes in (
            dict(zip(strategy.proto.graph_config.mesh.axis_names,
                     strategy.proto.graph_config.mesh.axis_sizes))
            if strategy is not None else {},
            resource_spec.mesh_request or {} if resource_spec is not None
            else {}):
        if AXIS_REPLICA_DCN in sizes and AXIS_REPLICA_ICI in sizes:
            return int(sizes[AXIS_REPLICA_DCN]), int(sizes[AXIS_REPLICA_ICI])
    return 1, R


def _schedule_ir_cost(prog, nbytes, R_dcn, R_ici, ici_bw, dcn_bw):
    """Per-phase wire cost of a synthesized schedule program for one
    ``nbytes``-sized gradient: ``(ici_bytes, dcn_bytes, seconds)``.

    Generalizes the two-level ``hier_ici_s``/``hier_dcn_s`` terms to N
    phases: scatter/gather phases pay a single ``(g-1)/g`` hop, cores pay
    the full ``2(g-1)/g`` ring, each at the bandwidth class of its slowest
    axis (``ph.dcn``) and scaled by the hop codec's wire-byte factor.
    Everything is linear in bytes, so per-variable accumulation composes
    with bucketing/overlap exactly like the legacy hier terms."""
    from autodist_tpu.const import AXIS_REPLICA_DCN, AXIS_REPLICA_ICI
    from autodist_tpu.kernel.synchronization.compressor import wire_byte_factor

    sizes = {AXIS_REPLICA_DCN: R_dcn, AXIS_REPLICA_ICI: R_ici}
    ici_b = dcn_b = secs = 0.0
    cur = float(nbytes)
    for ph in prog.phases:
        g = 1
        for a in ph.axes:
            g *= int(sizes.get(a, 1))
        if g <= 1:
            continue
        wf = wire_byte_factor(ph.codec, 1)
        bw = dcn_bw if ph.dcn else ici_bw
        if ph.op == "reduce_scatter":
            wire = cur * wf
            secs += _gather_time(wire, g, bw)
            cur /= g
        elif ph.op == "all_gather":
            cur *= g
            wire = cur * wf           # all-gather bills result bytes
            secs += _gather_time(wire, g, bw)
        elif ph.op == "ppermute_ring":
            wire = 2.0 * (g - 1) / g * cur * wf
            secs += wire / bw
        else:                         # all_reduce core
            wire = cur * wf
            secs += _ring_time(wire, g, bw)
        if ph.dcn:
            dcn_b += wire
        else:
            ici_b += wire
    return ici_b, dcn_b, secs


def estimate(strategy, model_item, resource_spec, *, flops_per_example=0.0,
             batch_per_chip=32, peak_flops=DEFAULT_PEAK_FLOPS,
             mxu_eff=DEFAULT_MXU_EFF, ici_gbps=DEFAULT_ICI_GBPS,
             dcn_gbps=None, avg_sparse_rows=None, hbm_gbps=DEFAULT_HBM_GBPS,
             opt_bytes_factor=DEFAULT_OPT_BYTES_FACTOR):
    """Estimate per-step cost of `strategy` for `model_item` on the spec.

    Multi-node DCN bandwidth comes from the spec's per-node
    ``network_bandwidth`` entries (the slowest node bounds the ring) unless
    overridden via ``dcn_gbps``.
    """
    R = max(1, resource_spec.num_accelerators)
    multi_node = not resource_spec.is_single_node
    if dcn_gbps is None:
        # only yaml-SPECIFIED bandwidths count (the parser defaults
        # unspecified nodes to 1 Gbps for reference parity, which would
        # silently price every default multi-node spec 100x too slow here)
        explicit = getattr(resource_spec, "explicit_bandwidths", {})
        dcn_gbps = min(explicit.values()) if explicit else DEFAULT_DCN_GBPS
    bw = (min(ici_gbps, dcn_gbps) if multi_node else ici_gbps) * 1e9 / 8
    plans = build_var_plans(strategy, model_item, R)

    compute_s = 0.0
    if flops_per_example:
        compute_s = 3.0 * flops_per_example * batch_per_chip / (peak_flops * mxu_eff)

    # mesh-axis-subset PS ("mesh:<axes>" reduction destinations): the
    # scatter/gather stays INSIDE the subset (ICI), and only shard-sized
    # pieces cross the remaining axes (DCN) — so those vars' PS bytes are
    # priced at ICI bandwidth plus a shard-sized cross-slice ring, instead
    # of pricing the full gradient at the DCN-bottlenecked ring.
    mesh_req = resource_spec.mesh_request or {}
    subset_ps_bytes = 0
    subset_R = subset_other = 1

    # two-level hierarchy (AllReduceSynchronizer.Hierarchy.TWO_LEVEL, or
    # AUTO on a factored mesh): the AR family's bulk reduce-scatter +
    # all-gather phases stay on ICI and only the 1/R_ici shard (optionally
    # wire-compressed) rides the DCN ring — priced per hop below instead
    # of the flat min(ici, dcn) ring
    R_dcn, R_ici = _hier_factors(strategy, resource_spec, R)
    mesh_factored = R_dcn > 1
    hier_ici_bytes = hier_dcn_bytes = 0.0
    # the one-way (scatter/gather) share of the DCN hop — sharded-update
    # buckets' grad scatter + param gather, priced at (n-1)/n instead of
    # the replicated shard ring's 2(n-1)/n
    hier_dcn_oneway_bytes = 0.0
    # ZeRO sharded-update flat wire: grad reduce-scatter (codec-scaled)
    # and fresh-param all-gather, each a single (n-1)/n phase
    shard_scatter_bytes = shard_gather_bytes = 0.0
    # synthesized schedule-IR plans: per-phase pricing accumulates here,
    # NOT into hier_* (those are re-priced through the two-level formulas
    # below and would double-bill the searched phases)
    searched_ici_bytes = searched_dcn_bytes = searched_s = 0.0

    ar_bytes = ps_bytes = gather_bytes = sparse_bytes = 0
    update_bytes = 0.0
    # bf16-master (Precision.BF16_COMPUTE_F32_MASTER) accounting: the
    # fraction of dense param bytes running bf16 compute scales the MXU
    # term (f32 contractions issue at half rate), and the fresh-param
    # gather legs of those buckets halve (bf16 wire)
    dense_param_bytes = bf16_master_bytes = 0.0
    # overlap schedule bookkeeping: which dense-AR vars request
    # Schedule.OVERLAP, and how many buckets they split into (one per
    # (group, dtype, compressor) — mirrors all_reduce.plan_buckets)
    ar_overlap = False
    ar_bucket_keys = set()
    for v in model_item.var_infos:
        plan = plans.get(v.name)
        if plan is None:
            continue
        nbytes = v.byte_size
        # optimizer phase: weight-update-sharded realizations touch 1/R of
        # the parameter (+ moments) per chip — SHARDED storage AND sync-PS
        # (the engine's PS is reduce-scatter → shard-local update →
        # all-gather even for replicated storage, graph_transformer.py);
        # replicated-AR / DIVERGENT update the full var on every chip.
        # async PS (ps_sync=False) updates FULL params on the host server
        # (async_ps/async_service runtimes), so only SYNCHRONOUS plans
        # earn the 1/R term — an async strategy (even a partitioned one)
        # must not inherit the HBM-bound discount in rankings (ADVICE r5)
        async_ps = plan.sync == SyncKind.PS and not plan.ps_sync
        # AR plans under ShardedUpdate.SHARDED join the 1/R update club —
        # the plan-level eligibility mirror of the engine's normalization
        # (block-codec buckets fall back to the replicated update)
        ar_sharded = plan_sharded_update(plan)
        sharded_update = not async_ps and (
            plan.placement == Placement.SHARDED
            or ar_sharded
            or (plan.sync == SyncKind.PS
                and plan.placement != Placement.DIVERGENT))
        update_bytes += nbytes / R if sharded_update else nbytes
        if plan.sparse:
            rows = avg_sparse_rows or batch_per_chip
            row_bytes = nbytes / max(1, v.shape[0] if v.shape else 1)
            sparse_bytes += rows * row_bytes * R  # all-gather of touched rows
            continue
        dense_param_bytes += nbytes
        prec = master_shard_storage(plan)
        if prec:
            bf16_master_bytes += nbytes
        pg = 0.5 if prec else 1.0  # bf16 fresh-param gather halves
        if plan.placement == Placement.SHARDED:
            ps_bytes += nbytes        # reduce-scatter grads (one phase)
            gather_bytes += nbytes    # all-gather params at use (one phase)
        elif plan.sync == SyncKind.PS:
            if plan.placement == Placement.DIVERGENT:
                ar_bytes += nbytes / plan.sync_period  # amortized averaging
            elif plan.ps_axes and mesh_req:
                r_ps = 1
                for a in plan.ps_axes:
                    r_ps *= int(mesh_req.get(a, 1))
                if r_ps >= R:
                    # subset covering the whole mesh == default realization
                    # (the engine normalizes exactly this case); price it
                    # identically so a search cannot "prefer" a byte-for-
                    # byte identical strategy
                    ps_bytes += nbytes
                    gather_bytes += nbytes
                else:
                    subset_ps_bytes += nbytes
                    subset_R = max(subset_R, r_ps)
                    subset_other = max(subset_other, R // max(1, r_ps))
            else:
                ps_bytes += nbytes
                gather_bytes += nbytes
        else:
            from autodist_tpu.proto import synchronizers_pb2

            _C = synchronizers_pb2.AllReduceSynchronizer
            if plan.schedule == _C.OVERLAP:
                ar_overlap = True
            ir_text = getattr(plan, "schedule_ir", "")
            ar_bucket_keys.add((plan.group, str(plan.dtype),
                                plan.compressor, plan.hierarchy,
                                plan.dcn_compressor, plan.sharded_update,
                                ir_text, getattr(plan, "precision", 0)))
            # mirror the engine's IR normalization (graph_transformer):
            # canonical FLAT/TWO_LEVEL-shaped programs collapse onto the
            # legacy knobs; only genuinely synthesized programs take the
            # per-phase pricing path
            comp_enum = plan.compressor
            dcn_enum = plan.dcn_compressor
            prog = None
            if ir_text:
                from autodist_tpu.const import (AXIS_REPLICA_DCN,
                                                AXIS_REPLICA_ICI)
                from autodist_tpu.kernel.synchronization import (
                    schedule_ir as _sir,
                )

                try:
                    prog = _sir.loads(ir_text)
                    kind = _sir.canonical_hierarchy(prog)
                except ValueError:
                    prog = kind = None  # malformed: Y010 flags it; price flat
                if prog is not None:
                    core = _sir.core_codec(prog)
                    if kind == _C.FLAT:
                        comp_enum = core
                        prog = None
                    elif (kind == _C.TWO_LEVEL and mesh_factored
                          and prog.phases[0].axes == (AXIS_REPLICA_ICI,)
                          and set(prog.phases[1].axes) == {AXIS_REPLICA_DCN}
                          and (core or not plan.compressor)):
                        dcn_enum = core
                        prog = None
            if prog is not None:
                i_b, d_b, s_s = _schedule_ir_cost(
                    prog, nbytes, R_dcn, R_ici,
                    ici_gbps * 1e9 / 8, dcn_gbps * 1e9 / 8)
                searched_ici_bytes += i_b
                searched_dcn_bytes += d_b
                searched_s += s_s
                continue
            # wire factors keyed on the proto enum (not raw ints) so a
            # reordering in synchronizers.proto cannot skew rankings;
            # PowerSGD's factor depends on the bucket geometry
            from autodist_tpu.kernel.synchronization.compressor import (
                wire_byte_factor,
            )

            comp_factor = wire_byte_factor(comp_enum, max(1, v.size))
            # mirror the engine's hierarchy resolution: explicit TWO_LEVEL
            # or AUTO, on a factored mesh; PowerSGD never decomposes
            two_level = (mesh_factored
                         and plan.hierarchy != _C.FLAT
                         and comp_enum != _C.PowerSGDCompressor)
            if two_level:
                dcn_factor = wire_byte_factor(
                    dcn_enum or comp_enum, max(1, v.size))
                # scatter + gather phases; a bf16-master bucket's gather
                # leg carries the bf16 COMPUTE copy (half the f32 wire)
                hier_ici_bytes += ((1.0 + pg) * nbytes if ar_sharded
                                   else 2.0 * nbytes)
                if ar_sharded:
                    # ZeRO x two-level: the DCN hop pays the grad-shard
                    # scatter (codec-scaled) + the param-shard gather
                    # (native, or bf16 under bf16-master), each one-way,
                    # instead of the shard ring
                    oneway = nbytes * (dcn_factor + pg) / R_ici
                    hier_dcn_bytes += oneway
                    hier_dcn_oneway_bytes += oneway
                else:
                    hier_dcn_bytes += nbytes * dcn_factor / R_ici
            elif ar_sharded:
                shard_scatter_bytes += nbytes * comp_factor
                shard_gather_bytes += nbytes * pg
            else:
                ar_bytes += nbytes * comp_factor

    # bf16-master compute term: the covered fraction's contractions run
    # the MXU at the bf16 issue rate (F32_CONTRACTION_SLOWDOWN x the f32
    # rate the default path is calibrated at) — contraction work
    # approximated as proportional to dense param volume
    bf16_frac = (bf16_master_bytes / dense_param_bytes
                 if dense_param_bytes else 0.0)
    if compute_s and bf16_frac:
        compute_s *= (1.0 - bf16_frac
                      * (1.0 - 1.0 / F32_CONTRACTION_SLOWDOWN))
    comm_s = (_ring_time(ar_bytes, R, bw)
              + _gather_time(ps_bytes, R, bw)      # reduce-scatter of grads
              + _gather_time(gather_bytes, R, bw)  # all-gather of params
              # ZeRO sharded update (flat): grad scatter + param gather,
              # one (n-1)/n phase each — the scatter+gather vs allreduce
              # wire delta the sharded mode trades on
              + _gather_time(shard_scatter_bytes, R, bw)
              + _gather_time(shard_gather_bytes, R, bw)
              + sparse_bytes / bw)
    subset_s = 0.0
    if subset_ps_bytes:
        ici_bw = ici_gbps * 1e9 / 8
        # scatter + gather within the subset at ICI speed, plus a ring
        # psum of the 1/R_ps-sized shards across the remaining axes at
        # the bottleneck (DCN) bandwidth
        subset_s = (2.0 * _gather_time(subset_ps_bytes, subset_R, ici_bw)
                    + _ring_time(subset_ps_bytes / subset_R, subset_other, bw))
        comm_s += subset_s
    # two-level AR: both bulk phases priced at ICI bandwidth inside the
    # slice + the shard-sized ring at DCN bandwidth across slices —
    # replacing the flat min(bw) ring those vars would otherwise pay
    hier_ici_s = hier_dcn_s = 0.0
    if hier_ici_bytes:
        ici_bw = ici_gbps * 1e9 / 8
        dcn_bw = dcn_gbps * 1e9 / 8
        hier_ici_s = _gather_time(hier_ici_bytes, R_ici, ici_bw)
        # the sharded-update share of the DCN hop moves one-way (grad
        # scatter + param gather); only the replicated share pays a ring
        hier_dcn_s = (_ring_time(hier_dcn_bytes - hier_dcn_oneway_bytes,
                                 R_dcn, dcn_bw)
                      + _gather_time(hier_dcn_oneway_bytes, R_dcn, dcn_bw))
        comm_s += hier_ici_s + hier_dcn_s
    comm_s += searched_s
    update_s = opt_bytes_factor * update_bytes / (hbm_gbps * 1e9)
    # overlap schedule (arXiv 2004.13336-style pipelining under the
    # latency-hiding scheduler): the per-bucket collectives hide behind
    # remaining backward FLOPs — total becomes max(comm, compute) — except
    # the topologically LAST bucket, whose reduce has no backward left to
    # hide behind; one bucket's share of the AR time stays exposed
    shard_scatter_s = _gather_time(shard_scatter_bytes, R, bw)
    shard_gather_s = _gather_time(shard_gather_bytes, R, bw)
    flat_ar_s = _ring_time(ar_bytes, R, bw)
    ar_ring_s = (flat_ar_s + hier_ici_s + hier_dcn_s + searched_s
                 + shard_scatter_s + shard_gather_s)
    exposed_s = ar_ring_s / max(1, len(ar_bucket_keys))
    return CostEstimate(compute_s + update_s, comm_s, {
        "ar_bytes": ar_bytes, "ps_bytes": ps_bytes,
        "gather_bytes": gather_bytes, "sparse_bytes": sparse_bytes,
        "subset_ps_bytes": subset_ps_bytes, "subset_ps_s": subset_s,
        "hier_ici_bytes": hier_ici_bytes, "hier_dcn_bytes": hier_dcn_bytes,
        "hier_ici_s": hier_ici_s, "hier_dcn_s": hier_dcn_s,
        "hier_replica_dcn": R_dcn if hier_ici_bytes or searched_s else 1,
        "hier_replica_ici": R_ici if hier_ici_bytes or searched_s else R,
        "searched_ici_bytes": searched_ici_bytes,
        "searched_dcn_bytes": searched_dcn_bytes,
        "searched_s": searched_s,
        "sharded_scatter_bytes": shard_scatter_bytes,
        "sharded_gather_bytes": shard_gather_bytes,
        "sharded_scatter_s": shard_scatter_s,
        "sharded_gather_s": shard_gather_s,
        "bf16_master_bytes": bf16_master_bytes,
        "bf16_master_frac": bf16_frac,
        "update_bytes": update_bytes, "update_s": update_s,
        "ar_buckets": len(ar_bucket_keys), "overlap_exposed_s": exposed_s,
        # the bandwidth INPUTS the estimate priced with, recorded so the
        # runtime audit can turn a measured hop wall back into a measured
        # bandwidth (measured_gbps = spec_gbps x predicted_s/measured_s)
        "flat_ar_s": flat_ar_s, "ici_gbps": ici_gbps, "dcn_gbps": dcn_gbps,
        "num_replicas": R},
        schedule="overlap" if ar_overlap else "barrier")


def predicted_comm_bytes(est: "CostEstimate") -> dict:
    """Per-phase wire-byte predictions of a :class:`CostEstimate`, keyed
    the way the HLO communication audit phases its realized/intended
    tables (``flat``/``ici_hop``/``dcn_hop``/``ps``/``materialize``) — so
    ``tools/telemetry_report.py --audit`` and ``AutoStrategy.last_audit``
    can put predicted, intended, realized, and measured side by side
    without each consumer re-mapping the breakdown keys."""
    b = est.breakdown
    return {
        "flat": float(b.get("ar_bytes", 0.0)
                      + b.get("sharded_scatter_bytes", 0.0)
                      + b.get("sharded_gather_bytes", 0.0)),
        "ici_hop": float(b.get("hier_ici_bytes", 0.0)
                         + b.get("searched_ici_bytes", 0.0)),
        "dcn_hop": float(b.get("hier_dcn_bytes", 0.0)
                         + b.get("searched_dcn_bytes", 0.0)),
        "ps": float(b.get("ps_bytes", 0.0) + b.get("gather_bytes", 0.0)
                    + b.get("subset_ps_bytes", 0.0)),
        "sparse": float(b.get("sparse_bytes", 0.0)),
    }


class _FracBox:
    """Opaque leaf carrying (expected update-space shape, per-chip
    fraction) through ``optax.tree_map_params`` (see
    ``graph_transformer._SpecBox``)."""

    __slots__ = ("shape", "frac")

    def __init__(self, shape, frac):
        self.shape = shape
        self.frac = frac


def hbm_footprint(strategy, model_item, num_replicas, *,
                  mesh_axis_sizes=None, param_specs=None, opt_slots=2):
    """Static per-chip HBM demand of realizing ``strategy`` (bytes).

    The memory counterpart of :func:`estimate`'s time terms, and the
    cross-check the analysis subsystem's HBM pass
    (``autodist_tpu/analysis``) compares its traced liveness peak against:

    - ``param_bytes``: storage per chip — replicated/PS vars keep a full
      (gathered) copy everywhere; SHARDED storage holds 1/R of the padded
      axis; DIVERGENT keeps one full local copy; CUSTOM divides by the
      product of its spec's mesh axes (``mesh_axis_sizes``).
    - ``opt_bytes``: optimizer state mirrors the *update space* — 1/R for
      weight-update-sharded (sync PS) and SHARDED plans, full otherwise.
      Computed from the real optimizer via ``eval_shape`` when the
      ModelItem carries one (scalar statistics count once, replicated);
      otherwise ``opt_slots`` update-space copies (adam-class default 2).
    - ``grad_bytes``: the transient full-gradient tree the backward pass
      materializes before scatter/reduce (conservative: counted in full).

    Activations are deliberately absent — they depend on the traced
    program and are measured by the liveness pass.
    """
    import jax

    R = max(1, num_replicas)
    plans = build_var_plans(strategy, model_item, R, param_specs=param_specs)

    def custom_frac(plan):
        if plan.custom_spec is None or not mesh_axis_sizes:
            return 1.0
        k = 1
        for entry in tuple(plan.custom_spec):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for a in names:
                k *= int(mesh_axis_sizes.get(a, 1))
        return 1.0 / max(1, k)

    param_bytes = grad_bytes = 0.0
    u_frac = {}    # name -> per-chip fraction of the update space
    for v in model_item.var_infos:
        plan = plans.get(v.name)
        if plan is None:
            continue
        nbytes = v.byte_size
        if plan.placement == Placement.SHARDED:
            dim = max(1, v.shape[plan.partition_axis])
            padded = nbytes * (plan.padded_dim / dim)
            param_bytes += padded / R
            grad_bytes += nbytes
            u_frac[v.name] = 1.0 / R
        elif plan.placement == Placement.DIVERGENT:
            param_bytes += nbytes
            grad_bytes += nbytes
            # update space is the (R, *shape) stack sharded over the axis:
            # per chip that is one full local copy, i.e. 1/R of the stack
            u_frac[v.name] = 1.0 / R
        elif plan.placement == Placement.CUSTOM:
            f = custom_frac(plan)
            param_bytes += nbytes * f
            grad_bytes += nbytes * f
            u_frac[v.name] = f
        elif plan.sync == SyncKind.PS and plan.ps_sync:
            param_bytes += nbytes    # gathered copy lives on every chip
            grad_bytes += nbytes
            u_frac[v.name] = 1.0 / R
        elif master_shard_storage(plan):
            # bf16-master: per chip, the f32 MASTER is only the 1/R flat
            # shard (storage == update space) and the gathered compute
            # copy — the only full-shape copy that ever exists — is bf16:
            # 2 + 4/R bytes/param instead of the replicated 4 (and the
            # sharded update's opt-state cut still applies below).  The
            # transient gradient is bf16 too (upcast happens on the
            # (ss,) shard after the scatter).
            param_bytes += nbytes * 0.5 + nbytes / R
            grad_bytes += nbytes * 0.5
            u_frac[v.name] = 1.0 / R
        elif plan_sharded_update(plan):
            # ZeRO sharded weight update: the gathered param copy still
            # lives on every chip, but the optimizer's update space — and
            # with it Adam's moments — shards 1/R (the ~2/3 Adam HBM cut
            # the mode exists for).  Async PS never qualifies: plan_
            # sharded_update is AR-only, so the PR 1 "no 1/R discount for
            # async" fix cannot regress through this branch.
            param_bytes += nbytes
            grad_bytes += nbytes
            u_frac[v.name] = 1.0 / R
        else:                        # replicated AR / async PS
            param_bytes += nbytes
            grad_bytes += nbytes
            u_frac[v.name] = 1.0

    import numpy as _np

    from autodist_tpu.kernel.partitioner import update_space_shape

    def u_bytes(v):
        shp = update_space_shape(plans[v.name], R)
        return float(_np.prod(shp)) * _np.dtype(v.dtype).itemsize \
            if shp else _np.dtype(v.dtype).itemsize

    opt = model_item.optimizer
    if opt is None:
        opt_bytes = opt_slots * sum(
            u_bytes(v) * u_frac[v.name]
            for v in model_item.var_infos if v.name in u_frac)
    else:
        import optax

        from autodist_tpu.model_item import path_name

        leaves = jax.tree_util.tree_leaves_with_path(model_item.params)
        treedef = jax.tree_util.tree_structure(model_item.params)
        names = [path_name(p) for p, _ in leaves]
        u_avals = treedef.unflatten([
            jax.ShapeDtypeStruct(
                update_space_shape(plans[n], R) if n in plans else l.shape,
                _np.dtype(l.dtype))
            for n, (_, l) in zip(names, leaves)])
        opt_shapes = jax.eval_shape(opt.init, u_avals)
        boxes = treedef.unflatten([
            _FracBox(update_space_shape(plans[n], R) if n in plans else None,
                     u_frac.get(n, 1.0))
            for n in names])
        boxed_state = optax.tree_map_params(
            opt, lambda _leaf, box: box, opt_shapes, boxes,
            transform_non_params=lambda _leaf: _FracBox(None, 1.0),
            is_leaf=lambda x: isinstance(x, _FracBox))
        opt_bytes = 0.0
        for leaf, box in zip(jax.tree.leaves(opt_shapes),
                             jax.tree.leaves(
                                 boxed_state,
                                 is_leaf=lambda x: isinstance(x, _FracBox))):
            nbytes = float(_np.prod(leaf.shape)) * _np.dtype(leaf.dtype).itemsize \
                if leaf.shape else _np.dtype(leaf.dtype).itemsize
            frac = box.frac if (box.shape is not None
                                and tuple(leaf.shape) == tuple(box.shape)) \
                else 1.0
            opt_bytes += nbytes * frac

    total = param_bytes + opt_bytes + grad_bytes
    return {"param_bytes": param_bytes, "opt_bytes": opt_bytes,
            "grad_bytes": grad_bytes, "total_bytes": total,
            "num_replicas": R}


def builder_label(b):
    """Variant-qualified display name of a strategy builder, so rankings
    and rejection lists can tell ``AllReduce`` from
    ``AllReduce:overlap:sharded`` (the AR family enumerates several
    knob combinations under one class name)."""
    name = type(b).__name__
    tags = []
    comp = getattr(b, "compressor", "NoneCompressor")
    if comp and comp != "NoneCompressor":
        tags.append(str(comp))
    if getattr(b, "schedule", "barrier") == "overlap":
        tags.append("overlap")
    if str(getattr(b, "hierarchy", "auto")).lower() in ("two_level",
                                                        "hierarchical",
                                                        "2level"):
        tags.append("two_level")
    if getattr(b, "dcn_compressor", None):
        tags.append(f"dcn={b.dcn_compressor}")
    shup = getattr(b, "sharded_update", "replicated")
    if shup not in ("replicated", 0, None, False):
        tags.append("sharded")
    prec = getattr(b, "precision", "f32")
    if prec not in ("f32", 0, None, False, ""):
        tags.append("bf16_master")
    if getattr(b, "schedule_ir", ""):
        tags.append("searched")
    return name + (":" + ":".join(tags) if tags else "")


def rank_strategies(builders, model_item, resource_spec, calibration=None, **kw):
    """Rank candidate builders by estimated step time (cheapest first);
    with ``calibration`` (from :func:`calibrate`) the measured-corrected
    totals are used instead of the analytic overlap heuristic."""
    scored = []
    for b in builders:
        s = b.build(model_item, resource_spec)
        est = estimate(s, model_item, resource_spec, **kw)
        total = (est.calibrated_total(calibration) if calibration
                 else est.total_s)
        scored.append((total, builder_label(b), b, est, s))
    scored.sort(key=lambda t: t[0])
    return scored


def measure_and_record(session, batch, resource_yaml="", steps=10, warmup=2):
    """Measure a session's step time and produce an AutoSync-style
    :class:`RuntimeRecord` — the reference dataset's (model, resource,
    strategy, runtime) tuple (``simulator/dataset/README.md``).

    Timing uses :func:`autodist_tpu.utils.timing.seconds_per_step`: one
    window of ``steps`` dependent steps after ``warmup``, closed by one
    scalar fetch."""
    from autodist_tpu.utils.timing import fetch_scalar, seconds_per_step

    if steps < 1:
        raise ValueError("steps must be >= 1")
    last = None
    for _ in range(warmup):
        last = session.run(batch)
    if last is not None:
        fetch_scalar(last["loss"])  # don't time in-flight warmup

    def run_steps(n):
        m = None
        for _ in range(n):
            m = session.run(batch)
        return m["loss"]

    dt = seconds_per_step(run_steps, k=steps)
    import jax

    t = session._t
    return RuntimeRecord(
        model_def=t.model_item.serialize(),
        strategy_pb=t.strategy.proto.SerializeToString(),
        resource_yaml=resource_yaml,
        step_time_s=dt,
        backend=jax.default_backend(),
    )


@dataclasses.dataclass
class RuntimeRecord:
    """AutoSync-style measured tuple: (model, resource, strategy, runtime).

    ``backend`` labels where the runtime was measured ("cpu" records are
    pipeline-validation artifacts and must never be merged into hardware
    claims — VERDICT r4 item 7)."""

    model_def: bytes          # ModelItemDef proto
    strategy_pb: bytes        # Strategy proto
    resource_yaml: str
    step_time_s: float
    backend: str = ""

    def dump(self, path):
        import base64

        with open(path, "w") as f:
            json.dump({
                "model_def": base64.b64encode(self.model_def).decode(),
                "strategy": base64.b64encode(self.strategy_pb).decode(),
                "resource": self.resource_yaml,
                "step_time_s": self.step_time_s,
                "backend": self.backend,
            }, f)
        return path

    @classmethod
    def load(cls, path):
        import base64

        with open(path) as f:
            d = json.load(f)
        return cls(model_def=base64.b64decode(d["model_def"]),
                   strategy_pb=base64.b64decode(d["strategy"]),
                   resource_yaml=d["resource"],
                   step_time_s=d["step_time_s"],
                   backend=d.get("backend", ""))


def _synthetic_record_loss(params, batch):
    """Quadratic loss over every trainable leaf — differentiable for every
    variable (the full gradient-sync program traces) and tolerant of
    engine-provided leaves like ShardedTable."""
    import jax
    import jax.numpy as jnp

    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree.leaves(params):
        total = total + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    x = jax.tree.leaves(batch)[0]
    return total * jnp.mean(jnp.ones_like(x, jnp.float32))


def rebuild_record_case(record, loss_fn=None):
    """Reconstruct ``(strategy, model_item, mesh_R)`` from a
    :class:`RuntimeRecord` — the variables come back at their recorded
    shapes/dtypes under a synthetic quadratic loss (the record carries no
    user code), which is exactly enough for :func:`estimate`, the static
    verifier, and :func:`hbm_footprint`.  Shared by
    ``tools/verify_strategy.py`` and :func:`calibrate_from_records`."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.proto import modelitem_pb2, strategy_pb2
    from autodist_tpu.strategy.base import Strategy

    mdef = modelitem_pb2.ModelItemDef()
    mdef.ParseFromString(record.model_def)
    params = {v.name: jnp.zeros(tuple(v.shape), np.dtype(v.dtype))
              for v in mdef.variables}
    sparse = [v.name for v in mdef.variables if v.sparse_gradient]
    item = ModelItem(loss_fn or _synthetic_record_loss, params,
                     optax.adam(1e-3), sparse_vars=sparse or None)
    pb = strategy_pb2.Strategy()
    pb.ParseFromString(record.strategy_pb)
    R = 1
    for s in pb.graph_config.mesh.axis_sizes:
        R *= int(s)
    return Strategy(pb), item, max(1, R)


def calibrate_bandwidths(measurements):
    """Aggregate measured per-hop bandwidths into the ``ici_gbps`` /
    ``dcn_gbps`` overrides :func:`estimate` accepts.

    ``measurements``: dicts carrying ``ici_gbps`` and/or ``dcn_gbps``
    (the runtime audit's T006 ``measured_bandwidths`` payload, or its
    ``hops`` table — ``{"ici": {"measured_gbps": ...}, ...}`` is
    unwrapped).  The per-hop MEDIAN is returned — one captured step with
    a congested link must not drag the whole calibration — with hops
    nobody measured absent from the result.  Feed the returned dict to
    :func:`calibrate_from_records` (``measured_bandwidths=``) or splat it
    into :func:`estimate` directly."""
    per_hop = {"ici_gbps": [], "dcn_gbps": []}
    for m in measurements:
        if not m:
            continue
        if "ici" in m or "dcn" in m:    # a T006 hops table
            m = {f"{hop}_gbps": (m.get(hop) or {}).get("measured_gbps")
                 for hop in ("ici", "dcn")}
        for key, vals in per_hop.items():
            v = m.get(key)
            if v:
                vals.append(float(v))
    out = {}
    for key, vals in per_hop.items():
        if vals:
            vals.sort()
            n = len(vals)
            out[key] = vals[n // 2] if n % 2 else \
                0.5 * (vals[n // 2 - 1] + vals[n // 2])
    return out


def calibrate_from_records(records, resource_spec=None,
                           measured_bandwidths=None, **estimate_kw):
    """The measured-feedback loop closed from telemetry manifests: rebuild
    each :class:`RuntimeRecord`'s (strategy, model) case, price it with
    :func:`estimate`, and :func:`calibrate` against the measured step
    times.  ``records`` may be RuntimeRecord objects or paths to their
    JSON dumps.  Returns ``(calibration, pairs)``.

    ``measured_bandwidths`` (a :func:`calibrate_bandwidths` dict) re-prices
    every estimate at the MEASURED per-hop bandwidths instead of the spec
    defaults, so the least-squares fit corrects schedule/overhead error
    rather than re-learning a link speed the timeline already measured.

    Mixed-backend record sets raise: a CPU pipeline artifact averaged
    into TPU measurements would silently skew every coefficient (the
    same hygiene RuntimeRecord's ``backend`` label exists for).
    """
    recs = [RuntimeRecord.load(r) if isinstance(r, str) else r
            for r in records]
    backends = {r.backend for r in recs if r.backend}
    if len(backends) > 1:
        raise ValueError(
            f"refusing to calibrate across mixed backends {sorted(backends)}; "
            f"filter records to one backend first")
    if measured_bandwidths:
        for key in ("ici_gbps", "dcn_gbps"):
            if measured_bandwidths.get(key) and key not in estimate_kw:
                estimate_kw[key] = float(measured_bandwidths[key])
    pairs = []
    for rec in recs:
        strategy, item, R = rebuild_record_case(rec)
        from autodist_tpu.resource_spec import ResourceSpec

        spec = resource_spec or ResourceSpec.from_num_chips(R)
        pairs.append((estimate(strategy, item, spec, **estimate_kw),
                      rec.step_time_s))
    return calibrate(pairs), pairs
