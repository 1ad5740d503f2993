"""The verifier's pluggable analysis passes.

Each pass is a function ``(ctx: AnalysisContext) -> list[Finding]``
registered in :data:`PASS_REGISTRY`.  Static passes (``sharding``,
``hbm-static``) need only the strategy + model metadata; trace passes
(``collectives``, ``donation``, ``hbm-traced``) additionally need
``ctx.jaxpr`` — the deviceless ``ClosedJaxpr`` of the transformed train
step (the AOT abstract-eval path, so everything runs on CPU in CI).

Finding codes (stable; tests and tools match on them):

  C001 ERROR   cond branches issue different collectives, predicate may
               vary across devices -> SPMD deadlock
  C002 INFO    cond branches differ but predicate is replicated (safe)
  C003 ERROR   while loop with collectives and a possibly-varying
               predicate -> divergent trip counts deadlock the collective
  C010 ERROR   ppermute permutation invalid (duplicate source/dest or
               index out of axis range)
  C011 WARNING ppermute is not a total permutation cycle
  C020 ERROR   psum over a sub-32-bit integer wire dtype (accumulator
               wraps -> silent overflow)
  C021 WARNING psum over a reduced-precision float wire with a large
               axis (mantissa exhaustion)
  S001 ERROR   mesh axis sizes do not multiply to the replica count
  S002 ERROR   duplicate node config for one variable
  S003 WARNING node config names a variable absent from the model
  S004 ERROR   more than one partition axis
  S005 ERROR   partition axis out of range for the variable's rank
  S006 WARNING more shards than rows along the partition axis (the pad
               plan keeps it valid, but whole shards are padding)
  S007 INFO    partition axis not divisible -> pad plan
  S008 ERROR   "mesh:<axes>" reduction destination names a missing axis
  S010 WARNING int8 wire compressor precision/overflow risk
  S011 ERROR   PartitionSpec names a nonexistent mesh axis
  S012 ERROR   PartitionSpec uses one mesh axis for two dimensions
  S013 WARNING sharded dimension not divisible by its mesh axis
  D001 ERROR   value read (or returned) after an inner jit donated it
  D002 WARNING donated input has no alias-compatible output (donation
               is wasted; the buffer counts in full toward HBM)
  D003 INFO    donated input is never used
  H001 ERROR   static footprint (params+opt+grads) exceeds the HBM budget
  H002 ERROR   traced liveness peak exceeds the HBM budget
  H003 WARNING traced liveness peak above 90% of the HBM budget
  H004 INFO    footprint summary (cost-model cross-check)
  Y001 ERROR   DCN-hop compressor is a block codec (PowerSGD): the
               cross-slice hop only admits elementwise codecs + int8
  Y002 ERROR   TWO_LEVEL hierarchy but the mesh declares no
               replica_dcn x replica_ici sub-axes
  Y003 ERROR   declared sub-axis sizes do not multiply to the device count
  Y004 WARNING PowerSGD main codec under TWO_LEVEL (engine realizes FLAT)
  Y005 WARNING dcn_compressor set on a non-TWO_LEVEL node (ignored)
  Y006 INFO    hierarchy summary (factorization + DCN-hop codec)
  Y007 WARNING sharded_update with a block wire codec (int8/PowerSGD):
               the scatter only decomposes elementwise codecs; the
               engine realizes the REPLICATED update for those buckets
  Y008 WARNING sharded_update var smaller than the shard count: whole
               shards are padding (prefer the replicated update for
               tiny vars, or a coarser bucket group)
  Y009 INFO    sharded-update summary (shard↔mesh factorization, per-var
               padding plan, 1/R opt-state fraction)
  Y010 ERROR   schedule_ir program is malformed (parse/grammar failure,
               or references a mesh axis the strategy does not declare)
  Y011 ERROR   schedule_ir places a block codec (int8) on a fast (non-DCN)
               hop: block codecs are confined to the slow wire
  Y012 INFO    searched-schedule summary (node count + the distinct
               synthesized programs)
  X000 INFO    HLO audit skipped (no lowered module / no transformer)
  X001 ERROR   unintended (resharding) collective in the lowered module,
               absent from the strategy's plan
  X002 ERROR   expected sync collective missing from the lowered module
  X003 WARNING realized wire bytes exceed the plan beyond tolerance
  X004 WARNING replica_groups inconsistent with the declared
               replica_dcn x replica_ici factorization
  X005 WARNING per-microbatch collective inside the scan where the plan
               says once-per-step
  X006 INFO    realized-vs-intended wire-byte summary (carries the
               machine-readable table in Finding.data)
  F000 INFO    compute audit skipped (no lowered module / no trace)
  F001 ERROR   realized contraction FLOPs exceed the model FLOPs
               (jaxpr count) beyond tolerance, with attribution table
  F002 WARNING duplicated expensive-op signature (recompute): remat
               multiplicity + HBM-saved-vs-FLOPs-paid estimate
  F003 WARNING f32 contractions eligible for bf16 under a master-weight
               policy (mixed-precision recipe)
  F004 WARNING donation declared but not realized at lowering (no
               input_output_alias-eligible attribute / no
               type-compatible output for the deferred donor)
  F005 WARNING batch-stats/elementwise share of the realized work above
               threshold (MXU idles through HBM-bound epilogues)
  F006 INFO    machine-readable compute table + predicted MFU ceiling
               (carried in Finding.data)
  F007 INFO    machine-readable HBM-traffic table: per-region bytes,
               arithmetic intensity, both roofline legs and the
               roofline-clamped MFU ceiling (carried in Finding.data)
  F008 WARNING memory-bound step: the HBM byte leg dominates the MXU
               leg beyond MEMORY_BOUND_RATIO — byte levers (fused
               norm, GroupNorm), not FLOP levers, move the wall
  T000 INFO    runtime audit skipped (no trace capture available)
  T001 ERROR   measured exposed-comm fraction beyond the predicted
               exposure + tolerance (the promised overlap is not
               happening on the device timeline)
  T002 ERROR   straggler worker: cross-worker step-wall skew above
               threshold (names the worker address)
  T003 WARNING measured per-hop (ICI/DCN) bandwidth below the spec's
               ``bw`` beyond tolerance
  T004 WARNING overlap credit priced but not realized in the capture
  T005 WARNING codec wire savings not realized on the DCN hop
  T006 INFO    machine-readable predicted-vs-realized-vs-measured table
               (carried in Finding.data)
  R000 INFO    regression audit skipped (no baseline blessed yet)
  R001 ERROR   throughput / engine-overhead regression vs the blessed
               baseline beyond tolerance
  R002 ERROR   non-finite loss/grad observed in the run's health verdict
  R003 WARNING loss-spike or grad-norm anomaly (rolling z-score)
  R004 WARNING predicted_mfu_ceiling dropped vs baseline (structural
               regression, caught before any chip)
  R005 WARNING realized comm bytes grew vs baseline
  R006 INFO    machine-readable run-vs-baseline table (carried in
               Finding.data)
  E000 INFO    reaction audit skipped (no cluster events recorded)
  E001 ERROR   persistent signal never acted on by the control plane
  E002 ERROR   signal->action latency beyond the MTTR budget
  E003 WARNING re-plan that regressed throughput vs the pre-replan window
  E004 WARNING heartbeat gap without a membership event
  E005 INFO    machine-readable event/causality table (carried in
               Finding.data)
  P000 INFO    postmortem audit skipped (no bundle attached)
  P001 ERROR   nonfinite cascade: first poisoned worker + step + tensor
               in corrected cluster time
  P002 ERROR   stall death: stall window + likely culprit collective
               channel (timeline tail joined against the X006 intended
               table)
  P003 WARNING postmortem bundle incomplete (torn files, missing
               workers, overflowed rings)
  P004 WARNING reaction mismatch: the black box shows a signal the
               control plane never acted on before death
  P005 INFO    machine-readable bundle table (carried in Finding.data)
  L000 INFO    lockstep audit skipped (nothing attached to expand)
  L001 ERROR   mismatched rendezvous: ranks in one group disagree on
               op/bytes/dtype (SPMD deadlock, culprit named)
  L002 ERROR   ordering cycle between rendezvous groups sharing ranks
               (happens-before cycle across overlapped buckets)
  L003 ERROR   invalid ppermute permutation: non-bijective or a
               cross-epoch ring (the pipeline-axis precondition)
  L004 ERROR   schedule-IR program whose phase expansion deadlocks on
               the concrete dcn x ici factorization
  L005 WARNING rank-asymmetric trip counts reachable only via varying
               predicates (collective-free loop body)
  L006 INFO    machine-readable per-rank trace table (carried in
               Finding.data; lands on ctx.lockstep_summary)
  N000 INFO    determinism audit skipped (nothing attached to analyze)
  N001 ERROR   replicated PRNG key feeds a per-replica stochastic op:
               identical dropout masks/noise on every data replica
               (correlated gradient noise; named key + mesh axes)
  N002 ERROR   key stream reused: one key consumed by two random ops,
               or inside a scan without a per-iteration split/fold_in
  N003 ERROR   batch-shard overlap/gap: batch_spec x mesh coverage
               broken (replicas reading the same rows, or shards the
               gradient sync never reconciles)
  N004 WARNING nondeterministic lowered op (possibly-colliding scatter)
               inside a strategy whose contract is otherwise bitwise
  N005 WARNING shard_map-body key derived without an axis-index fold_in
               where per-replica variance is required
  N006 INFO    machine-readable key-lineage table + the strategy's
               determinism class (bitwise | reduction_order |
               stochastic; carried in Finding.data, lands on
               ctx.determinism_summary)
  TR001 ERROR  tracing the strategy's train step failed
  TR002 INFO   trace skipped (trace passes did not run)

The X-codes and F-codes form the LOWERED tier
(:mod:`autodist_tpu.analysis.hlo_audit` — the realized collective
schedule — and :mod:`autodist_tpu.analysis.compute_audit` — the realized
FLOPs + MFU ceiling): they run over the StableHLO text of the
transformed step's lowering rather than the jaxpr.  The T-codes form the
RUNTIME (measured) tier (:mod:`autodist_tpu.analysis.runtime_audit`):
they run over a ``jax.profiler`` chrome-trace capture and the aggregated
cross-worker manifests, closing the predicted -> statically-realized ->
measured loop.  The R-codes form the CROSS-RUN tier
(:mod:`autodist_tpu.analysis.regression_audit`): they diff any of the
above — or a finalized run manifest — against the blessed baselines in
``records/baselines`` (:mod:`autodist_tpu.telemetry.baseline`), so a
regression is a ranked finding in the same Report as everything else.
The E-codes form the CONTROL-PLANE tier
(:mod:`autodist_tpu.analysis.reaction_audit`): they judge the causal
cluster event log (schema v3 ``cluster_event`` records — live signals,
control actions, cause, signal->action latency) against the reaction
contract, so an ignored alarm or a slow MTTR ranks in the same Report.
The Q-codes form the SERVING tier
(:mod:`autodist_tpu.analysis.serving_audit`): they judge the decode
service's schema-v5 serving telemetry (tokens/sec, TTFT, occupancy) and
the decode step's realized collectives against the interconnect budget
(Q001 exposed decode comm, Q002 occupancy collapse, Q003 TTFT p99,
Q004 the machine-readable serving table).  The P-codes form the
POSTMORTEM tier (:mod:`autodist_tpu.analysis.postmortem_audit`): they
judge the assembled black-box bundle a failure trigger dumped
(:mod:`autodist_tpu.telemetry.flight_recorder`) — the root-cause pass
for runs that did not survive to be judged by any other tier.
The L-codes form the LOCKSTEP tier
(:mod:`autodist_tpu.analysis.lockstep_audit`): a per-rank symbolic
interpreter that expands the traced jaxpr, the lowered module's
replica_groups, and the schedule-IR bucket programs into each rank's
ordered rendezvous trace and proves the emitted schedule deadlock-free
— the gate ``schedule_search`` runs on every candidate before pricing.
The N-codes form the DETERMINISM tier
(:mod:`autodist_tpu.analysis.determinism_audit`): a PRNG key-lineage
dataflow walk (split/fold_in derivation graph joined with the C-tier
varying-axes analysis), the batch_spec x mesh shard-coverage diff, and
an HLO leg for order-hazard scatters — proving key independence, shard
disjointness, and each strategy's determinism CLASS (``bitwise |
reduction_order | stochastic``, the contract the elastic reshard gate
and the equivalence tests consume via ``determinism_class``) before a
step runs.
"""
import numpy as np

from jax.extend import core as jex_core

from autodist_tpu.analysis.jaxpr_utils import (
    collective_axes, collective_signature, find_shard_map_bodies,
    liveness_peak_bytes, subjaxprs, varying_out, _as_jaxpr, _read,
)
from autodist_tpu.analysis.report import Finding, Severity

# axis size beyond which a bf16/f16 psum has lost every mantissa bit to
# same-sign accumulation (8 mantissa bits for bf16)
REDUCED_PRECISION_PSUM_AXIS = 256
# replica count beyond which int8 requantization of the reduced chunk
# costs more precision than bf16 would
INT8_WIRE_REPLICA_WARN = 64


def _f(sev, code, pass_name, msg, subject=""):
    return Finding(Severity(sev), code, pass_name, msg, subject)


# ---------------------------------------------------------------------------
# collective-consistency pass
# ---------------------------------------------------------------------------


def _check_ppermute(eqn, axis_sizes, findings):
    perm = eqn.params.get("perm") or ()
    axes = collective_axes(eqn)
    size = 1
    for a in axes:
        size *= int(axis_sizes.get(a, 1))
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    where = f"ppermute over {axes}"
    if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
        findings.append(_f(
            Severity.ERROR, "C010", "collectives",
            f"permutation {tuple(perm)} repeats a source or destination — "
            f"two peers would send to (or receive from) the same device",
            where))
        return
    bad = [i for i in srcs + dsts if not (0 <= i < size)]
    if bad:
        findings.append(_f(
            Severity.ERROR, "C010", "collectives",
            f"permutation index(es) {sorted(set(bad))} out of range for "
            f"axis size {size}", where))
        return
    if perm and (set(srcs) != set(range(size)) or set(dsts) != set(range(size))):
        findings.append(_f(
            Severity.WARNING, "C011", "collectives",
            f"permutation {tuple(perm)} is not a total cycle over the "
            f"{size}-device axis; non-participating devices receive zeros",
            where))


def _check_psum_wire(eqn, axis_sizes, findings):
    axes = collective_axes(eqn)
    size = 1
    for a in axes:
        size *= int(axis_sizes.get(a, 1))
    if size <= 1:
        return
    for a in eqn.invars:
        dt = np.dtype(getattr(a.aval, "dtype", np.float32))
        if dt.kind in "iu" and dt.itemsize < 4:
            findings.append(_f(
                Severity.ERROR, "C020", "collectives",
                f"psum over {axes} accumulates in the {dt.name} wire dtype: "
                f"summing {size} terms wraps silently — reduce in >=32-bit "
                f"or use the all_to_all/dequant-sum int8 recipe", str(dt)))
        elif (dt.kind == "f" and dt.itemsize < 4
              and size >= REDUCED_PRECISION_PSUM_AXIS):
            findings.append(_f(
                Severity.WARNING, "C021", "collectives",
                f"psum of a {dt.name} wire over {size} devices: same-sign "
                f"accumulation exhausts the mantissa; accumulate in f32",
                str(dt)))


def _sig_str(sig, limit=160):
    s = str(sig)
    return s if len(s) <= limit else s[:limit] + "..."


def _walk_collectives(jaxpr, in_varying, axis_sizes, findings, depth=0):
    """Recursive checker: per-eqn varying-axes env + structural checks."""
    jaxpr = _as_jaxpr(jaxpr)
    env, _ = varying_out(jaxpr, in_varying)
    for eqn in jaxpr.eqns:
        ins = [_read(env, a) for a in eqn.invars]
        union = frozenset().union(*ins) if ins else frozenset()
        name = eqn.primitive.name
        if name == "ppermute":
            _check_ppermute(eqn, axis_sizes, findings)
        elif name == "psum":
            _check_psum_wire(eqn, axis_sizes, findings)
        elif name == "cond":
            sigs = [collective_signature(b) for b in eqn.params["branches"]]
            if len(set(sigs)) > 1:
                pred_varying = ins[0]
                if pred_varying:
                    findings.append(_f(
                        Severity.ERROR, "C001", "collectives",
                        f"cond branches issue different collective "
                        f"sequences ({' vs '.join(_sig_str(s) for s in sigs)}) "
                        f"and the predicate may vary across mesh axes "
                        f"{sorted(pred_varying)}: devices taking different "
                        f"branches rendezvous on mismatched collectives — "
                        f"SPMD deadlock", "cond"))
                else:
                    findings.append(_f(
                        Severity.INFO, "C002", "collectives",
                        "cond branches issue different collectives but the "
                        "predicate is replicated; every device takes the "
                        "same branch (e.g. periodic averaging)", "cond"))
            for b in eqn.params["branches"]:
                _walk_collectives(b, ins[1:], axis_sizes, findings, depth + 1)
        elif name == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            cconsts, bconsts = ins[:cn], ins[cn:cn + bn]
            carry = list(ins[cn + bn:])
            for _ in range(16):
                _, new = varying_out(eqn.params["body_jaxpr"],
                                     list(bconsts) + carry)
                merged = [c | n for c, n in zip(carry, new)]
                if merged == carry:
                    break
                carry = merged
            _, pred_out = varying_out(eqn.params["cond_jaxpr"],
                                      list(cconsts) + carry)
            pred_varying = pred_out[0] if pred_out else frozenset()
            body_sig = collective_signature(eqn.params["body_jaxpr"])
            cond_sig = collective_signature(eqn.params["cond_jaxpr"])
            if (body_sig or cond_sig) and pred_varying:
                findings.append(_f(
                    Severity.ERROR, "C003", "collectives",
                    f"while loop contains collectives "
                    f"({_sig_str(body_sig or cond_sig)}) and its predicate "
                    f"may vary across mesh axes {sorted(pred_varying)}: "
                    f"devices disagree on the trip count and hang at the "
                    f"next collective", "while"))
            _walk_collectives(eqn.params["body_jaxpr"],
                              list(bconsts) + carry, axis_sizes, findings,
                              depth + 1)
        elif name == "scan":
            # body invars are (consts, carry, xs-slices); widen the carry
            # to its fixpoint first — a value that only becomes varying via
            # the carry after iteration 1 must still flag iteration 2's cond
            nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
            consts, carry, xs = ins[:nc], list(ins[nc:nc + ncar]), ins[nc + ncar:]
            body = eqn.params["jaxpr"]
            for _ in range(16):
                _, new = varying_out(body, list(consts) + carry + list(xs))
                merged = [c | n for c, n in zip(carry, new[:ncar])]
                if merged == carry:
                    break
                carry = merged
            _walk_collectives(body, list(consts) + carry + list(xs),
                              axis_sizes, findings, depth + 1)
        else:
            for sub in subjaxprs(eqn):
                sub_j = _as_jaxpr(sub)
                if len(sub_j.invars) == len(ins):
                    _walk_collectives(sub_j, ins, axis_sizes, findings,
                                      depth + 1)
                else:
                    _walk_collectives(sub_j,
                                      [union] * len(sub_j.invars),
                                      axis_sizes, findings, depth + 1)


def collectives_pass(ctx):
    """SPMD deadlock + wire-dtype analysis over every shard_map body."""
    findings = []
    if ctx.jaxpr is None:
        return findings
    bodies = find_shard_map_bodies(ctx.jaxpr)
    for body, mesh, in_varying in bodies:
        sizes = dict(getattr(mesh, "shape", {}) or ctx.axis_sizes)
        _walk_collectives(body, in_varying, sizes, findings)
    if not bodies:
        # no shard_map (e.g. a plain jit function under test): analyze the
        # top jaxpr with replicated inputs
        _walk_collectives(ctx.jaxpr,
                          [frozenset()] * len(_as_jaxpr(ctx.jaxpr).invars),
                          ctx.axis_sizes, findings)
    return findings


# ---------------------------------------------------------------------------
# sharding / strategy lint pass
# ---------------------------------------------------------------------------


def sharding_pass(ctx):
    findings = []
    axis_names = list(ctx.axis_names)
    axis_sizes = dict(ctx.axis_sizes)
    R = ctx.num_replicas
    proto = ctx.strategy.proto

    replicas = list(proto.graph_config.replicas)
    mesh_prod = 1
    for s in proto.graph_config.mesh.axis_sizes:
        mesh_prod *= int(s)
    if replicas and proto.graph_config.mesh.axis_sizes and \
            mesh_prod != len(replicas):
        findings.append(_f(
            Severity.ERROR, "S001", "sharding",
            f"mesh {dict(zip(proto.graph_config.mesh.axis_names, proto.graph_config.mesh.axis_sizes))} "
            f"spans {mesh_prod} devices but the strategy lists "
            f"{len(replicas)} replicas", "mesh"))

    var_infos = {v.name: v for v in ctx.model_item.var_infos} \
        if ctx.model_item is not None else {}
    seen = set()
    for node in proto.node_config:
        name = node.var_name
        if name in seen:
            findings.append(_f(
                Severity.ERROR, "S002", "sharding",
                "duplicate node config: two synchronizers for one variable "
                "would issue conflicting collectives", name))
            continue
        seen.add(name)
        v = var_infos.get(name)
        if var_infos and v is None:
            findings.append(_f(
                Severity.WARNING, "S003", "sharding",
                "node config names a variable absent from the model "
                "(the strategy compiler will prune it)", name))
            continue

        parts = list(node.partition)
        active = [i for i, k in enumerate(parts) if k > 1]
        if len(active) > 1:
            findings.append(_f(
                Severity.ERROR, "S004", "sharding",
                f"partition {parts} is active on {len(active)} axes; only "
                f"one partition axis is supported", name))
        elif active and v is not None:
            ax = active[0]
            if ax >= len(v.shape):
                findings.append(_f(
                    Severity.ERROR, "S005", "sharding",
                    f"partition axis {ax} out of range for shape "
                    f"{tuple(v.shape)}", name))
            else:
                dim = v.shape[ax]
                if R > dim:
                    findings.append(_f(
                        Severity.WARNING, "S006", "sharding",
                        f"axis {ax} has {dim} rows but the mesh shards it "
                        f"{R} ways: the pad plan keeps it valid, but some "
                        f"devices hold pure-padding (zero-gradient) shards "
                        f"— prefer replicating variables this small", name))
                elif dim % R:
                    padded = -(-dim // R) * R
                    findings.append(_f(
                        Severity.INFO, "S007", "sharding",
                        f"axis {ax} size {dim} not divisible by {R}; pad "
                        f"plan: padded to {padded} (pad rows carry zero "
                        f"gradients)", name))

        for src in (node, *node.part_config):
            which = src.WhichOneof("synchronizer")
            if which == "PSSynchronizer":
                dest = src.PSSynchronizer.reduction_destination
                if dest.startswith("mesh:"):
                    axes = tuple(a for a in dest[5:].split(",") if a)
                    missing = [a for a in axes if a not in axis_names]
                    if missing:
                        findings.append(_f(
                            Severity.ERROR, "S008", "sharding",
                            f"reduction destination {dest!r} names mesh "
                            f"axis(es) {missing} but the mesh has "
                            f"{axis_names}", name))
            elif which == "AllReduceSynchronizer":
                from autodist_tpu.proto import synchronizers_pb2

                _C = synchronizers_pb2.AllReduceSynchronizer
                comp = src.AllReduceSynchronizer.compressor
                if comp in (_C.Int8Compressor, _C.Int8CompressorEF) \
                        and R >= INT8_WIRE_REPLICA_WARN:
                    findings.append(_f(
                        Severity.WARNING, "S010", "sharding",
                        f"int8 wire over {R} replicas: requantizing the "
                        f"{R}-way reduced chunk costs ~log2({R}) bits of "
                        f"the 7-bit mantissa; prefer bf16 at this scale",
                        name))

    findings.extend(lint_param_specs(ctx.param_specs, axis_names, axis_sizes,
                                     var_infos))
    return findings


def lint_param_specs(param_specs, axis_names, axis_sizes, var_infos):
    """Validate user PartitionSpecs against the mesh.  Returns findings;
    entries producing ERRORs are reported with their pattern as subject so
    the verifier can drop them before tracing."""
    findings = []
    for pat, spec in (param_specs or {}).items():
        entries = tuple(spec)
        used = []
        for d, entry in enumerate(entries):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for a in names:
                missing = a not in axis_names
                if missing:
                    findings.append(_f(
                        Severity.ERROR, "S011", "sharding",
                        f"PartitionSpec {spec} names mesh axis {a!r} but "
                        f"the mesh axes are {axis_names}", pat))
                elif a in used:
                    findings.append(_f(
                        Severity.ERROR, "S012", "sharding",
                        f"PartitionSpec {spec} uses mesh axis {a!r} for "
                        f"two different dimensions", pat))
                used.append(a)
        # divisibility of the sharded dims, for exact-name patterns
        v = var_infos.get(pat)
        if v is None:
            continue
        for d, entry in enumerate(entries[:len(v.shape)]):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            k = 1
            for a in names:
                k *= int(axis_sizes.get(a, 1))
            if k > 1 and v.shape[d] % k:
                findings.append(_f(
                    Severity.WARNING, "S013", "sharding",
                    f"dim {d} (size {v.shape[d]}) is not divisible by the "
                    f"{k}-way mesh axes {names}", pat))
    return findings


# ---------------------------------------------------------------------------
# sync-hierarchy pass (two-level topology-aware gradient sync)
# ---------------------------------------------------------------------------


def hierarchy_pass(ctx):
    """Validate the two-level sync decomposition before anything compiles:
    the sub-axis factorization must cover the device count, TWO_LEVEL
    collectives must have declared ``replica_dcn x replica_ici`` axes to
    reference, and the DCN-hop codec must be shard-decomposable (the
    elementwise family + int8; a PowerSGD low-rank exchange cannot ride a
    shard hop — ERROR, per docs/performance.md "Hierarchical sync").

    Also the ZeRO sharded-update lint (Y007-Y009): verifies the
    shard↔mesh factorization and the per-var padding plan of
    ``ShardedUpdate.SHARDED`` nodes — block wire codecs fall back to the
    replicated update (Y007), vars smaller than the shard count waste
    whole shards on padding (Y008), and Y009 summarizes the sharded
    update's factorization + 1/R opt-state fraction."""
    from autodist_tpu.const import AXIS_REPLICA_DCN, AXIS_REPLICA_ICI
    from autodist_tpu.kernel.synchronization.all_reduce import (
        DCN_SAFE_CODECS, ELEMENTWISE_CODECS)
    from autodist_tpu.proto import synchronizers_pb2

    _C = synchronizers_pb2.AllReduceSynchronizer
    findings = []
    proto = ctx.strategy.proto
    axis_sizes = dict(ctx.axis_sizes)
    factored = (AXIS_REPLICA_DCN in axis_sizes
                and AXIS_REPLICA_ICI in axis_sizes)

    if factored:
        n_devices = len(proto.graph_config.replicas)
        if not n_devices and ctx.resource_spec is not None:
            n_devices = ctx.resource_spec.num_accelerators
        prod = 1
        for s in axis_sizes.values():
            prod *= int(s)
        if n_devices and prod != n_devices:
            findings.append(_f(
                Severity.ERROR, "Y003", "hierarchy",
                f"sub-axis factorization {axis_sizes} multiplies to {prod} "
                f"but the strategy spans {n_devices} device(s); the "
                f"two-level schedule would address devices that do not "
                f"exist (or leave some idle)", "mesh"))

    var_infos = {v.name: v for v in ctx.model_item.var_infos} \
        if ctx.model_item is not None else {}
    R = max(1, ctx.num_replicas)
    two_level_nodes = dcn_codecs = 0
    sharded_nodes = sharded_fallbacks = 0
    searched_nodes = 0
    searched_programs = set()
    for node in proto.node_config:
        for src in (node, *node.part_config):
            if src.WhichOneof("synchronizer") != "AllReduceSynchronizer":
                continue
            ar = src.AllReduceSynchronizer
            ir_text = getattr(ar, "schedule_ir", "")
            if ir_text:
                from autodist_tpu.kernel.synchronization import (
                    schedule_ir as sir,
                )

                searched_nodes += 1
                try:
                    prog = sir.loads(ir_text)
                    sir.validate_structure(prog)
                except ValueError as e:
                    findings.append(_f(
                        Severity.ERROR, "Y010", "hierarchy",
                        f"schedule_ir program {ir_text!r} is malformed: {e}",
                        node.var_name))
                    continue
                missing = [a for ph in prog.phases for a in ph.axes
                           if axis_sizes and a not in axis_sizes]
                if missing:
                    findings.append(_f(
                        Severity.ERROR, "Y010", "hierarchy",
                        f"schedule_ir program {ir_text!r} references mesh "
                        f"axis(es) {sorted(set(missing))} the strategy does "
                        f"not declare (mesh: {dict(axis_sizes)})",
                        node.var_name))
                for ph in sir.block_codec_violations(prog):
                    findings.append(_f(
                        Severity.ERROR, "Y011", "hierarchy",
                        f"schedule_ir phase '{ph.op}@{'+'.join(ph.axes)}' "
                        f"places a block codec on a fast (non-DCN) hop: "
                        f"the int8 all_to_all recipe only pays off on the "
                        f"slow wire, and the executor confines it there",
                        node.var_name))
                searched_programs.add(sir.dumps(prog))
            if ar.sharded_update:
                sharded_nodes += 1
                wire = (ar.dcn_compressor or ar.compressor
                        if ar.hierarchy != _C.FLAT else ar.compressor)
                if (ar.compressor not in ELEMENTWISE_CODECS
                        or wire not in ELEMENTWISE_CODECS):
                    sharded_fallbacks += 1
                    findings.append(_f(
                        Severity.WARNING, "Y007", "hierarchy",
                        f"sharded_update with a block wire codec "
                        f"(compressor={ar.compressor}, effective wire="
                        f"{wire}): a per-shard re-encoding of int8 blocks "
                        f"or PowerSGD factors approximates differently "
                        f"from the barrier reduce, so the engine realizes "
                        f"the REPLICATED update for this bucket — the 1/R "
                        f"opt-state saving does not apply",
                        node.var_name))
                else:
                    v = var_infos.get(node.var_name)
                    n_elems = 1
                    if v is not None and v.shape:
                        n_elems = 1
                        for d in v.shape:
                            n_elems *= int(d)
                    if v is not None and v.shape and n_elems < R:
                        findings.append(_f(
                            Severity.WARNING, "Y008", "hierarchy",
                            f"sharded_update over {R} shards but the "
                            f"variable has only {n_elems} element(s): "
                            f"{R - n_elems} shard(s) are pure padding — "
                            f"the scatter/gather wire and the flat-shard "
                            f"bookkeeping buy nothing for vars this "
                            f"small; prefer the replicated update",
                            node.var_name))
            if ar.dcn_compressor and \
                    ar.dcn_compressor not in DCN_SAFE_CODECS:
                findings.append(_f(
                    Severity.ERROR, "Y001", "hierarchy",
                    f"dcn_compressor {ar.dcn_compressor} is a block codec: "
                    f"the cross-slice hop reduces a 1/R_ici shard, which "
                    f"only elementwise codecs (none/bf16/bf16-EF) and the "
                    f"int8 all_to_all recipe decompose into — PowerSGD's "
                    f"factor exchange does not", node.var_name))
            if ar.hierarchy != _C.TWO_LEVEL:
                if ar.dcn_compressor and ar.hierarchy == _C.FLAT:
                    findings.append(_f(
                        Severity.WARNING, "Y005", "hierarchy",
                        "dcn_compressor is set but hierarchy=FLAT pins the "
                        "one-collective schedule; the DCN-hop codec is "
                        "ignored", node.var_name))
                continue
            two_level_nodes += 1
            if ar.dcn_compressor:
                dcn_codecs += 1
            if not factored:
                findings.append(_f(
                    Severity.ERROR, "Y002", "hierarchy",
                    f"hierarchy=TWO_LEVEL but the mesh "
                    f"({dict(axis_sizes) or list(ctx.axis_names)}) declares "
                    f"no '{AXIS_REPLICA_DCN}' x '{AXIS_REPLICA_ICI}' "
                    f"sub-axes for the schedule's collectives to "
                    f"reference — factor the mesh (YAML `mesh:` request "
                    f"or build_mesh(hierarchy=True))", node.var_name))
            if ar.compressor == _C.PowerSGDCompressor:
                findings.append(_f(
                    Severity.WARNING, "Y004", "hierarchy",
                    "PowerSGD under TWO_LEVEL: the low-rank factor "
                    "exchange does not decompose into ICI/DCN hops; the "
                    "engine realizes this bucket FLAT", node.var_name))
    if two_level_nodes and factored:
        findings.append(_f(
            Severity.INFO, "Y006", "hierarchy",
            f"two-level sync: {two_level_nodes} node(s) over "
            f"replica_dcn={axis_sizes[AXIS_REPLICA_DCN]} x "
            f"replica_ici={axis_sizes[AXIS_REPLICA_ICI]} "
            f"({dcn_codecs} with an explicit DCN-hop codec)", "mesh"))
    if searched_nodes:
        findings.append(_f(
            Severity.INFO, "Y012", "hierarchy",
            f"searched collective schedules: {searched_nodes} node(s) run "
            f"synthesized programs "
            f"{sorted(searched_programs) or '(all malformed)'} "
            f"(strategy/schedule_search.py; canonical FLAT/TWO_LEVEL-shaped "
            f"programs are normalized onto the legacy knobs by the engine)",
            "mesh"))
    if sharded_nodes:
        factorization = (
            f"replica_dcn={axis_sizes.get(AXIS_REPLICA_DCN)} x "
            f"replica_ici={axis_sizes.get(AXIS_REPLICA_ICI)} (fused "
            f"ici-major shards)" if factored else f"{R} flat shards")
        findings.append(_f(
            Severity.INFO, "Y009", "hierarchy",
            f"sharded weight update: {sharded_nodes} node(s) reduce-"
            f"scatter into {factorization}; optimizer state shards 1/{R} "
            f"per chip and an all-gather of fresh params replaces the "
            f"gradient all-gather"
            + (f" ({sharded_fallbacks} node(s) fall back to the "
               f"replicated update — block wire codec)"
               if sharded_fallbacks else ""), "mesh"))
    return findings


# ---------------------------------------------------------------------------
# donation-safety pass
# ---------------------------------------------------------------------------


def _donation_walk(jaxpr, findings):
    jaxpr = _as_jaxpr(jaxpr)
    outvars = set(v for v in jaxpr.outvars if isinstance(v, jex_core.Var))
    for i, eqn in enumerate(jaxpr.eqns):
        di = eqn.params.get("donated_invars")
        if di and any(di):
            for flag, a in zip(di, eqn.invars):
                if not flag or not isinstance(a, jex_core.Var):
                    continue
                readers = [j for j in range(i + 1, len(jaxpr.eqns))
                           if a in jaxpr.eqns[j].invars]
                if readers or a in outvars:
                    after = (f"eqn #{readers[0]} "
                             f"({jaxpr.eqns[readers[0]].primitive.name})"
                             if readers else "the jaxpr outputs")
                    findings.append(_f(
                        Severity.ERROR, "D001", "donation",
                        f"buffer donated to inner call "
                        f"'{eqn.params.get('name', eqn.primitive.name)}' "
                        f"(eqn #{i}) is read again by {after}: the donated "
                        f"buffer may already be overwritten — "
                        f"use-after-donation", str(a)))
        for sub in subjaxprs(eqn):
            _donation_walk(sub, findings)


def donation_pass(ctx):
    findings = []
    if ctx.jaxpr is None:
        return findings
    jaxpr = _as_jaxpr(ctx.jaxpr)
    _donation_walk(jaxpr, findings)

    donated = ctx.donated_invars or []
    if not any(donated):
        return findings
    used = set()
    for eqn in jaxpr.eqns:
        used.update(a for a in eqn.invars if isinstance(a, jex_core.Var))
    out_slots = {}
    for v in jaxpr.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            key = (tuple(aval.shape), np.dtype(aval.dtype).str)
            out_slots[key] = out_slots.get(key, 0) + 1
    for flag, v in zip(donated, jaxpr.invars):
        if not flag:
            continue
        if v not in used and v not in set(jaxpr.outvars):
            findings.append(_f(
                Severity.INFO, "D003", "donation",
                "donated input is never used; its buffer is freed but the "
                "donation bought nothing", str(v)))
            continue
        key = (tuple(v.aval.shape), np.dtype(v.aval.dtype).str)
        if out_slots.get(key, 0) > 0:
            out_slots[key] -= 1
        else:
            findings.append(_f(
                Severity.WARNING, "D002", "donation",
                f"donated input {v.aval.shape}/{np.dtype(v.aval.dtype).name} "
                f"has no shape/dtype-compatible output to alias: XLA cannot "
                f"honor the donation and the buffer counts in full toward "
                f"HBM", str(v)))
    return findings


# ---------------------------------------------------------------------------
# HBM footprint passes
# ---------------------------------------------------------------------------


def _gib(b):
    for unit, div in (("GiB", 1024 ** 3), ("MiB", 1024 ** 2), ("KiB", 1024)):
        if b >= div:
            return f"{b / div:.3f} {unit}"
    return f"{int(b)} B"


def hbm_static_pass(ctx):
    """Params + optimizer state + gradient footprint from the cost model,
    cross-checked against the per-chip budget."""
    from autodist_tpu.simulator.cost_model import hbm_footprint

    findings = []
    if ctx.model_item is None:
        return findings
    fp = hbm_footprint(ctx.strategy, ctx.model_item, ctx.num_replicas,
                       mesh_axis_sizes=ctx.axis_sizes,
                       param_specs=ctx.safe_param_specs)
    ctx.static_footprint = fp
    budget = ctx.hbm_bytes_per_device
    summary = (f"static per-chip footprint: params {_gib(fp['param_bytes'])} "
               f"+ opt {_gib(fp['opt_bytes'])} + grads "
               f"{_gib(fp['grad_bytes'])} = {_gib(fp['total_bytes'])}"
               + (f" (budget {_gib(budget)})" if budget else ""))
    findings.append(_f(Severity.INFO, "H004", "hbm-static", summary))
    if budget and fp["total_bytes"] > budget:
        findings.append(_f(
            Severity.ERROR, "H001", "hbm-static",
            f"static footprint {_gib(fp['total_bytes'])} exceeds the "
            f"per-chip HBM budget {_gib(budget)} — the step cannot fit "
            f"before activations are even counted", "footprint"))
    return findings


def hbm_traced_pass(ctx):
    """Liveness-based activation peak over the per-device program."""
    findings = []
    if ctx.jaxpr is None or not ctx.hbm_bytes_per_device:
        return findings
    budget = ctx.hbm_bytes_per_device
    bodies = find_shard_map_bodies(ctx.jaxpr)
    if bodies:
        peak = 0
        for body, _mesh, _varying in bodies:
            peak = max(peak, liveness_peak_bytes(body))
    else:
        R = max(1, ctx.num_replicas)
        peak = liveness_peak_bytes(ctx.jaxpr) // R
    ctx.traced_peak_bytes = peak
    static_total = (ctx.static_footprint or {}).get("total_bytes", 0)
    findings.append(_f(
        Severity.INFO, "H004", "hbm-traced",
        f"traced per-device liveness peak {_gib(peak)} "
        f"(static cross-check {_gib(static_total)}, "
        f"budget {_gib(budget)})"))
    if peak > budget:
        findings.append(_f(
            Severity.ERROR, "H002", "hbm-traced",
            f"liveness peak {_gib(peak)} exceeds the per-chip HBM budget "
            f"{_gib(budget)}: the traced step cannot fit", "liveness"))
    elif peak > 0.9 * budget:
        findings.append(_f(
            Severity.WARNING, "H003", "hbm-traced",
            f"liveness peak {_gib(peak)} is within 10% of the per-chip "
            f"HBM budget {_gib(budget)}; fragmentation or compiler "
            f"temporaries may tip it over", "liveness"))
    return findings


def hlo_audit_pass(ctx):
    """Lowered-tier pass: diff the realized collective schedule of the
    step's StableHLO lowering against the strategy's intended plan
    (:mod:`autodist_tpu.analysis.hlo_audit`)."""
    from autodist_tpu.analysis.hlo_audit import hlo_audit_pass as _run

    return _run(ctx)


def compute_audit_pass(ctx):
    """Lowered-tier pass: realized FLOPs vs model FLOPs, recompute /
    precision / donation-realization audit, and the predicted MFU
    ceiling (:mod:`autodist_tpu.analysis.compute_audit`)."""
    from autodist_tpu.analysis.compute_audit import compute_audit_pass as _run

    return _run(ctx)


def lockstep_audit_pass(ctx):
    """Lockstep-tier pass: expand the traced jaxpr, the lowered module,
    and the schedule-IR bucket programs into per-rank rendezvous traces
    and prove the schedule deadlock-free
    (:mod:`autodist_tpu.analysis.lockstep_audit`)."""
    from autodist_tpu.analysis.lockstep_audit import \
        lockstep_audit_pass as _run

    return _run(ctx)


def determinism_audit_pass(ctx):
    """Determinism-tier pass: PRNG key lineage + batch-shard coverage +
    lowered order-hazard scatters, exporting the strategy's determinism
    class (:mod:`autodist_tpu.analysis.determinism_audit`)."""
    from autodist_tpu.analysis.determinism_audit import \
        determinism_audit_pass as _run

    return _run(ctx)


def runtime_audit_pass(ctx):
    """Runtime-tier pass: the measured timeline of a ``jax.profiler``
    capture vs the intended channels and the cost estimate, plus
    cross-worker straggler skew from the aggregated manifests
    (:mod:`autodist_tpu.analysis.runtime_audit`)."""
    from autodist_tpu.analysis.runtime_audit import \
        runtime_audit_pass as _run

    return _run(ctx)


def regression_audit_pass(ctx):
    """Cross-run tier pass: diff this analysis (walls/health from
    aggregated manifests, F006 ceiling, X006 bytes) against the blessed
    baseline (:mod:`autodist_tpu.analysis.regression_audit`)."""
    from autodist_tpu.analysis.regression_audit import \
        regression_audit_pass as _run

    return _run(ctx)


def reaction_audit_pass(ctx):
    """Control-plane tier pass: judge the run's causal cluster event log
    (signals vs actions, cause, signal->action latency) against the
    reaction contract (:mod:`autodist_tpu.analysis.reaction_audit`)."""
    from autodist_tpu.analysis.reaction_audit import \
        reaction_audit_pass as _run

    return _run(ctx)


def serving_audit_pass(ctx):
    """Serving tier pass: judge the decode service's schema-v5 serving
    telemetry + realized decode collectives against the serving budgets
    (:mod:`autodist_tpu.analysis.serving_audit`)."""
    from autodist_tpu.analysis.serving_audit import \
        serving_audit_pass as _run

    return _run(ctx)


def postmortem_audit_pass(ctx):
    """Postmortem tier pass: root-cause the assembled black-box bundle a
    failure trigger dumped — nonfinite cascade origin, stall culprit,
    bundle completeness, unanswered signals
    (:mod:`autodist_tpu.analysis.postmortem_audit`)."""
    from autodist_tpu.analysis.postmortem_audit import \
        postmortem_audit_pass as _run

    return _run(ctx)


def fleet_audit_pass(ctx):
    """Scale tier pass: judge whether observability held up under fleet
    load — chief fold-in saturation, detection latency at worker count,
    drop budgets, snapshot-latency growth
    (:mod:`autodist_tpu.analysis.fleet_audit`)."""
    from autodist_tpu.analysis.fleet_audit import fleet_audit_pass as _run

    return _run(ctx)


PASS_REGISTRY = {
    "sharding": sharding_pass,
    "hierarchy": hierarchy_pass,
    "hbm-static": hbm_static_pass,
    "collectives": collectives_pass,
    "donation": donation_pass,
    "hbm-traced": hbm_traced_pass,
    "hlo-audit": hlo_audit_pass,
    "compute-audit": compute_audit_pass,
    "lockstep-audit": lockstep_audit_pass,
    "determinism-audit": determinism_audit_pass,
    "runtime-audit": runtime_audit_pass,
    "regression-audit": regression_audit_pass,
    "reaction-audit": reaction_audit_pass,
    "serving-audit": serving_audit_pass,
    "postmortem-audit": postmortem_audit_pass,
    "fleet-audit": fleet_audit_pass,
}

STATIC_PASSES = ("sharding", "hierarchy", "hbm-static")
TRACE_PASSES = ("collectives", "donation", "hbm-traced")
# passes over the LOWERED StableHLO module (the realized collective
# schedule + the realized compute table); opt-in via
# verify_strategy(passes=...), the CLI's --hlo/--compute, the AOT verify
# gate, and AutoStrategy's top-candidate audit
LOWERED_PASSES = ("hlo-audit", "compute-audit")
# the LOCKSTEP tier: per-rank rendezvous-trace expansion of the traced
# jaxpr + lowered module + schedule-IR bucket programs, proving the
# emitted schedule deadlock-free; opt-in via verify_strategy(passes=...),
# the CLI's --lockstep, the runner/AOT verify gates, and the
# schedule_search / AutoStrategy candidate gate
LOCKSTEP_PASSES = ("lockstep-audit",)
# the DETERMINISM tier: PRNG key-lineage + shard-coverage + lowered
# order-hazard analysis exporting the strategy's determinism class;
# opt-in via verify_strategy(passes=...), the CLI's --determinism, the
# runner/AOT verify gates, the elastic reshard gate, and AutoStrategy's
# candidate audit
DETERMINISM_PASSES = ("determinism-audit",)
# passes over a MEASURED jax.profiler capture + aggregated manifests;
# opt-in via verify_strategy(passes=..., trace_dir=...), the CLI's
# --runtime, and the watchdog's post-capture auto-analysis
RUNTIME_PASSES = ("runtime-audit",)
# the CROSS-RUN tier: diff whatever the earlier tiers produced (plus
# caller-supplied current_metrics) against the blessed baseline; opt-in
# via verify_strategy(passes=..., baseline=...), the CLI's --regression,
# and tools/perf_gate.py
REGRESSION_PASSES = ("regression-audit",)
# the CONTROL-PLANE tier: judge the causal cluster event log (live
# signals vs control actions + measured MTTR); opt-in via
# verify_strategy(passes=..., event_records=...), the CLI's --events,
# ElasticTrainer's end-of-fit export, and tools/monitor_check.py
EVENT_PASSES = ("reaction-audit",)
# the SERVING tier: judge the decode service's serving telemetry (+ the
# decode step's realized collectives) against the serving budgets;
# opt-in via verify_strategy(passes=..., serving_metrics=...), the CLI's
# --serving, and tools/serve_check.py
SERVING_PASSES = ("serving-audit",)
# the POSTMORTEM tier: root-cause the assembled black-box bundle of a
# dead run; opt-in via verify_strategy(passes=..., postmortem_bundle=...),
# the CLI's --postmortem, ElasticTrainer's dump-triggered audit, and
# tools/postmortem_check.py
POSTMORTEM_PASSES = ("postmortem-audit",)
# the SCALE tier: judge a fleet-simulator run's scale report (chief
# self-metrics, drop ledger, scripted-fault detection latency); opt-in
# via verify_strategy(passes=..., fleet_scale=...), the CLI's --fleet,
# and tools/fleet_check.py
FLEET_PASSES = ("fleet-audit",)
