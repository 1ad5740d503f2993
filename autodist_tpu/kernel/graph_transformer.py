"""GraphTransformer: realize a compiled Strategy as one SPMD train step.

The reference's ``GraphTransformer`` (``kernel/graph_transformer.py:28-193``)
rewrites a TF graph in four passes (partition, replicate, in-graph sync,
between-graph sync).  The TPU equivalent builds, at trace time, a single
``shard_map``-ped step function over the device mesh:

1.  *Partitioning* = storage representation per variable
    (:mod:`autodist_tpu.kernel.partitioner`).
2.  *Replication* = the mesh's replica axis: every device traces the same
    program on its batch shard (SPMD), so there is no graph copying.
3.  *In-graph + between-graph synchronization* collapse into explicit XLA
    collectives: bucketed (compressed) pmean for AllReduce variables,
    reduce-scatter -> shard-local optimizer update -> all-gather for PS
    variables (weight-update sharding), periodic parameter averaging for
    stale-sync variables, and sparse all-gather in the embedding backward.

The returned step is jitted once; XLA fuses and overlaps the collectives
(the ScopedAllocator/grouping analog is the bucketing in
:mod:`..synchronization.all_reduce` plus XLA collective combining).
"""
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from autodist_tpu.kernel import partitioner as part
from autodist_tpu.kernel.partitioner import Placement, SyncKind
from autodist_tpu.kernel.synchronization import all_reduce as ar_sync
from autodist_tpu.model_item import path_name
from autodist_tpu.ops.sparse import replica_axis_context
from autodist_tpu.utils import logging
from autodist_tpu.utils.rng import host_key


class _SpecBox:
    """Opaque holder so PartitionSpecs (plus the expected update-space
    shape) survive tree_map as leaves."""

    __slots__ = ("spec", "expected_shape")

    def __init__(self, spec, expected_shape=None):
        self.spec = spec
        self.expected_shape = expected_shape


class GraphTransformer:
    """Builds ``init_state`` and the jitted distributed ``train_step``."""

    def __init__(self, strategy, model_item, mesh, data_axes=None,
                 batch_spec=None, accum_steps=1, clip_global_norm=None,
                 param_specs=None, sync_schedule=None):
        """`data_axes`: mesh axes forming the data-parallel device set
        (default: ALL mesh axes — a pure-DP 1-D mesh, or replica x seq for
        sequence parallelism where gradients still synchronize over every
        device).  `batch_spec`: PartitionSpec prefix for batches; default
        shards dim 0 over the first data axis (and, when a "seq" axis
        exists, callers shard dim 1 over it via an explicit spec).
        `sync_schedule`: "overlap"|"barrier" override of the strategy's
        AllReduceSynchronizer.schedule (None = follow the strategy).
        """
        self.strategy = strategy
        self.model_item = model_item
        self.mesh = mesh
        self.accum_steps = int(accum_steps)
        self.clip_global_norm = clip_global_norm
        axes = tuple(data_axes) if data_axes else tuple(mesh.axis_names)
        # self.axis: the axis (name or tuple) every gradient collective uses
        self.axis = axes if len(axes) > 1 else axes[0]
        self.data_axes = axes
        self.num_replicas = int(np.prod([mesh.shape[a] for a in axes]))
        from autodist_tpu.const import AXIS_SEQUENCE

        has_seq = AXIS_SEQUENCE in mesh.axis_names and len(axes) > 1
        if batch_spec is None:
            if has_seq:
                first = tuple(a for a in axes if a != AXIS_SEQUENCE)
                batch_spec = P(first if len(first) > 1 else first[0], AXIS_SEQUENCE)
            else:
                # pure data parallelism shards dim 0 over EVERY data axis
                # (a factored replica_dcn x replica_ici mesh still gives
                # each device a distinct batch shard)
                batch_spec = P(axes if len(axes) > 1 else axes[0])
        self.batch_spec = batch_spec
        # sequence parallelism is active only when the batch's sequence dim
        # (dim >= 1) is actually sharded over the seq axis — a mesh merely
        # CONTAINING an axis named "seq" (or using it for dim-0 data
        # parallelism) must not trigger ring attention / position offsets
        self.seq_axis = None
        for entry in tuple(batch_spec)[1:]:
            names = entry if isinstance(entry, tuple) else (entry,)
            if AXIS_SEQUENCE in names:
                self.seq_axis = AXIS_SEQUENCE
                break

        leaves = jax.tree_util.tree_leaves_with_path(model_item.params)
        self.names = [path_name(p) for p, _ in leaves]
        self.treedef = jax.tree_util.tree_structure(model_item.params)

        self.plans: Dict[str, part.VarPlan] = part.build_var_plans(
            strategy, model_item, self.num_replicas, param_specs=param_specs
        )
        for name in self.names:
            if name not in self.plans:
                raise ValueError(f"No plan for variable {name}")
        # -- sync hierarchy (AllReduceSynchronizer.Hierarchy) --------------
        # A mesh factored into replica_dcn x replica_ici data sub-axes
        # enables the two-level schedule: ICI reduce-scatter -> DCN shard
        # ring -> ICI all-gather.  The cross-slice hop spans every data
        # axis except the ICI sub-axis (so e.g. a seq axis still reduces).
        from autodist_tpu.const import AXIS_REPLICA_DCN, AXIS_REPLICA_ICI

        self.hier_spec = None
        if AXIS_REPLICA_DCN in axes and AXIS_REPLICA_ICI in axes:
            self.hier_spec = ar_sync.HierAxes(
                ici=AXIS_REPLICA_ICI,
                dcn=tuple(a for a in axes if a != AXIS_REPLICA_ICI))
        _AR = ar_sync._AR
        from autodist_tpu.kernel.synchronization import schedule_ir as sir

        for name in self.names:
            plan = self.plans[name]
            if (plan.sync != SyncKind.ALL_REDUCE
                    or plan.placement != Placement.REPLICATED or plan.sparse):
                continue
            ir = getattr(plan, "schedule_ir", "")
            if ir:
                # searched collective schedule: validate against the mesh
                # (the analysis hierarchy pass mirrors these checks as
                # Y010/Y011), then normalize programs canonical to
                # FLAT/TWO_LEVEL back to the legacy knobs so sharded-
                # update composition and the per-hop channel accounting
                # take the battle-tested paths
                try:
                    prog = sir.loads(ir)
                    sir.validate(prog, data_axes=self.data_axes,
                                 axis_sizes=mesh.shape)
                except ValueError as e:
                    raise ValueError(
                        f"{name!r}: invalid schedule_ir: {e}") from None
                kind = sir.canonical_hierarchy(prog)
                core = sir.core_codec(prog)
                if kind == _AR.FLAT:
                    plan.schedule_ir = ""
                    plan.hierarchy = _AR.FLAT
                    plan.compressor = core
                    plan.dcn_compressor = 0
                elif (kind == _AR.TWO_LEVEL and self.hier_spec is not None
                      and prog.phases[0].axes == (self.hier_spec.ici,)
                      and set(prog.phases[1].axes) == set(self.hier_spec.dcn)
                      and (core or not plan.compressor)):
                    plan.schedule_ir = ""
                    plan.hierarchy = _AR.TWO_LEVEL
                    plan.dcn_compressor = core
                else:
                    # genuinely synthesized: the IR supersedes the
                    # hierarchy knobs end to end; pin FLAT so no
                    # two-level branch double-dips on these buckets
                    plan.hierarchy = _AR.FLAT
                    plan.dcn_compressor = 0
                    continue
            h = plan.hierarchy
            if h == _AR.TWO_LEVEL and self.hier_spec is None:
                raise ValueError(
                    f"{name!r}: hierarchy=TWO_LEVEL needs a mesh factored "
                    f"into '{AXIS_REPLICA_DCN}' x '{AXIS_REPLICA_ICI}' data "
                    f"sub-axes (YAML `mesh:` request or "
                    f"build_mesh(hierarchy=True)); mesh axes are "
                    f"{mesh.axis_names}")
            if h == _AR.AUTO_HIERARCHY:
                h = (_AR.TWO_LEVEL if self.hier_spec is not None
                     and mesh.shape[AXIS_REPLICA_DCN] > 1 else _AR.FLAT)
            if h == _AR.TWO_LEVEL:
                if plan.dcn_compressor not in (0, *ar_sync.DCN_SAFE_CODECS):
                    raise ValueError(
                        f"{name!r}: dcn_compressor {plan.dcn_compressor} is "
                        f"not DCN-hop safe; the cross-slice hop accepts "
                        f"only elementwise codecs (none/bf16/bf16-EF) and "
                        f"int8 — block codecs like PowerSGD do not "
                        f"decompose into a shard hop")
                if plan.compressor == _AR.PowerSGDCompressor:
                    # PowerSGD's factor exchange never decomposes; realize
                    # flat (the analysis hierarchy pass warns about this)
                    h = _AR.FLAT
            plan.hierarchy = h
        # -- ZeRO-style sharded weight update (ShardedUpdate.SHARDED) ------
        # Normalize eligibility AFTER hierarchy resolution: only dense,
        # non-scalar, replicated AR plans whose every wire transform is
        # elementwise realize the reduce-scatter -> shard update ->
        # param all-gather schedule; the rest (block codecs, sparse,
        # scalars) fall back to the replicated update (Y007 warns).
        for name in self.names:
            plan = self.plans[name]
            if not plan.sharded_update:
                continue
            if not part.plan_sharded_update(plan):
                if (plan.sync == SyncKind.ALL_REDUCE
                        and plan.placement == Placement.REPLICATED
                        and not plan.sparse and plan.shape):
                    logging.debug(
                        "Variable %s: sharded_update requested but the "
                        "wire codec is not elementwise; realizing the "
                        "replicated update", name)
                plan.sharded_update = 0
        # -- bf16-compute / f32-master mixed precision (Precision) ---------
        # The f32 master IS the flat 1/R sharded-update shard (storage ==
        # update space); the full-shape param exists only as a transient
        # bf16 compute copy gathered per bucket at the top of the step.
        # Eligibility therefore piggybacks on the sharded update: f32
        # dtype + a realized sharded update; everything else (non-f32
        # dtypes, block codecs, sparse, synthesized IR) keeps full F32.
        for name in self.names:
            plan = self.plans[name]
            if not getattr(plan, "precision", 0):
                continue
            if not part.master_shard_storage(plan):
                logging.debug(
                    "Variable %s: precision=BF16_COMPUTE_F32_MASTER "
                    "requested but the plan is not eligible (needs f32 "
                    "dtype and a realized sharded update); keeping F32",
                    name)
                plan.precision = 0
        shapes = {v.name: v.shape for v in model_item.var_infos}
        dtypes = {v.name: v.dtype for v in model_item.var_infos}
        self.buckets = ar_sync.plan_buckets(self.plans, shapes, dtypes,
                                            num_replicas=self.num_replicas)
        self.sharded_buckets = [b for b in self.buckets
                                if ar_sync.bucket_sharded(b)]
        # var name -> (bucket, flat shard length) for the update-space
        # param slice in the SPMD step
        self._shard_of = {
            n: (b, ss) for b in self.sharded_buckets
            for n, ss in zip(b.var_names, b.shard_sizes)}
        # bf16-master buckets: storage is the flat f32 master shard; the
        # compute copy gathers in bf16 at the top of the step and the
        # grads upcast to f32 right after value_and_grad
        self.precision_buckets = [b for b in self.sharded_buckets
                                  if b.precision]
        self._prec_names = frozenset(
            n for b in self.precision_buckets for n in b.var_names)
        # collective issue schedule: "overlap" = per-bucket reverse-
        # topological collectives under XLA's latency-hiding scheduler
        # (kernel/synchronization/all_reduce.sync_overlapped); "barrier" =
        # one bucketed sync point after the full backward pass
        if sync_schedule is None:
            sync_schedule = ar_sync.schedule_mode(self.plans)
        if sync_schedule not in ("overlap", "barrier"):
            raise ValueError(
                f"sync_schedule must be 'overlap' or 'barrier', got "
                f"{sync_schedule!r}")
        self.sync_schedule = sync_schedule
        # CUSTOM (tensor-parallel) vars: specs must only name NON-data mesh
        # axes (a data axis in a custom spec would make the data-axes pmean
        # average distinct blocks); fuse their grad pmeans per (spec, dtype)
        self.custom_groups = {}
        for name in self.names:
            plan = self.plans[name]
            if plan.placement is not Placement.CUSTOM:
                continue
            spec_axes = set()
            for entry in tuple(plan.custom_spec):
                if entry is None:
                    continue
                spec_axes.update(entry if isinstance(entry, tuple) else (entry,))
            bad = spec_axes & set(self.data_axes)
            if bad:
                raise ValueError(
                    f"param_specs for {name!r} names data axes {sorted(bad)}; "
                    f"custom specs may only use non-data (model) mesh axes — "
                    f"pass data_axes=... excluding them")
            unknown = spec_axes - set(mesh.axis_names)
            if unknown:
                raise ValueError(
                    f"param_specs for {name!r} names unknown mesh axes "
                    f"{sorted(unknown)}; mesh has {mesh.axis_names}")
            key = (str(plan.custom_spec), str(np.dtype(plan.dtype)))
            self.custom_groups.setdefault(key, ([], frozenset(spec_axes)))
            self.custom_groups[key][0].append(name)

        # PS mesh-axis subsets: a plan's "mesh:<axes>" reduction destination
        # confines its scatter/gather to those axes (ICI-only on a
        # dcn x ici mesh); remaining data axes see only the scattered
        # shards via psum.  Validate against the mesh/data axes up front.
        for name in self.names:
            plan = self.plans[name]
            if plan.sync != part.SyncKind.PS or not plan.ps_axes:
                continue
            bad = set(plan.ps_axes) - set(self.data_axes)
            if bad:
                raise ValueError(
                    f"{name!r}: ps_axes {sorted(bad)} are not data axes "
                    f"{self.data_axes} of the mesh {mesh.axis_names}")
            if tuple(plan.ps_axes) == tuple(self.data_axes):
                plan.ps_axes = None  # full set == default realization

        # fused-PS groups (static): (dtype, ps_axes) -> ordered names of
        # dense replicated PS vars whose reduce-scatter/all-gather merge
        self.ps_groups = {}
        for name in self.names:
            plan = self.plans[name]
            if (plan.sync == part.SyncKind.PS
                    and plan.placement == Placement.REPLICATED
                    and not plan.sparse):
                key = (str(np.dtype(plan.dtype)), plan.ps_axes or ())
                self.ps_groups.setdefault(key, []).append(name)
        logging.info(
            "Transform plan: %d vars, %d AR buckets (%s schedule, %s "
            "hierarchy, %d sharded-update), placements=%s",
            len(self.names), len(self.buckets), self.sync_schedule,
            self.sync_hierarchy, len(self.sharded_buckets),
            {p.value: sum(1 for q in self.plans.values() if q.placement is p)
             for p in Placement},
        )

    @property
    def sync_hierarchy(self):
        """``"searched"`` when any AR bucket runs a synthesized schedule
        IR, ``"two_level"`` when any uses the hierarchical schedule, else
        ``"flat"``."""
        if any(b.schedule_ir for b in self.buckets):
            return "searched"
        return ("two_level" if any(
            b.hierarchy == ar_sync._AR.TWO_LEVEL for b in self.buckets)
            else "flat")

    @property
    def sync_sharded_update(self):
        """``True`` when any AR bucket realizes the ZeRO-style sharded
        weight update (reduce-scatter -> shard update -> param gather)."""
        return bool(self.sharded_buckets)

    @property
    def sync_mixed_precision(self):
        """``True`` when any AR bucket runs bf16-compute / f32-master
        mixed precision (the F003 lever)."""
        return bool(self.precision_buckets)

    def sharded_update_summary(self):
        """Static accounting of the sharded weight update — what telemetry
        records (``sync.sharded_update``) and reports render next to the
        HBM numbers (docs/performance.md "Sharded weight update").

        ``shard_bytes`` is the per-chip update-space volume (the 1/R the
        optimizer touches instead of the full parameter set);
        ``padding_bytes`` is the per-chip cost of the per-var padding
        plan; ``param_gather_bytes`` the fresh-param all-gather volume
        that replaces the gradient all-gather."""
        import numpy as _np

        out = {"enabled": self.sync_sharded_update,
               "buckets": len(self.sharded_buckets),
               "vars": sum(len(b.var_names) for b in self.sharded_buckets),
               "num_shards": (self.sharded_buckets[0].num_shards
                              if self.sharded_buckets else 1),
               "shard_bytes": 0.0, "padding_bytes": 0.0,
               "param_gather_bytes": 0.0,
               "bf16_master_buckets": len(self.precision_buckets),
               "bf16_master_vars": sum(len(b.var_names)
                                       for b in self.precision_buckets)}
        for b in self.sharded_buckets:
            item = _np.dtype(b.dtype).itemsize
            out["shard_bytes"] += b.shard_total * item
            out["padding_bytes"] += \
                (b.padded_total - b.total) * item / b.num_shards
            # bf16-master buckets gather the COMPUTE copy at bf16 — half
            # the fresh-param wire of the f32 gather
            out["param_gather_bytes"] += \
                b.padded_total * item * (0.5 if b.precision else 1.0)
        return out

    def hierarchy_summary(self):
        """Static per-hop wire accounting of the chosen hierarchy — what
        telemetry records so reports can show predicted-vs-measured
        per-hop comm time (docs/performance.md "Hierarchical sync").

        ``ici_hop_bytes`` counts BOTH intra-slice phases (reduce-scatter +
        all-gather of the full bucket volume); ``dcn_hop_bytes`` is the
        ring volume of the cross-slice hop: the 1/R_ici shard, scaled by
        the DCN codec's wire factor.  FLAT buckets bill their whole codec
        volume to ``flat_bytes`` (one collective at min(ICI, DCN) speed).
        """
        import numpy as _np

        from autodist_tpu.kernel.synchronization.compressor import (
            get_compressor, wire_byte_factor)

        _AR = ar_sync._AR
        R_ici = (self.mesh.shape[self.hier_spec.ici]
                 if self.hier_spec is not None else 1)
        out = {"mode": self.sync_hierarchy,
               "replica_dcn": (self.num_replicas // R_ici
                               if self.hier_spec is not None else 1),
               "replica_ici": R_ici,
               "ici_hop_bytes": 0.0, "dcn_hop_bytes": 0.0,
               "flat_bytes": 0.0, "dcn_compressors": []}
        out["sharded_update"] = self.sync_sharded_update
        from autodist_tpu.kernel.synchronization import schedule_ir as sir

        for b in self.buckets:
            item = _np.dtype(b.dtype).itemsize
            nbytes = b.total * item
            sharded = ar_sync.bucket_sharded(b)
            # sharded-update buckets move the padded matrix: grad scatter
            # (codec-scaled) + FRESH-PARAM gather (native dtype) replace
            # the gradient allreduce's two ring phases
            pbytes = b.padded_total * item if sharded else nbytes
            if b.schedule_ir:
                # synthesized schedule: bill each phase's wire volume to
                # its bandwidth class (any DCN-class axis -> dcn hop)
                prog = sir.loads(b.schedule_ir)
                elems = b.total
                for ph in prog.phases:
                    g = sir.phase_group_size(ph, self.mesh.shape)
                    wf_ph = wire_byte_factor(ph.codec, b.total)
                    tgt = "dcn_hop_bytes" if ph.dcn else "ici_hop_bytes"
                    if ph.op == "reduce_scatter":
                        out[tgt] += (-(-elems // g) * g) * item * wf_ph
                        elems = -(-elems // g)
                    elif ph.op == "all_gather":
                        out[tgt] += elems * g * item * wf_ph
                        elems = elems * g
                    elif ph.op == "ppermute_ring":
                        out[tgt] += 2.0 * (g - 1) * (-(-elems // g)) \
                            * item * wf_ph
                    else:  # all_reduce core
                        out[tgt] += elems * item * wf_ph
                    if ph.dcn and ph.codec:
                        name = get_compressor(ph.codec).name
                        if name not in out["dcn_compressors"]:
                            out["dcn_compressors"].append(name)
                continue
            # the fresh-param gather leg of a sharded bucket is native
            # dtype — except bf16-master buckets, whose compute copy
            # gathers at bf16 (half the f32 wire)
            pg = 0.5 if getattr(b, "precision", 0) else 1.0
            if b.hierarchy == _AR.TWO_LEVEL:
                d = ar_sync.dcn_codec(b)
                dcn_f = wire_byte_factor(d, b.total)
                out["ici_hop_bytes"] += \
                    (1.0 + pg) * pbytes if sharded else 2.0 * pbytes
                out["dcn_hop_bytes"] += \
                    pbytes * ((dcn_f + pg) if sharded else dcn_f) \
                    / max(1, R_ici)
                name = get_compressor(d).name if d else "none"
                if name not in out["dcn_compressors"]:
                    out["dcn_compressors"].append(name)
            elif sharded:
                wf = wire_byte_factor(ar_sync.wire_codec(b), b.total)
                out["flat_bytes"] += pbytes * (wf + pg) / 2.0
            else:
                out["flat_bytes"] += \
                    nbytes * wire_byte_factor(b.compressor, b.total)
        return out

    def intended_collectives(self):
        """The strategy's communication sketch: every collective this
        transformer's step is EXPECTED to emit, as channel descriptors the
        HLO audit (:mod:`autodist_tpu.analysis.hlo_audit`) diffs the
        lowered module's realized schedule against.

        Each entry: ``{label, kinds, bytes, phase, group_sizes, in_scan,
        required}`` — ``bytes`` is per-STEP wire volume under the audit's
        accounting convention (all_reduce/reduce_scatter/all_to_all bill
        operands, all_gather bills results), already multiplied by the
        accum factor for channels the overlap schedule issues inside the
        scan; ``group_sizes`` are the replica-group sizes the collective
        may legitimately use (empty = any); ``required=False`` marks
        channels that only materialize when the user's loss exercises
        them (sparse lookups, mutable-state averaging).
        """
        from autodist_tpu.kernel.synchronization.compressor import (
            Int8Compressor, PowerSGDCompressor, wire_byte_factor)

        _AR = ar_sync._AR
        out = []
        R = self.num_replicas
        A = self.accum_steps
        R_ici = (self.mesh.shape[self.hier_spec.ici]
                 if self.hier_spec is not None else 1)
        R_dcn = (int(np.prod([self.mesh.shape[a]
                              for a in self.hier_spec.dcn]))
                 if self.hier_spec is not None else 1)

        def add(label, kinds, nbytes, phase, groups=(), in_scan=False,
                required=True):
            out.append({"label": label, "kinds": tuple(kinds),
                        "bytes": float(nbytes), "phase": phase,
                        "group_sizes": tuple(groups), "in_scan": in_scan,
                        "required": required})

        def int8_bytes(elems, n_dev):
            # per-device chunk padded to the quantization block; int8
            # payload + f32 scale sidecar, exchanged in BOTH phases
            # (all_to_all then all_gather) — see Int8Compressor
            B = Int8Compressor.BLOCK
            chunk = -(-(-(-elems // n_dev)) // B) * B
            per_phase = n_dev * chunk * (1 + 4.0 / B)
            return 2.0 * per_phase

        for b in self.buckets:
            item = np.dtype(b.dtype).itemsize
            nbytes = b.total * item
            in_scan = (self.sync_schedule == "overlap" and A > 1
                       and ar_sync.elementwise(b))
            mult = A if in_scan else 1
            if ar_sync.bucket_sharded(b):
                # ZeRO sharded update: grad reduce-scatter (codec-scaled,
                # in-scan under overlapped accumulation) + ONE fresh-param
                # all-gather per step (native dtype, never in the scan) —
                # there is no gradient all-gather at all
                pbytes = b.padded_total * item
                wf = wire_byte_factor(ar_sync.wire_codec(b), b.total)
                # bf16-master buckets gather the bf16 COMPUTE copy (at the
                # top of the step instead of post-update) — half the
                # fresh-param wire of the f32 gather, same channel shape
                pg = 0.5 if getattr(b, "precision", 0) else 1.0
                if b.hierarchy == _AR.TWO_LEVEL:
                    shard_b = pbytes / max(1, R_ici)
                    add(f"{b.key}/ici-scatter", ("reduce_scatter",),
                        pbytes * mult, "ici_hop", (R_ici,), in_scan)
                    add(f"{b.key}/dcn-scatter", ("reduce_scatter",),
                        shard_b * wf * mult, "dcn_hop", (R_dcn,), in_scan)
                    add(f"{b.key}/dcn-param-gather", ("all_gather",),
                        shard_b * pg, "dcn_hop", (R_dcn,))
                    add(f"{b.key}/ici-param-gather", ("all_gather",),
                        pbytes * pg, "ici_hop", (R_ici,))
                else:
                    add(f"{b.key}/shard-scatter", ("reduce_scatter",),
                        pbytes * wf * mult, "flat", (R,), in_scan)
                    add(f"{b.key}/param-gather", ("all_gather",),
                        pbytes * pg, "flat", (R,))
                continue
            if b.schedule_ir:
                # synthesized schedule: one channel per IR phase, volumes
                # tracked through the running shard size, wire bytes
                # scaled by each hop's codec — the X-audit pins whatever
                # the search emitted, phase for phase
                from autodist_tpu.kernel.synchronization import (
                    schedule_ir as sir)
                prog = sir.loads(b.schedule_ir)
                elems = b.total
                for i, ph in enumerate(prog.phases):
                    g = int(sir.phase_group_size(ph, self.mesh.shape))
                    phase = "dcn_hop" if ph.dcn else "ici_hop"
                    wf = wire_byte_factor(ph.codec, b.total)
                    if ph.op == "reduce_scatter":
                        padded = -(-elems // g) * g
                        add(f"{b.key}/p{i}-scatter", ("reduce_scatter",),
                            padded * item * wf * mult, phase, (g,), in_scan)
                        elems = -(-elems // g)
                    elif ph.op == "all_gather":
                        add(f"{b.key}/p{i}-gather", ("all_gather",),
                            elems * g * item * wf * mult, phase, (g,),
                            in_scan)
                        elems *= g
                    elif ph.op == "ppermute_ring":
                        piece = -(-elems // g)
                        add(f"{b.key}/p{i}-ring", ("collective_permute",),
                            2.0 * (g - 1) * piece * item * wf * mult,
                            phase, (), in_scan)
                    elif ph.codec in (_AR.Int8Compressor,
                                      _AR.Int8CompressorEF,
                                      _AR.EquarxInt8Compressor):
                        add(f"{b.key}/p{i}-int8",
                            ("all_to_all", "all_gather"),
                            int8_bytes(elems, g) * mult, phase, (g,),
                            in_scan)
                    else:
                        add(f"{b.key}/p{i}-reduce", ("all_reduce",),
                            elems * item * wf * mult, phase, (g,), in_scan)
                continue
            if b.hierarchy == _AR.TWO_LEVEL:
                shard = -(-b.total // R_ici)
                padded = shard * R_ici * item
                add(f"{b.key}/ici-scatter", ("reduce_scatter",),
                    padded * mult, "ici_hop", (R_ici,), in_scan)
                d = ar_sync.dcn_codec(b)
                if d in (_AR.Int8Compressor, _AR.Int8CompressorEF,
                         _AR.EquarxInt8Compressor):
                    add(f"{b.key}/dcn-int8", ("all_to_all", "all_gather"),
                        int8_bytes(shard, R_dcn) * mult, "dcn_hop",
                        (R_dcn,), in_scan)
                else:
                    add(f"{b.key}/dcn-reduce", ("all_reduce",),
                        shard * item * wire_byte_factor(d, b.total) * mult,
                        "dcn_hop", (R_dcn,), in_scan)
                add(f"{b.key}/ici-gather", ("all_gather",),
                    padded * mult, "ici_hop", (R_ici,), in_scan)
            elif b.compressor in (_AR.Int8Compressor, _AR.Int8CompressorEF,
                                  _AR.EquarxInt8Compressor):
                add(f"{b.key}/int8", ("all_to_all", "all_gather"),
                    int8_bytes(b.total, R), "flat", (R,))
            elif b.compressor == _AR.PowerSGDCompressor:
                # two separate factor psums per subspace iteration:
                # P (rows x r) and Q (cols x r), both f32
                rows, cols = PowerSGDCompressor._dims(b.total)
                r = PowerSGDCompressor._rank(b.total)
                add(f"{b.key}/powersgd-P", ("all_reduce",),
                    rows * r * 4.0, "flat", (R,))
                add(f"{b.key}/powersgd-Q", ("all_reduce",),
                    cols * r * 4.0, "flat", (R,))
            else:
                add(f"{b.key}", ("all_reduce",),
                    nbytes * wire_byte_factor(b.compressor, b.total) * mult,
                    "flat", (R,), in_scan)

        def _shard_len(plan):
            r = self._R_for(plan)
            n = int(np.prod(plan.shape)) if plan.shape else 1
            return (-(-n // r) * r) // r

        for (dtype, _axes_key), names in self.ps_groups.items():
            plan0 = self.plans[names[0]]
            r_ps = self._R_for(plan0)
            item = np.dtype(dtype).itemsize
            S = sum(_shard_len(self.plans[n]) for n in names)
            add(f"ps/{dtype}/scatter", ("reduce_scatter",),
                r_ps * S * item, "ps", (r_ps,))
            other = self._ps_other_axes(plan0)
            if other:
                r_other = int(np.prod([self.mesh.shape[a] for a in other]))
                add(f"ps/{dtype}/cross-psum", ("all_reduce",),
                    S * item, "ps", (r_other,))
            add(f"ps/{dtype}/gather", ("all_gather",),
                r_ps * S * item, "ps", (r_ps,))

        for name in self.names:
            plan = self.plans[name]
            item = np.dtype(plan.dtype).itemsize
            n = int(np.prod(plan.shape)) if plan.shape else 1
            if plan.placement == Placement.SHARDED:
                if plan.sparse and plan.partition_axis == 0:
                    # ShardedTable: lookups row-exchange only when the
                    # loss actually embeds (required=False)
                    add(f"{name}/table-lookup",
                        ("all_gather", "all_to_all", "all_reduce",
                         "collective_permute"),
                        n * item, "sparse", (), required=False)
                    continue
                dim = max(1, plan.shape[plan.partition_axis])
                padded = n * item * (plan.padded_dim / dim)
                add(f"{name}/materialize", ("all_gather",), padded,
                    "materialize", (R,))
                if not plan.sparse:
                    add(f"{name}/grad-scatter", ("reduce_scatter",),
                        padded, "materialize", (R,))
            elif plan.placement == Placement.DIVERGENT:
                # periodic averaging: the pmean sits inside a lax.cond
                # branch but is always PRESENT in the lowered program
                add(f"{name}/stale-avg", ("all_reduce",), n * item,
                    "stale", (R,))
            elif plan.sparse:
                # replicated/PS sparse var: the lookup backward syncs it
                # only when the loss embeds through it
                add(f"{name}/sparse-sync",
                    ("all_gather", "all_to_all", "all_reduce",
                     "collective_permute"),
                    n * item * 2, "sparse", (), required=False)

        for (_spec, dtype), (names_c, _axes) in self.custom_groups.items():
            item = np.dtype(dtype).itemsize if isinstance(dtype, str) else 4
            total = sum(
                int(np.prod(self.plans[n].shape)) if self.plans[n].shape
                else 1 for n in names_c)
            add(f"custom/{dtype}", ("all_reduce",), total * item,
                "custom", (R,))

        if self.model_item.mutable_state is not None:
            leaves = jax.tree.leaves(self.model_item.mutable_state)
            total = sum(
                l.size * np.dtype(l.dtype).itemsize for l in leaves
                if hasattr(l, "dtype")
                and np.issubdtype(np.dtype(l.dtype), np.floating))
            if total:
                add("mutable-state/pmean", ("all_reduce",), total,
                    "mutable", (R,), required=False)
        return out

    def plan_summary(self):
        """Human-readable transform plan — dump stage 0 of the 4-stage
        program-evolution artifacts (reference logs its graph after each
        transform pass, ``kernel/graph_transformer.py:62-90``)."""
        lines = [f"mesh: {dict(self.mesh.shape)}  data_axes: {self.data_axes}"
                 f"  batch_spec: {self.batch_spec}",
                 f"accum_steps: {self.accum_steps}  "
                 f"clip_global_norm: {self.clip_global_norm}",
                 f"AR buckets: {len(self.buckets)}  "
                 f"fused PS groups: {len(self.ps_groups)}  "
                 f"custom groups: {len(self.custom_groups)}  "
                 f"sync_schedule: {self.sync_schedule}  "
                 f"sync_hierarchy: {self.sync_hierarchy}  "
                 f"sharded_update_buckets: {len(self.sharded_buckets)}", ""]
        for name in self.names:
            p = self.plans[name]
            extra = ""
            if p.placement == Placement.SHARDED:
                extra = f" axis={p.partition_axis} padded={p.padded_dim}"
            if p.sync == part.SyncKind.PS and p.ps_axes:
                extra += f" ps_axes={p.ps_axes}"
            if p.staleness:
                extra += f" staleness={p.staleness}"
            if name in self._shard_of:
                extra += f" sharded_update(ss={self._shard_of[name][1]})"
            if name in self._prec_names:
                extra += " precision=bf16_master"
            lines.append(f"{name}: shape={tuple(p.shape)} "
                         f"{p.placement.value}/{p.sync.value}"
                         f"{' sparse' if p.sparse else ''}{extra}")
        return "\n".join(lines) + "\n"

    # -- per-plan PS axis helpers -----------------------------------------

    def _ps_axis(self, plan):
        """Axis name (or tuple) the plan's PS scatter/gather runs over."""
        if plan.ps_axes:
            axes = tuple(a for a in self.data_axes if a in plan.ps_axes)
            return axes if len(axes) > 1 else axes[0]
        return self.axis

    def _ps_other_axes(self, plan):
        """Data axes OUTSIDE the plan's PS subset (the shard-psum axes)."""
        if not plan.ps_axes:
            return ()
        return tuple(a for a in self.data_axes if a not in plan.ps_axes)

    def _R_for(self, plan):
        """Device count the plan's (flat-shard) PS update space shards
        over; every other placement shards over the full data axes."""
        if (plan.sync == part.SyncKind.PS and plan.ps_axes
                and plan.placement == Placement.REPLICATED):
            return int(np.prod([self.mesh.shape[a] for a in plan.ps_axes]))
        return self.num_replicas

    # -- spec trees --------------------------------------------------------

    def _params_spec_leaves(self, space):
        if space == "storage":
            def s_axis_for(plan):
                # bf16-master storage IS the flat shard — under the fused
                # TWO_LEVEL schedule its rows are ici-major, same as the
                # update space below
                if (plan.name in self._shard_of
                        and part.master_shard_storage(plan)
                        and plan.hierarchy == ar_sync._AR.TWO_LEVEL
                        and self.hier_spec is not None):
                    return (self.hier_spec.ici,) + tuple(self.hier_spec.dcn)
                return self.axis

            return [part.storage_spec(self.plans[n],
                                      s_axis_for(self.plans[n]))
                    for n in self.names]
        def axis_for(plan):
            # only the flat-shard PS update space moves to the subset axis;
            # SHARDED/DIVERGENT storage stays on the full data axes
            if (plan.sync == part.SyncKind.PS
                    and plan.placement == Placement.REPLICATED):
                return self._ps_axis(plan)
            # fused TWO_LEVEL sharded update: the scatter runs ICI first,
            # so the flat shard's global layout is ici-major — spec the
            # update space over (ici, *dcn) to match scatter_bucket's row
            # assignment (a P(self.axis) spec would permute the shards)
            if (plan.name in self._shard_of
                    and plan.hierarchy == ar_sync._AR.TWO_LEVEL
                    and self.hier_spec is not None):
                return (self.hier_spec.ici,) + tuple(self.hier_spec.dcn)
            return self.axis

        return [part.update_space_spec(self.plans[n], axis_for(self.plans[n]))
                for n in self.names]

    def params_spec_tree(self, space="storage"):
        return self.treedef.unflatten(self._params_spec_leaves(space))

    def _opt_spec_tree(self, opt_state_shapes):
        specs = self._params_spec_leaves("update")
        shapes = [part.update_space_shape(self.plans[n],
                                          self._R_for(self.plans[n]))
                  for n in self.names]
        boxed = self.treedef.unflatten(
            [_SpecBox(s, shp) for s, shp in zip(specs, shapes)]
        )
        boxed_state = optax.tree_map_params(
            self.model_item.optimizer,
            lambda _leaf, box: box,
            opt_state_shapes,
            boxed,
            transform_non_params=lambda _leaf: _SpecBox(P(), None),
            is_leaf=lambda x: isinstance(x, _SpecBox),
        )

        # some optimizers keep REDUCED state at param positions (novograd's
        # per-param scalar norm, adafactor's factored rows/cols): only a
        # leaf matching the update-space shape takes the sharded spec;
        # reduced leaves stay replicated
        def fit(shape_leaf, box):
            if (box.expected_shape is not None
                    and tuple(shape_leaf.shape) == tuple(box.expected_shape)):
                return box.spec
            return P()

        return jax.tree.map(fit, opt_state_shapes, boxed_state)

    def _comp_spec(self):
        return {b.key: (P(self.axis) if get_stateful(b) else ())
                for b in self.buckets}

    # -- state init --------------------------------------------------------

    def _to_storage(self, leaf, plan):
        if part.master_shard_storage(plan):
            # bf16-master: storage IS the flat padded f32 master (the
            # update space) — the full-shape param only ever exists as a
            # transient bf16 compute copy inside the step
            r = self._R_for(plan)
            n = leaf.size
            npad = -(-n // r) * r
            return jnp.zeros((npad,), leaf.dtype).at[:n].set(leaf.ravel())
        if plan.placement in (Placement.REPLICATED, Placement.CUSTOM):
            return leaf
        if plan.placement == Placement.SHARDED:
            pad = plan.padded_dim - leaf.shape[plan.partition_axis]
            if pad:
                widths = [(0, 0)] * leaf.ndim
                widths[plan.partition_axis] = (0, pad)
                leaf = jnp.pad(leaf, widths)
            return leaf
        if plan.placement == Placement.DIVERGENT:
            return jnp.broadcast_to(leaf[None],
                                    (self.num_replicas,) + leaf.shape)
        raise ValueError(plan.placement)

    def _to_update_space(self, leaf, plan):
        if plan.placement in (Placement.SHARDED, Placement.DIVERGENT):
            return self._to_storage(leaf, plan)
        if part.flat_shard_update(plan):
            r = self._R_for(plan)
            n = leaf.size
            npad = -(-n // r) * r
            return jnp.zeros((npad,), leaf.dtype).at[:n].set(leaf.ravel())
        return leaf

    def _plans_tree(self):
        return self.treedef.unflatten([self.plans[n] for n in self.names])

    def abstract_state(self, rng=None):
        """Abstract (ShapeDtypeStruct + NamedSharding) pytree matching
        :meth:`init_state`'s output, built WITHOUT touching any device —
        the AOT entry: trace ``make_train_step()`` with this over a
        deviceless PJRT topology and the full engine program compiles
        through the real TPU toolchain before a single chip is attached
        (tools/mosaic_aot_check.py; the deploy-before-the-pod-is-up
        workflow)."""
        params = self.model_item.params
        opt = self.model_item.optimizer
        if opt is None:
            raise ValueError("ModelItem has no optimizer")
        plans_tree = self._plans_tree()
        storage_shapes = jax.eval_shape(
            lambda p: jax.tree.map(self._to_storage, p, plans_tree), params)
        update0_shapes = jax.eval_shape(
            lambda p: jax.tree.map(self._to_update_space, p, plans_tree),
            params)
        opt_shapes = jax.eval_shape(opt.init, update0_shapes)
        # comp states: shapes from the host-side compressor init (cannot
        # eval_shape init_comp_states — it device_puts eagerly), stacked
        # along the replica axis like init_comp_states does
        csh = NamedSharding(self.mesh, P(self.axis))
        comp_avals = {
            key: jax.tree.map(
                lambda b: jax.ShapeDtypeStruct(
                    (self.num_replicas,) + b.shape, b.dtype, sharding=csh),
                base)
            for key, base in ar_sync.init_compressor_states(
                self.buckets).items()}
        rng_shapes = jax.eval_shape(
            lambda: rng if rng is not None else host_key(0))
        mut_shapes = (jax.eval_shape(lambda: self.model_item.mutable_state)
                      if self.model_item.mutable_state is not None else None)

        rep = NamedSharding(self.mesh, P())

        def shd(shapes, spec_tree):
            sharding = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), spec_tree,
                is_leaf=lambda x: isinstance(x, P))
            return jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                shapes, sharding)

        def replicated(shapes):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=rep), shapes)

        return {
            "params": shd(storage_shapes, self.params_spec_tree("storage")),
            "opt_state": shd(opt_shapes, self._opt_spec_tree(opt_shapes)),
            "comp": comp_avals,
            "mutable": replicated(mut_shapes) if mut_shapes is not None
            else None,
            "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            "rng": replicated(rng_shapes),
        }

    def batch_avals(self, batch_shapes):
        """``(shape, dtype)`` pytree -> abstract global batch with the
        engine's sharding (``batch_spec`` prefix per leaf rank), for
        deviceless tracing.  A bare ``(shape, dtype)`` tuple describes an
        array batch."""
        bspec = tuple(self.batch_spec)

        def to_aval(leaf):
            shp, dt = leaf
            spec = P(*bspec[:len(shp)])
            return jax.ShapeDtypeStruct(
                tuple(shp), dt, sharding=NamedSharding(self.mesh, spec))

        return jax.tree.map(
            to_aval, batch_shapes,
            is_leaf=lambda x: (isinstance(x, tuple) and len(x) == 2
                               and isinstance(x[0], (tuple, list))))

    def trace_step(self, batch_shapes, donate=True, rng=None,
                   state_avals=None):
        """Abstractly trace the train step: no devices touched, nothing
        compiled.  The shared AOT abstract-eval path — ``aot.py`` lowers
        the result for a TPU topology, the strategy verifier
        (:mod:`autodist_tpu.analysis`) walks its ``.jaxpr``, and both see
        the exact SPMD program ``make_train_step`` would run."""
        if state_avals is None:
            state_avals = self.abstract_state(rng=rng)
        step = self.make_train_step(donate=donate)
        return step.trace(state_avals, self.batch_avals(batch_shapes))

    def init_state(self, params=None, rng=None):
        """Build the global, correctly-sharded DistributedState dict."""
        params = self.model_item.params if params is None else params
        opt = self.model_item.optimizer
        if opt is None:
            raise ValueError("ModelItem has no optimizer")
        to_storage = self._to_storage
        to_update_space = self._to_update_space
        plans_tree = self._plans_tree()
        storage_sharding = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.params_spec_tree("storage"),
            is_leaf=lambda x: isinstance(x, P))

        make_storage = jax.jit(
            lambda p: jax.tree.map(to_storage, p, plans_tree),
            out_shardings=storage_sharding)
        storage = make_storage(params)

        update0 = jax.jit(
            lambda p: jax.tree.map(to_update_space, p, plans_tree))(params)
        opt_shapes = jax.eval_shape(opt.init, update0)
        opt_spec = self._opt_spec_tree(opt_shapes)
        opt_sharding = jax.tree.map(lambda s: NamedSharding(self.mesh, s), opt_spec,
                                    is_leaf=lambda x: isinstance(x, P))
        opt_state = jax.jit(opt.init, out_shardings=opt_sharding)(update0)

        comp = self.init_comp_states()

        rep = NamedSharding(self.mesh, P())

        def fresh(tree):
            # device_put aliases arrays that already live on-device with the
            # right sharding; the step donates its state, so an aliased
            # user-held array would be deleted out from under them.  A jit
            # copy never aliases its inputs (and handles typed PRNG keys).
            return jax.jit(lambda t: jax.tree.map(jnp.copy, t),
                           out_shardings=rep)(tree)

        state = {
            "params": storage,
            "opt_state": opt_state,
            "comp": comp,
            "mutable": (fresh(self.model_item.mutable_state)
                        if self.model_item.mutable_state is not None else None),
            "step": jax.device_put(jnp.zeros((), jnp.int32), rep),
            "rng": fresh(rng if rng is not None else host_key(0)),
        }
        return state

    # -- the SPMD step -----------------------------------------------------

    def _materialize(self, leaf, plan):
        """storage (local view) -> what the forward pass sees.  CUSTOM
        (tensor-parallel) vars stay LOCAL blocks — the loss fn handles them
        with parallel.tensor_parallel helpers.  Row-sharded SPARSE tables
        stay local too: the loss sees a ShardedTable and embedding_lookup
        row-exchanges, so no device ever holds the (vocab, dim) array."""
        if plan.placement in (Placement.REPLICATED, Placement.CUSTOM):
            return leaf
        if plan.placement == Placement.SHARDED:
            if plan.sparse and plan.partition_axis == 0:
                from autodist_tpu.ops.sparse import ShardedTable

                return ShardedTable(leaf, self.axis, full_shape=plan.shape)
            full = jax.lax.all_gather(leaf, self.axis, axis=plan.partition_axis,
                                      tiled=True)
            dim = plan.shape[plan.partition_axis]
            if full.shape[plan.partition_axis] != dim:
                full = jax.lax.slice_in_dim(full, 0, dim, axis=plan.partition_axis)
            return full
        if plan.placement == Placement.DIVERGENT:
            return leaf[0]
        raise ValueError(plan.placement)

    def _pad_axis(self, x, plan):
        pad = plan.padded_dim - x.shape[plan.partition_axis]
        if pad:
            widths = [(0, 0)] * x.ndim
            widths[plan.partition_axis] = (0, pad)
            x = jnp.pad(x, widths)
        return x

    def _spmd_step(self, storage, opt_state, comp, mutable, step, rng, batch):
        """One device's program of the step.  Each numbered stage sits in a
        ``jax.named_scope`` (``ad.materialize``, ``ad.grad``, ``ad.sync``,
        ``ad.clip``, ``ad.update``, ``ad.gather``): metadata only, the
        compiled program is the same, and a profile's device operations
        carry the scope in their ``op_name`` (PERF.md section 3)."""
        from autodist_tpu.parallel.collectives import axis_index

        axis = self.axis
        R = self.num_replicas
        my = axis_index(axis)
        plans = [self.plans[n] for n in self.names]

        # 1. materialize full params.  bf16-master buckets: storage is
        # the local flat f32 master shard; the full-shape COMPUTE copy is
        # all-gathered per bucket in bf16 — half the param-gather wire of
        # the f32 schedule, and the only full-shape copy that ever exists
        # (the F003 lever).  There is no post-update gather for these
        # buckets: 6b writes the fresh f32 shard straight back.
        s_leaves = self.treedef.flatten_up_to(storage)
        s_by_name = dict(zip(self.names, s_leaves))
        with jax.named_scope("ad.materialize"):
            bf16_full = {}
            for b_pr in self.precision_buckets:
                shards = {n: s_by_name[n].astype(jnp.bfloat16)
                          for n in b_pr.var_names}
                bf16_full.update(ar_sync.gather_bucket_params(
                    shards, b_pr, axis, self.hier_spec))
            full_leaves = [bf16_full[n] if n in bf16_full
                           else self._materialize(l, p)
                           for n, l, p in zip(self.names, s_leaves, plans)]
            full = self.treedef.unflatten(full_leaves)

        # 2. local gradients (sparse lookups sync inside their backward)
        item = self.model_item
        has_mutable = item.mutable_state is not None

        # uneven global batch (runner._pad_uneven): scale each device's loss
        # by s_local * R / S so that the plain pmean/psum-scatter downstream
        # — and the sparse backward's internal sync — all deliver the
        # reference's WEIGHTED average over real examples
        # (``cases/c0.py:88-121`` semantics); pad rows carry mask 0 and the
        # loss fn is responsible for excluding them from its local mean.
        from autodist_tpu.const import BATCH_MASK_KEY

        mask_present = isinstance(batch, dict) and BATCH_MASK_KEY in batch
        if mask_present:
            S_total = jax.lax.psum(
                jnp.sum(batch[BATCH_MASK_KEY].astype(jnp.float32)), axis)

        def loss_wrapper(p, mut, *rest):
            # normalized aux shape: (loss, (mutable_or_None, aux_dict))
            if has_mutable:
                out = item.loss_fn(p, mut, *rest)
                if item.has_aux:
                    loss_, (new_mut, aux_) = out
                else:
                    loss_, new_mut = out
                    aux_ = {}
            elif item.has_aux:
                loss_, aux_ = item.loss_fn(p, *rest)
                new_mut = None
            else:
                loss_ = item.loss_fn(p, *rest)
                new_mut, aux_ = None, {}
            if mask_present:
                m = rest[0][BATCH_MASK_KEY].astype(jnp.float32)
                w = (jnp.sum(m) * (self.num_replicas * self.accum_steps)
                     / jnp.maximum(S_total, 1.0))
                loss_ = loss_ * w
            return loss_, (new_mut, aux_)

        vag = jax.value_and_grad(loss_wrapper, has_aux=True)

        # bf16-master vars produce bf16 grads (the compute copy is bf16);
        # upcast to f32 immediately so accumulation, the wire reduce and
        # the optimizer all run at master precision — the ONLY bf16
        # stages are the forward/backward contractions and the wire legs
        # that were already bf16
        prec_names = self._prec_names

        def upcast_grads(g):
            if not prec_names:
                return g
            leaves = self.treedef.flatten_up_to(g)
            leaves = [l.astype(jnp.float32) if n in prec_names else l
                      for n, l in zip(self.names, leaves)]
            return self.treedef.unflatten(leaves)

        def run_vag(micro_batch, micro_idx, mut):
            args = (full, mut, micro_batch)
            if item.has_rng:
                step_rng = jax.random.fold_in(
                    jax.random.fold_in(jax.random.fold_in(rng, step), my),
                    micro_idx)
                args = args + (step_rng,)
            return vag(*args)

        from autodist_tpu.parallel.context import seq_axis_context

        A = self.accum_steps
        # compressor state arrives stacked per device; unwrap the local
        # copy here (rewrapped after sync)
        comp_local = {k: jax.tree.map(lambda a: a[0], v) for k, v in comp.items()}
        # overlap + accumulation: each microbatch's bucket collectives are
        # emitted INSIDE the scan, as soon as that iteration's grads are
        # final — XLA's latency-hiding scheduler hoists iteration i's
        # reduce behind iteration i+1's forward/backward compute.  The
        # mean-of-partial-means equals the barrier's mean-of-accumulated
        # gradients (collectives are linear), at A× wire volume — the
        # latency-for-bandwidth trade docs/performance.md documents.
        # Only ELEMENTWISE codecs qualify (none/bf16 ± error feedback);
        # block codecs applied to partial gradients (int8 re-blocking,
        # PowerSGD's low-rank fit) compute a different approximation, so
        # those buckets keep accumulating and sync once after the scan.
        scan_buckets = [b for b in self.buckets if ar_sync.elementwise(b)] \
            if (self.sync_schedule == "overlap" and A > 1) else []
        overlap_in_scan = bool(scan_buckets)
        post_buckets = [b for b in self.buckets if b not in scan_buckets]
        bucket_names = frozenset(
            n for b in scan_buckets for n in b.var_names)
        synced = comp_new_local = None
        with replica_axis_context(axis), seq_axis_context(self.seq_axis):
            with jax.named_scope("ad.grad"):
                if A <= 1:
                    (loss, (maybe_mut, aux)), grads = run_vag(batch, 0, mutable)
                    grads = upcast_grads(grads)
                    new_mutable = maybe_mut if has_mutable else None
                else:
                    # gradient accumulation: split the local batch into A
                    # microbatches, scan value_and_grad, average — one sync per
                    # step regardless of A (trades HBM for step latency).
                    # Mutable state (e.g. BN stats) threads THROUGH the scan so
                    # each microbatch updates the previous one's statistics.
                    def to_micro(x):
                        if x.shape[0] % A:
                            raise ValueError(
                                f"Per-device batch {x.shape[0]} must divide by "
                                f"accum_steps={A}")
                        return x.reshape((A, x.shape[0] // A) + x.shape[1:])

                    micro = jax.tree.map(to_micro, batch)

                    def scan_body(carry, mb_i):
                        mb, i = mb_i
                        acc_l, acc_g, mut_cur = carry
                        (l, (mut_next, aux_)), g = run_vag(mb, i, mut_cur)
                        g = upcast_grads(g)
                        if not has_mutable:
                            mut_next = mut_cur
                        return ((acc_l + l / A,
                                 jax.tree.map(lambda a, b: a + b / A, acc_g, g),
                                 mut_next),
                                aux_)

                    def scan_body_overlap(carry, mb_i):
                        mb, i = mb_i
                        acc_l, acc_g, mut_cur, comp_cur, acc_synced = carry
                        (l, (mut_next, aux_)), g = run_vag(mb, i, mut_cur)
                        g = upcast_grads(g)
                        if not has_mutable:
                            mut_next = mut_cur
                        g_leaves_ = self.treedef.flatten_up_to(g)
                        g_names = dict(zip(self.names, g_leaves_))
                        # nested under ad.grad: a reader that takes the
                        # innermost scope charges it to sync
                        with jax.named_scope("ad.sync"):
                            synced_i, comp_next = ar_sync.sync_overlapped(
                                g_names, scan_buckets, comp_cur, axis,
                                hier=self.hier_spec)
                        acc_synced = {n: acc_synced[n] + synced_i[n] / A
                                      for n in acc_synced}
                        # bucketed vars accumulate ONLY their synced mean (the
                        # raw-grad accumulator stays zero for them — no double
                        # buffering of the bucketed gradient set)
                        acc_leaves = self.treedef.flatten_up_to(acc_g)
                        new_acc = [a if n in bucket_names else a + gl / A
                                   for n, a, gl in zip(self.names, acc_leaves,
                                                       g_leaves_)]
                        return ((acc_l + l / A,
                                 self.treedef.unflatten(new_acc),
                                 mut_next, comp_next, acc_synced),
                                aux_)

                    # grads of bf16-master vars are upcast to f32 before
                    # accumulation, so their accumulators carry f32 too
                    zero_g = jax.tree.map(jnp.zeros_like, upcast_grads(full))
                    if overlap_in_scan:
                        # sharded-update buckets sync into per-var (ss,) flat
                        # SHARDS inside the scan; their accumulator carries the
                        # shard shape, never the full gradient
                        zero_synced = {
                            n: (jnp.zeros((self._shard_of[n][1],),
                                          jnp.float32 if n in prec_names
                                          else leaf.dtype)
                                if n in self._shard_of else jnp.zeros_like(leaf))
                            for n, leaf in zip(self.names,
                                               self.treedef.flatten_up_to(full))
                            if n in bucket_names}
                        comp_scan = {b.key: comp_local[b.key]
                                     for b in scan_buckets}
                        (loss, grads, mut_final, comp_scan_new, synced), auxs = (
                            jax.lax.scan(
                                scan_body_overlap,
                                (jnp.zeros((), jnp.float32), zero_g, mutable,
                                 comp_scan, zero_synced),
                                (micro, jnp.arange(A))))
                    else:
                        (loss, grads, mut_final), auxs = jax.lax.scan(
                            scan_body,
                            (jnp.zeros((), jnp.float32), zero_g, mutable),
                            (micro, jnp.arange(A)))
                    new_mutable = mut_final if has_mutable else None
                    aux = jax.tree.map(lambda x: jnp.mean(x, axis=0), auxs)
            with jax.named_scope("ad.sync"):
                if has_mutable:
                    # cross-replica average of float statistics (e.g. BN stats)
                    new_mutable = jax.tree.map(
                        lambda x: jax.lax.pmean(x, axis)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x,
                        new_mutable)

                # 3. bucketed allreduce for dense AR vars.  barrier: one sync
                # point here, after the full backward; overlap (A<=1): per-
                # bucket reverse-topological collectives the latency-hiding
                # scheduler can pipeline; overlap (A>1): elementwise-codec
                # buckets already synced inside the scan above, block-codec
                # buckets sync here on the accumulated gradients.
                g_leaves = self.treedef.flatten_up_to(grads)
                g_by_name = dict(zip(self.names, g_leaves))
                if synced is None:
                    if self.sync_schedule == "overlap":
                        synced, comp_new_local = ar_sync.sync_overlapped(
                            g_by_name, self.buckets, comp_local, axis,
                            hier=self.hier_spec)
                    elif self.hier_spec is not None:
                        # barrier schedule on a factored mesh: the two-level
                        # entry (FLAT buckets inside it still reduce flat)
                        synced, comp_new_local = ar_sync.sync_hierarchical(
                            g_by_name, self.buckets, comp_local, axis,
                            hier=self.hier_spec)
                    else:
                        synced, comp_new_local = ar_sync.sync_bucketed(
                            g_by_name, self.buckets, comp_local, axis)
                elif post_buckets:
                    synced_post, comp_post = ar_sync.sync_overlapped(
                        g_by_name, post_buckets, comp_local, axis,
                        hier=self.hier_spec)
                    synced = {**synced, **synced_post}
                    comp_new_local = {**comp_post, **comp_scan_new}
                else:
                    comp_new_local = {**comp_local, **comp_scan_new}
        with jax.named_scope("ad.sync"):
            comp_new = {k: jax.tree.map(lambda a: a[None], v)
                        for k, v in comp_new_local.items()}

            # 4a. fused reduce-scatter for the dense PS family: every PS var's
            # flat padding reshapes to (R_ps, shard); concatenating along dim 1
            # lets ONE psum_scatter per (dtype, ps_axes) group deliver every
            # device exactly its row — its shard of every variable — instead of
            # a collective per variable (hundreds, for transformer-sized
            # models).  With a mesh-axis SUBSET (e.g. ici of a dcn x ici mesh)
            # the scatter stays inside the subset and only the 1/R_ps-sized
            # shards cross the remaining axes via psum — DCN sees shard-sized
            # traffic, never full gradients (the reference shapes this with
            # load-balanced PS placement, ``ps_synchronizer.py:635-656``).
            def _ps_shard_len(plan):
                r = self._R_for(plan)
                n = int(np.prod(plan.shape)) if plan.shape else 1
                return (-(-n // r) * r) // r

            ps_fused = self.ps_groups
            ps_grad_shards = {}
            for (dtype, _axes_key), names_d in ps_fused.items():
                plan0 = self.plans[names_d[0]]
                ps_axis = self._ps_axis(plan0)
                other = self._ps_other_axes(plan0)
                r_ps = self._R_for(plan0)
                mats = []
                for name in names_d:
                    plan = self.plans[name]
                    g = g_by_name[name]
                    ss = _ps_shard_len(plan)
                    flatg = jnp.zeros((ss * r_ps,), g.dtype).at[:g.size].set(g.ravel())
                    mats.append(flatg.reshape(r_ps, ss))
                bucket = jnp.concatenate(mats, axis=1) if len(mats) > 1 else mats[0]
                red = jax.lax.psum_scatter(bucket, ps_axis, scatter_dimension=0,
                                           tiled=True)            # (1, S) -> (S,)
                if other:  # cross-slice sum of the already-scattered shards
                    red = jax.lax.psum(red, other)
                red = red.reshape(-1) / R
                off = 0
                for name in names_d:
                    ss = _ps_shard_len(self.plans[name])
                    ps_grad_shards[name] = jax.lax.dynamic_slice_in_dim(red, off, ss)
                    off += ss

            # 4a'. fused pmean of CUSTOM (tensor-parallel) grads: one collective
            # per (spec, dtype) group over the data axes instead of one per var
            custom_synced = {}
            for (_, _), (names_c, _axes) in self.custom_groups.items():
                flats = [jnp.ravel(g_by_name[n]) for n in names_c]
                buf = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
                buf = jax.lax.pmean(buf, axis)
                off = 0
                for n in names_c:
                    gshape = g_by_name[n].shape
                    size = g_by_name[n].size
                    custom_synced[n] = jax.lax.dynamic_slice_in_dim(
                        buf, off, size).reshape(gshape)
                    off += size

        # 4b. update-space params/grads per variable.  Sharded-update AR
        # vars slice their flat padded 1/R param shard at the row the
        # bucket's reduce-scatter assigned this device (ici-major under
        # the fused TWO_LEVEL schedule).
        with jax.named_scope("ad.update"):
            shard_rows = {b_sh.key: ar_sync.shard_index(b_sh, axis,
                                                        self.hier_spec)
                          for b_sh in self.sharded_buckets}
            u_params, u_grads = [], []
            for name, plan, s_leaf in zip(self.names, plans, s_leaves):
                g = g_by_name[name]
                if plan.placement == Placement.CUSTOM:
                    # tensor-parallel block: replicated over the data axes,
                    # sharded over model axes -> averaged over data axes (fused)
                    u_params.append(s_leaf)
                    u_grads.append(custom_synced[name])
                elif plan.placement == Placement.SHARDED:
                    if plan.sparse and plan.partition_axis == 0:
                        # ShardedTable lookup: the backward already produced the
                        # local block's mean gradient (update space) directly
                        from autodist_tpu.ops.sparse import ShardedTable

                        assert isinstance(g, ShardedTable)
                        u_params.append(s_leaf)
                        u_grads.append(g.block)
                    elif plan.sparse:
                        # non-dim0 shard of a sparse var: pre-synced dense mean
                        gp = self._pad_axis(g, plan)
                        block = plan.padded_dim // R
                        ug = jax.lax.dynamic_slice_in_dim(
                            gp, my * block, block, axis=plan.partition_axis)
                        u_params.append(s_leaf)
                        u_grads.append(ug)
                    else:
                        with jax.named_scope("ad.sync"):
                            gp = self._pad_axis(g, plan)
                            ug = jax.lax.psum_scatter(
                                gp, axis,
                                scatter_dimension=plan.partition_axis,
                                tiled=True) / R
                        u_params.append(s_leaf)
                        u_grads.append(ug)
                elif plan.placement == Placement.DIVERGENT:
                    # local update either way: dense grads are local by nature,
                    # sparse grads arrive pre-synced (a harmless strengthening)
                    u_params.append(s_leaf)
                    u_grads.append(g[None])
                elif plan.sync == SyncKind.PS:
                    r_ps = self._R_for(plan)
                    my_ps = my if r_ps == R else axis_index(self._ps_axis(plan))
                    n = int(np.prod(plan.shape)) if plan.shape else 1
                    ss = _ps_shard_len(plan)
                    npad = ss * r_ps
                    flatp = jnp.zeros((npad,), s_leaf.dtype).at[:n].set(s_leaf.ravel())
                    u_params.append(jax.lax.dynamic_slice_in_dim(flatp, my_ps * ss, ss))
                    if plan.sparse:
                        # sparse grads arrive pre-synced (full-mesh mean), so
                        # the subset shard is identical across the other axes
                        flatg = jnp.zeros((npad,), g.dtype).at[:n].set(g.ravel())
                        ug = jax.lax.dynamic_slice_in_dim(flatg, my_ps * ss, ss)
                    else:
                        ug = ps_grad_shards[name]
                    u_grads.append(ug)
                elif name in self._shard_of:
                    # ZeRO sharded update: the bucket scatter already delivered
                    # this device's (ss,) gradient shard in `synced`; pair it
                    # with the matching flat param shard
                    b_sh, ss = self._shard_of[name]
                    if b_sh.precision:
                        # bf16-master: s_leaf IS this device's flat f32
                        # master shard (storage == update space)
                        u_params.append(s_leaf)
                    else:
                        n = int(np.prod(plan.shape)) if plan.shape else 1
                        flatp = jnp.zeros((ss * b_sh.num_shards,),
                                          s_leaf.dtype).at[:n].set(s_leaf.ravel())
                        u_params.append(jax.lax.dynamic_slice_in_dim(
                            flatp, shard_rows[b_sh.key] * ss, ss))
                    u_grads.append(synced[name])
                else:  # REPLICATED + AllReduce
                    u_params.append(s_leaf)
                    u_grads.append(synced.get(name, g))  # sparse: pre-synced

        # 4c. mesh-aware global-norm clipping: optax.clip_by_global_norm
        # would see per-shard norms for PS/SHARDED update spaces; here the
        # TRUE global norm is assembled from per-leaf contributions (sharded
        # leaves psum their squared sums; replicated leaves count once)
        with jax.named_scope("ad.clip"):
            grad_norm = None
            if self.clip_global_norm is not None:
                sq = jnp.zeros((), jnp.float32)
                sq_sharded = jnp.zeros((), jnp.float32)
                # CUSTOM blocks are disjoint only over the axes their spec
                # names; psum per spec-axis set (a block replicated over an
                # unnamed model axis must be counted once)
                sq_custom = {}  # frozenset(axes) -> scalar
                for plan, ug in zip(plans, u_grads):
                    s = jnp.sum(jnp.square(ug.astype(jnp.float32)))
                    if plan.placement == Placement.CUSTOM:
                        axes_key = next(a for (_, _), (ns, a)
                                        in self.custom_groups.items()
                                        if plan.name in ns)
                        sq_custom[axes_key] = sq_custom.get(
                            axes_key, jnp.zeros((), jnp.float32)) + s
                    elif plan.placement == Placement.DIVERGENT:
                        # local (or pre-synced sparse) gradients: count each
                        # device's copy once by averaging, not summing, over the
                        # axis — keeps the norm comparable to single-device
                        sq_sharded = sq_sharded + s / R
                    elif (plan.placement == Placement.SHARDED
                            or part.flat_shard_update(plan)):
                        # disjoint shards (PS flat shards, sharded-update AR
                        # shards, SHARDED storage): full-axis psum = true sum.
                        # A subset-axis PS shard is replicated over the other
                        # data axes, so pre-divide by that multiplicity.
                        mult = R // self._R_for(plan)
                        sq_sharded = sq_sharded + (s / mult if mult > 1 else s)
                    else:
                        sq = sq + s
                total = sq + jax.lax.psum(sq_sharded, axis)
                for axes_key, s in sq_custom.items():
                    total = (total + jax.lax.psum(s, tuple(sorted(axes_key)))
                             if axes_key else total + s)
                grad_norm = jnp.sqrt(total)
                scale = jnp.minimum(
                    1.0, self.clip_global_norm / jnp.maximum(grad_norm, 1e-12))
                u_grads = [g * scale.astype(g.dtype) for g in u_grads]

        with jax.named_scope("ad.update"):
            u_params_t = self.treedef.unflatten(u_params)
            u_grads_t = self.treedef.unflatten(u_grads)

            # 5. optimizer (elementwise transforms shard transparently)
            updates, opt_new = self.model_item.optimizer.update(
                u_grads_t, opt_state, u_params_t)
            new_u = optax.apply_updates(u_params_t, updates)
            new_u_leaves = self.treedef.flatten_up_to(new_u)

        with jax.named_scope("ad.gather"):
            # 6a. fused all-gather of updated PS shards (mirror of 4a): one
            # all_gather per (dtype, ps_axes) group rebuilds every PS
            # variable's full value — over the subset axis only; shards are
            # identical across the other axes (same grads -> same update), so
            # no cross-slice gather is needed at all.
            new_by_name = dict(zip(self.names, new_u_leaves))

            # 6a'. fused per-bucket all-gather of FRESH PARAMS for the ZeRO
            # sharded-update buckets — the collective that replaces the
            # replicated schedule's gradient all-gather (under TWO_LEVEL it
            # retraces the scatter hops in reverse: DCN shard gather, then
            # ICI gather).  One gather per bucket, each depending only on its
            # own bucket's updated shards, so under schedule="overlap" the
            # latency-hiding scheduler pipelines bucket i's gather behind
            # bucket i+1's still-running shard update.
            sharded_full = {}
            for b_sh in self.sharded_buckets:
                if b_sh.precision:
                    # bf16-master: no post-update gather — the fresh f32
                    # shard IS the new storage (6b falls through to `nu`);
                    # the NEXT step's entry gather rebuilds the bf16 copy
                    continue
                sharded_full.update(ar_sync.gather_bucket_params(
                    new_by_name, b_sh, axis, self.hier_spec))

            ps_full = {}
            for (dtype, _axes_key), names_d in ps_fused.items():
                plan0 = self.plans[names_d[0]]
                ps_axis = self._ps_axis(plan0)
                r_ps = self._R_for(plan0)
                cat = (jnp.concatenate([new_by_name[n] for n in names_d])
                       if len(names_d) > 1 else new_by_name[names_d[0]])
                S = cat.shape[0]
                gathered = jax.lax.all_gather(cat, ps_axis, axis=0, tiled=True)
                gathered = gathered.reshape(r_ps, S)
                off = 0
                for name in names_d:
                    plan = self.plans[name]
                    ss = _ps_shard_len(plan)
                    n = int(np.prod(plan.shape)) if plan.shape else 1
                    cols = jax.lax.dynamic_slice_in_dim(gathered, off, ss, axis=1)
                    ps_full[name] = jnp.reshape(cols.reshape(-1)[:n], plan.shape)
                    off += ss

        # 6b. write back to storage
        with jax.named_scope("ad.update"):
            new_storage = []
            for name, plan, nu, s_leaf in zip(self.names, plans, new_u_leaves, s_leaves):
                if plan.placement in (Placement.SHARDED, Placement.CUSTOM):
                    new_storage.append(nu)
                elif plan.placement == Placement.DIVERGENT:
                    # lax.cond skips the collective entirely on non-averaging
                    # steps (the whole point of staleness); the predicate is
                    # replicated so all devices take the same branch
                    period = plan.sync_period
                    do_avg = jnp.equal(jnp.mod(step + 1, period), 0)
                    with jax.named_scope("ad.sync"):
                        new_storage.append(jax.lax.cond(
                            do_avg,
                            lambda x: jax.lax.pmean(x, axis),
                            lambda x: x,
                            nu))
                elif plan.sync == SyncKind.PS:
                    if name in ps_full:
                        new_storage.append(ps_full[name])
                    else:  # sparse PS var: gather its own shard ring
                        n = int(np.prod(plan.shape)) if plan.shape else 1
                        with jax.named_scope("ad.gather"):
                            flat = jax.lax.all_gather(
                                nu, self._ps_axis(plan), axis=0, tiled=True)
                            new_storage.append(
                                jnp.reshape(flat[:n], plan.shape))
                elif name in sharded_full:  # sharded-update AR var
                    new_storage.append(sharded_full[name])
                else:
                    new_storage.append(nu)

        metrics = {"loss": jax.lax.pmean(loss, axis), "step": step + 1}
        if grad_norm is not None:
            # total already includes the cross-device psums -> replicated
            metrics["grad_norm"] = grad_norm
        for k, v in (aux.items() if isinstance(aux, dict) else ()):
            metrics[k] = jax.lax.pmean(v, axis)

        return (self.treedef.unflatten(new_storage), opt_new, comp_new,
                new_mutable, step + 1, rng, metrics)

    def init_comp_states(self):
        """Fresh per-device compressor state (a pytree per bucket; every
        leaf is stacked along the replica axis, one copy per device)."""
        sharding = NamedSharding(self.mesh, P(self.axis))
        comp = {}
        for key, base in ar_sync.init_compressor_states(self.buckets).items():
            comp[key] = jax.tree.map(
                lambda b: jax.device_put(
                    jnp.broadcast_to(b[None], (self.num_replicas,) + b.shape),
                    sharding),
                base)
        return comp

    # -- canonical (single-device) forms for checkpointing -----------------

    def _canon_leaf(self, leaf, plan):
        """update-space array -> original param shape (global arrays).
        Leaves that are not update-space-shaped (e.g. a per-param scalar
        statistic) pass through unchanged."""
        if tuple(leaf.shape) != part.update_space_shape(plan, self._R_for(plan)):
            return leaf
        if plan.placement == Placement.SHARDED:
            dim = plan.shape[plan.partition_axis]
            if leaf.shape[plan.partition_axis] != dim:
                leaf = jax.lax.slice_in_dim(leaf, 0, dim, axis=plan.partition_axis)
            return leaf
        if plan.placement == Placement.DIVERGENT:
            return jnp.mean(leaf, axis=0)
        if part.flat_shard_update(plan):
            n = int(np.prod(plan.shape)) if plan.shape else 1
            return jnp.reshape(leaf[:n], plan.shape)
        return leaf

    def _uncanon_leaf(self, leaf, plan):
        """original param shape -> update-space array (inverse of above).
        Non-param-shaped leaves (per-param scalar statistics) pass through."""
        R = self.num_replicas
        if tuple(leaf.shape) != tuple(plan.shape):
            return leaf
        if plan.placement == Placement.SHARDED:
            pad = plan.padded_dim - leaf.shape[plan.partition_axis]
            if pad:
                widths = [(0, 0)] * leaf.ndim
                widths[plan.partition_axis] = (0, pad)
                leaf = jnp.pad(leaf, widths)
            return leaf
        if plan.placement == Placement.DIVERGENT:
            return jnp.broadcast_to(leaf[None], (R,) + leaf.shape)
        if part.flat_shard_update(plan):
            r = self._R_for(plan)
            n = leaf.size
            npad = -(-n // r) * r
            return jnp.zeros((npad,), leaf.dtype).at[:n].set(leaf.ravel())
        return leaf

    def _plans_boxed_tree(self):
        return self.treedef.unflatten([_SpecBox(self.plans[n]) for n in self.names])

    def canonicalize_opt_state(self, opt_state):
        """Sharded optimizer state -> single-device-shaped state (the
        reference Saver's 'original variable names/shapes' contract,
        ``checkpoint/saver.py:50-58``).  Output is REPLICATED so every
        process can fetch it (multi-host ``device_get`` cannot touch
        non-addressable shards)."""
        boxed = self._plans_boxed_tree()
        fn = jax.jit(lambda s: optax.tree_map_params(
            self.model_item.optimizer,
            lambda leaf, box: self._canon_leaf(leaf, box.spec),
            s, boxed,
            transform_non_params=lambda leaf: leaf,
            is_leaf=lambda x: isinstance(x, _SpecBox)),
            out_shardings=NamedSharding(self.mesh, P()))
        return fn(opt_state)

    def uncanonicalize_opt_state(self, canonical):
        boxed = self._plans_boxed_tree()
        opt_spec = self._opt_spec_tree(jax.eval_shape(lambda s: s, canonical))
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), opt_spec,
                                 is_leaf=lambda x: isinstance(x, P))
        fn = jax.jit(lambda s: optax.tree_map_params(
            self.model_item.optimizer,
            lambda leaf, box: self._uncanon_leaf(leaf, box.spec),
            s, boxed,
            transform_non_params=lambda leaf: leaf,
            is_leaf=lambda x: isinstance(x, _SpecBox)),
            out_shardings=shardings)
        return fn(canonical)

    def canonicalize_params(self, storage):
        """Storage tree -> original-shape param tree (REPLICATED output so
        multi-host fetch works — see canonicalize_opt_state)."""
        plans_tree = self.treedef.unflatten([self.plans[n] for n in self.names])

        def fetch(leaf, plan):
            # bf16-master REPLICATED plans store the FLAT f32 master —
            # canonical form still reshapes it back to the param shape
            if (plan.placement == Placement.REPLICATED
                    and not part.master_shard_storage(plan)):
                return leaf
            return self._canon_leaf(leaf, plan)

        return jax.jit(lambda s: jax.tree.map(fetch, s, plans_tree),
                       out_shardings=NamedSharding(self.mesh, P()))(storage)

    def uncanonicalize_params(self, params):
        plans_tree = self.treedef.unflatten([self.plans[n] for n in self.names])
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.params_spec_tree("storage"),
            is_leaf=lambda x: isinstance(x, P))

        def to_storage(leaf, plan):
            if (plan.placement == Placement.REPLICATED
                    and not part.master_shard_storage(plan)):
                return leaf
            return self._uncanon_leaf(leaf, plan)

        return jax.jit(lambda p: jax.tree.map(to_storage, p, plans_tree),
                       out_shardings=shardings)(params)

    # -- public: build the jitted step ------------------------------------

    def make_train_step(self, donate=True):
        p_spec = self.params_spec_tree("storage")
        comp_spec = self._comp_spec()

        def step_fn(state, batch):
            opt_spec = self._opt_spec_tree(
                jax.eval_shape(lambda s: s, state["opt_state"]))
            state_spec = {"params": p_spec, "opt_state": opt_spec,
                          "comp": comp_spec, "mutable": P(),
                          "step": P(), "rng": P()}
            # per-leaf batch specs: lower-rank leaves (e.g. (B,) labels)
            # shard only their leading dims
            bspec = tuple(self.batch_spec)
            batch_specs = jax.tree.map(lambda x: P(*bspec[:x.ndim]), batch)
            in_specs = (state_spec, batch_specs)
            out_specs = (state_spec, P())

            def body(state_, batch_):
                ns, no, nc, nm, nstep, nrng, metrics = self._spmd_step(
                    state_["params"], state_["opt_state"], state_["comp"],
                    state_["mutable"], state_["step"], state_["rng"], batch_)
                return ({"params": ns, "opt_state": no, "comp": nc,
                         "mutable": nm, "step": nstep, "rng": nrng}, metrics)

            return jax.shard_map(
                body, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )(state, batch)

        # overlap schedule: compile with the latency-hiding scheduler +
        # bucket-sized combine thresholds so the per-bucket collectives
        # actually pipeline (kernel/xla_options.py); TPU backend only —
        # other backends reject the TPU-namespaced flags — and probed
        # down to what this libtpu's per-compile surface supports
        from autodist_tpu.kernel.xla_options import (compiler_options_for,
                                                     probe_supported_options)

        opts = compiler_options_for(self.sync_schedule)
        if opts:
            opts = probe_supported_options(opts)
        kwargs = {"donate_argnums": (0,) if donate else ()}
        if opts:
            kwargs["compiler_options"] = opts
        return jax.jit(step_fn, **kwargs)


def get_stateful(bucket):
    from autodist_tpu.kernel.synchronization.compressor import get_compressor

    # TWO_LEVEL buckets carry their DCN-hop codec's state (the only wire
    # transform they apply); flat buckets their own compressor's
    return get_compressor(ar_sync.wire_codec(bucket)).stateful
