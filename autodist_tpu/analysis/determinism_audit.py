"""PRNG & determinism static auditor (the N-code tier).

The engine's correctness story leans on exactness claims — canonical
schedule-IR programs normalize bitwise onto legacy executors, ``serve()``
bit-matches ``generate()``, same-geometry restore is bitwise, R->R'
resharding is EXACT — yet those claims rest on preconditions no other
tier proves: DP replicas must draw INDEPENDENT PRNG streams, consume
DISJOINT batch shards, and a strategy sold as bit-reproducible must not
hide a nondeterministic lowered op.  This pass proves them statically,
before a step runs, by joining three legs in one Report:

1. **key lineage** (TRACE leg) — a jaxpr dataflow walk tracking every
   PRNG value from its root (``random_seed`` / a wrapped engine key)
   through ``random_split`` / ``random_fold_in`` derivations to each
   ``random_bits`` consumption, fused with the C-tier varying-axes
   replication analysis so every key carries the mesh axes it may
   differ over AND a loop-variance bit per enclosing ``scan``;
2. **shard coverage** (STATIC leg) — the transformer's ``batch_spec``
   diffed against its data axes: every data axis of size > 1 must shard
   the batch (else two replicas read the same rows), and every sharding
   axis must be a data axis (else the gradient sync never reconciles
   the shards);
3. **lowered nondeterminism** (LOWERED leg) — the X-audit walker over
   the StableHLO module for scatters with possibly-colliding indices
   (``unique_indices = false``), the classic reduction-order hazard.

Codes::

  N000 INFO    audit skipped (nothing attached to analyze)
  N001 ERROR   replicated key feeds a per-replica stochastic op: the
               same mask/noise on every data replica (correlated
               gradient noise — loss still decreases, statistics wrong)
  N002 ERROR   key stream reused: one key consumed by two random ops,
               or consumed inside a scan without a per-iteration
               split/fold_in
  N003 ERROR   batch-shard overlap/gap: batch_spec x mesh coverage
               broken (replicas reading the same shard, or shards the
               gradient sync never partitions)
  N004 WARNING nondeterministic lowered op (colliding scatter) inside a
               strategy whose equivalence contract is otherwise bitwise
  N005 WARNING shard_map-body key derived without an axis-index fold-in
               where per-replica variance is required
  N006 INFO    machine-readable key-lineage table + the strategy's
               determinism class (bitwise | reduction_order |
               stochastic), exported as ``ctx.determinism_summary``

The determinism CLASS is the contract other layers consume through
:func:`determinism_class` instead of ad-hoc assumptions: ``bitwise``
(no PRNG draws, no order-hazard ops — re-running or resharding must
reproduce bits), ``reduction_order`` (deterministic per schedule, but a
different collective schedule may legally drift in rounding), and
``stochastic`` (PRNG draws dominate; equivalence holds in expectation).
The elastic reshard gate logs the old-vs-new class on every restore and
the equivalence tests pin canonical-vs-searched schedules with it.

Known limits (documented, pinned by tests): a remat replay of the same
draw (same label, same shape, inside a ``remat``/``checkpoint`` region)
is collapsed rather than flagged as N002 — the recompute IS the same
sample; and keys reaching a random op through an unknown higher-order
primitive degrade to unlabeled (conservative-quiet, never a false
ERROR).
"""
import dataclasses
import itertools
import re
from collections import defaultdict

from jax import core as jax_core
from jax.extend import core as jex_core

from autodist_tpu.analysis.jaxpr_utils import (_UNIFORMIZING_PRIMS,
                                               _VARYING_PRIMS, _as_jaxpr,
                                               collective_axes,
                                               collective_signature,
                                               find_shard_map_bodies)
from autodist_tpu.analysis.report import Finding, Severity

# the determinism-class lattice: weakest contract wins when classes join
CLASS_ORDER = {"bitwise": 0, "reduction_order": 1, "stochastic": 2}

# scatters whose colliding updates are combined in hardware arrival
# order — the reduction-order hazard N004 exists for
_SCATTER_PRIMS = frozenset({"scatter-add", "scatter-mul", "scatter-min",
                            "scatter-max", "scatter"})
_HLO_SCATTER_RE = re.compile(r'"?stablehlo\.(scatter)"?[\s(<]')

# prims a key value flows through unchanged (same stream, new layout)
_KEY_PLUMBING = frozenset({"random_unwrap", "convert_element_type",
                           "reshape", "squeeze", "broadcast_in_dim",
                           "transpose", "copy", "device_put"})

_INLINE_PRIMS = ("jit", "closed_call", "core_call", "custom_jvp_call",
                 "custom_vjp_call")
_REPLAY_PRIMS = ("remat", "remat2", "checkpoint")


def _f(sev, code, msg, subject="", data=None):
    return Finding(sev, code, "determinism-audit", msg, subject, data=data)


@dataclasses.dataclass(frozen=True)
class _Val:
    """One jaxpr value under the combined walk: the mesh axes it may
    vary over (the C-tier analysis), the PRNG stream label it carries
    (None for non-key values), the random-consumption sites tainting it,
    and whether it varies across iterations of the innermost scan."""

    varying: frozenset = frozenset()
    key: object = None
    taints: frozenset = frozenset()
    loop_variant: bool = False


class _State:
    """Walk-global accumulator: the lineage table (label -> derivation
    row), every consumption site, and the jaxpr-leg scatter sites."""

    def __init__(self, data_axes):
        self.data_axes = frozenset(data_axes)
        self.labels = {}          # label -> lineage row (N006 table)
        self.sites = {}           # site id -> consumption row
        self.scatter_sites = []
        self.rootmemo = {}        # per-body: wrapped var -> root label
        self.body_sharded = False
        self._n = itertools.count()

    def fresh(self, stem):
        return f"{stem}#{next(self._n)}"

    def reg(self, label, op, parent=None, replica_derived=False,
            varying=frozenset(), detail=""):
        if label not in self.labels:
            self.labels[label] = {
                "label": label, "op": op, "parent": parent,
                "replica_derived": bool(replica_derived),
                "varying": sorted(varying), "detail": detail}

    def replica_derived(self, label):
        row = self.labels.get(label)
        return bool(row and row["replica_derived"])


def _walk(state, jaxpr, in_vals, *, record=True, scan_depth=0,
          replay=False):
    """Interpret a jaxpr over :class:`_Val`s; returns the outvar vals.

    ``record=False`` walks (loop fixpoints) propagate varying/taints but
    create no lineage rows and no consumption sites, so a scan body is
    recorded exactly once."""
    jaxpr = _as_jaxpr(jaxpr)
    env = {}

    def rd(a):
        if isinstance(a, jex_core.Literal):
            return _Val()
        return env.get(a, _Val())

    for v, val in zip(jaxpr.invars, in_vals):
        env[v] = val
    for v in jaxpr.constvars:
        env[v] = _Val()

    for eqn in jaxpr.eqns:
        ins = [rd(a) for a in eqn.invars]
        union_v = frozenset().union(*(v.varying for v in ins)) \
            if ins else frozenset()
        union_t = frozenset().union(*(v.taints for v in ins)) \
            if ins else frozenset()
        union_l = any(v.loop_variant for v in ins)
        name = eqn.primitive.name

        # N001's join: a sampled value meeting a data-varying value is
        # "applied per replica" — if its key was replicated, every
        # replica just applied the same draw to different data
        if record and union_t and any(v.varying & state.data_axes
                                      for v in ins):
            for s in union_t:
                if s in state.sites:
                    state.sites[s]["applied_per_replica"] = True

        if name == "random_seed":
            atom = eqn.invars[0]
            if isinstance(atom, jex_core.Literal):
                label = f"seed({atom.val})"
            else:
                label = state.fresh("seed")
            if record:
                state.reg(label, "seed", varying=ins[0].varying)
            outs = [_Val(varying=ins[0].varying, key=label,
                         taints=ins[0].taints,
                         loop_variant=ins[0].loop_variant)]
        elif name == "random_wrap":
            v = ins[0]
            label = v.key
            if label is None:
                var = eqn.invars[0]
                label = None if isinstance(var, jex_core.Literal) \
                    else state.rootmemo.get(var)
                if label is None:
                    label = state.fresh("key")
                    if not isinstance(var, jex_core.Literal):
                        state.rootmemo[var] = label
                if record:
                    state.reg(label, "root", varying=v.varying)
            outs = [_Val(varying=v.varying, key=label, taints=v.taints,
                         loop_variant=v.loop_variant)]
        elif name == "random_split":
            v = ins[0]
            label = state.fresh("split") + f"({v.key})"
            if record:
                state.reg(label, "split", parent=v.key,
                          replica_derived=state.replica_derived(v.key),
                          varying=v.varying)
            outs = [_Val(varying=v.varying, key=label, taints=v.taints,
                         loop_variant=v.loop_variant)]
        elif name == "random_fold_in":
            k, d = ins[0], ins[1]
            varying = k.varying | d.varying
            folded_data = sorted(d.varying & state.data_axes)
            rderived = state.replica_derived(k.key) or bool(folded_data)
            label = state.fresh("fold") + f"({k.key})"
            if record:
                state.reg(label, "fold_in", parent=k.key,
                          replica_derived=rderived, varying=varying,
                          detail=(f"folds axis-varying {folded_data}"
                                  if folded_data else ""))
            outs = [_Val(varying=varying, key=label,
                         taints=k.taints | d.taints,
                         loop_variant=k.loop_variant or d.loop_variant)]
        elif name == "random_bits":
            k = ins[0]
            taints = k.taints
            if record:
                sid = next(state._n)
                state.sites[sid] = {
                    "label": k.key,
                    "shape": tuple(int(s) for s in
                                   eqn.params.get("shape", ())),
                    "bit_width": int(eqn.params.get("bit_width", 32)),
                    "varying": sorted(k.varying),
                    "replica_derived": state.replica_derived(k.key),
                    "loop_variant": bool(k.loop_variant),
                    "in_scan": scan_depth, "replay": bool(replay),
                    "applied_per_replica": False,
                    "body_sharded": state.body_sharded,
                }
                taints = taints | frozenset({sid})
            outs = [_Val(varying=k.varying, taints=taints,
                         loop_variant=k.loop_variant or union_l)
                    for _ in eqn.outvars]
        elif name in _KEY_PLUMBING and ins:
            outs = [dataclasses.replace(ins[0]) for _ in eqn.outvars]
        elif name == "slice" and ins and ins[0].key is not None:
            v = ins[0]
            si = ",".join(str(int(s))
                          for s in eqn.params.get("start_indices", ()))
            label = f"{v.key}[{si}]"
            if record:
                state.reg(label, "index", parent=v.key,
                          replica_derived=state.replica_derived(v.key),
                          varying=v.varying)
            outs = [_Val(varying=v.varying, key=label, taints=v.taints,
                         loop_variant=v.loop_variant)]
        elif name == "dynamic_slice" and ins and ins[0].key is not None:
            v = ins[0]
            lv = union_l  # a loop-variant index selects a fresh child
            label = state.fresh("dyn") + f"({v.key})"
            if record:
                state.reg(label, "index", parent=v.key,
                          replica_derived=state.replica_derived(v.key),
                          varying=union_v)
            outs = [_Val(varying=union_v, key=label, taints=union_t,
                         loop_variant=lv)]
        elif name == "axis_index":
            outs = [_Val(varying=frozenset(collective_axes(eqn)))]
        elif name in _UNIFORMIZING_PRIMS:
            axes = frozenset(collective_axes(eqn))
            outs = [_Val(varying=union_v - axes, taints=union_t,
                         loop_variant=union_l) for _ in eqn.outvars]
        elif name in _VARYING_PRIMS:
            axes = frozenset(collective_axes(eqn))
            outs = [_Val(varying=union_v | axes, taints=union_t,
                         loop_variant=union_l) for _ in eqn.outvars]
        elif name == "cond":
            pred, ops = ins[0], ins[1:]
            branch_res = [_walk(state, b, ops, record=record,
                                scan_depth=scan_depth, replay=replay)
                          for b in eqn.params["branches"]]
            outs = []
            for k in range(len(eqn.outvars)):
                vs = [br[k] for br in branch_res if k < len(br)]
                if not vs:
                    outs.append(_Val(varying=union_v, taints=union_t,
                                     loop_variant=union_l))
                    continue
                key = vs[0].key if all(v.key == vs[0].key
                                       for v in vs) else None
                outs.append(_Val(
                    varying=pred.varying | frozenset().union(
                        *(v.varying for v in vs)),
                    key=key,
                    taints=frozenset().union(*(v.taints for v in vs)),
                    loop_variant=union_l or any(v.loop_variant
                                                for v in vs)))
        elif name == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            consts = [dataclasses.replace(v, loop_variant=False)
                      for v in ins[:nc]]
            carry = [dataclasses.replace(v, loop_variant=True)
                     for v in ins[nc:nc + ncar]]
            xs = [dataclasses.replace(v, loop_variant=True)
                  for v in ins[nc + ncar:]]
            body = eqn.params["jaxpr"]
            for _ in range(8):   # fixpoint: varying/taints only grow
                res = _walk(state, body, consts + carry + xs,
                            record=False, scan_depth=scan_depth + 1,
                            replay=replay)
                merged = [_Val(varying=c.varying | r.varying,
                               key=c.key if c.key == r.key else None,
                               taints=c.taints | r.taints,
                               loop_variant=True)
                          for c, r in zip(carry, res[:ncar])]
                if all(m.varying == c.varying and m.taints == c.taints
                       and m.key == c.key
                       for m, c in zip(merged, carry)):
                    carry = merged
                    break
                carry = merged
            res = _walk(state, body, consts + carry + xs, record=record,
                        scan_depth=scan_depth + 1, replay=replay)
            outs = [_Val(varying=v.varying, key=v.key, taints=v.taints,
                         loop_variant=union_l) for v in res]
            while len(outs) < len(eqn.outvars):
                outs.append(_Val(varying=union_v, taints=union_t,
                                 loop_variant=union_l))
        elif name == "while":
            cn = eqn.params["cond_nconsts"]
            bn = eqn.params["body_nconsts"]
            cconsts = ins[:cn]
            bconsts = [dataclasses.replace(v, loop_variant=False)
                       for v in ins[cn:cn + bn]]
            carry = [dataclasses.replace(v, loop_variant=True)
                     for v in ins[cn + bn:]]
            body = eqn.params["body_jaxpr"]
            for _ in range(8):
                res = _walk(state, body, bconsts + carry, record=False,
                            scan_depth=scan_depth + 1, replay=replay)
                merged = [_Val(varying=c.varying | r.varying,
                               key=c.key if c.key == r.key else None,
                               taints=c.taints | r.taints,
                               loop_variant=True)
                          for c, r in zip(carry, res)]
                if all(m.varying == c.varying and m.taints == c.taints
                       and m.key == c.key
                       for m, c in zip(merged, carry)):
                    carry = merged
                    break
                carry = merged
            _walk(state, body, bconsts + carry, record=record,
                  scan_depth=scan_depth + 1, replay=replay)
            _walk(state, eqn.params["cond_jaxpr"],
                  list(cconsts) + carry, record=record,
                  scan_depth=scan_depth + 1, replay=replay)
            outs = [_Val(varying=c.varying, key=c.key, taints=c.taints,
                         loop_variant=union_l) for c in carry]
        elif name in _INLINE_PRIMS + _REPLAY_PRIMS:
            sub = (eqn.params.get("jaxpr")
                   or eqn.params.get("call_jaxpr")
                   or eqn.params.get("fun_jaxpr"))
            rep = replay or name in _REPLAY_PRIMS
            if sub is not None and \
                    len(_as_jaxpr(sub).invars) == len(ins):
                outs = _walk(state, sub, ins, record=record,
                             scan_depth=scan_depth, replay=rep)
                if len(outs) != len(eqn.outvars):
                    outs = [_Val(varying=union_v, taints=union_t,
                                 loop_variant=union_l)
                            for _ in eqn.outvars]
            else:
                outs = [_Val(varying=union_v, taints=union_t,
                             loop_variant=union_l)
                        for _ in eqn.outvars]
        else:
            if record and name in _SCATTER_PRIMS \
                    and not eqn.params.get("unique_indices", False):
                state.scatter_sites.append({
                    "op": name, "where": "jaxpr",
                    "in_scan": scan_depth, "count": 1})
            outs = [_Val(varying=union_v, taints=union_t,
                         loop_variant=union_l) for _ in eqn.outvars]

        for v, val in zip(eqn.outvars, outs):
            if not isinstance(v, jax_core.DropVar):
                env[v] = val

    return [rd(v) for v in jaxpr.outvars]


# -- the three legs --------------------------------------------------------


def batch_coverage(batch_spec, data_axes, axis_sizes):
    """(overlap, gap) of a batch PartitionSpec against the data axes.

    ``overlap``: data axes of size > 1 the spec never shards over — the
    replicas along them read the SAME global rows.  ``gap``: spec axes
    that are not data axes — the batch is sharded along a direction the
    gradient sync never reconciles."""
    spec_axes = set()
    for entry in tuple(batch_spec or ()):
        if entry is None:
            continue
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        spec_axes.update(a for a in names if isinstance(a, str))
    overlap = sorted(a for a in data_axes
                     if int(axis_sizes.get(a, 1)) > 1
                     and a not in spec_axes)
    gap = sorted(a for a in spec_axes
                 if a not in data_axes and int(axis_sizes.get(a, 1)) > 1)
    return overlap, gap


def _analyze_trace(ctx, state):
    """Walk every shard_map body (or the bare jaxpr) with the combined
    lineage + varying + loop-variance interpreter."""
    bodies = find_shard_map_bodies(ctx.jaxpr)
    if not bodies:
        j = _as_jaxpr(ctx.jaxpr)
        state.body_sharded = False
        state.rootmemo = {}
        _walk(state, j, [_Val() for _ in j.invars])
        return
    for body, _mesh, in_varying in bodies:
        state.body_sharded = any(v & state.data_axes for v in in_varying)
        state.rootmemo = {}
        _walk(state, body,
              [_Val(varying=frozenset(v)) for v in in_varying])


def _hlo_scatter_sites(ctx):
    """LOWERED leg: colliding-index scatters straight off the module
    text (the X-audit walker), best-effort — no lowering, no leg."""
    from autodist_tpu.analysis.hlo_audit import (lowered_text_for,
                                                 walk_module_ops)

    try:
        text, source = lowered_text_for(ctx)
    except Exception:
        return [], None
    if not text:
        return [], None
    sites = []
    try:
        for op in walk_module_ops(text, _HLO_SCATTER_RE):
            if "unique_indices = false" in op.text:
                sites.append({"op": "stablehlo.scatter", "where": "hlo",
                              "in_scan": 1 if op.in_loop else 0,
                              "count": float(op.count)})
    except Exception:
        return [], source
    return sites, source


# -- the class lattice ------------------------------------------------------


def determinism_class(a, b=None):
    """Join determinism contracts: the weakest class wins.

    Accepts class strings or N006 summary dicts.  With two arguments it
    answers "what equivalence can these two runs/schedules promise each
    other?" — two ``bitwise`` programs whose collective schedules
    (``schedule_fingerprint``) differ still only promise
    ``reduction_order`` equality, because a different reduction tree
    legally rounds differently."""
    def cls_of(x):
        if x is None:
            return "bitwise"
        if isinstance(x, str):
            return x if x in CLASS_ORDER else "stochastic"
        return x.get("determinism_class", "bitwise")

    ca = cls_of(a)
    if b is None:
        return ca
    cb = cls_of(b)
    joined = ca if CLASS_ORDER[ca] >= CLASS_ORDER[cb] else cb
    if CLASS_ORDER[joined] == 0:
        fa = a.get("schedule_fingerprint") if isinstance(a, dict) else None
        fb = b.get("schedule_fingerprint") if isinstance(b, dict) else None
        if fa is not None and fb is not None and fa != fb:
            return "reduction_order"
    return joined


# -- the pass ---------------------------------------------------------------


def determinism_audit_pass(ctx):
    findings = []
    transformer = getattr(ctx, "transformer", None)
    jaxpr = getattr(ctx, "jaxpr", None)
    if transformer is None and jaxpr is None:
        return [_f(Severity.INFO, "N000",
                   "determinism audit skipped: no transformer and no "
                   "traced step attached — nothing to analyze")]

    data_axes = tuple(getattr(transformer, "data_axes", None)
                      or ctx.axis_names)
    axis_sizes = dict(ctx.axis_sizes or {})
    sharded_mesh = any(int(axis_sizes.get(a, 1)) > 1 for a in data_axes)

    # STATIC leg: batch_spec x mesh coverage (N003)
    overlap = gap = []
    if transformer is not None:
        overlap, gap = batch_coverage(
            getattr(transformer, "batch_spec", None), data_axes,
            axis_sizes)
        for a in overlap:
            findings.append(_f(
                Severity.ERROR, "N003",
                f"batch-shard overlap: the batch_spec "
                f"{getattr(transformer, 'batch_spec', None)} never "
                f"shards over data axis '{a}' (size "
                f"{axis_sizes.get(a)}), so all {axis_sizes.get(a)} "
                f"replicas along it read the SAME global rows — the "
                f"'global batch' is {axis_sizes.get(a)}x smaller than "
                f"the engine accounts for and every gradient is a "
                f"duplicate, not a shard", subject=f"axis {a}",
                data={"axis": a, "kind": "overlap",
                      "suggested_batch_spec": list(data_axes)}))
        for a in gap:
            findings.append(_f(
                Severity.ERROR, "N003",
                f"batch-shard gap: batch_spec shards the batch over "
                f"'{a}', which is not a data axis "
                f"({sorted(data_axes)}) — the gradient sync never "
                f"reconciles those shards, so devices along '{a}' "
                f"train on disjoint data with no reduction partner",
                subject=f"axis {a}",
                data={"axis": a, "kind": "gap",
                      "suggested_batch_spec": list(data_axes)}))

    # TRACE leg: the combined lineage walk (N001/N002/N005)
    state = _State(data_axes)
    if jaxpr is not None:
        _analyze_trace(ctx, state)

    sites = list(state.sites.values())
    if sharded_mesh:
        for c in sites:
            replicated = not (set(c["varying"]) & set(data_axes)) \
                and not c["replica_derived"]
            if not replicated:
                continue
            where = (f"key {c['label']}" if c["label"] else
                     "an unlabeled key")
            if c["applied_per_replica"]:
                findings.append(_f(
                    Severity.ERROR, "N001",
                    f"replicated key feeds a per-replica stochastic "
                    f"op: {where} varies over no data axis "
                    f"({sorted(data_axes)}), yet its "
                    f"{c['bit_width']}-bit draw of shape "
                    f"{list(c['shape'])} is applied to data-varying "
                    f"values — every replica uses the IDENTICAL "
                    f"mask/noise, so the 'independent' gradient noise "
                    f"is perfectly correlated across the mesh; derive "
                    f"the key through utils/rng.replica_key "
                    f"(fold_in(axis_index))", subject=str(c["label"]),
                    data=dict(c)))
            elif c["body_sharded"]:
                findings.append(_f(
                    Severity.WARNING, "N005",
                    f"shard_map-body key without an axis-index "
                    f"fold_in: {where} is consumed inside a body whose "
                    f"inputs are sharded over {sorted(data_axes)}, but "
                    f"its lineage never folds an axis-varying value — "
                    f"if this draw is meant to differ per replica, "
                    f"route it through utils/rng.replica_key",
                    subject=str(c["label"]), data=dict(c)))

    # N002: stream reuse across sites / across scan iterations
    by_label = defaultdict(list)
    for c in sites:
        if c["label"] is not None:
            by_label[c["label"]].append(c)
    for label, cs in sorted(by_label.items()):
        events, replay_sig = [], {}
        for c in cs:
            sig = (c["shape"], c["bit_width"])
            if sig in replay_sig and (c["replay"] or replay_sig[sig]):
                continue  # a remat replay of the same draw
            events.append(c)
            replay_sig[sig] = replay_sig.get(sig, False) or c["replay"]
        if len(events) >= 2:
            shapes = ", ".join(str(list(c["shape"])) for c in events)
            findings.append(_f(
                Severity.ERROR, "N002",
                f"key stream {label} is consumed by {len(events)} "
                f"random ops (shapes {shapes}) without an intervening "
                f"split/fold_in — the draws are NOT independent (two "
                f"dropout layers sharing one key drop the same units); "
                f"split the key or fold in a per-site constant",
                subject=label, data={"label": label,
                                     "consumptions": len(events)}))
        scan_stale = [c for c in cs
                      if c["in_scan"] > 0 and not c["loop_variant"]]
        if scan_stale and len(events) < 2:
            findings.append(_f(
                Severity.ERROR, "N002",
                f"key stream {label} is consumed inside a scan but is "
                f"loop-INVARIANT (derived only from scan constants): "
                f"every iteration redraws the identical sample; fold "
                f"the iteration index in (utils/rng.step_key)",
                subject=label,
                data={"label": label, "kind": "scan_reuse"}))

    # LOWERED leg + N004: order-hazard scatters, gated on the contract
    scatters = list(state.scatter_sites)
    hlo_sites, hlo_source = _hlo_scatter_sites(ctx)
    scatters.extend(hlo_sites)
    cls = ("stochastic" if sites
           else "reduction_order" if scatters else "bitwise")
    if scatters and not sites:
        kinds = sorted({s["op"] for s in scatters})
        findings.append(_f(
            Severity.WARNING, "N004",
            f"{len(scatters)} scatter site(s) with possibly-colliding "
            f"indices ({', '.join(kinds)}; unique_indices=false) inside "
            f"a strategy whose equivalence contract is otherwise "
            f"bitwise: colliding updates combine in arrival order, so "
            f"re-runs may differ in low bits — the strategy's "
            f"determinism class is 'reduction_order', not 'bitwise'",
            subject=kinds[0], data={"sites": scatters}))

    fingerprint = repr(collective_signature(ctx.jaxpr)) \
        if jaxpr is not None else None
    summary = {
        "strategy": getattr(ctx.strategy, "id", "") or "",
        "determinism_class": cls,
        "data_axes": sorted(data_axes),
        "batch_spec": (str(getattr(transformer, "batch_spec", None))
                       if transformer is not None else None),
        "shard_overlap": overlap, "shard_gap": gap,
        "keys": sorted(state.labels.values(),
                       key=lambda r: r["label"]),
        "consumptions": [dict(c, shape=list(c["shape"]))
                         for c in sites],
        "nondeterministic_sites": scatters,
        "hlo_source": hlo_source,
        "schedule_fingerprint": fingerprint,
        "codes": sorted({f.code for f in findings}),
    }
    ctx.determinism_summary = summary
    n_rep = sum(1 for c in sites if c["replica_derived"])
    findings.append(_f(
        Severity.INFO, "N006",
        f"determinism class '{cls}': {len(state.labels)} key stream(s), "
        f"{len(sites)} random consumption(s) ({n_rep} replica-derived), "
        f"{len(scatters)} order-hazard scatter site(s); batch coverage "
        f"{'BROKEN' if (overlap or gap) else 'disjoint and complete'} "
        f"over data axes {sorted(data_axes)}",
        subject="determinism", data=summary))
    return findings
