"""Jaxpr walking machinery shared by the analysis passes.

Three building blocks:

- **collective signatures** — the ordered sequence of (collective, mesh
  axes) a (sub)jaxpr issues, with control flow folded in structurally
  (``scan`` keeps its trip count, ``cond``/``while`` keep per-branch /
  per-phase signatures).  Two SPMD programs deadlock-match iff their
  signatures are equal, so comparing branch signatures is the static
  deadlock check.
- **varying-axes dataflow** — for every jaxpr variable, the set of mesh
  axes along which its value may DIFFER between devices (the static
  analog of jax's "varying manifest across" / replication tracking that
  ``check_vma=False`` turns off).  A ``cond`` whose branches issue
  different collectives is only a deadlock when its predicate may vary;
  the engine's own staleness-averaging ``cond`` has a replicated
  predicate and must pass.
- **liveness peak** — a conservative peak-live-bytes walk over the
  per-device program (activations + temporaries), the traced complement
  to the cost model's static params+opt footprint.

Everything here is best-effort static analysis: unknown higher-order
primitives degrade to the conservative default (union of input
varyings; sub-jaxpr signatures inlined) rather than failing.
"""
import numpy as np

from jax import core as jax_core
from jax.extend import core as jex_core

# primitives that synchronize devices over mesh axes (an SPMD rendezvous:
# every participant must issue them in the same order or the program hangs)
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmin", "pmax", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "pgather",
})

# collectives whose OUTPUT is identical on every participating device
# (full reductions / gathers) — they REMOVE the reduced axes from a
# value's varying set
_UNIFORMIZING_PRIMS = frozenset({"psum", "pmin", "pmax", "all_gather"})

# collectives whose output stays (or becomes) device-dependent along the
# named axes (each device receives a different shard / permuted peer value)
_VARYING_PRIMS = frozenset({"ppermute", "all_to_all", "reduce_scatter",
                            "pgather"})


def _as_jaxpr(j):
    return j.jaxpr if isinstance(j, jex_core.ClosedJaxpr) else j


def collective_axes(eqn):
    """Mesh axis names a collective eqn synchronizes over, as a tuple."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def subjaxprs(eqn):
    """All sub-jaxprs of an eqn (generic fallback for unknown prims)."""
    subs = []
    for v in eqn.params.values():
        if isinstance(v, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
            subs.append(_as_jaxpr(v))
        elif isinstance(v, (tuple, list)):
            subs.extend(_as_jaxpr(x) for x in v
                        if isinstance(x, (jex_core.Jaxpr, jex_core.ClosedJaxpr)))
    return subs


def collective_signature(jaxpr):
    """Ordered structural signature of the collectives a jaxpr issues.

    Elements are tuples:
      ("<prim>", axes)                        — a collective eqn
      ("scan", length, inner_sig)             — repeated inner signature
      ("cond", (sig_branch0, sig_branch1...)) — per-branch signatures
      ("while", cond_sig, body_sig)           — unbounded repetition
    Sub-jaxprs of inlining primitives (jit, remat, custom_*) contribute
    their signature in place.  Empty sub-structures are dropped so
    collective-free control flow does not pollute the signature.
    """
    jaxpr = _as_jaxpr(jaxpr)
    sig = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            sig.append((name, collective_axes(eqn)))
        elif name == "cond":
            branches = tuple(collective_signature(b)
                             for b in eqn.params["branches"])
            if any(branches):
                sig.append(("cond", branches))
        elif name == "scan":
            inner = collective_signature(eqn.params["jaxpr"])
            if inner:
                sig.append(("scan", eqn.params.get("length"), inner))
        elif name == "while":
            c = collective_signature(eqn.params["cond_jaxpr"])
            b = collective_signature(eqn.params["body_jaxpr"])
            if c or b:
                sig.append(("while", c, b))
        else:
            for sub in subjaxprs(eqn):
                sig.extend(collective_signature(sub))
    return tuple(sig)


def iter_eqns(jaxpr):
    """Yield every eqn recursively (generic descent into sub-jaxprs)."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in subjaxprs(eqn):
            yield from iter_eqns(sub)


def find_shard_map_bodies(jaxpr):
    """(body_jaxpr, mesh, in_varying) for every shard_map eqn, recursively.

    ``in_varying``: per-invar frozensets of mesh axes the device-local
    block may vary over — the axes its ``in_specs`` entry shards it over
    (a replicated in_spec means every device sees the same value).
    """
    out = []
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            body = _as_jaxpr(eqn.params["jaxpr"])
            mesh = eqn.params.get("mesh")
            varying = []
            for spec in eqn.params.get("in_specs", ()):
                axes = set()
                for entry in spec:       # None | axis name | tuple of names
                    if entry is not None:
                        axes.update(entry if isinstance(entry, tuple)
                                    else (entry,))
                varying.append(frozenset(a for a in axes if isinstance(a, str)))
            # in_specs covers the body invars positionally; pad defensively
            while len(varying) < len(body.invars):
                varying.append(frozenset())
            out.append((body, mesh, varying))
        else:
            for sub in subjaxprs(eqn):
                out.extend(find_shard_map_bodies(sub))
    return out


# -- varying-axes dataflow -------------------------------------------------


def _read(env, atom):
    if isinstance(atom, jex_core.Literal):
        return frozenset()
    return env.get(atom, frozenset())


def varying_out(jaxpr, in_varying, const_varying=None):
    """Propagate varying-axes sets through a jaxpr; returns (env, outs).

    ``env`` maps each jaxpr Var to the frozenset of mesh axes its value may
    vary over; ``outs`` is the list for ``jaxpr.outvars``.  Conservative:
    unknown primitives propagate the union of their inputs; loop carries
    run to fixpoint (sets only grow).
    """
    jaxpr = _as_jaxpr(jaxpr)
    env = {}
    for v, s in zip(jaxpr.invars, in_varying):
        env[v] = frozenset(s)
    for i, v in enumerate(jaxpr.constvars):
        if const_varying is not None and i < len(const_varying):
            env[v] = frozenset(const_varying[i])
        else:
            env[v] = frozenset()

    for eqn in jaxpr.eqns:
        ins = [_read(env, a) for a in eqn.invars]
        union = frozenset().union(*ins) if ins else frozenset()
        name = eqn.primitive.name
        if name == "axis_index":
            outs = [frozenset(collective_axes(eqn))]
        elif name in _UNIFORMIZING_PRIMS:
            axes = frozenset(collective_axes(eqn))
            outs = [union - axes for _ in eqn.outvars]
        elif name in _VARYING_PRIMS:
            axes = frozenset(collective_axes(eqn))
            outs = [union | axes for _ in eqn.outvars]
        elif name == "cond":
            pred = ins[0]
            ops = ins[1:]
            branch_outs = [varying_out(b, ops)[1] for b in eqn.params["branches"]]
            outs = []
            for k in range(len(eqn.outvars)):
                o = frozenset(pred)
                for bo in branch_outs:
                    o |= bo[k]
                outs.append(o)
        elif name == "while":
            cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
            cconsts, bconsts = ins[:cn], ins[cn:cn + bn]
            carry = list(ins[cn + bn:])
            for _ in range(16):  # fixpoint (sets only grow; axes are few)
                _, new = varying_out(eqn.params["body_jaxpr"],
                                     list(bconsts) + carry)
                merged = [c | n for c, n in zip(carry, new)]
                if merged == carry:
                    break
                carry = merged
            _, pred = varying_out(eqn.params["cond_jaxpr"],
                                  list(cconsts) + carry)
            p = pred[0] if pred else frozenset()
            outs = [c | p for c in carry]
        elif name == "scan":
            nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
            consts, carry, xs = ins[:nc], list(ins[nc:nc + ncar]), ins[nc + ncar:]
            body = eqn.params["jaxpr"]
            ys = []
            for _ in range(16):
                _, new = varying_out(body, list(consts) + carry + list(xs))
                new_carry = [c | n for c, n in zip(carry, new[:ncar])]
                ys = new[ncar:]
                if new_carry == carry:
                    break
                carry = new_carry
            outs = carry + list(ys)
        elif name in ("jit", "closed_call", "core_call", "remat",
                      "checkpoint", "custom_jvp_call", "custom_vjp_call"):
            sub = (eqn.params.get("jaxpr")
                   or eqn.params.get("call_jaxpr")
                   or eqn.params.get("fun_jaxpr"))
            if sub is not None and len(_as_jaxpr(sub).invars) == len(ins):
                _, outs = varying_out(sub, ins)
                # defensive: a mismatch in outvar arity falls back below
                if len(outs) != len(eqn.outvars):
                    outs = [union for _ in eqn.outvars]
            else:
                outs = [union for _ in eqn.outvars]
        else:
            outs = [union for _ in eqn.outvars]
        for v, s in zip(eqn.outvars, outs):
            if not isinstance(v, jax_core.DropVar):
                env[v] = s
    return env, [_read(env, v) for v in jaxpr.outvars]


# -- liveness --------------------------------------------------------------


def aval_bytes(aval):
    try:
        return int(np.prod(aval.shape)) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def liveness_peak_bytes(jaxpr, pinned_invars=None):
    """Conservative peak live bytes executing the jaxpr in eqn order.

    A var dies after its last reading eqn; outvars (and ``pinned_invars``,
    e.g. non-donated arguments whose caller keeps the buffer) stay live to
    the end.  Sub-jaxpr internal peaks are added on top of the live set at
    their call site (over-counting operands slightly — conservative in the
    safe direction for an HBM-budget check).
    """
    jaxpr = _as_jaxpr(jaxpr)
    last_use = {}
    n = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for a in eqn.invars:
            if isinstance(a, jex_core.Var):
                last_use[a] = i
    for v in jaxpr.outvars:
        if isinstance(v, jex_core.Var):
            last_use[v] = n
    if pinned_invars:
        for v in pinned_invars:
            last_use[v] = n

    live = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if v in last_use:  # unused inputs can be freed immediately
            live[v] = aval_bytes(v.aval)
    current = sum(live.values())
    peak = current
    for i, eqn in enumerate(jaxpr.eqns):
        inner = 0
        for sub in subjaxprs(eqn):
            inner = max(inner, liveness_peak_bytes(sub))
        for v in eqn.outvars:
            if isinstance(v, jax_core.DropVar) or v not in last_use:
                continue
            live[v] = aval_bytes(v.aval)
            current += live[v]
        peak = max(peak, current + inner)
        for a in set(a for a in eqn.invars if isinstance(a, jex_core.Var)):
            if last_use.get(a) == i and a in live:
                current -= live.pop(a)
    return peak
