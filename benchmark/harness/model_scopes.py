"""Device time by the model's own scopes (``jax.named_scope``s inside a
model, no ``ad.`` prefix: ``gdn.rule``, ``moe.experts`` ...).

``program_trace.of(run)`` keeps, of an operation's ``op_name``, the engine's
stage only, so the profile is read once more here for the model's scopes:
``program_trace.op_names_by_event_name`` gives the ``op_name`` of every
operation of device 0, and the innermost of ``SCOPES`` in it is the
operation's scope.  The form kept, and the form a test hands in under
``run["model_trace"]``::

    {"ops": [[name, start_ns, duration_ns, scope or None, kind], ...]}

``kind`` is ``"forward"``, ``"recompute"`` or ``"backward"`` by the markers
of ``program_trace``.  Against a program without such scopes (the parent of
the PR that brought them, or another model) every function returns ``None``.
"""
import os
import re

from benchmark.harness import program_trace, trace

SCOPES = ("gdn.proj", "gdn.rule", "attn", "moe.route", "moe.experts",
          "moe.shared")
# the compiler's grouped-matmul kernels (``lax.ragged_dot``): it names them
# itself and drops their ``op_name``; only the expert layer calls them, and a
# forward one cannot be told from a recomputed or a backward one
GROUPED = "ragged-dot"
_SCOPE = re.compile(r"(?<=/)(%s)(?=/)" % "|".join(map(re.escape, SCOPES)))

_cache = {}


def classify(op_name):
    """``(scope, kind)`` of one ``op_name``."""
    found = _SCOPE.findall(op_name or "")
    kind = ("recompute" if program_trace.RECOMPUTE in (op_name or "")
            else "backward" if program_trace.BACKWARD in (op_name or "")
            else "forward")
    return (found[-1] if found else None), kind


def classify_op(name, op_name):
    """``classify`` for the operation ``name`` (``%fusion.12``): a grouped
    product without an ``op_name`` is the expert layer's."""
    scope, kind = classify(op_name)
    if scope is None and trace.stem(name).startswith(GROUPED):
        scope = "moe.experts"
    return scope, kind


def load_xplane(path):
    from jax.profiler import ProfileData

    op_names = program_trace.op_names_by_event_name(path)
    ops = []
    for plane in ProfileData.from_file(path).planes:
        device = trace.DEVICE_PLANE.match(plane.name)
        if not device or int(device.group(1)) != 0:
            continue
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                name = trace.short_event(ev.name)[0]
                ops.append([name, float(ev.start_ns), float(ev.duration_ns),
                            *classify_op(name, op_names.get(ev.name))])
    ops.sort(key=lambda o: o[1])
    return {"ops": ops}


def of(run):
    if run.get("model_trace") is not None:
        return run["model_trace"]
    path = (trace.find_xplane(program_trace.trace_dir(run))
            if run.get("cell") else None)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = load_xplane(path)
    return _cache[key]


def scope_seconds(run):
    """``{(scope, kind): seconds}`` of self time inside the steady window,
    or ``None`` where no operation carries one of ``SCOPES``."""
    rec = of(run)
    if not rec or not run.get("summary") \
            or not any(op[3] for op in rec["ops"]):
        return None
    lo, hi = run["summary"]["window"]
    keyed = [[f"{op[3]}|{op[4]}", op[1], op[2]] for op in rec["ops"]]
    out = {}
    for key, seconds in trace.self_seconds(keyed, lo, hi).items():
        scope, _, kind = key.partition("|")
        if scope != "None":
            out[(scope, kind)] = out.get((scope, kind), 0.0) + seconds
    return out


def scope_ms(run, scope):
    """Milliseconds a step under ``scope`` on device 0 (forward, recomputed
    and backward work together), or ``None``."""
    seconds = scope_seconds(run)
    if seconds is None:
        return None
    mine = [v for (s, _), v in seconds.items() if s == scope]
    if not mine:
        return None
    return 1e3 * sum(mine) / run["summary"]["steps"]


def run_argument_mean(run, argument):
    """Mean over the steady ``ad.run`` spans of a numeric ``argument`` the
    runner wrote there (a loss's auxiliary counters), or ``None``."""
    values = program_trace.span_arguments(run, "ad.run", argument)
    if not values:
        return None
    return sum(float(v) for v in values) / len(values)
