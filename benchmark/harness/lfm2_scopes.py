"""Device time under the short-convolution mixer's scopes (``sconv.proj``,
``sconv.mix``: ``jax.named_scope``s of ``autodist_tpu/models/lfm2.py``).

Built as ``model_scopes.py`` is, whose fixed tuple of scopes does not hold
these two: ``program_trace.op_names_by_event_name`` gives the ``op_name`` of
every operation of device 0, the innermost of ``SCOPES`` in it is the
operation's scope, and ``trace.self_seconds`` the self time.  The form kept,
and the form a test hands in under ``run["sconv_trace"]``::

    {"ops": [[name, start_ns, duration_ns, scope or None], ...]}

Against a program without such scopes every function returns ``None``.
"""
import os
import re

from benchmark.harness import program_trace, trace

SCOPES = ("sconv.proj", "sconv.mix")
_SCOPE = re.compile(r"(?<=/)(%s)(?=/)" % "|".join(map(re.escape, SCOPES)))

_cache = {}


def classify(op_name):
    """The innermost of ``SCOPES`` in one ``op_name``, or ``None``."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


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
                ops.append([trace.short_event(ev.name)[0],
                            float(ev.start_ns), float(ev.duration_ns),
                            classify(op_names.get(ev.name))])
    ops.sort(key=lambda o: o[1])
    return {"ops": ops}


def of(run):
    if run.get("sconv_trace") is not None:
        return run["sconv_trace"]
    path = (trace.find_xplane(program_trace.trace_dir(run))
            if run.get("cell") else None)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = load_xplane(path)
    return _cache[key]


def scope_ms(run, scope):
    """Milliseconds a step of self time under ``scope`` on device 0 inside
    the steady window (forward, recomputed and backward work together), or
    ``None`` where no operation carries it."""
    rec = of(run)
    if not rec or not run.get("summary"):
        return None
    lo, hi = run["summary"]["window"]
    keyed = [[str(op[3]), op[1], op[2]] for op in rec["ops"]]
    seconds = trace.self_seconds(keyed, lo, hi).get(scope)
    if not seconds:
        return None
    return 1e3 * seconds / run["summary"]["steps"]
