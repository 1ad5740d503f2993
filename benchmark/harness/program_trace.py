"""What the program itself wrote into a traced run's profile: its host spans
(``ad.run``, ``ad.dispatch``, ``ad.prefetch.next``, ... with their
arguments) and, for each operation on device 0, the stage of the step it
belongs to (the innermost ``ad.`` scope of its ``op_name``, whether it is
backward work, whether it is forward work run again under ``remat``).

``run.py`` keeps lanes that have dropped everything but the ``bench.*``
spans and the short operation names, so the profile is read again here, from
``<repo>/.benchmark_work/<cell>/trace``, once per file.  The form it is kept
in, which is also the form of the test's recorded chip trace::

    {"op_name_from": "...",
     "spans": [[name, start_ns, duration_ns, {argument: value}, thread], ...],
     "ops":   [[name, start_ns, duration_ns, scope, backward, recompute], ...]}

``spans`` are the ``ad.*`` and ``bench.*`` events of the host threads,
``ops`` the events of device 0's ``XLA Ops`` line; ``scope`` is ``None`` for
an operation whose metadata names no ``ad.`` scope.  Against a program that
has no such scopes or spans every function here returns ``None``.
"""
import os
import re

from benchmark.harness import cells, trace

SCOPE = re.compile(r"\bad\.[a-z_]+")
BACKWARD = "transpose("
# jax.checkpoint marks the forward operations it runs a second time in the
# backward pass; ``checkpoint`` alone also sits on the true backward ones
RECOMPUTE = "rematted_computation"
PROGRAM_SPAN = re.compile(r"^(ad|bench)\.")
OP_NAME_STAT = "tf_op"        # "<op_name>:<op_type>" on an operation's metadata

PHASES = ("forward", "backward", "update", "sync", "unscoped")
_PHASE_OF_SCOPE = {"ad.clip": "update", "ad.update": "update",
                   "ad.materialize": "sync", "ad.sync": "sync",
                   "ad.gather": "sync"}

_cache = {}


def classify(op_name):
    """``(scope, backward, recompute)`` of one ``op_name``.  The scope is the
    last ``ad.`` name in it: JAX repeats the enclosing scope inside its
    ``transpose(jvp(...))`` wrapper, and a sync nested in the accumulation
    scan reads ``.../ad.grad/.../ad.sync/...``."""
    found = SCOPE.findall(op_name or "")
    return (found[-1] if found else None, BACKWARD in (op_name or ""),
            RECOMPUTE in (op_name or ""))


# -- the profile's file, as far as ``ProfileData`` does not show it ---------
#
# The device plane names every operation's ``op_name`` in the ``tf_op`` stat
# of the operation's *metadata* (``XEventMetadata.stats``).  ``ProfileData``
# (jax 0.9) gives an event's name, times and own stats, not its metadata's,
# so the metadata table is read from the file's protobuf wire format here:
# ``XSpace.planes = 1``; ``XPlane``: ``name = 2``, ``event_metadata = 4``
# (map entry: value = 2), ``stat_metadata = 5`` (map entry: value = 2);
# ``XEventMetadata``: ``name = 2``, ``stats = 5``; ``XStat``:
# ``metadata_id = 1``, ``str_value = 5``, ``ref_value = 7``;
# ``XStatMetadata``: ``id = 1``, ``name = 2``.

def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf, lo, hi):
    """``(field, wire type, value)`` of one message in ``buf[lo:hi]``: an int
    for a varint, ``(start, end)`` for a length-delimited field."""
    at = lo
    while at < hi:
        key, at = _varint(buf, at)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = (at, at + size), at + size
        elif wire == 1:
            value, at = None, at + 8
        elif wire == 5:
            value, at = None, at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield field, wire, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    for field, wire, value in _fields(buf, *span):
        if field == 2 and wire == 2:
            return value
    return None


def op_names_by_event_name(path):
    """``{event name: op_name}`` for the operations of the first device
    plane of ``path``; empty where the plane carries no ``tf_op``."""
    import mmap

    with open(path, "rb") as f, \
            mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        for field, wire, plane in _fields(buf, 0, len(buf)):
            if field != 1 or wire != 2:
                continue
            name, events, stats = None, [], []
            for pf, pw, value in _fields(buf, *plane):
                if pf == 2 and pw == 2:
                    name = _text(buf, value)
                elif pf == 4 and pw == 2:
                    events.append(value)
                elif pf == 5 and pw == 2:
                    stats.append(value)
            if not name or not trace.DEVICE_PLANE.match(name) \
                    or int(trace.DEVICE_PLANE.match(name).group(1)) != 0:
                continue
            stat_names = {}
            for entry in stats:
                sid = sname = None
                for sf, sw, value in _fields(buf, *_map_value(buf, entry)):
                    if sf == 1 and sw == 0:
                        sid = value
                    elif sf == 2 and sw == 2:
                        sname = _text(buf, value)
                stat_names[sid] = sname
            wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
            out = {}
            for entry in events:
                ev_name = op_name = None
                for ef, ew, value in _fields(buf, *_map_value(buf, entry)):
                    if ef == 2 and ew == 2:
                        ev_name = _text(buf, value)
                    elif ef == 5 and ew == 2:
                        stat = dict((sf, v) for sf, _, v
                                    in _fields(buf, *value))
                        if stat.get(1) in wanted:
                            op_name = (_text(buf, stat[5]) if 5 in stat
                                       else stat_names.get(stat.get(7)))
                if ev_name and op_name:
                    out[ev_name] = op_name
            return out
    return {}


def load_xplane(path):
    """The record of one ``*.xplane.pb``."""
    from jax.profiler import ProfileData

    op_names = op_names_by_event_name(path)
    spans, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        device = trace.DEVICE_PLANE.match(plane.name)
        if device and int(device.group(1)) == 0:
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([trace.short_event(ev.name)[0],
                                float(ev.start_ns), float(ev.duration_ns),
                                *classify(op_names.get(ev.name))])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if PROGRAM_SPAN.match(ev.name):
                        args = {k: v for k, v in ev.stats
                                if not k.startswith("_")}
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns), args,
                                      line.name])
    spans.sort(key=lambda s: (s[1], -s[2]))
    ops.sort(key=lambda o: o[1])
    return {"op_name_from": "tf_op of the operation's metadata"
            if op_names else None, "spans": spans, "ops": ops}


def trace_dir(run):
    return os.path.join(cells.REPO_DIR, ".benchmark_work",
                        run["cell"]["name"], "trace")


def of(run):
    """The record of this run's profile: what a test handed in under
    ``run["program_trace"]``, else the traced run's own file (read once)."""
    if run.get("program_trace") is not None:
        return run["program_trace"]
    path = trace.find_xplane(trace_dir(run)) if run.get("cell") else None
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = load_xplane(path)
    return _cache[key]


# -- the device side: self time per phase of the step ------------------------

def phase_of(scope, backward):
    if scope == "ad.grad":
        return "backward" if backward else "forward"
    return _PHASE_OF_SCOPE.get(scope, "unscoped")


def phase_seconds(run):
    """Seconds inside the steady window per phase of the step, self time (a
    ``while`` holds its body on the same line), and ``recompute``, the part
    of ``backward`` that is forward work run again.  ``None`` where no
    operation carries an ``ad.`` scope."""
    rec = of(run)
    if not rec or not run.get("summary") \
            or not any(op[3] for op in rec["ops"]):
        return None
    lo, hi = run["summary"]["window"]
    keyed = [[phase_of(op[3], op[4]) + ("+recompute" if op[5] else ""),
              op[1], op[2]] for op in rec["ops"]]
    by_key = trace.self_seconds(keyed, lo, hi)
    out = {p: 0.0 for p in PHASES + ("recompute",)}
    for key, seconds in by_key.items():
        phase, _, again = key.partition("+")
        out[phase] += seconds
        if again and phase == "backward":
            out["recompute"] += seconds
    return out


def phase_ms(run, phase):
    """Milliseconds a step of ``phase`` on device 0, or ``None``."""
    seconds = phase_seconds(run)
    if seconds is None:
        return None
    return 1e3 * seconds[phase] / run["summary"]["steps"]


# -- the host side: the program's spans over the steady steps ----------------

def steady_host_window(rec):
    """``(lo, hi, steps)`` on the host: from the start of the second
    ``bench.input_wait`` in the profile to the start of the last, which are
    the iterations whose spans the benchmark itself averages (it drops the
    one at either edge of the traced stretch)."""
    waits = [s for s in rec["spans"] if s[0] == "bench.input_wait"]
    if len(waits) < 3:
        return None
    return waits[1][1], waits[-1][1], len(waits) - 2


def steady_spans(run, name, under=None):
    """The spans ``name`` that start inside the steady host window, only
    those inside a span ``under`` on the same thread if that is given, and
    the number of steps in the window.  ``None`` where the program wrote no
    such span."""
    rec = of(run)
    if not rec:
        return None
    win = steady_host_window(rec)
    if win is None:
        return None
    lo, hi, steps = win
    mine = [s for s in rec["spans"] if s[0] == name and lo <= s[1] < hi]
    if under is not None:
        parents = [s for s in rec["spans"] if s[0] == under]
        mine = [s for s in mine if any(
            p[4] == s[4] and p[1] <= s[1] and s[1] + s[2] <= p[1] + p[2]
            for p in parents)]
    if not mine:
        return None
    return mine, steps


def span_ms(run, name, under=None):
    """Mean milliseconds a step spends in the spans ``name``."""
    found = steady_spans(run, name, under)
    if found is None:
        return None
    spans, steps = found
    return 1e-6 * sum(s[2] for s in spans) / steps


def span_arguments(run, name, argument):
    """The values of ``argument`` on the steady spans ``name``."""
    found = steady_spans(run, name)
    if found is None:
        return None
    values = [s[3][argument] for s in found[0] if argument in s[3]]
    return values or None


def idle_in_program_ms(run):
    """Milliseconds a step in which device 0 runs nothing while some thread
    of the host is inside an ``ad.*`` span."""
    rec = of(run)
    if not rec or not run.get("summary"):
        return None
    program = [s for s in rec["spans"] if s[0].startswith("ad.")]
    if not program:
        return None
    lo, hi = run["summary"]["window"]
    busy = trace.merge_intervals(trace.clip(rec["ops"], lo, hi))
    idle = trace.complement(busy, lo, hi)
    inside = trace.merge_intervals(trace.clip(program, lo, hi))
    return 1e-6 * trace.interval_intersection(idle, inside) \
        / run["summary"]["steps"]
