"""From a profiler trace to numbers: busy and idle time, time per step,
collective and exposed-collective time, the top operations, and the idle
gaps by what the host was doing.

A trace here is a list of lanes ``{"plane", "line", "events"}`` with events
``[name, start_ns, duration_ns]`` on one clock.  ``load_xplane`` makes it
from the profiler's ``*.xplane.pb`` (read with ``jax.profiler.ProfileData``);
``load_lanes`` reads the same form back from JSON, which is how the test's
recorded chip trace is kept.  The interval algebra is copied from
``autodist_tpu/telemetry/timeline.py``.
"""
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"    # the flight of each asynchronous operation
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute")
CONTAINER = re.compile(r"^%(while|conditional|call)\b")   # hold other ops
HOST_SPAN = re.compile(r"^bench\.")


# -- loading ----------------------------------------------------------------

def find_xplane(trace_dir):
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


SHORT_NAME = re.compile(r"^(%[^ ]+) = ")
CUSTOM_CALL = re.compile(
    r"^%[^ ]+ = (.*?) custom-call\(.*custom_call_target=\"([^\"]+)\"")


def short_event(text):
    """``(name, detail)`` of a device event.  The trace names an operation by
    its whole HLO text (``%attn.96 = (bf16[512,1024,64]..., f32[...])
    custom-call(...), custom_call_target="tpu_custom_call", ...``): the name
    kept is ``%attn.96``, and for a custom call the detail is its result type
    and target, which is what tells the Pallas kernels apart."""
    m = SHORT_NAME.match(text)
    if not m:
        return text, None
    cc = CUSTOM_CALL.match(text)
    return m.group(1), (f"{cc.group(1)} -> {cc.group(2)}" if cc else None)


def load_xplane(path):
    """Lanes of the device planes' op and module lines, and of every host
    line that carries one of the benchmark's spans."""
    from jax.profiler import ProfileData

    lanes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE,
                                            ASYNC_LINE):
                continue
            events = []
            for ev in line.events:
                if not device and not HOST_SPAN.match(ev.name):
                    continue
                name, detail = (short_event(ev.name) if device
                                else (ev.name, None))
                if line.name == ASYNC_LINE and not COLLECTIVE.search(name):
                    continue        # copies and slices in flight: not kept
                row = [name, float(ev.start_ns), float(ev.duration_ns)]
                if detail:
                    row.append(detail)
                events.append(row)
            if events:
                lanes.append({"plane": plane.name, "line": line.name,
                              "events": events})
    return lanes


def load_lanes(path):
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt") as f:
        return json.load(f)


def save_lanes(lanes, path):
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "wt") as f:
        json.dump(lanes, f, separators=(",", ":"))


def device_planes(lanes):
    """Device plane names, in the order of their device number."""
    names = {lane["plane"] for lane in lanes if DEVICE_PLANE.match(lane["plane"])}
    return sorted(names, key=lambda n: int(DEVICE_PLANE.match(n).group(1)))


def events_of(lanes, plane, line):
    out = []
    for lane in lanes:
        if lane["plane"] == plane and lane["line"] == line:
            out.extend(lane["events"])
    return sorted(out, key=lambda e: e[1])


def host_spans(lanes):
    """The benchmark's spans from the host lines: ``[name, start, dur]``."""
    out = []
    for lane in lanes:
        if lane["plane"].startswith("/host:"):
            out.extend(e for e in lane["events"] if HOST_SPAN.match(e[0]))
    return sorted(out, key=lambda e: e[1])


# -- interval algebra -------------------------------------------------------

def merge_intervals(intervals):
    """Overlapping or touching ``(start, end)`` -> their disjoint union."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def interval_total(merged):
    return sum(hi - lo for lo, hi in merged)


def interval_intersection(a, b):
    """Total length of the intersection of two disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip(events, lo, hi):
    """``(start, end)`` of the events' parts inside ``[lo, hi]``."""
    out = []
    for _, start, dur in (e[:3] for e in events):
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def complement(merged, lo, hi):
    gaps, at = [], lo
    for a, b in merged:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


# -- reduction --------------------------------------------------------------

def step_events(lanes, plane):
    """The module events of the training step on ``plane``: those of the
    module that took most of the device's time."""
    by_name = {}
    for e in events_of(lanes, plane, MODULES_LINE):
        by_name.setdefault(re.sub(r"\(\d+\)$", "", e[0]), []).append(e)
    if not by_name:
        return []
    return max(by_name.values(), key=lambda evs: sum(e[2] for e in evs))


def steady_window(lanes, plane):
    """``(lo, hi, steps)``: from the start of the second step event in the
    trace to the end of the last but one.  The profiler starts and stops
    while a step runs, so the first and the last are cut, and starting it
    stalls the host before the first.  ``None`` with fewer than three."""
    steps = step_events(lanes, plane)[1:-1]
    if not steps:
        return None
    return steps[0][1], steps[-1][1] + steps[-1][2], len(steps)


def stem(name):
    """``%convolution_add_fusion.149`` -> ``convolution_add_fusion``."""
    return re.sub(r"\.\d+$", "", name.lstrip("%"))


def self_seconds(events, lo, hi):
    """Seconds per name stem inside ``[lo, hi]``, each event counted for the
    time in which no event nested in it runs (a ``while`` holds its body's
    operations on the same line)."""
    out = {}
    stack = []          # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for (name, *_), (a, b) in zip(
            (e for e in events if min(e[1] + e[2], hi) > max(e[1], lo)),
            clip(events, lo, hi)):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([stem(name), b, b - a])
    close(float("inf"))
    return out


def kernel_calls(lanes, plane, lo, hi, target="tpu_custom_call"):
    """``[name, seconds, result type]`` of each custom call to ``target``
    (the Pallas kernels) that runs whole inside ``[lo, hi]``."""
    out = []
    for e in events_of(lanes, plane, OPS_LINE):
        if len(e) > 3 and e[3].endswith("-> " + target) \
                and e[1] >= lo and e[1] + e[2] <= hi:
            out.append([e[0], e[2] / 1e9, e[3].rsplit(" -> ", 1)[0]])
    return out


def summarize(lanes, top=10):
    """The reduction of one trace, seconds throughout.

    Per device plane: busy (union of op intervals inside the steady window),
    collective time (the collective operations on the op line and the
    flights of the asynchronous ones) and its exposed part (in which no
    other operation runs there).
    Over device 0: seconds per operation name (self time, instances of one
    name summed) and the idle gaps with the host span that covers most of
    each.  ``None`` if no device plane has a whole step between two others.
    """
    planes = device_planes(lanes)
    if not planes:
        return None
    first = steady_window(lanes, planes[0])
    if first is None:
        return None
    out = {"planes": planes, "steps": first[2],
           "window_s": (first[1] - first[0]) / 1e9, "per_device": []}
    for plane in planes:
        win = steady_window(lanes, plane)
        if win is None:
            continue
        lo, hi, _ = win
        ops = events_of(lanes, plane, OPS_LINE)
        coll = [e for e in ops if COLLECTIVE.search(e[0])]
        coll += events_of(lanes, plane, ASYNC_LINE)
        rest = [e for e in ops if not COLLECTIVE.search(e[0])
                and not CONTAINER.match(e[0])]
        busy = merge_intervals(clip(ops, lo, hi))
        coll_iv = merge_intervals(clip(coll, lo, hi))
        rest_iv = merge_intervals(clip(rest, lo, hi))
        coll_s = interval_total(coll_iv)
        out["per_device"].append({
            "plane": plane, "window_s": (hi - lo) / 1e9,
            "busy_s": interval_total(busy) / 1e9,
            "collective_s": coll_s / 1e9,
            "collective_exposed_s":
                (coll_s - interval_intersection(coll_iv, rest_iv)) / 1e9,
        })
    lo, hi, _ = first
    out["window"] = (lo, hi)
    ops = events_of(lanes, planes[0], OPS_LINE)
    by_op = self_seconds(ops, lo, hi)
    out["device_ops"] = [[n, t] for n, t in sorted(
        by_op.items(), key=lambda kv: -kv[1])[:top]]
    busy0 = merge_intervals(clip(ops, lo, hi))
    spans = host_spans(lanes)
    gaps = []
    for a, b in complement(busy0, lo, hi):
        cover = {}
        for name, start, dur in (s[:3] for s in spans):
            part = min(b, start + dur) - max(a, start)
            if part > 0:
                cover[name] = cover.get(name, 0.0) + part
        owner = max(cover, key=cover.get) if cover else "host.other"
        gaps.append([owner, (b - a) / 1e9])
    out["idle_gaps"] = sorted(gaps, key=lambda g: -g[1])[:top]
    n = len(out["per_device"])
    out["busy_s"] = sum(d["busy_s"] for d in out["per_device"]) / n
    return out
