"""Summarize a jax.profiler trace: top ops by device time.

Usage:  python tools/trace_summary.py <trace_dir> [--top 25]
                                      [--host-spans spans.trace.json]

Reads the chrome-trace JSON (``*.trace.json.gz``) that
``jax.profiler.trace`` writes under ``<dir>/plugins/profile/<run>/`` and
aggregates complete events on device-side tracks (TPU/accelerator lanes)
by event name — the quick "where do the milliseconds go" view for MFU work
without external profiler tooling.

The chrome-trace event model (loaders, device-lane detection) lives in
:mod:`autodist_tpu.telemetry.timeline` — the one blessed parser
(``tools/lint.py`` AD04) — and is re-exported here for compatibility;
this tool is the human-facing view, ``autodist_tpu/analysis/
runtime_audit.py`` the machine-facing one.

``--host-spans`` joins the host-side span file the telemetry layer dumps
(``host_spans_worker_<rank>.trace.json`` — same wall-clock-microsecond
timebase) against the device lanes: per host span, how much device time
ran concurrently inside its window — the host/device overlap view for
input-pipeline and dispatch-stall hunting (docs/observability.md).
"""
import argparse
import os
import sys
from collections import defaultdict

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from autodist_tpu.telemetry import timeline  # noqa: E402
from autodist_tpu.telemetry.timeline import (DEVICE_PAT,  # noqa: E402,F401
                                             load_events, process_names)

# compatibility alias: tests and older callers import the pattern under
# its historical name
_DEVICE_PAT = DEVICE_PAT


def find_trace_file(trace_dir):
    """Newest trace file under ``trace_dir``; exits with a clear message
    when none exists (CLI contract — the library-side
    :func:`timeline.find_trace_file` returns None instead)."""
    path = timeline.find_trace_file(trace_dir)
    if path is None:
        raise SystemExit(f"no *.trace.json(.gz) under {trace_dir}")
    return path


def summarize(events, device_only=True):
    """name -> (total_us, count), restricted to device tracks when the
    metadata allows telling them apart."""
    pnames = process_names(events)
    device_pids = {pid for pid, n in pnames.items()
                   if DEVICE_PAT.search(n or "")}
    agg = defaultdict(lambda: [0.0, 0])
    total = 0.0
    for e in events:
        if e.get("ph") != "X":
            continue
        if device_only and device_pids and e.get("pid") not in device_pids:
            continue
        dur = float(e.get("dur", 0.0))
        name = e.get("name", "?")
        agg[name][0] += dur
        agg[name][1] += 1
        total += dur
    return agg, total, pnames


def device_intervals(events, pnames=None):
    """Complete events on device tracks as (start_us, end_us) intervals."""
    if pnames is None:
        pnames = process_names(events)
    device_pids = {pid for pid, n in pnames.items()
                   if DEVICE_PAT.search(n or "")}
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        if device_pids and e.get("pid") not in device_pids:
            continue
        ts = float(e.get("ts", 0.0))
        out.append((ts, ts + float(e.get("dur", 0.0))))
    return out


def _overlap_us(window, intervals):
    lo, hi = window
    return sum(max(0.0, min(hi, b) - max(lo, a)) for a, b in intervals)


def join_host_spans(device_events, span_events):
    """Join host spans against device lanes (shared wall-clock-µs
    timebase): per span name -> dict with host total/count and the
    device time that ran concurrently inside the span windows.

    ``device_ms`` double-counts overlapping device lanes (it is a busy
    SUM, like :func:`summarize`'s totals); ``device_share`` therefore
    answers "while the host was in this span, how busy were the
    devices", and can exceed 1.0 on multi-lane captures.
    """
    intervals = device_intervals(device_events)
    rows = {}
    for e in span_events:
        if e.get("ph") not in (None, "X"):
            continue
        name = e.get("name", "?")
        ts = float(e.get("ts", 0.0))
        dur = float(e.get("dur", 0.0))
        row = rows.setdefault(name, {"host_us": 0.0, "count": 0,
                                     "device_us": 0.0})
        row["host_us"] += dur
        row["count"] += 1
        row["device_us"] += _overlap_us((ts, ts + dur), intervals)
    for row in rows.values():
        row["device_share"] = (row["device_us"] / row["host_us"]
                               if row["host_us"] else 0.0)
    return rows


def load_span_events(path):
    """Load host-span events from a telemetry chrome-trace dump (or any
    chrome-trace JSON): complete ("X") events only."""
    events = load_events(path)
    return [e for e in events if e.get("ph") == "X"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--all-tracks", action="store_true",
                    help="include host-side tracks too")
    ap.add_argument("--host-spans", default="",
                    help="telemetry host-span trace JSON to join against "
                         "the device lanes")
    args = ap.parse_args(argv)

    path = find_trace_file(args.trace_dir)
    events = load_events(path)
    agg, total, pnames = summarize(events, device_only=not args.all_tracks)
    device_pids = {pid for pid, n in pnames.items()
                   if DEVICE_PAT.search(n or "")}
    host_only = not device_pids
    if not agg and not host_only:
        # device lanes declared but empty: fall back to every track
        agg, total, pnames = summarize(events, device_only=False)
        print("(device track declared but empty; showing all tracks)")
    elif host_only and not args.all_tracks:
        # no device lane at all (CPU-backend capture, host-side dump):
        # summarize what exists instead of pretending lanes are there
        print("no device events — host-only trace; summarizing host "
              "tracks")
    if not agg:
        print(f"trace: {path}")
        print("no complete ('X') events in this trace — nothing to "
              "summarize")
        return 0
    print(f"trace: {path}")
    print(f"tracks: {sorted(set(filter(None, pnames.values())))[:8]}")
    print(f"total event time: {total / 1e3:.2f} ms over {len(agg)} op names")
    print(f"{'total_ms':>10} {'count':>7} {'share':>6}  name")
    rows = sorted(agg.items(), key=lambda kv: -kv[1][0])[: args.top]
    for name, (us, count) in rows:
        share = us / total if total else 0.0
        print(f"{us / 1e3:10.2f} {count:7d} {share:6.1%}  {name[:90]}")
    if args.host_spans:
        spans = load_span_events(args.host_spans)
        joined = join_host_spans(events, spans)
        print(f"\nhost spans ({args.host_spans}):")
        print(f"{'host_ms':>10} {'count':>7} {'dev_ms':>10} {'dev_share':>9}  span")
        for name, row in sorted(joined.items(),
                                key=lambda kv: -kv[1]["host_us"]):
            print(f"{row['host_us'] / 1e3:10.2f} {row['count']:7d} "
                  f"{row['device_us'] / 1e3:10.2f} "
                  f"{row['device_share']:9.1%}  {name[:80]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
