"""Summarize a jax.profiler trace: top ops by device time.

Usage:  python tools/trace_summary.py <trace_dir> [--top 25]

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

The program's own host spans (``ad.run``, ``ad.dispatch``,
``ad.prefetch.next``, ... — docs/observability.md) are
``jax.profiler.TraceAnnotation``s: they sit in the same profile, on the
host tracks, on the device's clock; ``--all-tracks`` lists them.
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


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--all-tracks", action="store_true",
                    help="include host-side tracks too")
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
