"""tools/trace_summary.py on synthetic chrome traces.

Pins the top-ops aggregation (device-track filtering, totals, counts) on a
small hand-built trace — no profiler run needed, so the numbers are exact.
"""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tools.trace_summary import (device_intervals, find_trace_file,  # noqa: E402
                                 load_events, summarize)
from tools import trace_summary  # noqa: E402

# two lanes: pid 1 is a device track (name matches the device pattern),
# pid 2 is host-side python and must be excluded by device_only
SYNTHETIC_EVENTS = [
    {"ph": "M", "name": "process_name", "pid": 1,
     "args": {"name": "/device:TPU:0"}},
    {"ph": "M", "name": "process_name", "pid": 2,
     "args": {"name": "python host"}},
    {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 1000, "dur": 100},
    {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.1", "ts": 2000, "dur": 50},
    {"ph": "X", "pid": 1, "tid": 2, "name": "copy.2", "ts": 1500, "dur": 30},
    {"ph": "X", "pid": 2, "tid": 9, "name": "host_thing", "ts": 0, "dur": 9999},
    # non-complete events must be ignored by the aggregation
    {"ph": "B", "pid": 1, "tid": 1, "name": "begin.only", "ts": 100},
]

def _write_trace(tmp_path, gz=True):
    run_dir = tmp_path / "plugins" / "profile" / "run1"
    run_dir.mkdir(parents=True)
    payload = json.dumps({"traceEvents": SYNTHETIC_EVENTS})
    if gz:
        path = run_dir / "host.trace.json.gz"
        with gzip.open(path, "wt") as f:
            f.write(payload)
    else:
        path = run_dir / "host.trace.json"
        path.write_text(payload)
    return str(path)


def test_find_and_load_gz(tmp_path):
    path = _write_trace(tmp_path, gz=True)
    assert find_trace_file(str(tmp_path)) == path
    events = load_events(path)
    assert len(events) == len(SYNTHETIC_EVENTS)


def test_top_ops_aggregation_device_only(tmp_path):
    events = load_events(_write_trace(tmp_path, gz=False))
    agg, total, pnames = summarize(events, device_only=True)
    # host_thing (pid 2) and the "B" event are excluded; totals are exact
    assert set(agg) == {"fusion.1", "copy.2"}
    assert agg["fusion.1"] == [150.0, 2]
    assert agg["copy.2"] == [30.0, 1]
    assert total == 180.0
    assert pnames[1] == "/device:TPU:0"


def test_all_tracks_includes_host():
    agg, total, _ = summarize(SYNTHETIC_EVENTS, device_only=False)
    assert "host_thing" in agg
    assert total == 180.0 + 9999.0


def test_device_intervals_filters_host():
    ivs = device_intervals(SYNTHETIC_EVENTS)
    assert (0.0, 9999.0) not in ivs
    assert (1000.0, 1100.0) in ivs and (1500.0, 1530.0) in ivs


def test_main_host_only_trace_degrades_gracefully(tmp_path, capsys):
    # a CPU/host-only capture has no device-pattern lane — the CLI must
    # say so and summarize the host tracks instead of printing nothing
    run_dir = tmp_path / "plugins" / "profile" / "run1"
    run_dir.mkdir(parents=True)
    host_only = [
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "python host"}},
        {"ph": "X", "pid": 2, "tid": 9, "name": "host_thing",
         "ts": 0, "dur": 500},
    ]
    (run_dir / "host.trace.json").write_text(
        json.dumps({"traceEvents": host_only}))
    rc = trace_summary.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no device events — host-only trace" in out
    assert "host_thing" in out


def test_main_trace_without_complete_events(tmp_path, capsys):
    # metadata only, zero 'X' events: still exits 0 with a clear message
    run_dir = tmp_path / "plugins" / "profile" / "run1"
    run_dir.mkdir(parents=True)
    meta_only = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "B", "pid": 1, "tid": 1, "name": "begin.only", "ts": 100},
    ]
    (run_dir / "host.trace.json").write_text(
        json.dumps({"traceEvents": meta_only}))
    rc = trace_summary.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no complete ('X') events" in out
