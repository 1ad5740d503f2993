"""Render a telemetry run manifest into a human-readable summary.

Usage:  python tools/telemetry_report.py <run_dir | manifest.jsonl> [--json]

Reads the JSONL manifest a telemetry-enabled run writes (per-worker
files are merged in memory when the chief's ``manifest.jsonl`` is
absent; schema in ``autodist_tpu/telemetry/schema.py``) and reports:

- step-time percentiles (RTT-cancelled walls) + compile split,
- throughput and achieved-MFU percentiles (MFU only where the device
  kind has an entry in the peak table),
- HBM peak and headroom against the device generation's budget (when
  the backend reports ``memory_stats`` and the kind is recognized),
- predicted comm/compute overlap from the recorded cost estimate next
  to the measured walls (predicted-vs-measured error),
- async-PS staleness counters and watchdog captures when present,
- the serving block when the manifest came from the decode tier
  (tokens/sec, TTFT/latency percentiles) including the schema-v5 TTFT
  phase breakdown — queue -> prefill -> handoff -> first decode — so
  the dominant phase a Q003 breach names is visible at a glance,
- with ``--audit <report.json>`` (the ``tools/verify_strategy.py --hlo
  --json`` output, or an ``AutoStrategy.last_audit`` dump): the HLO
  communication audit's INTENDED vs REALIZED wire bytes per phase, next
  to the cost model's PREDICTED bytes and the run's MEASURED walls — the
  full plan -> lowering -> hardware chain in one table,
- with ``--compute <report.json>`` (the ``tools/verify_strategy.py
  --compute --json`` output, or an ``AutoStrategy.last_compute_audit``
  dump): the HLO compute audit's F006 table — model vs realized FLOPs,
  per-region attribution, recompute — with the PREDICTED MFU ceiling
  joined against the run's MEASURED achieved MFU: a measured MFU close
  to the ceiling means the gap is structural (recompute, lowering-added
  work), not a launch/overlap problem,
- with ``--timeline [report.json]`` (the ``tools/verify_strategy.py
  --runtime --json`` output, or a bare T006 ``data`` dump): the runtime
  audit's three-way table — predicted vs statically-realized vs MEASURED
  step decomposition, per-hop predicted-vs-measured bandwidth error,
  worker skew, and the overlap reconciliation; with no artifact argument
  the tables come from the manifest itself (the ``runtime_finding``
  records a SlowStepWatchdog capture auto-writes),
- with ``--health [BASELINE]`` (a blessed baseline name under
  ``records/baselines`` or a baseline JSON path; default: look one up by
  the run id): the run's health verdict — the HealthMonitor's
  ``health_finding`` records (NaN/Inf, loss/grad spikes, step-time
  drift) and counts — plus the cross-run R-code diff
  (:mod:`autodist_tpu.analysis.regression_audit`) against the baseline.

Merge hygiene: when the per-worker manifests are merged (or a chief
manifest is parsed), lines the reader skipped (torn writes) and
duplicate records dropped are surfaced as ``merge_hygiene`` — nonzero
counts mean the manifest needs attention before its numbers are trusted.

Live runs: ``--follow`` tails a GROWING run dir (per-worker manifests
plus the ``events.jsonl`` cluster event log) and re-renders a compact
status line every ``--interval`` seconds — no finalized summary trailer
is required, so it works mid-run; ``--max-updates N`` bounds the loop
for CI (default: until interrupted).
"""
import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from autodist_tpu.telemetry import (load_manifest_with_stats,  # noqa: E402
                                    percentiles)


def _fmt_s(x):
    if x is None:
        return "-"
    if x >= 1.0:
        return f"{x:.3f}s"
    return f"{x * 1e3:.3f}ms"


def _fmt_bytes(x):
    for unit, div in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if x >= div:
            return f"{x / div:.2f}{unit}"
    return f"{x}B"


def _hbm_budget(device_kind):
    try:
        from autodist_tpu.aot import HBM_BY_DEVICE_KIND

        for key, budget in HBM_BY_DEVICE_KIND.items():
            if device_kind and device_kind.startswith(key):
                return budget
    except Exception:
        pass
    return None


def summarize_manifest(records, stats=None):
    """Manifest records -> summary dict (the --json payload)."""
    meta = next((r for r in records if r.get("kind") == "meta"), {})
    steps = [r for r in records if r.get("kind") == "step"]
    snaps = [r for r in records if r.get("kind") == "snapshot"]
    summaries = [r for r in records if r.get("kind") == "summary"]
    watchdogs = [r for r in records if r.get("kind") == "watchdog"]

    walls = [r.get("wall_cancelled_s", r.get("wall_s")) for r in steps[1:]] \
        or [r.get("wall_cancelled_s", r.get("wall_s")) for r in steps]
    walls = [w for w in walls if w is not None]
    ps = percentiles(walls)
    out = {
        "run_id": meta.get("run_id"),
        "backend": meta.get("backend"),
        "device_kind": meta.get("device_kind"),
        "num_devices": meta.get("num_devices"),
        "workers": sorted({r.get("w", 0) for r in records}),
        "steps": len(steps),
        "step_time_p50_s": ps[0.5], "step_time_p90_s": ps[0.9],
        "step_time_p99_s": ps[0.99],
        "watchdog_captures": len(watchdogs),
    }
    thr = [r["throughput_eps"] for r in steps if "throughput_eps" in r]
    if thr:
        out["throughput_eps_p50"] = percentiles(thr)[0.5]
    mfus = [r["mfu"] for r in steps if "mfu" in r]
    if mfus:
        out["mfu_p50"] = percentiles(mfus)[0.5]
    for s in summaries:
        if "compile_s" in s:
            out["compile_s"] = s["compile_s"]
        if "runtime_record" in s:
            out.setdefault("runtime_records", []).append(s["runtime_record"])
    peaks = [r["peak_bytes"] for r in snaps if r.get("peak_bytes") is not None]
    if peaks:
        out["hbm_peak_bytes"] = max(peaks)
        budget = _hbm_budget(meta.get("device_kind", ""))
        if budget:
            out["hbm_budget_bytes"] = budget
            out["hbm_headroom_bytes"] = budget - max(peaks)
    hier = meta.get("hierarchy")
    if hier:
        out["hierarchy"] = hier
    est = meta.get("cost_estimate")
    if est:
        out["predicted"] = {
            "total_s": est.get("total_s"),
            "serialized_s": est.get("serialized_s"),
            "overlapped_s": est.get("overlapped_s"),
            "schedule": est.get("schedule"),
        }
        # per-hop predicted comm time of the two-level schedule, next to
        # the recorded per-hop wire volumes (meta["hierarchy"])
        if est.get("hier_ici_s") or est.get("hier_dcn_s"):
            out["predicted"]["ici_hop_s"] = est.get("hier_ici_s")
            out["predicted"]["dcn_hop_s"] = est.get("hier_dcn_s")
        ser, ovl = est.get("serialized_s"), est.get("overlapped_s")
        if ser and ovl is not None and ser > 0:
            # the overlap credit the schedule is predicted to earn: 0 =
            # fully serialized, higher = more comm hidden behind compute
            out["predicted_overlap_credit"] = 1.0 - ovl / ser
        if ps[0.5] and est.get("total_s"):
            out["predicted_vs_measured_rel_error"] = (
                (est["total_s"] - ps[0.5]) / ps[0.5])
    # async-PS staleness counters, surfaced from any summary's aggregates
    for s in summaries:
        counters = (s.get("aggregates") or {}).get("counters", {})
        for key in ("async_ps.pushes", "async_ps.stale_pushes"):
            if key in counters:
                out.setdefault("async_ps", {})[key.split(".", 1)[1]] = \
                    counters[key]
    # merge hygiene: torn lines skipped + duplicates dropped — from the
    # reader's own parse stats AND any counters the run recorded (the
    # same merge may be counted in both places, so take the max)
    hygiene = {"skipped_lines": 0, "skipped_duplicates": 0}
    for k in hygiene:
        if stats:
            hygiene[k] = max(hygiene[k], int(stats.get(k, 0) or 0))
        for s in summaries:
            counters = (s.get("aggregates") or {}).get("counters", {})
            hygiene[k] = max(hygiene[k],
                             int(counters.get(f"aggregate.{k}", 0) or 0))
    out["merge_hygiene"] = hygiene
    # the run's own health verdict, surfaced from any summary
    for s in summaries:
        if s.get("health"):
            out["health"] = s["health"]
    # the serving block (decode-tier manifests), surfaced from any summary
    for s in summaries:
        if s.get("serving"):
            out["serving"] = s["serving"]
    return out


def render(summary):
    lines = []
    add = lines.append
    add(f"run {summary.get('run_id')} — backend={summary.get('backend')} "
        f"({summary.get('device_kind')}), "
        f"{summary.get('num_devices')} device(s), "
        f"workers={summary.get('workers')}")
    add(f"steps: {summary['steps']}   "
        f"p50 {_fmt_s(summary['step_time_p50_s'])}   "
        f"p90 {_fmt_s(summary['step_time_p90_s'])}   "
        f"p99 {_fmt_s(summary['step_time_p99_s'])}")
    if "compile_s" in summary:
        add(f"compile (first-step estimate): {_fmt_s(summary['compile_s'])}")
    if "throughput_eps_p50" in summary:
        add(f"throughput p50: {summary['throughput_eps_p50']:.1f} examples/s")
    if "mfu_p50" in summary:
        add(f"achieved MFU p50: {summary['mfu_p50']:.4%}")
    if "hbm_peak_bytes" in summary:
        line = f"HBM peak: {_fmt_bytes(summary['hbm_peak_bytes'])}"
        if "hbm_headroom_bytes" in summary:
            line += (f" of {_fmt_bytes(summary['hbm_budget_bytes'])} "
                     f"(headroom {_fmt_bytes(summary['hbm_headroom_bytes'])})")
        add(line)
    hier = summary.get("hierarchy")
    if hier and hier.get("mode") == "two_level":
        add(f"sync hierarchy: two_level "
            f"(replica_dcn={hier.get('replica_dcn')} x "
            f"replica_ici={hier.get('replica_ici')}) — "
            f"ICI hops {_fmt_bytes(int(hier.get('ici_hop_bytes', 0)))}, "
            f"DCN hop {_fmt_bytes(int(hier.get('dcn_hop_bytes', 0)))}"
            + (f" [{'/'.join(hier['dcn_compressors'])} on DCN]"
               if hier.get("dcn_compressors") else ""))
    pred = summary.get("predicted")
    if pred:
        add(f"cost model: predicted {_fmt_s(pred.get('total_s'))} "
            f"({pred.get('schedule')} schedule)")
        if pred.get("ici_hop_s") is not None or pred.get("dcn_hop_s") is not None:
            add(f"  per-hop comm: ICI {_fmt_s(pred.get('ici_hop_s'))} + "
                f"DCN {_fmt_s(pred.get('dcn_hop_s'))} (measured wall "
                f"p50 {_fmt_s(summary.get('step_time_p50_s'))})")
        if "predicted_overlap_credit" in summary:
            add(f"  comm/compute overlap credit: "
                f"{summary['predicted_overlap_credit']:.1%} "
                f"(serialized {_fmt_s(pred.get('serialized_s'))} -> "
                f"overlapped {_fmt_s(pred.get('overlapped_s'))})")
        if "predicted_vs_measured_rel_error" in summary:
            add(f"  predicted vs measured: "
                f"{summary['predicted_vs_measured_rel_error']:+.1%} "
                f"(refit with cost_model.calibrate_from_records on "
                f"the run's RuntimeRecords if large)")
    if summary.get("async_ps"):
        a = summary["async_ps"]
        add(f"async PS: {a.get('pushes', 0):.0f} pushes, "
            f"{a.get('stale_pushes', 0):.0f} stale")
    if summary.get("watchdog_captures"):
        add(f"watchdog captures: {summary['watchdog_captures']}")
    if summary.get("runtime_records"):
        add("runtime records: " + ", ".join(summary["runtime_records"]))
    hygiene = summary.get("merge_hygiene") or {}
    if any(hygiene.values()):
        add(f"MERGE HYGIENE: {hygiene.get('skipped_lines', 0)} torn "
            f"line(s) skipped, {hygiene.get('skipped_duplicates', 0)} "
            f"duplicate record(s) dropped — inspect the per-worker "
            f"manifests before trusting these numbers")
    health = summary.get("health") or {}
    if health.get("counts"):
        add("health: " + ", ".join(
            f"{k}={v}" for k, v in sorted(health["counts"].items()))
            + " (details with --health)")
    serving = summary.get("serving") or {}
    if serving:
        add(f"serving: {serving.get('requests', 0)} request(s), "
            f"{serving.get('tokens_per_s', 0.0):.1f} tok/s, "
            f"TTFT p99 {_fmt_s(serving.get('ttft_p99_s'))}, "
            f"latency p99 {_fmt_s(serving.get('latency_p99_s'))}, "
            f"occupancy {serving.get('occupancy_mean', 0.0):.0%}")
        phases = serving.get("ttft_phases") or {}
        parts = []
        for key in ("queue_s", "prefill_s", "handoff_s",
                    "first_decode_s"):
            p = phases.get(key)
            if isinstance(p, dict):
                parts.append(f"{key[:-2]} {_fmt_s(p.get('mean'))}")
        if parts:
            add("  TTFT phases (mean): " + " -> ".join(parts)
                + " — the dominant phase is what a Q003 breach names")
    return "\n".join(lines)


def load_audit(path):
    """Extract per-phase intended/realized byte tables from an audit
    artifact: a ``verify_strategy --hlo --json`` report (X006 findings
    carry the table in ``data``) or a bare ``AutoStrategy.last_audit``
    dict dump.  When the same report carries the determinism audit's
    N006 key-lineage summary, the strategy's determinism class rides
    along under the table's ``"determinism_class"`` key so the rendered
    verdict says what "matches the plan" can mean bitwise.
    Returns ``[(name, table), ...]``."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "intended" in doc and "realized" in doc:
        return [(doc.get("strategy", os.path.basename(path)), doc)]
    out = []
    for name, report in (doc.items() if isinstance(doc, dict) else []):
        det = next((f.get("data", {}).get("determinism_class")
                    for f in report.get("findings", [])
                    if f.get("code") == "N006" and f.get("data")), None)
        for finding in report.get("findings", []):
            if finding.get("code") == "X006" and finding.get("data"):
                table = dict(finding["data"])
                if det and "determinism_class" not in table:
                    table["determinism_class"] = det
                out.append((os.path.basename(name), table))
    return out


def load_compute(path):
    """Extract F006 compute tables from a compute-audit artifact: a
    ``verify_strategy --compute --json`` report (F006 findings carry the
    table in ``data``) or a bare ``AutoStrategy.last_compute_audit``
    dict dump.  When the report also carries the F007 HBM-traffic table
    it is attached under the F006 table's ``"traffic"`` key (the
    roofline join).  Returns ``[(name, table), ...]``."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "realized_flops" in doc:
        return [(doc.get("strategy", os.path.basename(path)), doc)]
    out = []
    for name, report in (doc.items() if isinstance(doc, dict) else []):
        table, traffic = None, None
        for finding in report.get("findings", []):
            if finding.get("code") == "F006" and finding.get("data"):
                table = dict(finding["data"])
            elif finding.get("code") == "F007" and finding.get("data"):
                traffic = finding["data"]
        if table is not None:
            if traffic is not None:
                table["traffic"] = traffic
            out.append((os.path.basename(name), table))
    return out


def _fmt_flops(x):
    for unit, div in (("TFLOP", 1e12), ("GFLOP", 1e9), ("MFLOP", 1e6),
                      ("kFLOP", 1e3)):
        if x >= div:
            return f"{x / div:.2f}{unit}"
    return f"{x:.0f}FLOP"


def render_compute(computes, summary=None):
    """Model vs realized FLOPs with the predicted MFU ceiling, joined
    against the run's measured achieved MFU when a manifest summary is
    at hand."""
    lines = []
    for name, table in computes:
        model = table.get("model_flops")
        realized = table.get("realized_flops", 0)
        ratio = table.get("flop_ratio")
        lines.append(
            f"compute audit — {name} "
            f"({table.get('n_contractions', '?')} contraction(s), "
            f"{table.get('source', 'lowered module')}):")
        row = f"  realized {_fmt_flops(realized)}"
        if model is not None:
            row += f"  model {_fmt_flops(model)}"
        if ratio is not None:
            row += f"  ratio {ratio:.2f}x"
        lines.append(row)
        per_region = table.get("per_region") or {}
        if per_region:
            lines.append("  per-region: " + ", ".join(
                f"{r} {_fmt_flops(v)}"
                for r, v in sorted(per_region.items())))
        for rc in table.get("recompute", []):
            lines.append(
                f"  recompute x{rc.get('multiplicity', '?')}: "
                f"{rc.get('signature', '?')} "
                f"(+{_fmt_flops(rc.get('flops_paid', 0))}/step)")
        ceiling = table.get("predicted_mfu_ceiling")
        if ceiling is not None:
            row = (f"  predicted MFU ceiling: {ceiling:.2%} "
                   f"(mxu_eff {table.get('mxu_eff', 0):.0%} x "
                   f"model/realized)")
            if summary and summary.get("mfu_p50") is not None:
                measured = summary["mfu_p50"]
                row += f"  — measured MFU p50 {measured:.2%}"
                if ceiling > 0:
                    verdict = ("the gap is launch/overlap, not compute"
                               if measured / ceiling < 0.8 else
                               "the remaining gap is structural — fix "
                               "the F-codes, not the schedule")
                    row += (f" ({measured / ceiling:.0%} of ceiling: "
                            f"{verdict})")
            lines.append(row)
        traffic = table.get("traffic")
        if traffic:
            row = (f"  HBM traffic: "
                   f"{_fmt_bytes(int(traffic.get('hbm_bytes', 0)))} "
                   f"({traffic.get('arithmetic_intensity', 0):.1f} "
                   f"flops/byte)  roofline "
                   f"{_fmt_s(traffic.get('roofline_s', 0))}")
            if summary and summary.get("hbm_peak_bytes") is not None:
                row += (f"  — measured peak "
                        f"{_fmt_bytes(int(summary['hbm_peak_bytes']))}")
            lines.append(row)
            bound = traffic.get("roofline_bound")
            if bound:
                verdict = (
                    "the step is MEMORY-bound: byte levers (fused norm, "
                    "norm=\"gn\", bf16 activations) move the wall, more "
                    "MXU efficiency does not" if bound == "memory" else
                    "the step is compute-bound: the F006 FLOP levers "
                    "(remat off, bf16 contractions) move the wall, not "
                    "byte traffic")
                row = f"  roofline verdict: {verdict}"
                if summary and summary.get("step_time_p50_s"):
                    rl = traffic.get("roofline_s") or 0.0
                    row += (f" (roofline explains "
                            f"{rl / summary['step_time_p50_s']:.0%} of "
                            f"the measured p50 wall)")
                lines.append(row)
    return "\n".join(lines)


def render_audit(audits, summary=None):
    """Intended (plan) vs realized (lowered HLO) vs predicted (cost
    model) wire bytes, next to the measured step wall when a manifest
    summary is at hand."""
    lines = []
    for name, table in audits:
        intended = table.get("intended", {})
        realized = table.get("realized", {})
        predicted = table.get("predicted", {})
        det = table.get("determinism_class")
        lines.append(f"HLO audit — {name} "
                     f"({table.get('n_collectives', '?')} collective(s), "
                     f"{table.get('source', 'lowered module')}"
                     + (f", determinism: {det}" if det else "") + "):")
        for phase in sorted(set(intended) | set(realized) | set(predicted)):
            row = (f"  {phase:12s} intended {_fmt_bytes(int(intended.get(phase, 0)))}"
                   f"  realized {_fmt_bytes(int(realized.get(phase, 0)))}")
            if phase in predicted:
                row += f"  predicted {_fmt_bytes(int(predicted[phase]))}"
            lines.append(row)
        extra = []
        if table.get("control_bytes"):
            extra.append(f"control {_fmt_bytes(int(table['control_bytes']))}")
        if table.get("user_bytes"):
            extra.append(
                f"user model-parallel {_fmt_bytes(int(table['user_bytes']))}")
        if table.get("unmatched_bytes"):
            extra.append(
                f"UNPLANNED {_fmt_bytes(int(table['unmatched_bytes']))}")
        if extra:
            lines.append("  " + ", ".join(extra))
    if summary and summary.get("step_time_p50_s") is not None:
        lines.append(f"  measured step wall p50: "
                     f"{_fmt_s(summary['step_time_p50_s'])}")
    return "\n".join(lines)


def load_timeline(path=None, records=None):
    """Extract T006 three-way tables from a runtime-audit artifact
    (``verify_strategy --runtime --json`` report, or a bare T006 ``data``
    dump) and/or the manifest's own ``runtime_finding`` records (written
    when a SlowStepWatchdog capture auto-runs the analyzer).  Returns
    ``[(name, table), ...]``."""
    out = []
    if path:
        with open(path) as f:
            doc = json.load(f)
        if isinstance(doc, dict) and "measured" in doc:
            out.append((doc.get("source", os.path.basename(path)), doc))
        else:
            for name, report in (doc.items()
                                 if isinstance(doc, dict) else []):
                for finding in report.get("findings", []):
                    if finding.get("code") == "T006" and finding.get("data"):
                        out.append((os.path.basename(name),
                                    finding["data"]))
    for r in records or []:
        if r.get("kind") == "runtime_finding" and r.get("code") == "T006" \
                and r.get("data"):
            out.append((f"watchdog step {r.get('step')}", r["data"]))
    return out


def render_timeline(timelines, summary=None):
    """The three-way closing of the loop: predicted (cost model) vs
    statically-realized (plan channels) vs MEASURED (device timeline)
    step decomposition, per-hop bandwidth error, and worker skew."""
    lines = []
    for name, table in timelines:
        meas = table.get("measured") or {}
        host = " [host-only capture]" if table.get("host_only") else ""
        lines.append(
            f"runtime timeline — {name} "
            f"({table.get('n_collective_events', 0)} collective "
            f"event(s), {table.get('source', 'trace')}){host}:")
        lines.append(
            f"  measured  total {_fmt_s(meas.get('total_s'))}  compute "
            f"{_fmt_s(meas.get('compute_s'))}  collective "
            f"{_fmt_s(meas.get('collective_s'))}  exposed "
            f"{meas.get('exposed_frac', 0.0):.0%}  overlap "
            f"{meas.get('overlap_frac', 0.0):.0%}")
        pred = table.get("predicted")
        if pred:
            lines.append(
                f"  predicted total {_fmt_s(pred.get('total_s'))}  "
                f"compute {_fmt_s(pred.get('compute_s'))}  comm "
                f"{_fmt_s(pred.get('comm_s'))}  exposed "
                f"{pred.get('exposed_frac', 0.0):.0%} "
                f"({pred.get('schedule')} schedule)")
        for hop, h in sorted((table.get("hops") or {}).items()):
            row = (f"  {hop.upper():4s} hop  predicted "
                   f"{_fmt_s(h.get('predicted_s'))}  measured "
                   f"{_fmt_s(h.get('measured_s'))}")
            if h.get("measured_gbps") is not None:
                row += (f"  bw {h['measured_gbps']:.0f}/"
                        f"{h.get('spec_gbps', 0):.0f} Gbit/s "
                        f"(error {h['rel_error']:+.0%})")
            lines.append(row)
        skew = table.get("skew")
        if skew:
            who = skew.get("straggler_addr") or skew.get("straggler")
            lines.append(
                f"  worker skew {_fmt_s(skew.get('skew_s'))} "
                f"(fastest {_fmt_s(skew.get('fastest_s'))}, threshold "
                f"{_fmt_s(skew.get('threshold_s'))})"
                + (f" — straggler {who}" if who is not None else ""))
        rec = table.get("reconcile")
        if rec and rec.get("rel_error") is not None:
            lines.append(
                f"  reconcile: measured {_fmt_s(rec.get('measured_total_s'))}"
                f" vs predicted {_fmt_s(rec.get('predicted_total_s'))} "
                f"({rec['rel_error']:+.1%})")
    if summary and summary.get("step_time_p50_s") is not None:
        lines.append(f"  measured step wall p50: "
                     f"{_fmt_s(summary['step_time_p50_s'])}")
    return "\n".join(lines)


def load_health(records, baseline_spec=None):
    """The run's health verdict + the cross-run R-code diff.  Returns
    ``(health_findings, regression_findings)`` where the former are the
    manifest's ``health_finding`` records and the latter are R-code
    :class:`Finding` objects from the regression audit (against the
    blessed baseline named/pathed by ``baseline_spec``, or looked up by
    the run id; no baseline -> the audit still judges R002/R003 and
    notes R000)."""
    from autodist_tpu.analysis.regression_audit import regression_audit
    from autodist_tpu.telemetry.baseline import (baseline_from_manifest,
                                                 load_baseline)

    meta = next((r for r in records if r.get("kind") == "meta"), {})
    name = str(meta.get("run_id") or "run")
    current = baseline_from_manifest(records, name=name)
    baseline = None
    if baseline_spec and os.path.exists(baseline_spec):
        with open(baseline_spec) as f:
            baseline = json.load(f)
    elif baseline_spec:
        baseline = load_baseline(baseline_spec)
    else:
        baseline = load_baseline(name)
    hf = [r for r in records if r.get("kind") == "health_finding"]
    return hf, regression_audit(current, baseline)


def render_health(health_findings, regression_findings, summary=None):
    """The health & regression section: per-step online detections, the
    run's aggregate counts, and the R-code diff against the baseline."""
    lines = []
    h = (summary or {}).get("health") or {}
    counts = h.get("counts") or {}
    lines.append(
        f"health — {h.get('observed_steps', 0)} step(s) observed, "
        f"{h.get('findings', len(health_findings))} finding(s)"
        + (": " + ", ".join(f"{k}={v}"
                            for k, v in sorted(counts.items()))
           if counts else " (clean)"))
    if h.get("first_nonfinite_step") is not None:
        lines.append(f"  first non-finite at step "
                     f"{h['first_nonfinite_step']} — every later "
                     f"step is poisoned")
    for r in health_findings[:20]:
        lines.append(f"  step {r.get('step')}: [{r.get('severity')}] "
                     f"{r.get('check')} — {r.get('message')}")
    if len(health_findings) > 20:
        lines.append(f"  ... {len(health_findings) - 20} more "
                     f"health finding(s)")
    r006 = next((f.data for f in regression_findings
                 if f.code == "R006"), None)
    base = (r006 or {}).get("baseline")
    lines.append("regression vs baseline"
                 + (f" '{base.get('name')}'" if base else " (none blessed)")
                 + ":")
    for f in regression_findings:
        if f.code != "R006":
            lines.append(f"  [{f.severity.name}] {f.code}: {f.message}")
    for metric, d in ((r006 or {}).get("diffs") or {}).items():
        lines.append(f"  {metric:28s} current {d['current']:.4g}  "
                     f"blessed {d['baseline']:.4g}  "
                     f"limit {d['limit']:.4g}")
    verdict = (r006 or {}).get("regressed") or []
    lines.append("  verdict: "
                 + ("REGRESSED " + ", ".join(verdict) if verdict
                    else "clean"))
    return "\n".join(lines)


def render_live(records, stats=None):
    """One compact status block for a GROWING manifest (no summary
    trailer required): per-worker front step, wall p50 so far, health
    counts, and the tail of the cluster event log."""
    lines = []
    steps = [r for r in records if r.get("kind") == "step"]
    events = [r for r in records if r.get("kind") == "cluster_event"]
    by_worker = {}
    for r in steps:
        w = r.get("w", 0)
        if isinstance(r.get("step"), (int, float)):
            by_worker[w] = max(by_worker.get(w, -1), int(r["step"]))
    walls = [r.get("wall_cancelled_s", r.get("wall_s"))
             for r in steps if r.get("step") not in (0, None)]
    walls = [w for w in walls if w is not None]
    p50 = percentiles(walls)[0.5] if walls else None
    front = max(by_worker.values()) if by_worker else None
    lines.append(
        f"live: {len(steps)} step record(s), front step {front}, "
        f"workers " + (", ".join(
            f"w{w}@{s}" for w, s in sorted(by_worker.items()))
            if by_worker else "-")
        + (f", wall p50 {_fmt_s(p50)}" if p50 is not None else ""))
    health = {}
    for r in records:
        if r.get("kind") == "health_finding":
            health[r.get("check")] = health.get(r.get("check"), 0) + 1
    if health:
        lines.append("  health: " + ", ".join(
            f"{k}={v}" for k, v in sorted(health.items())))
    if events:
        by_event = {}
        for e in events:
            by_event[e.get("event")] = by_event.get(e.get("event"), 0) + 1
        lines.append(f"  events: {len(events)} (" + ", ".join(
            f"{k}={v}" for k, v in sorted(by_event.items())) + ")")
        for e in events[-3:]:
            cause = e.get("cause") or {}
            lines.append(
                f"    {e.get('event')}"
                + (f"@{e.get('step')}" if e.get("step") is not None
                   else "")
                + (f" signal={e.get('signal')}"
                   if e.get("event") == "signal" else "")
                + (f" <- {cause.get('signal')}({cause.get('worker')})"
                   if cause else "")
                + (f" latency {e['latency_s'] * 1e3:.1f}ms"
                   if isinstance(e.get("latency_s"), (int, float))
                   else ""))
    if stats and (stats.get("skipped_lines") or stats.get("rotated_files")):
        lines.append(f"  hygiene: {stats.get('skipped_lines', 0)} torn "
                     f"line(s), {stats.get('rotated_files', 0)} rotated "
                     f"segment(s)")
    return "\n".join(lines)


def follow(path, interval_s=1.0, max_updates=None, out=None):
    """Tail a growing run dir / manifest: re-read and re-render every
    ``interval_s`` until interrupted (or ``max_updates`` renders).
    Returns the number of renders."""
    import time as _time

    out = out or sys.stdout
    n = 0
    try:
        while True:
            try:
                records, stats = load_manifest_with_stats(path)
            except (OSError, ValueError):
                records, stats = [], {}
            if records:
                print(render_live(records, stats), file=out, flush=True)
            else:
                print(f"(waiting for records under {path})", file=out,
                      flush=True)
            n += 1
            if max_updates is not None and n >= max_updates:
                return n
            _time.sleep(interval_s)
    except KeyboardInterrupt:
        return n


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("path", help="telemetry run dir or manifest.jsonl")
    ap.add_argument("--follow", action="store_true",
                    help="tail a GROWING run dir: re-render a compact "
                         "live status every --interval seconds (no "
                         "finalized summary trailer needed)")
    ap.add_argument("--interval", type=float, default=1.0,
                    help="--follow refresh period in seconds (default 1)")
    ap.add_argument("--max-updates", type=int, default=None,
                    help="stop --follow after N renders (default: until "
                         "interrupted)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of text")
    ap.add_argument("--audit", default=None,
                    help="HLO-audit artifact (verify_strategy --hlo --json "
                         "output or an AutoStrategy.last_audit dump): show "
                         "intended vs realized vs predicted wire bytes "
                         "next to the measured walls")
    ap.add_argument("--compute", default=None,
                    help="compute-audit artifact (verify_strategy "
                         "--compute --json output or an "
                         "AutoStrategy.last_compute_audit dump): show the "
                         "F006 FLOP table, join the predicted MFU "
                         "ceiling against the measured achieved MFU, and "
                         "when the report carries the F007 HBM-traffic "
                         "table, print the roofline memory-bound-vs-"
                         "compute-bound verdict next to the measured "
                         "memory_stats peak")
    ap.add_argument("--timeline", nargs="?", const="", default=None,
                    metavar="REPORT_JSON",
                    help="runtime-audit artifact (verify_strategy "
                         "--runtime --json output or a bare T006 data "
                         "dump; default: the manifest's own "
                         "runtime_finding records): show the T006 "
                         "three-way table with per-hop "
                         "predicted-vs-measured bandwidth error")
    ap.add_argument("--health", nargs="?", const="", default=None,
                    metavar="BASELINE",
                    help="show the run's health verdict (health_finding "
                         "records, counts) and the cross-run R-code diff "
                         "against a blessed baseline (a name under "
                         "records/baselines or a JSON path; default: "
                         "look one up by the run id)")
    args = ap.parse_args(argv)
    if args.follow:
        follow(args.path, interval_s=args.interval,
               max_updates=args.max_updates)
        return 0
    records, stats = load_manifest_with_stats(args.path)
    if not records:
        print(f"no telemetry records under {args.path}", file=sys.stderr)
        return 1
    summary = summarize_manifest(records, stats=stats)
    audits = load_audit(args.audit) if args.audit else []
    if audits:
        summary["hlo_audit"] = {name: table for name, table in audits}
    computes = load_compute(args.compute) if args.compute else []
    if computes:
        summary["compute_audit"] = {name: table for name, table in computes}
    timelines = []
    if args.timeline is not None:
        timelines = load_timeline(args.timeline or None, records)
        if not timelines:
            print("no T006 timeline tables found (pass a verify_strategy "
                  "--runtime --json artifact, or run with a watchdog "
                  "capture in the manifest)", file=sys.stderr)
        else:
            summary["runtime_timeline"] = {n: t for n, t in timelines}
    health_findings, regression_findings = [], []
    if args.health is not None:
        health_findings, regression_findings = \
            load_health(records, args.health or None)
        summary["health_findings"] = health_findings
        summary["regression"] = next(
            (f.data for f in regression_findings if f.code == "R006"),
            None)
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        print(render(summary))
        if audits:
            print(render_audit(audits, summary))
        if computes:
            print(render_compute(computes, summary))
        if timelines:
            print(render_timeline(timelines, summary))
        if args.health is not None:
            print(render_health(health_findings, regression_findings,
                                summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
