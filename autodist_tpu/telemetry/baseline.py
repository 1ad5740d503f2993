"""Committed cross-run performance baselines (the regression memory).

A baseline is one small JSON file per ``<model>_<strategy>`` case under
``records/baselines/`` capturing the *blessed* level of every signal the
regression audit (:mod:`autodist_tpu.analysis.regression_audit`) knows
how to diff:

- step-wall percentiles + achieved ``mfu_p50`` from a finalized
  manifest's summary trailer;
- ``cpu_mesh_engine_overhead`` — the machine-normalized engine-vs-raw
  ratio from the cpu_proxy sweep (a CPU-backend signal, never a chip
  speed);
- ``predicted_mfu_ceiling`` (F006) and realized comm bytes (X006) — the
  *static* quantities, so a structural regression is caught by
  ``make perf-gate`` before any chip is touched.

Machine-dependent absolutes (CPU step walls, raw/engine milliseconds)
are stored under ``info`` — reported in the R006 table but never gated,
so a committed baseline doesn't flake across hosts.  Test fixtures that
*want* wall gating put ``step_time_p50_s`` at the top level.

Blessing workflow (docs/observability.md): run
``python tools/perf_gate.py --update-baseline`` after an intentional
perf change and commit the rewritten ``records/baselines/*.json``.
"""
import json
import os

BASELINE_SCHEMA = 1
BASELINE_DIR = os.path.join("records", "baselines")

# summary-trailer fields copied verbatim into the baseline when present
_SUMMARY_FIELDS = ("steps", "step_time_p50_s", "step_time_p90_s",
                   "step_time_p99_s", "mfu_p50", "compile_s", "rtt_s")


def baseline_path(name, baseline_dir=None):
    return os.path.join(baseline_dir or BASELINE_DIR, f"{name}.json")


def baseline_from_manifest(records, *, name="", extras=None):
    """Reduce finalized manifest records (``aggregate.load_manifest``
    output) to a baseline dict.

    Harvests the meta header (backend, device count), the summary
    trailer's percentiles/MFU, and the run's health verdict — from the
    summary's ``health`` block when the session wrote one, else by
    counting raw ``health_finding`` records (older manifests).
    ``extras`` merges in caller-known signals (engine overhead, F006
    ceiling, X006 bytes)."""
    out = {"schema": BASELINE_SCHEMA, "name": name}
    meta = next((r for r in records if r.get("kind") == "meta"), None)
    if meta:
        for k in ("backend", "num_devices", "run_id"):
            if meta.get(k) is not None:
                out[k] = meta[k]
    summary = None
    for r in records:
        if r.get("kind") == "summary":
            summary = r        # last trailer wins (merged manifests)
    if summary:
        for k in _SUMMARY_FIELDS:
            if summary.get(k) is not None:
                out[k] = summary[k]
        if isinstance(summary.get("health"), dict):
            out["health"] = summary["health"]
    if "health" not in out:
        counts = {}
        first_nonfinite = None
        for r in records:
            if r.get("kind") != "health_finding":
                continue
            c = r.get("check", "?")
            counts[c] = counts.get(c, 0) + 1
            if c == "nonfinite" and first_nonfinite is None:
                first_nonfinite = r.get("step")
        if counts:
            out["health"] = {"counts": counts,
                             "findings": sum(counts.values())}
            if first_nonfinite is not None:
                out["health"]["first_nonfinite_step"] = first_nonfinite
    if extras:
        out.update({k: v for k, v in extras.items() if v is not None})
    return out


def save_baseline(b, *, baseline_dir=None):
    """Write (bless) a baseline; returns the path."""
    b = dict(b)
    b.setdefault("schema", BASELINE_SCHEMA)
    path = baseline_path(b.get("name") or "unnamed", baseline_dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(b, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_baseline(name, *, baseline_dir=None):
    """The blessed baseline for ``name``, or None if never blessed."""
    path = baseline_path(name, baseline_dir)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_baselines(baseline_dir=None):
    """All blessed baselines in ``baseline_dir`` keyed by name."""
    d = baseline_dir or BASELINE_DIR
    out = {}
    if not os.path.isdir(d):
        return out
    for fn in sorted(os.listdir(d)):
        if fn.endswith(".json"):
            with open(os.path.join(d, fn)) as f:
                out[fn[:-len(".json")]] = json.load(f)
    return out
