"""CI perf gate: the cpu_proxy sweep diffed against committed baselines
(``make perf-gate``, wired into ``make check``).

For every strategy record under ``records/cpu_mesh`` this rebuilds the
case on a virtual CPU mesh and measures the *machine-normalized*
engine overhead (engine SPMD step / raw single-jit step), audits the
lowering (F006 ``predicted_mfu_ceiling``, X006 realized comm bytes), and
runs the cross-run REGRESSION tier
(:mod:`autodist_tpu.analysis.regression_audit`) against the blessed
baseline in ``records/baselines/<name>.json``:

- every case must emit its R006 run-vs-baseline table;
- **R001** (engine-overhead regression) and **R004** (the statically
  predicted MFU ceiling dropped — a structural regression, caught with
  zero chips) fail the gate;
- a case with no blessed baseline fails with instructions to bless one.

The serving record (``gpt_tiny_serve_decode.json`` — not a
RuntimeRecord) gets its own leg: the continuous-batching decode engine
is re-measured against static ``generate()`` rollouts
(:mod:`autodist_tpu.serving.benchmark`) and the machine-normalized
``serving_decode_overhead`` ratio gated against its blessed baseline,
so the serving tier's tokens/sec overhead trajectory rides the same
gate between chip windows.

``--update-baseline`` re-blesses the measured level (run after an
*intentional* perf change, commit the rewritten files);
``--selftest`` proves the tier's teeth on the golden fixtures under
``tests/data/regression`` (the seeded slow manifest must fire R001, the
NaN manifest must fire R002, the control must stay clean).
"""
import argparse
import glob
import json
import os
import sys

# CPU mesh, no real accelerator needed — must precede any jax import
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("AUTODIST_IS_TESTING", "True")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

STEPS = 5
FIXTURE_DIR = os.path.join(_REPO, "tests", "data", "regression")
# serving_decode_overhead gate: the engine-vs-generate wall ratio cancels
# host speed but CPU scheduler noise on a ~60-token run is real — the
# tolerance mirrors the cpu_mesh_engine_overhead gate's
SERVE_TOL_REL = 0.75
SERVE_ABS_SLACK = 1.0


def _mesh_for(strategy, R):
    """Concrete CPU mesh shaped like the strategy's graph_config mesh."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    gm = strategy.proto.graph_config.mesh
    if gm.axis_names:
        names = tuple(gm.axis_names)
        shape = tuple(int(s) for s in gm.axis_sizes)
    else:
        names, shape = ("replica",), (R,)
    devices = jax.devices()
    if len(devices) < R:
        return None
    return Mesh(np.array(devices[:R]).reshape(shape), names)


def _engine_overhead(strategy, item, mesh, R):
    """(overhead_ratio, info) — the engine's full SPMD step timed against
    a raw single-jit step of the same math on the same host (the ratio
    cancels host speed; the absolute milliseconds ride along ungated)."""
    import jax
    import numpy as np
    import optax

    from autodist_tpu.kernel.graph_transformer import GraphTransformer
    from autodist_tpu.runner import DistributedSession
    from autodist_tpu.utils.timing import fetch_scalar, seconds_per_step

    rs = np.random.RandomState(0)
    batch = {"x": rs.randn(2 * R, 4).astype(np.float32)}

    t = GraphTransformer(strategy, item, mesh)
    sess = DistributedSession(t)
    g = sess._shard_batch(batch)
    fetch_scalar(sess.run(g)["loss"])      # compile + warm

    def run_engine(k):
        m = None
        for _ in range(k):
            m = sess.run(g)
        return m["loss"]

    # the least of three windows: the ratio's noise floor must sit well
    # under the gate tolerance or the committed baselines flake
    eng_dt = min(seconds_per_step(run_engine, STEPS) for _ in range(3))

    opt = item.optimizer
    state = [item.params, opt.init(item.params)]

    @jax.jit
    def raw_step(p, s, b):
        loss_v, grads = jax.value_and_grad(item.loss_fn)(p, b)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss_v

    _, _, loss_v = raw_step(state[0], state[1], batch)
    fetch_scalar(loss_v)                   # compile + warm

    def run_raw(k):
        loss_v = None
        for _ in range(k):
            state[0], state[1], loss_v = raw_step(state[0], state[1],
                                                  batch)
        return loss_v

    # the raw step is microseconds on these tiny models — a k this small
    # would put scheduler jitter straight into the ratio's denominator,
    # so run many more of them (they cost ~nothing)
    raw_dt = min(seconds_per_step(run_raw, 20 * STEPS) for _ in range(3))
    overhead = eng_dt / max(raw_dt, 1e-9)
    info = {"engine_step_ms": round(eng_dt * 1e3, 3),
            "raw_step_ms": round(raw_dt * 1e3, 3)}
    return round(overhead, 3), info


def check_record(path, baseline_dir):
    """Measure + audit one cpu_mesh record against its blessed baseline.
    Returns (name, findings, r006_data, problems)."""
    from autodist_tpu.analysis import verify_strategy
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.simulator.cost_model import (RuntimeRecord,
                                                   rebuild_record_case)
    from autodist_tpu.telemetry.baseline import load_baseline
    from tools.verify_strategy import _synthetic_loss

    name = os.path.basename(path)[:-len(".json")]
    rec = RuntimeRecord.load(path)
    strategy, item, R = rebuild_record_case(rec, loss_fn=_synthetic_loss)
    mesh = _mesh_for(strategy, R)
    if mesh is None:
        return name, [], None, [f"mesh needs {R} devices"]
    overhead, info = _engine_overhead(strategy, item, mesh, R)
    baseline = load_baseline(name, baseline_dir=baseline_dir)
    report = verify_strategy(
        strategy, item, ResourceSpec.from_num_chips(R),
        batch_shapes={"x": ((2 * R, 4), "float32")},
        passes=("hlo-audit", "compute-audit", "regression-audit"),
        baseline=baseline,
        current_metrics={"name": name,
                         "cpu_mesh_engine_overhead": overhead,
                         "backend": "cpu", "num_devices": R,
                         "info": info})
    findings = report.findings
    r006 = next((f.data for f in findings if f.code == "R006"), None)
    problems = []
    if r006 is None:
        problems.append("no R006 run-vs-baseline table emitted")
    for f in findings:
        if f.code in ("R001", "R004"):
            problems.append(f"{f.code}: {f.message}")
    if baseline is None:
        problems.append(
            f"no blessed baseline records/baselines/{name}.json — run "
            f"'python tools/perf_gate.py --update-baseline' and commit")
    return name, findings, r006, problems


def check_serving(path, baseline_dir, update=False):
    """Re-measure the serving decode overhead live and gate it against
    the blessed baseline.  Returns (name, overhead, problems)."""
    import json

    from autodist_tpu.serving.benchmark import measure_serve_decode
    from autodist_tpu.telemetry.baseline import baseline_path

    name = os.path.basename(path)[:-len(".json")]
    cur = measure_serve_decode()
    ov = cur["serving_decode_overhead"]
    bpath = baseline_path(name, baseline_dir=baseline_dir)
    if update:
        with open(bpath, "w") as f:
            json.dump(cur, f, indent=2, sort_keys=True)
            f.write("\n")
        return name, ov, []
    problems = []
    try:
        with open(bpath) as f:
            baseline = json.load(f)
    except (OSError, ValueError):
        problems.append(
            f"no blessed baseline records/baselines/{name}.json — run "
            f"'python tools/perf_gate.py --update-baseline' and commit")
        return name, ov, problems
    base_ov = baseline.get("serving_decode_overhead")
    if not isinstance(base_ov, (int, float)):
        problems.append(f"baseline {bpath} has no serving_decode_overhead")
        return name, ov, problems
    limit = base_ov * (1.0 + SERVE_TOL_REL) + SERVE_ABS_SLACK
    if ov > limit:
        problems.append(
            f"serving decode overhead regression: engine-vs-generate "
            f"ratio {ov:.2f}x vs blessed {base_ov:.2f}x (limit "
            f"{limit:.2f}x = +{SERVE_TOL_REL:.0%} + {SERVE_ABS_SLACK})")
    return name, ov, problems


def bless(r006, baseline_dir):
    """Write the measured level as the new blessed baseline."""
    from autodist_tpu.telemetry.baseline import save_baseline

    b = {"name": r006["name"]}
    b.update(r006["current"])
    return save_baseline(b, baseline_dir=baseline_dir)


def selftest():
    """The tier's teeth, proven on golden fixtures: the seeded slow
    manifest fires R001, the NaN manifest fires R002, the control stays
    clean.  Pure-fixture path — no mesh, no jit."""
    from autodist_tpu.analysis.regression_audit import audit_fixture

    base = os.path.join(FIXTURE_DIR, "baseline.json")
    legs = []

    f = audit_fixture(manifest_dir=os.path.join(FIXTURE_DIR, "slow_run"),
                      baseline_path=base, name="regfix")
    codes = {x.code for x in f}
    legs.append(("slow_run fires R001", "R001" in codes, sorted(codes)))
    legs.append(("slow_run emits R006", "R006" in codes, sorted(codes)))

    f = audit_fixture(manifest_dir=os.path.join(FIXTURE_DIR, "nan_run"),
                      baseline_path=base, name="regfix")
    codes = {x.code for x in f}
    legs.append(("nan_run fires R002", "R002" in codes, sorted(codes)))
    legs.append(("nan_run does not fire R001", "R001" not in codes,
                 sorted(codes)))

    # control: the blessed level diffed against itself must be clean
    f = audit_fixture(current_path=base, baseline_path=base,
                      name="regfix")
    codes = {x.code for x in f}
    bad = codes & {"R001", "R002", "R004", "R005"}
    legs.append(("control stays clean", not bad, sorted(codes)))

    failed = [name for name, ok, _ in legs if not ok]
    for name, ok, codes in legs:
        print(f"  {'PASS' if ok else 'FAIL'}: {name} (codes: {codes})")
    if failed:
        print(f"SELFTEST FAIL: {failed}")
        return 1
    print(f"SELFTEST OK: {len(legs)} fixture legs")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="cpu_proxy sweep vs committed perf baselines")
    ap.add_argument("--records", default=os.path.join(_REPO, "records",
                                                      "cpu_mesh"))
    ap.add_argument("--baselines", default=os.path.join(_REPO, "records",
                                                        "baselines"))
    ap.add_argument("--only", action="append", default=None,
                    help="limit to record stems (repeatable)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="bless the measured level instead of gating")
    ap.add_argument("--selftest", action="store_true",
                    help="prove R001/R002 fire on the golden fixtures")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()

    records = sorted(glob.glob(os.path.join(args.records, "*.json")))
    records = [p for p in records if not p.endswith("_summary.json")]
    if args.only:
        records = [p for p in records
                   if os.path.basename(p)[:-len(".json")] in args.only]
    if not records:
        print(f"FAIL: no records under {args.records}")
        return 1
    failed = False
    print(f"{'strategy':40} {'overhead':>9} {'ceiling':>8} {'verdict'}")
    for path in records:
        try:
            with open(path) as f:
                head = json.load(f)
        except (OSError, ValueError):
            head = {}
        if not {"model_def", "strategy"} <= set(head):
            # not a RuntimeRecord: the serving decode record gets its own
            # leg; anything else (sweep summaries) is skipped
            if head.get("metric") == "serving_decode_overhead":
                name, ov, problems = check_serving(
                    path, args.baselines, update=args.update_baseline)
                if args.update_baseline:
                    print(f"{name:40} {ov:>9} {'-':>8} blessed -> "
                          f"records/baselines/{name}.json")
                elif problems:
                    failed = True
                    print(f"{name:40} {ov:>9} {'-':>8} FAIL")
                    for p in problems:
                        print(f"  - {p}")
                else:
                    print(f"{name:40} {ov:>9} {'-':>8} clean")
            else:
                print(f"{os.path.basename(path)[:-len('.json')]:40} "
                      f"SKIP: not a RuntimeRecord")
            continue
        name, findings, r006, problems = check_record(path, args.baselines)
        cur = (r006 or {}).get("current", {})
        ov = cur.get("cpu_mesh_engine_overhead")
        ceil = cur.get("predicted_mfu_ceiling")
        if args.update_baseline:
            if r006 is None:
                failed = True
                print(f"{name:40} FAIL: {problems}")
                continue
            out = bless(r006, args.baselines)
            print(f"{name:40} {ov if ov is not None else '?':>9} "
                  f"{ceil if ceil is not None else '?':>8} blessed -> "
                  f"{os.path.relpath(out, _REPO)}")
            continue
        if problems:
            failed = True
            print(f"{name:40} {ov if ov is not None else '?':>9} "
                  f"{ceil if ceil is not None else '?':>8} FAIL")
            for p in problems:
                print(f"  - {p}")
        else:
            regressed = (r006 or {}).get("regressed") or []
            verdict = "regressed " + ",".join(regressed) if regressed \
                else "clean"
            print(f"{name:40} {ov:>9} {ceil if ceil is not None else '?':>8}"
                  f" {verdict}")
    if failed:
        print("FAIL: see problems above (an intentional perf change is "
              "blessed with --update-baseline)")
        return 1
    mode = "blessed" if args.update_baseline else \
        "R006 emitted, zero R001/R004"
    print(f"OK: {len(records)} strategies, {mode}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
