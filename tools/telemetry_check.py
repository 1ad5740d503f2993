"""CI gate: a live 5-step CPU-mesh run with telemetry on must produce a
schema-valid manifest (``make telemetry-check``, wired into ``make
check``).

Asserts the acceptance contract of the telemetry subsystem end-to-end:

1. the run writes a JSONL manifest with per-step wall time, throughput,
   an achieved-MFU estimate and memory snapshots, and it validates
   against the documented schema (``autodist_tpu/telemetry/schema.py``);
2. ``tools/telemetry_report.py`` renders it;
3. the emitted RuntimeRecord round-trips through
   ``cost_model.calibrate_from_records`` (the measured-feedback loop).
"""
import os
import sys
import tempfile

# CPU mesh, no real accelerator needed — must precede any jax import
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()
os.environ.setdefault("AUTODIST_IS_TESTING", "True")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

STEPS = 5


def main():
    import numpy as np
    import jax.numpy as jnp
    import optax

    from autodist_tpu import telemetry
    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.simulator.cost_model import calibrate_from_records
    from autodist_tpu.strategy import AllReduce
    from tools.telemetry_report import render, summarize_manifest

    run_dir = tempfile.mkdtemp(prefix="telemetry_check_")
    telemetry.enable(run_dir=run_dir)

    rs = np.random.RandomState(0)
    params = {"w": jnp.asarray(rs.randn(12, 3), jnp.float32),
              "b": jnp.zeros((3,), jnp.float32)}

    def loss(p, b):
        return jnp.mean((b @ p["w"] + p["b"]) ** 2)

    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(4),
                  strategy_builder=AllReduce())
    sess = ad.distribute(loss, params, optax.sgd(0.1))
    batch = rs.randn(16, 12).astype(np.float32)
    sess.run_steps([batch] * STEPS)

    manifest = os.path.join(run_dir, "manifest.jsonl")
    records, errors = telemetry.validate_manifest(manifest, require_steps=True)
    if errors:
        print(f"FAIL: manifest schema errors in {manifest}:")
        for e in errors:
            print(f"  - {e}")
        return 1
    steps = [r for r in records if r["kind"] == "step"]
    problems = []
    if len(steps) != STEPS:
        problems.append(f"expected {STEPS} step records, got {len(steps)}")
    # (no "mfu": the gate runs on the CPU, which the peak table has no
    # entry for — the FLOP count is what a CPU run can say)
    for field in ("wall_s", "throughput_eps", "flops_per_device"):
        if not any(field in r for r in steps):
            problems.append(f"no step record carries '{field}'")
    if not any(r["kind"] == "snapshot" for r in records):
        problems.append("no memory snapshot record")

    summary = summarize_manifest(records)
    report = render(summary)
    if "p50" not in report:
        problems.append("telemetry_report rendered no percentiles")

    rec_paths = summary.get("runtime_records") or []
    if not rec_paths:
        problems.append("no RuntimeRecord emitted")
    else:
        cal, pairs = calibrate_from_records(rec_paths)
        if set(cal) != {"compute_scale", "comm_scale", "overhead_s"}:
            problems.append(f"calibration malformed: {cal}")
        if not pairs or pairs[0][1] <= 0:
            problems.append(f"calibration pairs malformed: {pairs}")

    if problems:
        print(f"FAIL: {manifest}")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(report)
    print(f"OK: {len(records)} schema-valid records, {len(steps)} steps, "
          f"RuntimeRecord -> calibrate round-trip passed ({manifest})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
