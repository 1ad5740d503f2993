"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that owns the chips from start to end.  It finds the cell's files
by name (``benchmark/harness/cells.py``), writes the seeded data, runs the
plain reference, builds the session through the entry points a user calls
(``AutoDist(...).distribute(...)`` -> ``sess.run``), warms up, measures for
``--seconds`` and prints one JSON object as its last line.  Every earlier
line is a JSON object too and names the device it ran on.  It exits non-zero
and prints no result line when there is no TPU, fewer chips than the cell
asks for, no native loader, or when a step raises.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import cells, loop, monitor, timing, trace as tr  # noqa: E402
from benchmark.harness.peaks import peaks_for  # noqa: E402

WORK_DIR = os.path.join(REPO, ".benchmark_work")

# Engine against reference, both bf16 forward passes of one seeded model on
# one batch: flash against XLA attention and another reduction order move the
# f32-accumulated mean loss by a few bf16 ulps (2**-8 relative each).  A
# forward pass in a lower precision than the configuration states, or an
# update that was not applied before the second loss, moves it by more.
LOSS_RTOL = 2e-2


def memory_field(devices, field):
    return [(d.memory_stats() or {}).get(field) for d in devices]


def memory_peak(devices):
    """Peak bytes of each device.  ``peak_bytes_in_use`` counts live arrays
    only; a loaded executable's temporaries (activations, recomputation
    buffers: 3.2 GB of GPT-2-medium's step) are ``bytes_reserved`` while it
    stays loaded.  So the peak is the larger of the arrays' high-water mark
    and arrays plus reservations as they stand after the window, with the
    session's step still loaded."""
    out = []
    for d in devices:
        m = d.memory_stats() or {}
        out.append(max(m.get("peak_bytes_in_use", 0),
                       m.get("bytes_in_use", 0) + m.get("bytes_reserved", 0)))
    return out


def build_strategy(spec):
    from autodist_tpu import strategy

    return getattr(strategy, spec["builder"])(**spec.get("args", {}))


def build_resource_spec(spec):
    from autodist_tpu.resource_spec import ResourceSpec

    if "from_num_chips" in spec:
        return ResourceSpec.from_num_chips(spec["from_num_chips"])
    return ResourceSpec()       # no file: what this host holds


def replicas_agree(sess, devices, leaves=4):
    """Bit-equality, across the devices, of the first few replicated weight
    leaves: a broken gradient sync would not leave them equal."""
    import jax
    import numpy as np

    checked = 0
    for leaf in jax.tree.leaves(sess.state["params"]):
        if checked == leaves:
            break
        if not leaf.sharding.is_fully_replicated:
            continue
        shards = {s.device: np.asarray(s.data) for s in leaf.addressable_shards}
        if set(shards) != set(devices):
            return False, checked
        first = shards[devices[0]]
        if not all(np.array_equal(first, shards[d]) for d in devices[1:]):
            return False, checked
        checked += 1
    return checked > 0, checked


def sharding_facts(ad, sess, gbatch, devices):
    """Where the mesh, the batch and the optimizer state ended up."""
    import jax

    mesh_devices = list(ad.mesh.devices.flat)
    opt_leaves = jax.tree.leaves(sess.state["opt_state"])
    first = jax.tree.leaves(gbatch)[0]
    return {
        "mesh_shape": dict(ad.mesh.shape),
        "mesh_holds_the_devices": set(mesh_devices) == set(devices),
        "batch_devices": len({s.device for s in first.addressable_shards}),
        "opt_state_leaves": len(opt_leaves),
        "opt_state_sharded_leaves": sum(
            1 for x in opt_leaves if not x.sharding.is_fully_replicated),
    }


def run_cell(cell, config, manifest, *, seed, seconds, trace, devices, emit,
             work_dir):
    """Set up, warm up, measure and reduce one cell on ``devices``; returns
    the result object of the last line."""
    import jax

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.data.loader import DevicePrefetcher
    from autodist_tpu.utils.compile_cache import ensure_compile_cache

    parts = {"imports_s": time.perf_counter() - T_PROCESS_START}
    mark = time.perf_counter()

    def part(name):
        nonlocal mark
        now = time.perf_counter()
        parts[name] = now - mark
        mark = now

    cache_dir = ensure_compile_cache()
    events = monitor.CompileEvents()
    family = cells.load_family(config["family"])
    # the large seeds a driver passes fit neither RandomState nor a PRNGKey
    seed31 = seed % (2 ** 31 - 1)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    job = family.Job(cell, config, seed31, work_dir)
    part("data_s")

    # the reference first, and freed, so that two copies of the training
    # state never share the chip
    ref_spec = cell["reference"]
    stream = iter(job.stream)
    first_batches = [next(stream) for _ in range(ref_spec["steps"])]
    params = job.make_params()
    flops_per_unit = job.flops_per_unit(params)
    part("weights_s")
    ref_losses = job.reference_losses(params, first_batches, devices[0])
    gc.collect()
    part("reference_s")
    peak_after_reference = memory_field(devices, "peak_bytes_in_use")
    if cell.get("params_on") == "host":
        # the program keeps the captured parameters alive beside the
        # session's copy; as host arrays they take none of chip 0's memory
        params = jax.device_get(params)
    ad = AutoDist(resource_spec=build_resource_spec(cell["resource_spec"]),
                  strategy_builder=build_strategy(cell["strategy"]))
    sess = ad.distribute(job.loss_fn, params, job.optimizer,
                         **job.distribute_kwargs)
    del params
    part("distribute_s")
    prefetch = DevicePrefetcher(itertools.chain(first_batches, stream), sess,
                                depth=cell["feed"]["prefetch_depth"])

    def step(gbatch):
        return sess.run(gbatch)["loss"]

    gbatch = next(prefetch)
    facts = sharding_facts(ad, sess, gbatch, devices)
    warm_losses = [float(step(gbatch))]
    del gbatch
    part("first_step_s")      # trace, lower, compile or cache hit, one step
    for _ in range(cell["warmup_steps"] - 1):
        warm_losses.append(float(step(next(prefetch))))
    part("warmup_s")
    setup_events = events.snapshot()
    setup_s = time.perf_counter() - T_PROCESS_START
    emit({"phase": "setup", "setup_s": setup_s, "parts": parts,
          "compile_cache_dir": cache_dir, **setup_events,
          "reference_losses": ref_losses, "first_losses": warm_losses,
          "peak_bytes_after_reference": peak_after_reference, **facts})

    trace_dir = os.path.join(work_dir, "trace")
    trace_plan = None
    if trace:
        options = jax.profiler.ProfileOptions()
        # the three spans are enough; at the default levels the host tracer
        # records millions of transfer tasks and the traced loop crawls
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        n = cell["trace_steps"]
        # one step at either edge is dropped by the reduction
        trace_plan = (2, 2 + n + 2,
                      lambda: jax.profiler.start_trace(
                          trace_dir, profiler_options=options),
                      jax.profiler.stop_trace)
    window = loop.run_window(lambda: next(prefetch), step, seconds,
                             trace=trace_plan)
    in_window = monitor.since(setup_events, events.snapshot())
    peak_bytes = memory_peak(devices)

    losses = warm_losses + window.losses
    _, reasons = timing.loss_checks(losses, ref_losses, LOSS_RTOL)
    if in_window["compilations"]:
        reasons.append("compiled inside the window")
    if len(devices) > 1:
        agree, n_leaves = replicas_agree(sess, devices)
        if not agree:
            reasons.append("replicated weights differ between the chips")
        if not (facts["mesh_holds_the_devices"]
                and facts["batch_devices"] == len(devices)):
            reasons.append("the mesh or the batch does not span the chips")
        emit({"phase": "replicas", "replicated_leaves_bit_equal": agree,
              "leaves_checked": n_leaves})
    job.close()

    run = {"cell": cell, "config": config, "job": job, "window": window,
           "setup_s": setup_s, "peaks": peaks_for(devices[0].device_kind)
           if devices[0].platform == "tpu" else None,
           "memory_peak_bytes": max(peak_bytes),
           "shapes": family.layer_shapes(cell, config),
           "summary": None, "lanes": None, "spans": {}}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": not reasons, "attempted": window.dispatched,
              "failed": window.failed, "metrics": {}, "device": device}

    section = "per_layer" if trace else "end_to_end"
    if trace:
        xplane = tr.find_xplane(trace_dir)
        if xplane:
            run["lanes"] = tr.load_xplane(xplane)
            run["summary"] = tr.summarize(run["lanes"])
        if window.traced:
            lo, hi = window.traced[0] + 1, window.traced[1] - 1
            run["spans"] = {k: v[lo:hi] for k, v in window.spans.items()}
        if run["summary"]:
            s = run["summary"]
            device["busy_s"], device["window_s"] = s["busy_s"], s["window_s"]
            result["breakdown"] = {"device_ops": s["device_ops"],
                                   "idle_gaps": s["idle_gaps"]}
    for m in cells.metrics_of(cell["name"], section, manifest):
        value = cells.load_reader(section, m["name"])(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    rate = timing.throughput(window.finish, job.units_per_step)
    steps_ms = timing.intervals_ms(window.finish)
    emit({"phase": "window", "seconds": seconds, "steps_finished":
          len(window.finish), "dispatched": window.dispatched,
          "compilations_in_window": in_window["compilations"],
          f"{job.unit}_per_s": rate,
          "step_ms_median": timing.percentile(steps_ms, 50),
          "step_ms_max": max(steps_ms, default=None),
          "model_flops_per_unit": flops_per_unit,
          "mfu": rate * flops_per_unit / (len(devices) * run["peaks"][
              "bf16_flops_per_s"])
          if rate and flops_per_unit and run["peaks"] else None,
          "losses_first5": losses[:5], "losses_last5": losses[-5:],
          "not_correct_because": reasons,
          "peak_bytes": peak_bytes,
          "memory_stats_device0": devices[0].memory_stats()})
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = cells.load_manifest()
    cell, config = cells.load_cell(args.workload, manifest)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: no TPU: jax.devices()[0] is {devices[0]}",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"benchmark: {cell['name']} needs {cell['chips']} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    peaks_for(devices[0].device_kind)      # an unknown chip is an error

    from autodist_tpu.data import loader

    kind = loader.loader_kind()
    if not kind.startswith("native"):
        print(f"benchmark: the native loader is not there (got: {kind})",
              file=sys.stderr)
        return 2

    named = {"platform": devices[0].platform,
             "device_kind": devices[0].device_kind, "count": len(devices),
             "workload": cell["name"]}

    def emit(rec):
        print(json.dumps({**named, **rec}), flush=True)

    emit({"phase": "start", "seed": args.seed, "loader": kind,
          "trace": args.trace})
    result = run_cell(cell, config, manifest, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, emit=emit,
                      work_dir=os.path.join(WORK_DIR, cell["name"]))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
