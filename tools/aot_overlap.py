"""Overlap-scheduler lever: barrier vs overlap engine compiles for v5e.

Deviceless compile-time counts for the ``BENCH_OVERLAP`` bench lever (no
chip, so no measurement): the SAME model compiles twice through the real
XLA:TPU toolchain — once with the barrier sync schedule and the default
scheduler, once with ``schedule="overlap"`` + the latency-hiding
scheduler flags (``kernel/xla_options.py``) — and the record captures

  - XLA's own cost analysis per variant (flops / bytes accessed: the
    overlap schedule must NOT change the math, only its ordering);
  - the analytic cost model's serialized vs overlapped step estimates
    (``CostEstimate.serialized_s`` / ``overlapped_s``) — the predicted
    effect the cost model now ranks strategies by;
  - per-variant compile seconds and HLO collective counts.

Writes ``records/v5e_aot/overlap_lever.json``.  Compile-time evidence,
honestly labeled — the schedulers' RELATIVE estimates on the emitted
program, never an on-chip measurement.  Run: ``make aot-overlap``.

Models: ``gpt`` (GPT-2-small-family flagship, scaled by env) and
``resnet`` (argv selects a subset, default both at reduced size so the
tool finishes in minutes).
"""
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# deviceless topology construction must not wait on a GCE metadata
# server that off-GCE hosts cannot answer (hangs otherwise)
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

TOPOLOGY = os.environ.get("MOSAIC_AOT_TOPOLOGY", "v5e:2x2")


def _collective_stats(hlo_text):
    """Count the collective ops (and async starts) the schedule emitted."""
    return {
        "all_reduce_ops": len(re.findall(r"all-reduce(?:-start)?\(", hlo_text)),
        "reduce_scatter_ops": len(re.findall(r"reduce-scatter\(", hlo_text)),
        "all_gather_ops": len(
            re.findall(r"all-gather(?:-start)?\(", hlo_text)),
        "async_collective_starts": len(
            re.findall(r"(?:all-reduce|all-gather|collective-permute)-start",
                       hlo_text)),
    }


def _capture(model, n):
    import optax

    from autodist_tpu.models import train_lib
    from autodist_tpu.model_item import ModelItem

    if model == "gpt":
        import dataclasses

        from autodist_tpu.models.gpt import GPT_SMALL

        S = int(os.environ.get("AOT_OVERLAP_SEQ", "256"))
        # attention_impl defaults to the XLA path here: this lever isolates
        # the COLLECTIVE schedule, and the Mosaic flash kernel's compile
        # validation already lives in mosaic_aot_check.py (older toolchains
        # can lack the kernel's Mosaic features without losing the lever)
        attn = os.environ.get("AOT_OVERLAP_ATTN", "xla")
        cfg = dataclasses.replace(GPT_SMALL, max_position=max(
            S, GPT_SMALL.max_position), dtype=jnp.bfloat16,
            attention_impl=attn)
        loss_fn, params, sparse = train_lib.gpt_capture(
            cfg, S, streaming_loss=True)
        item = ModelItem(loss_fn, params, optax.adamw(1e-4),
                         sparse_vars=sparse, has_rng=True)
        B = int(os.environ.get("AOT_OVERLAP_BATCH", "8")) * n
        batch_avals = {"tokens": ((B, S), jnp.int32),
                       "targets": ((B, S), jnp.int32)}
        flops_per_example = 0.0
        return item, batch_avals, flops_per_example
    if model == "resnet":
        from autodist_tpu.models import ResNet50

        m = ResNet50(num_classes=1000)
        loss_fn, params, state = train_lib.classifier_capture(
            m, (224, 224, 3))
        item = ModelItem(loss_fn, params, train_lib.sgd_momentum(0.1),
                         mutable_state=state)
        B = int(os.environ.get("AOT_OVERLAP_BATCH", "64")) * n
        batch_avals = {"image": ((B, 224, 224, 3), jnp.bfloat16),
                       "label": ((B,), jnp.int32)}
        return item, batch_avals, 3 * 4.089e9
    raise SystemExit(f"unknown model {model!r} (gpt | resnet)")


def main():
    from tools.mosaic_aot_check import _git_sha, _xla_stats

    from autodist_tpu.aot import force_on_tpu_selection
    from autodist_tpu.kernel.graph_transformer import GraphTransformer
    from autodist_tpu.kernel.xla_options import (compile_lowered,
                                                 overlap_compiler_options)
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.simulator.cost_model import estimate
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler

    os.environ.setdefault("AUTODIST_IS_TESTING", "True")
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    n = len(topo.devices)
    mesh = Mesh(np.array(topo.devices), ("replica",))
    spec = ResourceSpec.from_num_chips(n)

    out_dir = os.environ.get("AOT_SWEEP_DIR") or os.path.join(
        REPO, "records", "v5e_aot")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "overlap_lever.json")
    results = {
        "topology": TOPOLOGY, "n_devices": n,
        "method": (
            "deviceless XLA:TPU compile of the full engine train step per "
            "(model, schedule); overlap compiles with "
            "xla_tpu_enable_latency_hiding_scheduler + bucket-sized "
            "combine thresholds; estimates are the analytic cost model's "
            "serialized vs overlapped terms — RELATIVE compile-time "
            "evidence, not an on-chip measurement"),
        "compiler_options_overlap": overlap_compiler_options(),
        "models": {}}
    try:
        with open(out) as f:
            results["models"] = json.load(f).get("models", {})
    except (OSError, ValueError):
        pass

    for model in (sys.argv[1:] or ["gpt", "resnet"]):
        item, batch_shapes, fpe = _capture(model, n)
        entry = {"config": {
            "batch_per_chip": int(os.environ.get("AOT_OVERLAP_BATCH",
                                                 "8" if model == "gpt"
                                                 else "64")),
            **({"seq_len": int(os.environ.get("AOT_OVERLAP_SEQ", "256"))}
               if model == "gpt" else {}),
        }, "schedules": {}}
        for schedule in ("barrier", "overlap"):
            t0 = time.time()
            strat = StrategyCompiler(item, spec).compile(
                AllReduce(schedule=schedule).build(item, spec))
            t = GraphTransformer(strat, item, mesh)
            assert t.sync_schedule == schedule
            bspec = tuple(t.batch_spec)

            def to_aval(leaf):
                shp, dt = leaf
                return jax.ShapeDtypeStruct(
                    tuple(shp), dt, sharding=NamedSharding(
                        mesh, P(*bspec[:len(shp)])))

            batch_avals = jax.tree.map(
                to_aval, batch_shapes,
                is_leaf=lambda x: (isinstance(x, tuple) and len(x) == 2
                                   and isinstance(x[0], (tuple, list))))
            step = t.make_train_step(donate=True)
            with force_on_tpu_selection():
                lowered = step.trace(t.abstract_state(), batch_avals).lower(
                    lowering_platforms=("tpu",))
            opts = (overlap_compiler_options() if schedule == "overlap"
                    else None)
            exe, applied = compile_lowered(lowered, opts)
            txt = exe.as_text()
            est = estimate(strat, item, spec, flops_per_example=fpe,
                           batch_per_chip=int(
                               os.environ.get("AOT_OVERLAP_BATCH", "8")))
            entry["schedules"][schedule] = {
                **_xla_stats(exe), **_collective_stats(txt),
                "applied_compiler_options": applied,
                "compile_seconds": round(time.time() - t0, 1),
                "cost_model": {
                    "schedule": est.schedule,
                    "serialized_s": est.serialized_s,
                    "overlapped_s": est.overlapped_s,
                    "total_s": est.total_s,
                    "comm_s": est.comm_s, "compute_s": est.compute_s,
                    "ar_buckets": est.breakdown["ar_buckets"],
                    "overlap_exposed_s":
                        est.breakdown["overlap_exposed_s"],
                },
            }
            print(f"[aot-overlap] {model}/{schedule}: "
                  f"{entry['schedules'][schedule]}", flush=True)
        bar = entry["schedules"]["barrier"]["cost_model"]
        ovl = entry["schedules"]["overlap"]["cost_model"]
        entry["predicted_step_speedup"] = (
            round(bar["serialized_s"] / ovl["overlapped_s"], 4)
            if ovl["overlapped_s"] else None)
        entry["git_sha"] = _git_sha()
        entry["recorded_unix"] = int(time.time())
        results["models"][model] = entry
        with open(out, "w") as f:  # merge-write per model
            json.dump(results, f, indent=2)
            f.write("\n")
    print(f"[aot-overlap] wrote {out}")


if __name__ == "__main__":
    main()
