"""Quantify the ResNet-50 MFU levers with the real TPU compiler, no chip.

The round-3 on-chip diagnosis: 99.8 ms/step at B=256 (MFU 0.16), XLA
emitting 1.95x the model FLOPs, BN batch-stats 8.8 ms of a 30.1 ms
forward.  The levers are coded (``BENCH_STEM=space_to_depth``,
``BENCH_BN_STATS=bf16``) but unmeasured on a chip.
This tool compiles each variant FULL-SIZE (B=256 @224, bf16, AllReduce
engine step) for the deviceless v5e topology and records XLA:TPU's own
``cost_analysis`` per variant:

  - ``xla_flops``          — the compiler's emitted-FLOP count (the 1.95x
                              overhead made visible per variant)
  - ``xla_bytes_accessed`` — HBM traffic (what the BN-stat lever attacks)
  - roofline step-time prediction ``max(flops/(peak·eff), bytes/hbm_bw)``

Compile-time evidence, honestly labeled — the levers' RELATIVE effect on
the emitted program, not an on-chip measurement.  Writes
``records/v5e_aot/resnet_levers.json`` (merging per-variant, argv
selects a subset).  Run: ``make aot-levers`` (minutes per variant).
"""
import json
import os
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
PEAK_FLOPS = 394e12
MXU_EFF = 0.45
HBM_BW = 819e9
B = int(os.environ.get("AOT_LEVERS_BATCH", "256"))
MODEL_FLOPS_PER_STEP = 3 * 4.089e9 * B     # bench.py's MFU numerator

VARIANTS = {
    "conv_f32stats": dict(stem="conv", bn_f32_stats=True),
    "s2d_f32stats": dict(stem="space_to_depth", bn_f32_stats=True),
    "conv_bf16stats": dict(stem="conv", bn_f32_stats=False),
    "s2d_bf16stats": dict(stem="space_to_depth", bn_f32_stats=False),
}


def main():
    from tools.mosaic_aot_check import _git_sha, _xla_stats

    import optax  # noqa: F401

    from autodist_tpu.kernel.graph_transformer import GraphTransformer
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.models import ResNet50, train_lib
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler

    os.environ.setdefault("AUTODIST_IS_TESTING", "True")
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    mesh = Mesh(np.array(topo.devices[:1]), ("replica",))
    bsh = NamedSharding(mesh, P("replica"))
    spec = ResourceSpec.from_num_chips(1)

    out_dir = os.environ.get("AOT_SWEEP_DIR") or os.path.join(
        REPO, "records", "v5e_aot")
    os.makedirs(out_dir, exist_ok=True)
    # non-default batches get their own file — the variants are keyed by
    # stem/stats only, so mixing batches in one file would collide
    out = os.path.join(out_dir, "resnet_levers.json" if B == 256
                       else f"resnet_levers_b{B}.json")
    results = {"topology": TOPOLOGY, "batch": B,
               "model_flops_per_step": MODEL_FLOPS_PER_STEP,
               "baseline_onchip": {
                   "note": "round-3 measured conv/f32 on-chip step",
                   "step_ms": 99.8, "mfu": 0.16},
               "method": (
                   "deviceless XLA:TPU compile of the full engine train "
                   "step per variant; roofline pred = max(flops/"
                   "(peak*mxu_eff), bytes/hbm_bw); RELATIVE compile-time "
                   "evidence, not an on-chip measurement"),
               "variants": {}}
    try:
        with open(out) as f:
            results["variants"] = json.load(f).get("variants", {})
    except (OSError, ValueError):
        pass

    selected = sys.argv[1:] or list(VARIANTS)
    for name in selected:
        cfg = VARIANTS[name]
        t0 = time.time()
        model = ResNet50(num_classes=1000, **cfg)
        loss_fn, params, state = train_lib.classifier_capture(
            model, (224, 224, 3))
        item = ModelItem(loss_fn, params, train_lib.sgd_momentum(0.1),
                         mutable_state=state)
        strat = StrategyCompiler(item, spec).compile(
            AllReduce().build(item, spec))
        t = GraphTransformer(strat, item, mesh)
        batch_avals = {
            "image": jax.ShapeDtypeStruct((B, 224, 224, 3), jnp.bfloat16,
                                          sharding=bsh),
            "label": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=bsh)}
        step = t.make_train_step(donate=True)
        lowered = step.trace(t.abstract_state(), batch_avals).lower(
            lowering_platforms=("tpu",))
        exe = lowered.compile()
        stats = _xla_stats(exe)
        flops = stats.get("xla_flops", 0.0)
        bytes_ = stats.get("xla_bytes_accessed", 0.0)
        compute_s = flops / (PEAK_FLOPS * MXU_EFF)
        mem_s = bytes_ / HBM_BW
        pred_s = max(compute_s, mem_s)
        results["variants"][name] = {
            **cfg, **stats,
            "flops_overhead_vs_model": round(
                flops / MODEL_FLOPS_PER_STEP, 3) if flops else None,
            "roofline_pred_ms": round(1000 * pred_s, 2),
            "roofline_bound": "compute" if compute_s >= mem_s else "memory",
            "mfu_at_pred": round(
                MODEL_FLOPS_PER_STEP / pred_s / PEAK_FLOPS, 3),
            "compile_seconds": round(time.time() - t0, 1),
            # per-VARIANT provenance: merged records keep their own commit
            "git_sha": _git_sha(),
            "recorded_unix": int(time.time()),
        }
        print(f"[aot-levers] {name}: {results['variants'][name]}",
              flush=True)
        # merge-write after EVERY variant: an external kill cannot erase
        # finished compiles
        results["last_run_git_sha"] = _git_sha()
        results["last_run_unix"] = int(time.time())
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
            f.write("\n")
    print(f"[aot-levers] wrote {out}")


if __name__ == "__main__":
    main()
