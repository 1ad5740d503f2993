"""Multi-model x multi-strategy sweep against the REAL v5e compiler, no
chip required.

For each (model, strategy): build the engine exactly as ``distribute()``
does, AOT-compile the full training step for a deviceless ``v5e:2x2``
PJRT topology (tools/mosaic_aot_check.py's mechanism), and record
XLA:TPU's own ``cost_analysis`` / ``memory_analysis`` numbers.  A
roofline prediction per strategy falls out:

    step_pred = max(flops / (peak * mxu_eff), bytes / hbm_bw) + comm_s

with the comm term from the analytic cost model (the collectives'
schedule isn't in XLA's per-op counts).  The ranking is COMPILE-TIME
evidence from the actual TPU toolchain — stronger than the CPU-mesh
timings (which measure a different machine) and honestly labeled weaker
than a real on-chip measurement (no overlap/latency effects).

Writes ``records/v5e_aot/summary.json``.  Run: ``make aot-sweep``.
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
import optax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

TOPOLOGY = os.environ.get("MOSAIC_AOT_TOPOLOGY", "v5e:2x2")
# same v5e numbers the cost model uses (simulator/cost_model.py)
PEAK_FLOPS = 394e12
MXU_EFF = 0.45
HBM_BW = 819e9

STRATEGIES = ("AllReduce", "PS", "PartitionedPS", "Parallax")


def _captures(n):
    """Model zoo for the sweep: one dense conv-free LM, one sparse-routed
    LM, one flash+streaming GPT — tiny layer counts (compile time), real
    structures."""
    from autodist_tpu.models import train_lib
    from autodist_tpu.models.bert import BertConfig
    from autodist_tpu.models.gpt import GPTConfig

    B = 2 * n
    out = {}

    S = 128
    bcfg = BertConfig(vocab_size=2048, hidden_size=128, num_layers=2,
                      num_heads=2, intermediate_size=512, max_position=S)
    loss_fn, params, sparse = train_lib.bert_capture(bcfg, seq_len=S)
    out["bert_tiny"] = dict(
        loss_fn=loss_fn, params=params, sparse=sparse, has_rng=True,
        batch={"input_ids": ((B, S), jnp.int32),
               "labels": ((B, S), jnp.int32),
               "next_sentence_label": ((B,), jnp.int32)})

    gcfg = GPTConfig(vocab_size=2048, hidden_size=128, num_layers=2,
                     num_heads=2, intermediate_size=512, max_position=S,
                     dropout_rate=0.0, dtype=jnp.bfloat16,
                     attention_impl="auto")
    loss_fn, params, sparse = train_lib.gpt_capture(
        gcfg, S, streaming_loss=True, loss_chunk=500)
    out["gpt_tiny_flash_streaming"] = dict(
        loss_fn=loss_fn, params=params, sparse=sparse, has_rng=True,
        batch={"tokens": ((B, S), jnp.int32),
               "targets": ((B, S), jnp.int32)})
    return out


def main():
    from tools.mosaic_aot_check import _pretend_on_tpu, _xla_stats, _git_sha

    from autodist_tpu import strategy as S
    from autodist_tpu.kernel.graph_transformer import GraphTransformer
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.simulator.cost_model import estimate

    os.environ.setdefault("AUTODIST_IS_TESTING", "True")
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    n = len(topo.devices)
    spec = ResourceSpec.from_num_chips(n)
    mesh = Mesh(np.array(topo.devices), ("replica",))
    bsh = NamedSharding(mesh, P("replica"))
    results = {"topology": TOPOLOGY, "n_devices": n,
               "method": (
                   "deviceless XLA:TPU compile; step_pred = max(flops/"
                   "(peak*mxu_eff), bytes/hbm_bw) + analytic comm_s; "
                   "COMPILE-TIME evidence, not an on-chip measurement"),
               "models": {}}
    for model_name, cap in _captures(n).items():
        per = {}
        for sname in STRATEGIES:
            t0 = time.time()
            item = ModelItem(cap["loss_fn"], cap["params"],
                             optimizer=optax.adamw(1e-3),
                             sparse_vars=cap["sparse"],
                             has_rng=cap["has_rng"])
            from autodist_tpu.strategy.base import StrategyCompiler

            strat = StrategyCompiler(item, spec).compile(
                getattr(S, sname)().build(item, spec))
            t = GraphTransformer(strat, item, mesh)
            batch_avals = {
                k: jax.ShapeDtypeStruct(shape, dt, sharding=bsh)
                for k, (shape, dt) in cap["batch"].items()}
            step = t.make_train_step(donate=False)
            with _pretend_on_tpu():
                lowered = step.trace(t.abstract_state(), batch_avals).lower(
                    lowering_platforms=("tpu",))
            exe = lowered.compile()
            stats = _xla_stats(exe)
            est = estimate(strat, item, spec)
            compute_s = stats.get("xla_flops", 0.0) / (PEAK_FLOPS * MXU_EFF)
            mem_s = stats.get("xla_bytes_accessed", 0.0) / HBM_BW
            per[sname] = {
                **stats,
                "analytic_comm_s": est.comm_s,
                "step_pred_s": max(compute_s, mem_s) + est.comm_s,
                "compile_seconds": round(time.time() - t0, 1),
            }
            print(f"[aot-sweep] {model_name} x {sname}: "
                  f"pred={per[sname]['step_pred_s']:.3e}s "
                  f"(compile {per[sname]['compile_seconds']}s)", flush=True)
        rank = sorted(per, key=lambda k: per[k]["step_pred_s"])
        results["models"][model_name] = {"strategies": per,
                                         "predicted_rank": rank}
    results["git_sha"] = _git_sha()
    results["recorded_unix"] = int(time.time())
    out_dir = os.environ.get("AOT_SWEEP_DIR") or os.path.join(
        REPO, "records", "v5e_aot")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "summary.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"[aot-sweep] wrote {out}")


if __name__ == "__main__":
    main()
