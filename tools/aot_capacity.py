"""HBM capacity proof for full-size training steps, no chip needed.

Compiles the models ``chip_smoke.py`` runs — ResNet-50 @224
B=256 bf16 AllReduce, and GPT-2-small S=1024 flash + streaming vocab
loss + remat adamw — as FULL training steps through the engine against
the deviceless v5e topology, with donated state (the session's real
memory behavior), and records XLA:TPU's memory_analysis against the v5e
16 GiB HBM budget.  Writes ``records/v5e_aot/capacity.json``.

Run: ``make aot-capacity`` (takes several minutes — real compiles of
full-size models).
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

HBM_BYTES = 16 * 1024 ** 3          # v5e: 16 GiB per chip
TOPOLOGY = os.environ.get("MOSAIC_AOT_TOPOLOGY", "v5e:2x2")


def _engine_step_avals(loss_fn, params, optimizer, batch_avals, *,
                       sparse=None, has_rng=False, mutable_state=None,
                       mesh=None):
    from autodist_tpu.kernel.graph_transformer import GraphTransformer
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler

    n = len(mesh.devices.ravel())
    spec = ResourceSpec.from_num_chips(n)
    item = ModelItem(loss_fn, params, optimizer, sparse_vars=sparse,
                     has_rng=has_rng, mutable_state=mutable_state)
    strat = StrategyCompiler(item, spec).compile(
        AllReduce().build(item, spec))
    t = GraphTransformer(strat, item, mesh)
    # donate=True: the session's real behavior — outputs alias the donated
    # state, so HBM demand is arguments + temps (not 2x the state)
    return t.make_train_step(donate=True), t.abstract_state(), batch_avals


def main():
    from tools.mosaic_aot_check import _pretend_on_tpu, _git_sha

    os.environ.setdefault("AUTODIST_IS_TESTING", "True")
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    # single-chip configs: one chip holds the whole state
    mesh = Mesh(np.array(topo.devices[:1]), ("replica",))
    bsh = NamedSharding(mesh, P("replica"))
    results = {"topology": TOPOLOGY, "hbm_bytes": HBM_BYTES, "configs": {}}

    def record(name, builder):
        t0 = time.time()
        try:
            step, state_avals, batch_avals, units = builder()
            with _pretend_on_tpu():
                lowered = step.trace(state_avals, batch_avals).lower(
                    lowering_platforms=("tpu",))
            exe = lowered.compile()
            ma = exe.memory_analysis()
            arg = int(ma.argument_size_in_bytes)
            tmp = int(ma.temp_size_in_bytes)
            # donated outputs alias arguments; demand = args + temps + code
            code = int(getattr(ma, "generated_code_size_in_bytes", 0))
            demand = arg + tmp + code
            results["configs"][name] = {
                "ok": True,
                "argument_bytes": arg, "temp_bytes": tmp,
                "code_bytes": code, "demand_bytes": demand,
                "demand_gib": round(demand / 1024 ** 3, 2),
                "fits_hbm": demand <= HBM_BYTES,
                "headroom_gib": round((HBM_BYTES - demand) / 1024 ** 3, 2),
                "compile_seconds": round(time.time() - t0, 1),
                # per-CONFIG provenance: merged records must never be
                # re-attributed to a later run's commit
                "git_sha": _git_sha(),
                "recorded_unix": int(time.time()),
            }
            # roofline throughput prediction from XLA's own counts —
            # compile-time evidence, labeled, never a measured claim
            from tools.mosaic_aot_check import _xla_stats

            stats = _xla_stats(exe)
            flops = stats.get("xla_flops", 0.0)
            bytes_ = stats.get("xla_bytes_accessed", 0.0)
            if flops and bytes_ and units:
                pred_s = max(flops / (394e12 * 0.45), bytes_ / 819e9)
                unit_name, n_units = units
                results["configs"][name].update({
                    "xla_flops": flops, "xla_bytes_accessed": bytes_,
                    "roofline_pred_step_ms": round(1000 * pred_s, 2),
                    f"roofline_pred_{unit_name}_per_sec": round(
                        n_units / pred_s, 1),
                })
        except Exception as e:
            import traceback

            traceback.print_exc()
            results["configs"][name] = {
                "ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
        print(f"[aot-capacity] {name}: "
              f"{results['configs'][name]}", flush=True)

    def gpt_small():
        import dataclasses

        from autodist_tpu.models import GPT_SMALL, train_lib

        S, B = 1024, 8
        cfg = dataclasses.replace(GPT_SMALL, max_position=S, remat=True)
        loss_fn, params, sparse = train_lib.gpt_capture(
            cfg, S, streaming_loss=True)
        batch_avals = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh),
            "targets": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh)}
        return (*_engine_step_avals(loss_fn, params, optax.adamw(1e-4),
                                    batch_avals, sparse=sparse,
                                    has_rng=True, mesh=mesh),
                ("tokens", B * S))

    def resnet50():
        from autodist_tpu.models import ResNet50, train_lib

        B = 256
        model = ResNet50(num_classes=1000)
        loss_fn, params, state = train_lib.classifier_capture(
            model, (224, 224, 3))
        batch_avals = {
            "image": jax.ShapeDtypeStruct((B, 224, 224, 3), jnp.bfloat16,
                                          sharding=bsh),
            "label": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=bsh)}
        return (*_engine_step_avals(loss_fn, params,
                                    train_lib.sgd_momentum(0.1),
                                    batch_avals, mutable_state=state,
                                    mesh=mesh),
                ("images", B))

    def gpt_longcontext_ring():
        """The long-context pillar at scale: S=8192 sharded over a
        4-device ``seq`` axis (per-device block 2048), causal flash RING
        attention streaming K/V blocks around the mesh, streaming vocab
        loss, remat — per-device memory must be O(S_local), not O(S)."""
        import dataclasses

        from autodist_tpu.models import GPT_SMALL, train_lib

        S, B = 8192, 2
        n_seq = 4
        cfg = dataclasses.replace(GPT_SMALL, max_position=S, remat=True)
        loss_fn, params, sparse = train_lib.gpt_capture(
            cfg, S, streaming_loss=True)
        ring_mesh = Mesh(np.array(topo.devices).reshape(1, n_seq),
                         ("replica", "seq"))
        rsh = NamedSharding(ring_mesh, P("replica", "seq"))
        batch_avals = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=rsh),
            "targets": jax.ShapeDtypeStruct((B, S), jnp.int32,
                                            sharding=rsh)}
        # per-DEVICE cost stats on a 4-device mesh: units are global
        # tokens; per-chip = global / 4
        return (*_engine_step_avals(loss_fn, params, optax.adamw(1e-4),
                                    batch_avals, sparse=sparse,
                                    has_rng=True, mesh=ring_mesh),
                ("tokens_global", B * S))

    builders = {
        "gpt_small_s1024_b8_flash_streaming_remat": gpt_small,
        "resnet50_224_b256_bf16": resnet50,
        "gpt_small_s8192_b2_ring_seq4": gpt_longcontext_ring,
    }
    # argv selects a subset (full-size compiles take minutes each); the
    # results MERGE into the existing artifact so configs can be recorded
    # one at a time under an external per-process time budget
    selected = sys.argv[1:] or list(builders)
    unknown = [s for s in selected if s not in builders]
    if unknown:
        raise SystemExit(f"unknown configs {unknown}; have {list(builders)}")

    out_dir = os.environ.get("AOT_SWEEP_DIR") or os.path.join(
        REPO, "records", "v5e_aot")
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "capacity.json")
    try:
        with open(out) as f:
            results["configs"] = json.load(f).get("configs", {})
    except (OSError, ValueError):
        pass

    for name in selected:
        record(name, builders[name])

    results["ok"] = all(c.get("ok") and c.get("fits_hbm")
                        for c in results["configs"].values())
    results["last_run_git_sha"] = _git_sha()
    results["last_run_unix"] = int(time.time())
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"[aot-capacity] wrote {out}: ok={results['ok']}")
    sys.exit(0 if results["ok"] else 1)


if __name__ == "__main__":
    main()
