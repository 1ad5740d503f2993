"""Deviceless Mosaic/XLA:TPU compile validation.

The installed ``libtpu`` can build a PJRT *topology description* for a
known TPU generation WITHOUT hardware attached, and jax's AOT path
(``jit(f).trace(...).lower(lowering_platforms=("tpu",)).compile()``)
compiles against it through the full XLA:TPU + Mosaic stack.  That means
the Pallas kernel surface — tiling, VMEM budgeting, Mosaic lowering — is
validated by the REAL TPU compiler with no chip attached; only
execution (numerics on hardware) needs the chip.  The
interpreter-mode tests cover those numerics; this closes the other half.

Checks (all against a ``v5e:2x2`` topology, bf16):
  1. flash attention forward (causal) — Pallas kernel, Mosaic
  2. flash attention backward — the two hand-written bwd kernels
  3. int8 quantize / dequant-sum kernels
  4. ring attention over a 4-device "seq" mesh — shard_map + ppermute +
     the flash kernel inside, GSPMD-partitioned for real TPU devices
  5. the driver's ``entry()`` flagship (GPT-2-small @ S=1024, flash
     attention auto-selected ON TPU, streaming vocab loss)
  6. the gated delta rule's forward and backward kernels at the shape of
     the benchmark's Qwen3-Next cell

Writes MOSAIC_AOT.json at the repo root and exits nonzero on any
failure.  Run via ``make mosaic-aot``.
"""
import json
import os
import sys
import time
import traceback

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

TOPOLOGY = os.environ.get("MOSAIC_AOT_TOPOLOGY", "v5e:2x2")


def _git_sha():
    """HEAD sha, '-dirty'-marked so the evidence file can never attribute
    a pass to a commit whose tree didn't produce it."""
    import subprocess

    try:
        sha = subprocess.run(["git", "-C", REPO, "rev-parse", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip()[:12] or "unknown"
        dirty = subprocess.run(["git", "-C", REPO, "status", "--porcelain"],
                               capture_output=True, text=True,
                               timeout=10).stdout.strip()
        return sha + ("-dirty" if dirty else "")
    except Exception:
        return "unknown"

# This process has NO attached backend (default backend would be cpu), but
# every compile below targets TPU via lowering_platforms.  The kernels'
# interpret/impl auto-selection keys on the DEFAULT backend, so force the
# on-TPU answer AT TRACE TIME — otherwise the harness would silently
# compile the interpreter fallback and validate nothing (the exact trap
# this tool exists to close).  Scoped to the trace: eager setup work
# (model.init builds params on the host backend) must keep the honest
# answer or it would try to EXECUTE Mosaic kernels on the CPU.
from autodist_tpu.aot import (  # noqa: E402
    force_on_tpu_selection as _pretend_on_tpu)


TOPO = None


def _compile(fn, *avals, expect_mosaic=True, in_shardings=None):
    """AOT-compile ``fn`` AGAINST THE TPU TOPOLOGY (deviceless).

    The shardings must reference the topology's device descriptions —
    that is what routes ``compile()`` through the topology's compile
    client instead of the default (host) backend, which cannot compile
    ``tpu_custom_call``.  ``expect_mosaic`` asserts the executable really
    contains a Mosaic kernel call, so a silent fallback to the XLA path
    can never masquerade as kernel validation."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if in_shardings is None:
        mesh = Mesh(np.array(TOPO.devices[:1]), ("x",))
        in_shardings = NamedSharding(mesh, P())
    traced = jax.jit(fn, in_shardings=in_shardings)
    with _pretend_on_tpu():
        lowered = traced.trace(*avals).lower(lowering_platforms=("tpu",))
    exe = lowered.compile()
    txt = exe.as_text()
    if expect_mosaic:
        assert "tpu_custom_call" in txt, (
            "no Mosaic custom call in the compiled executable — the XLA "
            "fallback was silently selected")
    return exe, txt


def _xla_stats(exe):
    """XLA:TPU's own per-device cost + memory analysis of a
    topology-compiled executable — real v5e numbers, no chip.  The memory
    view is the deployment question (does the step fit 16 GB HBM?); the
    flops view feeds the cost model's compute term."""
    stats = {}
    try:
        ca = exe.cost_analysis()
        ca = dict(ca[0] if isinstance(ca, (list, tuple)) else ca)
        stats["xla_flops"] = float(ca.get("flops", 0.0))
        stats["xla_bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:
        stats["cost_analysis_error"] = str(e)[:200]
    try:
        ma = exe.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                stats[k] = int(v)
    except Exception as e:
        stats["memory_analysis_error"] = str(e)[:200]
    return stats


def main():
    global TOPO
    t0 = time.time()
    TOPO = topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    results = {"topology": TOPOLOGY,
               "device_kind": topo.devices[0].device_kind,
               "n_devices": len(topo.devices), "checks": {}}
    ok = True

    def check(name, fn):
        nonlocal ok
        t = time.time()
        try:
            info = fn() or {}
            results["checks"][name] = {"ok": True,
                                       "seconds": round(time.time() - t, 1),
                                       **info}
            print(f"[mosaic-aot] {name}: OK ({time.time() - t:.1f}s)",
                  flush=True)
        except Exception as e:
            ok = False
            results["checks"][name] = {
                "ok": False, "error": f"{type(e).__name__}: {e}"[:1000]}
            print(f"[mosaic-aot] {name}: FAIL\n{traceback.format_exc()}",
                  flush=True)

    from autodist_tpu.ops.pallas.flash_attention import flash_attention

    # model layout (B, S, H, D) — the layout models/gpt.py feeds
    B, S, H, D = 2, 512, 4, 64
    qav = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)

    def flash_fwd():
        _, txt = _compile(
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            qav, qav, qav)
        assert "fusion" in txt or "custom-call" in txt
        return {"shape": list(qav.shape)}

    def flash_bwd():
        def loss(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True).astype(jnp.float32))

        _compile(jax.grad(loss, argnums=(0, 1, 2)), qav, qav, qav)
        return {}

    def quantize():
        from autodist_tpu.ops.pallas.quantize import (dequant_sum,
                                                      quantize_int8)

        xav = jax.ShapeDtypeStruct((256, 256), jnp.float32)

        def roundtrip(x):
            q, s = quantize_int8(x)         # (N, BLOCK) -> int8 + scales
            return dequant_sum(q[None], s[None])   # one-peer reduce

        _compile(roundtrip, xav)
        return {}

    def ring():
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.parallel.ring_attention import ring_attention

        n = len(topo.devices)
        mesh = Mesh(np.array(topo.devices), ("seq",))
        Sr = 128 * n

        def f(q, k, v):
            # check_vma=False: pallas_call out_shapes carry no vma, so the
            # flash ring (like every Pallas kernel under shard_map in this
            # jax version, and like the engine itself —
            # graph_transformer.py) runs with the VMA check off; the XLA
            # ring path is VMA-clean under the default check
            # (tests/test_ring_attention.py pins that)
            return jax.shard_map(
                lambda q_, k_, v_: ring_attention(q_, k_, v_, "seq",
                                                  causal=True),
                mesh=mesh,
                in_specs=(P(None, "seq", None, None),) * 3,
                out_specs=P(None, "seq", None, None),
                check_vma=False)(q, k, v)

        # model layout (B, S, H, D); the flash ring is auto-selected (the
        # forced on-TPU answer above) so this is the Mosaic ring kernel
        rav = jax.ShapeDtypeStruct((2, Sr, 2, 64), jnp.bfloat16)
        sh = NamedSharding(mesh, P(None, "seq", None, None))
        _, txt = _compile(f, rav, rav, rav, in_shardings=(sh, sh, sh))
        assert "collective-permute" in txt, "ring ppermute missing from HLO"
        return {"n_devices": n, "seq_global": Sr}

    def flagship_entry():
        import __graft_entry__ as g

        fwd, (params, toks, tgts) = g.entry()
        avals = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a), a.dtype),
            (params, toks, tgts))
        exe, _ = _compile(fwd, *avals)
        return {"seq": int(toks.shape[1]), **_xla_stats(exe)}

    def engine_step():
        """The FULL distributed training step — Parallax routing (sparse
        embedding -> sharded PS, dense -> bucketed AR), adamw, shard_map
        over 4 real v5e device targets — compiled by the real TPU
        toolchain via GraphTransformer.abstract_state() (no device ever
        touched)."""
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.models import train_lib
        from autodist_tpu.models.bert import BertConfig
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import Parallax
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        n = len(topo.devices)
        spec = ResourceSpec.from_num_chips(n)
        cfg = BertConfig(vocab_size=512, hidden_size=64, num_layers=2,
                         num_heads=2, intermediate_size=128, max_position=64)
        S = 16
        loss_fn, params, sparse = train_lib.bert_capture(cfg, seq_len=S)
        item = ModelItem(loss_fn, params, optax.adamw(1e-3),
                         sparse_vars=sparse, has_rng=True)
        strat = StrategyCompiler(item, spec).compile(
            Parallax().build(item, spec))
        mesh = Mesh(np.array(topo.devices), ("replica",))
        t = GraphTransformer(strat, item, mesh)
        state_avals = t.abstract_state()
        B = 2 * n
        bsh = NamedSharding(mesh, P("replica"))

        def bav(shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=bsh)

        batch_avals = {"input_ids": bav((B, S)), "labels": bav((B, S)),
                       "next_sentence_label": bav((B,))}
        step = t.make_train_step(donate=False)
        with _pretend_on_tpu():
            lowered = step.trace(state_avals, batch_avals).lower(
                lowering_platforms=("tpu",))
        exe = lowered.compile()
        txt = exe.as_text()
        assert "all-reduce" in txt or "reduce-scatter" in txt, (
            "no cross-replica collective in the compiled engine step")
        return {"n_devices": n, "strategy": "Parallax", **_xla_stats(exe)}

    def gpt_train_step():
        """The long-context flagship TRAINING configuration through the
        engine — flash attention (Mosaic) + streaming vocab loss
        (non-dividing chunks) + Parallax routing + adamw — compiled for 4
        real v5e targets."""
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.models import train_lib
        from autodist_tpu.models.gpt import GPTConfig
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import Parallax
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        n = len(topo.devices)
        S = 128                      # flash-tileable (128-aligned blocks)
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                        num_heads=2, intermediate_size=128, max_position=S,
                        dropout_rate=0.0, dtype=jnp.bfloat16,
                        attention_impl="auto")
        loss_fn, params, sparse = train_lib.gpt_capture(
            cfg, S, streaming_loss=True, loss_chunk=100)   # 100 !| 512
        item = ModelItem(loss_fn, params, optax.adamw(1e-3),
                         sparse_vars=sparse, has_rng=True)
        spec = ResourceSpec.from_num_chips(n)
        strat = StrategyCompiler(item, spec).compile(
            Parallax().build(item, spec))
        mesh = Mesh(np.array(topo.devices), ("replica",))
        t = GraphTransformer(strat, item, mesh)
        bsh = NamedSharding(mesh, P("replica"))
        B = 2 * n
        batch_avals = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh),
            "targets": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh)}
        step = t.make_train_step(donate=False)
        with _pretend_on_tpu():
            lowered = step.trace(t.abstract_state(), batch_avals).lower(
                lowering_platforms=("tpu",))
        exe = lowered.compile()
        txt = exe.as_text()
        assert "tpu_custom_call" in txt, "flash kernel missing (fallback?)"
        assert "all-reduce" in txt or "reduce-scatter" in txt
        return {"n_devices": n, "seq": S, "streaming_loss": True,
                **_xla_stats(exe)}

    def multihost_subset_ps():
        """MULTI-HOST: the subset-axis PS engine step compiled for a real
        16-chip / 4-host v5e:4x4 topology — the scatter/gather confined to
        the within-host ``ici`` axis (replica_groups of contiguous
        same-host ids asserted in the HLO), only shard-sized psums
        crossing the ``dcn`` (cross-host) axis.  The multi-slice traffic
        shape the framework is designed around, validated by the real
        toolchain with zero hosts attached."""
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import PS
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        big = topologies.get_topology_desc("v5e:4x4", "tpu")
        devs = sorted(big.devices, key=lambda d: (d.process_index, d.id))
        hosts = sorted({d.process_index for d in devs})
        n = len(devs)
        per_host = n // len(hosts)
        spec = ResourceSpec(resource_info={
            "nodes": [{"address": "localhost", "chips": list(range(n))}],
            "mesh": {"dcn": len(hosts), "ici": per_host}})
        r = np.random.RandomState(0)
        params = {"w": jnp.asarray(r.randn(512, 256) * 0.1, jnp.float32),
                  "b": jnp.zeros((256,), jnp.float32)}

        def loss(p, batch):
            return jnp.mean((batch["x"] @ p["w"] + p["b"]
                             - batch["y"]) ** 2)

        item = ModelItem(loss, params, optax.sgd(0.05))
        strat = StrategyCompiler(item, spec).compile(
            PS(ps_axes=("ici",)).build(item, spec))
        mesh = Mesh(np.array(devs).reshape(len(hosts), per_host),
                    ("dcn", "ici"))
        t = GraphTransformer(strat, item, mesh, data_axes=("dcn", "ici"))
        B = 2 * n
        bsh = NamedSharding(mesh, P(("dcn", "ici")))
        batch_avals = {
            "x": jax.ShapeDtypeStruct((B, 512), jnp.float32, sharding=bsh),
            "y": jax.ShapeDtypeStruct((B, 256), jnp.float32, sharding=bsh)}
        step = t.make_train_step(donate=False)
        lowered = step.trace(t.abstract_state(), batch_avals).lower(
            lowering_platforms=("tpu",))
        exe = lowered.compile()
        txt = exe.as_text()
        within_host = "{0,1,2,3}" in txt.replace(" ", "")
        assert within_host, (
            "no within-host {0,1,2,3} replica group found — the PS "
            "scatter/gather is not confined to the ici axis")
        return {"n_devices": n, "n_hosts": len(hosts),
                "within_host_groups": True, **_xla_stats(exe)}

    def wire_dtype_bf16():
        """The compressed-AR wire receipt (VERDICT r3 item 4's HLO proof,
        deviceless form): an AllReduce(BF16Compressor) engine step
        compiled for v5e must carry a cross-replica all-reduce whose
        operand is bf16 — the compressor halves the wire bytes on the
        actual TPU compile path, not just in the jaxpr."""
        import re

        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import AllReduce
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        n = len(topo.devices)
        spec = ResourceSpec.from_num_chips(n)
        r = np.random.RandomState(0)
        params = {"w": jnp.asarray(r.randn(256, 256) * 0.1, jnp.float32)}

        def loss(p, b):
            return jnp.mean((b @ p["w"]) ** 2)

        item = ModelItem(loss, params, optax.sgd(0.1))
        strat = StrategyCompiler(item, spec).compile(
            AllReduce(compressor="BF16Compressor").build(item, spec))
        mesh = Mesh(np.array(topo.devices), ("replica",))
        t = GraphTransformer(strat, item, mesh)
        bsh = NamedSharding(mesh, P("replica"))
        batch_avals = jax.ShapeDtypeStruct((8 * n, 256), jnp.float32,
                                           sharding=bsh)
        step = t.make_train_step(donate=False)
        lowered = step.trace(t.abstract_state(), batch_avals).lower(
            lowering_platforms=("tpu",))
        txt = lowered.compile().as_text()
        bf16_ar = re.findall(r"bf16\[[0-9,]*\][^\n]*all-reduce", txt)
        assert bf16_ar, "no bf16-operand all-reduce in the optimized HLO"
        return {"bf16_allreduce_ops": len(bf16_ar)}

    def overlap_schedule_engine_step():
        """The overlap sync schedule through the real toolchain: an
        AllReduce(schedule="overlap") engine step (multiple per-bucket
        collectives, reverse-topological issue order) compiled WITH the
        latency-hiding-scheduler + combine-threshold flags — recording
        XLA's stats next to the cost model's serialized vs overlapped
        estimates: ``AllReduce(schedule="overlap")`` without a chip."""
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.kernel.xla_options import (
            compile_lowered, overlap_compiler_options)
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.simulator.cost_model import estimate
        from autodist_tpu.strategy import AllReduce
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        n = len(topo.devices)
        spec = ResourceSpec.from_num_chips(n)
        r = np.random.RandomState(0)
        params = {"w1": jnp.asarray(r.randn(256, 512) * 0.05, jnp.float32),
                  "w2": jnp.asarray(r.randn(512, 256) * 0.05, jnp.float32),
                  "w3": jnp.asarray(r.randn(256, 64) * 0.05, jnp.float32)}

        def loss(p, b):
            h = jnp.tanh(b @ p["w1"]) @ p["w2"]
            return jnp.mean((jnp.tanh(h) @ p["w3"]) ** 2)

        item = ModelItem(loss, params, optax.adamw(1e-3))
        # chunk_size=1: one bucket group per var -> several independent
        # collectives for the scheduler to pipeline
        builder = AllReduce(chunk_size=1, schedule="overlap")
        strat = StrategyCompiler(item, spec).compile(
            builder.build(item, spec))
        mesh = Mesh(np.array(topo.devices), ("replica",))
        t = GraphTransformer(strat, item, mesh)
        assert t.sync_schedule == "overlap"
        bsh = NamedSharding(mesh, P("replica"))
        bav = jax.ShapeDtypeStruct((8 * n, 256), jnp.float32, sharding=bsh)
        step = t.make_train_step(donate=False)
        lowered = step.trace(t.abstract_state(), bav).lower(
            lowering_platforms=("tpu",))
        exe, applied = compile_lowered(lowered, overlap_compiler_options())
        txt = exe.as_text()
        assert "all-reduce" in txt, "no cross-replica collective in HLO"
        assert "xla_tpu_enable_latency_hiding_scheduler" in applied, (
            "this libtpu rejected even the latency-hiding flag")
        est = estimate(strat, item, spec)
        assert est.schedule == "overlap"
        assert est.overlapped_s <= est.serialized_s
        return {"n_devices": n, "ar_buckets": est.breakdown["ar_buckets"],
                "applied_compiler_options": applied,
                "cost_model_serialized_s": est.serialized_s,
                "cost_model_overlapped_s": est.overlapped_s,
                **_xla_stats(exe)}

    def llama_gqa_train_step():
        """The Llama family's GQA path through the kernel — group>1 means
        the shared-K/V-block index maps and the group-summed f32 dkdv
        outputs, a DISTINCT Mosaic program from the MHA checks above —
        compiled as a full engine train step for 4 v5e targets."""
        import dataclasses

        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.models import train_lib
        from autodist_tpu.models.llama import LLAMA_TINY
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import Parallax
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        n = len(topo.devices)
        S = 128
        cfg = dataclasses.replace(LLAMA_TINY, dtype=jnp.bfloat16,
                                  attention_impl="auto")
        assert cfg.num_kv_heads < cfg.num_heads  # GQA, not MHA
        loss_fn, params, sparse = train_lib.llama_capture(
            cfg, S, streaming_loss=True, loss_chunk=100)
        item = ModelItem(loss_fn, params, optax.adamw(1e-3),
                         sparse_vars=sparse)
        spec = ResourceSpec.from_num_chips(n)
        strat = StrategyCompiler(item, spec).compile(
            Parallax().build(item, spec))
        mesh = Mesh(np.array(topo.devices), ("replica",))
        t = GraphTransformer(strat, item, mesh)
        bsh = NamedSharding(mesh, P("replica"))
        B = 2 * n
        batch_avals = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh),
            "targets": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bsh)}
        step = t.make_train_step(donate=False)
        with _pretend_on_tpu():
            lowered = step.trace(t.abstract_state(), batch_avals).lower(
                lowering_platforms=("tpu",))
        exe = lowered.compile()
        assert "tpu_custom_call" in exe.as_text()
        return {"n_devices": n, "gqa_group":
                cfg.num_heads // cfg.num_kv_heads, **_xla_stats(exe)}

    def pipeline_1f1b():
        """The 1F1B interleaved pipeline schedule — stacked stage params
        sharded over the pipe axis, ppermute activation handoff — as an
        engine step over a replica x pipe mesh of 4 v5e targets."""
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.const import AXIS_PIPELINE
        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.parallel.pipeline import (pipeline_train_loss,
                                                    stack_stages_interleaved)
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import AllReduce
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        Spipe, L = 4, 2
        rr = np.random.RandomState(7)
        stages = [{"w": jnp.asarray(rr.randn(128, 128) * 0.1, jnp.float32)}
                  for _ in range(Spipe * L)]
        blocks = stack_stages_interleaved(stages, Spipe)

        def pp_loss(p, b):
            return pipeline_train_loss(
                lambda sp, a: a + jnp.tanh(a @ sp["w"]),
                lambda act, y: jnp.mean((act - y) ** 2),
                p["blocks"], b["x"], b["y"], AXIS_PIPELINE,
                num_microbatches=Spipe, schedule="1f1b")

        spec = ResourceSpec(resource_info={
            "nodes": [{"address": "localhost", "chips": list(range(4))}],
            "mesh": {"replica": 1, "pipe": Spipe}})
        item = ModelItem(pp_loss, {"blocks": blocks}, optax.sgd(0.01))
        strat = StrategyCompiler(item, spec).compile(
            AllReduce().build(item, spec))
        mesh = Mesh(np.array(topo.devices).reshape(1, Spipe),
                    ("replica", AXIS_PIPELINE))
        t = GraphTransformer(strat, item, mesh, data_axes=("replica",),
                             param_specs={"blocks/w": P(AXIS_PIPELINE)})
        bsh = NamedSharding(mesh, P("replica"))
        bav = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=bsh)
        step = t.make_train_step(donate=False)
        lowered = step.trace(t.abstract_state(),
                             {"x": bav, "y": bav}).lower(
            lowering_platforms=("tpu",))
        txt = lowered.compile().as_text()
        assert "collective-permute" in txt, "no ppermute handoff in HLO"
        return {"stages": Spipe, "layers_per_stage": L}

    def gpt_decode_rollout():
        """The serving path: GPT-2-small autoregressive decode — the
        jitted lax.scan rollout with per-layer KV caches (one token per
        step, prompt replay, greedy head) — compiled for a v5e target."""
        from autodist_tpu.models.decoding import _cache_shapes, _make_rollout
        from autodist_tpu.models.gpt import GPT, GPT_SMALL

        B, total = 4, 128
        model = GPT(GPT_SMALL, decode=True)
        params_shapes = jax.eval_shape(
            model.init, jax.random.PRNGKey(0),
            jnp.zeros((B, 1), jnp.int32))["params"]
        cache_avals = jax.tree.map(
            lambda sd: jax.ShapeDtypeStruct(*sd), _cache_shapes(model, B),
            is_leaf=lambda x: isinstance(x, tuple))
        rollout = _make_rollout(model, total, 0.0)
        avals = (
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         params_shapes),
            cache_avals,
            jax.ShapeDtypeStruct((B, total), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        )
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(TOPO.devices[:1]), ("x",))
        s = NamedSharding(mesh, P())
        lowered = jax.jit(rollout.__wrapped__ if hasattr(
            rollout, "__wrapped__") else rollout,
            in_shardings=s).trace(*avals).lower(lowering_platforms=("tpu",))
        exe = lowered.compile()
        return {"batch": B, "total_len": total, **_xla_stats(exe)}

    def tensor_parallel():
        """Megatron TP over a replica x model mesh — CUSTOM-placement
        local weight blocks, the copy-in / psum-out collective pair in
        the loss — as an engine step for 4 v5e targets."""
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.parallel.tensor_parallel import tp_mlp
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import AllReduce
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        spec = ResourceSpec(resource_info={
            "nodes": [{"address": "localhost", "chips": list(range(4))}],
            "mesh": {"replica": 2, "model": 2}})
        rr = np.random.RandomState(0)
        params = {"w1": jnp.asarray(rr.randn(128, 256) * 0.1, jnp.float32),
                  "w2": jnp.asarray(rr.randn(256, 128) * 0.1, jnp.float32)}

        def loss(p, b):
            return jnp.mean(tp_mlp(b, p["w1"], p["w2"], "model") ** 2)

        item = ModelItem(loss, params, optax.sgd(0.01))
        strat = StrategyCompiler(item, spec).compile(
            AllReduce().build(item, spec))
        mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                    ("replica", "model"))
        t = GraphTransformer(strat, item, mesh, data_axes=("replica",),
                             param_specs={"w1": P(None, "model"),
                                          "w2": P("model", None)})
        bsh = NamedSharding(mesh, P("replica"))
        bav = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=bsh)
        step = t.make_train_step(donate=False)
        lowered = step.trace(t.abstract_state(), bav).lower(
            lowering_platforms=("tpu",))
        txt = lowered.compile().as_text()
        assert "all-reduce" in txt
        return {"mesh": "replica2 x model2"}

    def expert_parallel():
        """MoE expert parallelism: each device of the expert axis holds its
        share of the routed experts (``parallel/moe.py``), computes its part
        with grouped matrix products (the Pallas kernels of
        ``ops/pallas/grouped_matmul.py``, taken by the layer's own rule) and
        the parts are summed over the axis, as an engine step for 4 v5e
        targets."""
        import optax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from autodist_tpu.kernel.graph_transformer import GraphTransformer
        from autodist_tpu.model_item import ModelItem
        from autodist_tpu.parallel.moe import expert_layer
        from autodist_tpu.resource_spec import ResourceSpec
        from autodist_tpu.strategy import AllReduce
        from autodist_tpu.strategy.base import StrategyCompiler

        os.environ.setdefault("AUTODIST_IS_TESTING", "True")
        ep, E, D, H = 2, 4, 128, 256
        spec = ResourceSpec(resource_info={
            "nodes": [{"address": "localhost", "chips": list(range(4))}],
            "mesh": {"replica": 4 // ep, "expert": ep}})
        rr = np.random.RandomState(5)
        params = {
            "router": jnp.asarray(rr.randn(D, E) * 0.3, jnp.float32),
            "gate": jnp.asarray(rr.randn(E, D, H) * 0.2, jnp.float32),
            "up": jnp.asarray(rr.randn(E, D, H) * 0.2, jnp.float32),
            "down": jnp.asarray(rr.randn(E, H, D) * 0.2, jnp.float32)}

        def loss(p, b):
            out, _ = expert_layer(b, p["router"], p["gate"], p["up"],
                                  p["down"], top_k=2, axis_name="expert")
            return jnp.mean(out ** 2)

        item = ModelItem(loss, params, optax.sgd(0.05))
        strat = StrategyCompiler(item, spec).compile(
            AllReduce().build(item, spec))
        mesh = Mesh(np.array(topo.devices).reshape(4 // ep, ep),
                    ("replica", "expert"))
        t = GraphTransformer(strat, item, mesh, data_axes=("replica",),
                             param_specs={k: P("expert")
                                          for k in ("gate", "up", "down")})
        bsh = NamedSharding(mesh, P("replica"))
        bav = jax.ShapeDtypeStruct((256, D), jnp.float32, sharding=bsh)
        step = t.make_train_step(donate=False)
        with _pretend_on_tpu():
            traced = step.trace(t.abstract_state(), bav)
        txt = traced.lower(lowering_platforms=("tpu",)).compile().as_text()
        # the layer's own kernels (ops/pallas/grouped_matmul.py): three
        # products forward, each one's weights' gradient, and the rows'
        # cotangent of the down product alone (the tokens get no gradient)
        kernels = txt.count('custom_call_target="tpu_custom_call"')
        assert kernels == 7, \
            f"{kernels} Pallas kernels where the grouped products are 7"
        assert "ragged-dot" not in txt, "a grouped product left to XLA"
        assert "all-reduce" in txt, "the parts are not summed over the axis"
        return {"experts": E, "expert_axis": ep}

    def gated_delta_rule():
        """The delta rule's two kernels at the shape of the benchmark's
        Qwen3-Next cell (4 x 8,192 positions, 16 key heads on 32 value
        heads of 128, chunks of 64, bfloat16), taken by
        ``chunk_gated_delta_rule``'s own rule and compiled inside the
        default scoped VMEM."""
        from autodist_tpu.ops.gated_delta import chunk_gated_delta_rule

        b, s, h_k, h_v, d = 4, 8192, 16, 32, 128
        qk = jax.ShapeDtypeStruct((b, s, h_k, d), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((b, s, h_v, d), jnp.bfloat16)
        gate = jax.ShapeDtypeStruct((b, s, h_v), jnp.float32)

        def loss(q, k, v, g, beta):
            return jnp.sum(chunk_gated_delta_rule(
                q, k, v, g, beta, chunk_size=64).astype(jnp.float32))

        exe, txt = _compile(jax.grad(loss, argnums=range(5)), qk, qk, v,
                            gate, gate)
        assert txt.count('custom_call_target="tpu_custom_call"') == 2
        return {"shape": [b, s, h_k, h_v, d], **_xla_stats(exe)}

    check("flash_attention_fwd", flash_fwd)
    check("flash_attention_bwd", flash_bwd)
    check("int8_quantize", quantize)
    check("ring_attention_4dev", ring)
    check("entry_flagship_gpt", flagship_entry)
    check("engine_step_parallax_4dev", engine_step)
    check("gpt_train_step_flash_streaming_4dev", gpt_train_step)
    check("multihost_subset_ps_16dev_4host", multihost_subset_ps)
    check("wire_dtype_bf16_allreduce", wire_dtype_bf16)
    check("overlap_schedule_engine_step_4dev", overlap_schedule_engine_step)
    check("llama_gqa_train_step_4dev", llama_gqa_train_step)
    check("pipeline_1f1b_4dev", pipeline_1f1b)
    check("gpt_decode_rollout_serving", gpt_decode_rollout)
    check("tensor_parallel_2x2", tensor_parallel)
    check("expert_parallel_moe_2x2", expert_parallel)
    check("gated_delta_rule_qwen3_next_fwd_bwd", gated_delta_rule)

    results["ok"] = ok
    results["total_seconds"] = round(time.time() - t0, 1)
    results["git_sha"] = _git_sha()
    results["recorded_unix"] = int(time.time())
    out = os.environ.get("MOSAIC_AOT_OUT") or os.path.join(
        REPO, "MOSAIC_AOT.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"[mosaic-aot] wrote {out}: ok={ok}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
