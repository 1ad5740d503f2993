"""Benchmark: per-chip training throughput + MFU of one model, measured on
the attached TPU in this one process.

    python bench.py                 # BENCH_MODEL=resnet50 (default) | gpt_small
    python bench.py --cpu-proxy     # named CPU mode: engine overhead, CPU mesh
    python bench.py --serve-proxy   # named CPU mode: serving decode overhead

Models (``BENCH_MODEL``): ``resnet50`` (images/sec/chip) and ``gpt_small``
(GPT-2-small with flash attention + streaming vocab loss at S=1024;
tokens/sec/chip).

The measuring path prints one JSON record and exits 0, or fails: when
``jax.devices()[0].platform`` is not ``tpu``, or when any step raises, the
process exits non-zero and prints no record.  Nothing is retried, halved,
swapped for another model or filled in from an earlier run.  The two proxy
modes run only when asked for by name; they force the CPU platform and
label their records as CPU — they say nothing about a chip.

Timing (``autodist_tpu/utils/timing.py``): K dependent steps closed by one
host scalar fetch, differenced against 2K steps so constant per-window
costs cancel.

``vs_baseline`` is the same-chip ratio mfu / MFU_PASS_BAR; the old
cross-hardware ratio to the reference's published T4 figure survives as
``vs_t4_reference``, documented as apples-to-oranges.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

MODELS = {
    "resnet50": {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "unit": "images/sec/chip",
        "default_batch": 256,        # per chip
        # ResNet-50 @224: fwd ~4.089 GFLOPs/image (standard 2-FLOPs-per-MAC
        # count); training ~3x fwd (bwd ~2x).  The MFU numerator.
        "train_flops_per_example": 3 * 4.089e9,
        # reference's closest published number: ResNet-101 @ 1x T4 = ~62
        # img/s (BASELINE.md figure1 row 2) — DIFFERENT hardware
        "t4_reference": 62.0,
    },
    "gpt_small": {
        "metric": "gpt_small_train_tokens_per_sec_per_chip",
        "unit": "tokens/sec/chip",
        # sequences per chip at S=1024: 3.5 GiB with remat, 11.7 GiB
        # without (BENCH_REMAT=0), by the deviceless v5e compile
        # (records/v5e_aot/gpt_levers.json)
        "default_batch": 32,
        "train_flops_per_example": None,   # computed from params at run time
        # reference's closest published LM number: BERT-large @ 1x T4
        # ~11 examples/sec @ S=128 => ~1408 tokens/sec (figure1 row 5) —
        # DIFFERENT hardware AND model class
        "t4_reference": 1408.0,
    },
}
MFU_PASS_BAR = 0.35
# CPU-mesh proxy metric: engine SPMD step vs a raw jitted step over the
# same math — the ENGINE's overhead on a virtual CPU mesh (tools/perf_gate)
CPU_PROXY_METRIC = "cpu_mesh_engine_overhead"
# the serving tier's continuous-batching decode overhead vs static
# generate() rollouts on the CPU mesh (docs/serving.md)
SERVE_PROXY_METRIC = "serving_decode_overhead"


def _model_name():
    name = os.environ.get("BENCH_MODEL", "resnet50")
    if name not in MODELS:
        raise SystemExit(f"BENCH_MODEL={name!r} not in {sorted(MODELS)}")
    return name


def _bench_schedule():
    """``BENCH_OVERLAP=1`` selects the overlap gradient-sync schedule
    (per-bucket collectives + XLA latency-hiding scheduler; predicted
    effect recorded in ``records/v5e_aot/overlap_lever.json``, produced by
    ``tools/aot_overlap.py``); default stays the measured-comparable
    barrier schedule."""
    return ("overlap" if os.environ.get("BENCH_OVERLAP", "0") != "0"
            else "barrier")


def _bench_searched_ir(spec):
    """``BENCH_SCHEDULE=searched`` synthesizes a collective-schedule IR
    program for the bench mesh (``strategy/schedule_search``, priced
    against the calibrated per-hop bandwidths) and runs the session on
    the winner; returns the IR text, or ``""`` when the lever is off or
    the mesh cannot factor into ``replica_dcn x replica_ici``."""
    if os.environ.get("BENCH_SCHEDULE", "") != "searched":
        return ""
    from autodist_tpu.strategy.schedule_search import search

    entries = search(spec, top_k=1)
    return entries[0]["ir"] if entries else ""


def _bench_sync(n_chips):
    """Resolve the gradient-sync levers into ``(spec, builder_kwargs,
    extras)``: the barrier/overlap schedule, the flat/two_level hierarchy
    spec, the searched schedule-IR program (which needs the factored
    mesh, so ``BENCH_SCHEDULE=searched`` implies the two_level spec),
    the EQuARX fused quantized DCN codec (``BENCH_SCHEDULE=equarx`` —
    also needs the factored mesh), and the bf16-master mixed-precision
    knob (``BENCH_PRECISION=bf16_master``)."""
    schedule = _bench_schedule()
    searched = os.environ.get("BENCH_SCHEDULE", "") == "searched"
    equarx = os.environ.get("BENCH_SCHEDULE", "") == "equarx"
    spec, hierarchy = _bench_hierarchy_spec(
        n_chips, force_two_level=searched or equarx)
    kwargs = {"schedule": schedule}
    ir = _bench_searched_ir(spec)
    extras = {"sync_schedule": schedule, "sync_hierarchy": hierarchy}
    if ir:
        kwargs.update(schedule_ir=ir, hierarchy="two_level")
        extras["sync_hierarchy"] = "searched"
        extras["schedule_ir"] = ir
    elif searched:
        extras["sync_hierarchy"] = \
            f"{hierarchy} (searched requested; mesh did not factor)"
    elif equarx:
        if hierarchy == "two_level":
            # the fused block-quantized ring hop on the slow DCN wire
            # (ops/pallas/quantize.equarx_hop via the equarx_int8 codec)
            kwargs.update(hierarchy="two_level",
                          dcn_compressor="equarx_int8")
            extras["sync_hierarchy"] = "two_level+equarx"
        else:
            extras["sync_hierarchy"] = \
                f"{hierarchy} (equarx requested; mesh did not factor)"
    if os.environ.get("BENCH_PRECISION", "f32") == "bf16_master":
        # bf16-compute/f32-master: half the param-gather wire + the MXU's
        # bf16 contraction rate; implies the ZeRO-style sharded update
        kwargs["precision"] = "bf16_master"
        extras["sync_precision"] = "bf16_master"
    return spec, kwargs, extras


def _bench_hierarchy_spec(n_chips, force_two_level=False):
    """``BENCH_HIERARCHY=flat|two_level`` gradient-sync hierarchy lever
    (docs/performance.md "Hierarchical sync").  ``two_level`` factors the
    mesh into ``replica_dcn x replica_ici`` — by host boundaries on a
    multi-process run, else ``BENCH_DCN_SLICES`` (default 2) synthetic
    slices so the schedule is exercisable single-host — and selects the
    ICI reduce-scatter -> DCN shard ring -> ICI all-gather schedule.
    Returns ``(resource_spec, hierarchy_name)``; falls back to flat (with
    the reason recorded in the result's ``sync_hierarchy``) when the chip
    count does not factor.  ``force_two_level`` factors regardless of the
    env lever (``BENCH_SCHEDULE=searched`` needs the factored mesh)."""
    import jax

    from autodist_tpu.resource_spec import ResourceSpec

    mode = os.environ.get("BENCH_HIERARCHY", "flat")
    if mode != "two_level" and not force_two_level:
        return ResourceSpec.from_num_chips(n_chips), "flat"
    n_slices = jax.process_count()
    if n_slices <= 1:
        n_slices = int(os.environ.get("BENCH_DCN_SLICES", "2"))
    if n_slices <= 1 or n_chips % n_slices or n_chips // n_slices < 1:
        return ResourceSpec.from_num_chips(n_chips), \
            f"flat (cannot factor {n_chips} chips into {n_slices} slices)"
    spec = ResourceSpec(resource_info={
        "nodes": [{"address": "localhost", "chips": list(range(n_chips)),
                   "chief": True}],
        "mesh": {"replica_dcn": n_slices,
                 "replica_ici": n_chips // n_slices}})
    return spec, "two_level"


def _build_resnet(n_chips, batch_per_chip):
    """Returns (sess, gbatch, train_flops_per_example, extras)."""
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.models import ResNet50, train_lib
    from autodist_tpu.strategy import AllReduce

    B = batch_per_chip * n_chips
    # bf16 compute (default dtype); BENCH_STEM=space_to_depth selects the
    # exact MXU-friendly stem reparametrization (tests/test_models.py);
    # BENCH_BN_STATS=bf16 reduces BN stats in bf16 (approximate — manual
    # experiments only, never the recorded default)
    stem = os.environ.get("BENCH_STEM", "conv")
    bn_f32 = os.environ.get("BENCH_BN_STATS", "f32") != "bf16"
    # BENCH_NORM=fused selects the single-VMEM-pass Pallas batch norm
    # (the F008 memory-bound remediation — one activation HBM read
    # instead of three); BENCH_NORM=gn the stat-free GroupNorm variant
    norm = {"fused": "bn_fused", "gn": "gn"}.get(
        os.environ.get("BENCH_NORM", "bn"), "bn")
    spec, sync_kwargs, sync_extras = _bench_sync(n_chips)
    model = ResNet50(num_classes=1000, stem=stem, bn_f32_stats=bn_f32,
                     norm=norm)
    loss_fn, params, state = train_lib.classifier_capture(model, (224, 224, 3))
    ad = AutoDist(resource_spec=spec,
                  strategy_builder=AllReduce(**sync_kwargs))
    sess = ad.distribute(loss_fn, params, train_lib.sgd_momentum(0.1),
                         mutable_state=state)

    r = np.random.RandomState(0)
    batch = {"image": r.randn(B, 224, 224, 3).astype(np.float32),
             "label": r.randint(0, 1000, B)}
    # Shard onto device(s) once; sess.run's device_put on a correctly-sharded
    # jax.Array is an alias, so the timed loop never re-uploads the batch.
    gbatch = sess._shard_batch(batch)
    gbatch["image"] = jnp.asarray(gbatch["image"], jnp.bfloat16)
    return sess, gbatch, MODELS["resnet50"]["train_flops_per_example"], {
        "stem": stem, "bn_stats": "f32" if bn_f32 else "bf16",
        "norm": norm, **sync_extras}


def _build_gpt(n_chips, batch_per_chip):
    """GPT-2-small, S=1024, flash attention, streaming vocab loss, remat —
    the long-context configuration the framework is built around.  The
    throughput unit is TOKENS (examples x seq_len)."""
    import dataclasses

    import numpy as np
    import optax

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.models import GPT_SMALL, train_lib
    from autodist_tpu.strategy import AllReduce

    S = int(os.environ.get("BENCH_SEQ_LEN", "1024"))
    streaming = os.environ.get("BENCH_STREAMING_LOSS", "1") != "0"
    remat = os.environ.get("BENCH_REMAT", "1") != "0"
    spec, sync_kwargs, sync_extras = _bench_sync(n_chips)
    cfg = dataclasses.replace(GPT_SMALL, max_position=max(
        S, GPT_SMALL.max_position), remat=remat)
    loss_fn, params, sparse = train_lib.gpt_capture(
        cfg, S, streaming_loss=streaming)
    ad = AutoDist(resource_spec=spec,
                  strategy_builder=AllReduce(**sync_kwargs))
    sess = ad.distribute(loss_fn, params, optax.adamw(1e-4),
                         sparse_vars=sparse, has_rng=True)
    B = batch_per_chip * n_chips
    r = np.random.RandomState(0)
    toks = r.randint(0, cfg.vocab_size, (B, S + 1)).astype(np.int32)
    gbatch = sess._shard_batch(
        {"tokens": toks[:, :-1], "targets": toks[:, 1:]})

    # model fwd FLOPs per TOKEN from the actual param count (lookup-only
    # wpe excluded) + the causal attention matmuls; x3 for training
    import jax

    n_matmul = sum(
        int(np.prod(leaf.shape))
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
        if "wpe" not in jax.tree_util.keystr(path))
    fwd_per_example = (2.0 * n_matmul * S
                       + 2.0 * cfg.num_layers * S * S * cfg.hidden_size)
    return sess, gbatch, 3.0 * fwd_per_example / S, {
        "seq_len": S, "streaming_loss": streaming, "remat": remat,
        "tokens_per_example": S, **sync_extras}


def _bench():
    import jax

    from autodist_tpu.utils.timing import (fetch_scalar, measure_per_step,
                                           peak_flops)

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"bench: no TPU: jax.devices()[0] is {device}; "
                         "nothing is measured on another platform")
    name = _model_name()
    spec = MODELS[name]
    # BENCH_TELEMETRY=<dir>: run the measured session with the runtime
    # telemetry layer on (per-step JSONL manifest + RuntimeRecord under
    # <dir>; docs/observability.md).  Enabled BEFORE the session is built
    # so DistributedSession picks the instrumented path.
    bench_telemetry_dir = os.environ.get("BENCH_TELEMETRY", "")
    if bench_telemetry_dir:
        from autodist_tpu import telemetry

        telemetry.enable(run_dir=bench_telemetry_dir)
    n_chips = jax.device_count()
    batch_per_chip = int(os.environ.get("BENCH_BATCH",
                                        str(spec["default_batch"])))
    B = batch_per_chip * n_chips
    sess, gbatch, flops_per_unit, extras = (
        _build_resnet(n_chips, batch_per_chip) if name == "resnet50"
        else _build_gpt(n_chips, batch_per_chip))
    units_per_example = extras.get("tokens_per_example", 1)

    # XLA's own FLOP count for the compiled step: includes the real extra
    # work the compiler emits (dilated stride-2 backward convs, BN stats)
    # that the model-FLOPs MFU numerator deliberately excludes.
    # cost_analysis is on the post-GSPMD PER-DEVICE module, so flops is
    # per-chip work.
    cost = sess._step.lower(sess.state, gbatch).compile().cost_analysis()
    xla_flops_per_chip = float(cost["flops"])
    for _ in range(3):  # warmup
        m = sess.run(gbatch)
    fetch_scalar(m["loss"])

    def run_steps(n):
        mm = None
        for _ in range(n):
            mm = sess.run(gbatch)
        return mm["loss"]

    trace_dir = os.environ.get("BENCH_TRACE", "")
    if trace_dir:  # one traced window for profile analysis (jax.profiler)
        m = sess.run(gbatch, trace_dir=trace_dir)
        fetch_scalar(m["loss"])
    k = int(os.environ.get("BENCH_STEPS", "15"))
    per_step, diag = measure_per_step(run_steps, k=k)

    units_per_sec = B * units_per_example / per_step
    per_chip = units_per_sec / n_chips
    peak = peak_flops(device)
    mfu = flops_per_unit * per_chip / peak
    rec = {
        "metric": spec["metric"],
        "value": round(per_chip, 2),
        "unit": spec["unit"],
        # same-chip roofline ratio: >= 1.0 means the repo's own 0.35 MFU
        # bar is met on this hardware (the honest normalization)
        "vs_baseline": round(mfu / MFU_PASS_BAR, 3),
        # cross-hardware ratio to the reference's published T4 figure —
        # different hardware (and for gpt, different model class); kept
        # for continuity with the reference's perf study only
        "vs_t4_reference": round(per_chip / spec["t4_reference"], 3),
        "mfu": round(mfu, 4),
        "mfu_pass": bool(mfu >= MFU_PASS_BAR),
        # per-chip XLA-counted flops over per-chip peak: the "how busy is
        # the MXU" view next to mfu's "useful model math per second" view
        "hw_util_xla": round(xla_flops_per_chip / per_step / peak, 4),
        "peak_bf16_tflops": round(peak / 1e12, 1),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_chips": n_chips,
        "batch_per_chip": batch_per_chip,
        "step_ms": round(1000 * per_step, 2),
        "timing": {"method": "chain-diff",
                   "t_k_s": round(diag["t_k_s"], 3),
                   "t_2k_s": round(diag["t_2k_s"], 3), "k": diag["k"],
                   "naive_fallback": diag["naive_fallback"]},
    }
    rec.update({k2: v for k2, v in extras.items()
                if k2 != "tokens_per_example"})
    if bench_telemetry_dir:
        manifest = sess.finalize_telemetry()
        if manifest:
            rec["telemetry_manifest"] = manifest
    if mfu > 1.0:
        # physically impossible => the sync point itself is broken; never
        # report a >peak number as a win
        rec["timing_suspect"] = True
        rec["mfu_pass"] = False
    return rec


# ---------------------------------------------------------- cpu proxy --

def _cpu_proxy(steps=8):
    """CPU-mesh engine-overhead proxy: the engine's full SPMD step (an
    AllReduce session over a virtual CPU mesh — shard_map, bucketed
    collectives, the whole transform) timed against a raw single-jit
    train step on the same model/batch/optimizer.  No TPU involved, so
    the ratio says nothing about chip throughput — it tracks the
    ENGINE's dispatch/transform overhead on the CPU backend.  Also times
    the ZeRO sharded-update variant so that sync path's overhead is
    observable from the same record.  Runs only when asked for by name
    (``--cpu-proxy``, tools/perf_gate.py, the tests)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=4").strip()
    os.environ.setdefault("AUTODIST_IS_TESTING", "True")  # two sessions
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.utils.timing import fetch_scalar, measure_per_step

    n = jax.device_count()
    r = np.random.RandomState(0)
    D = 256
    B = 8 * n
    params = {"w1": jnp.asarray(r.randn(D, D) * 0.05, jnp.float32),
              "b1": jnp.zeros((D,), jnp.float32),
              "w2": jnp.asarray(r.randn(D, D) * 0.05, jnp.float32)}
    batch = {"x": r.randn(B, D).astype(np.float32),
             "y": r.randn(B, D).astype(np.float32)}

    def loss(p, b):
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    opt = optax.adam(1e-3)

    def engine_ms(spec=None, out=None, **kw):
        ad = AutoDist(resource_spec=spec or ResourceSpec.from_num_chips(n),
                      strategy_builder=AllReduce(**kw))
        sess = ad.distribute(loss, params, opt)
        if out is not None:   # sharded-update wire accounting for extras
            out.update(sess._t.sharded_update_summary())
        g = sess._shard_batch(batch)
        fetch_scalar(sess.run(g)["loss"])  # compile + warm

        def run(k):
            m = None
            for _ in range(k):
                m = sess.run(g)
            return m["loss"]

        dt, _ = measure_per_step(run, k=steps, repeats=1)
        return dt * 1e3

    # raw baseline: the same math, one jit, no engine in the loop
    state = [params, opt.init(params)]

    @jax.jit
    def raw_step(p, s, b):
        loss_v, grads = jax.value_and_grad(loss)(p, b)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss_v

    _, _, loss_v = raw_step(state[0], state[1], batch)
    fetch_scalar(loss_v)                   # compile + warm

    def run_raw(k):
        loss_v = None
        for _ in range(k):
            state[0], state[1], loss_v = raw_step(state[0], state[1], batch)
        return loss_v

    raw_dt, _ = measure_per_step(run_raw, k=steps, repeats=1)
    raw_ms = raw_dt * 1e3
    eng_ms = engine_ms()
    shard_info, prec_info = {}, {}
    shard_ms = engine_ms(sharded_update="sharded", out=shard_info)
    # the bf16-master mixed-precision variant: same flat-shard update,
    # bf16 compute-param gather at half the wire — the param_gather_bytes
    # delta vs the f32 sharded update is the lever's wire evidence
    bf16_ms = engine_ms(precision="bf16_master", out=prec_info)
    # the searched collective-schedule variant (strategy/schedule_search):
    # synthesize the top program for a 2 x n/2 factored virtual mesh and
    # time the session executing the schedule IR — the new sync path's
    # engine overhead rides in the same trajectory record
    searched_ms = searched_ir = equarx_ms = None
    if n >= 4 and n % 2 == 0:
        from autodist_tpu.strategy.schedule_search import search

        searched_spec = ResourceSpec(resource_info={
            "nodes": [{"address": "localhost", "chips": list(range(n)),
                       "chief": True}],
            "mesh": {"replica_dcn": 2, "replica_ici": n // 2}})
        entries = search(searched_spec, top_k=1)
        if entries:
            searched_ir = entries[0]["ir"]
            searched_ms = engine_ms(spec=searched_spec,
                                    schedule_ir=searched_ir,
                                    hierarchy="two_level")
        # the EQuARX fused quantized codec on the synthetic DCN hop —
        # the same factored mesh, int8+scales wire with the fused
        # dequant/accumulate/requant hop kernel
        equarx_ms = engine_ms(spec=searched_spec, hierarchy="two_level",
                              dcn_compressor="equarx_int8")
    out = {
        "metric": CPU_PROXY_METRIC,
        "value": round(eng_ms / max(raw_ms, 1e-9), 3),
        "unit": "engine_step / raw_jit_step (cpu mesh)",
        "backend": jax.default_backend(),
        "n_devices": n,
        "raw_step_ms": round(raw_ms, 3),
        "engine_step_ms": round(eng_ms, 3),
        "engine_sharded_update_step_ms": round(shard_ms, 3),
        "sharded_update_ratio": round(shard_ms / max(raw_ms, 1e-9), 3),
        "engine_bf16_step_ms": round(bf16_ms, 3),
        "bf16_master_ratio": round(bf16_ms / max(raw_ms, 1e-9), 3),
        # the wire evidence: bf16 compute-param gather is half the f32
        # sharded update's fresh-param gather volume
        "param_gather_bytes": {
            "sharded_f32": shard_info.get("param_gather_bytes"),
            "bf16_master": prec_info.get("param_gather_bytes"),
        },
        "note": ("CPU-mesh pipeline proxy — engine dispatch/transform "
                 "overhead only, never a hardware throughput claim"),
    }
    if searched_ms is not None:
        out["engine_searched_step_ms"] = round(searched_ms, 3)
        out["searched_ratio"] = round(searched_ms / max(raw_ms, 1e-9), 3)
        out["searched_schedule_ir"] = searched_ir
    if equarx_ms is not None:
        out["engine_equarx_step_ms"] = round(equarx_ms, 3)
        out["equarx_ratio"] = round(equarx_ms / max(raw_ms, 1e-9), 3)
    # the HLO compute audit of the same step (F006: model vs realized
    # FLOPs + predicted MFU ceiling; F007: per-region HBM bytes,
    # arithmetic intensity and the roofline verdict) — priced from the
    # lowering alone
    from autodist_tpu.analysis import verify_strategy
    from autodist_tpu.model_item import ModelItem

    item = ModelItem(loss, params, opt)
    spec = ResourceSpec.from_num_chips(n)
    report = verify_strategy(
        AllReduce().build(item, spec), item, spec,
        batch_shapes={"x": ((B, D), "float32"),
                      "y": ((B, D), "float32")},
        passes=("compute-audit",))
    by_code = {f.code: f.data for f in report.findings}
    if "F006" in by_code:
        out["compute_audit"] = by_code["F006"]
        out["predicted_mfu_ceiling"] = \
            by_code["F006"]["predicted_mfu_ceiling"]
    if "F007" in by_code:
        out["traffic_audit"] = {
            k: by_code["F007"][k] for k in
            ("hbm_bytes", "by_class", "arithmetic_intensity",
             "roofline_s", "roofline_bound",
             "predicted_mfu_ceiling_roofline") if k in by_code["F007"]}
    return out


def _serve_proxy():
    """CPU-mesh serving proxy (``--serve-proxy``): the continuous-batching
    decode engine timed against static per-request ``generate()`` rollouts
    on the same request set, machine-normalized like ``_cpu_proxy``."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=4").strip()
    os.environ.setdefault("AUTODIST_IS_TESTING", "True")  # two sessions
    from autodist_tpu.serving.benchmark import measure_serve_decode

    return measure_serve_decode()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--cpu-proxy", action="store_true",
                      help="CPU mode: engine step vs raw jit step on a "
                           "virtual CPU mesh")
    mode.add_argument("--serve-proxy", action="store_true",
                      help="CPU mode: continuous-batching decode vs static "
                           "generate() on a virtual CPU mesh")
    args = ap.parse_args(argv)
    if args.cpu_proxy:
        rec = _cpu_proxy()
    elif args.serve_proxy:
        rec = _serve_proxy()
    else:
        rec = _bench()
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
