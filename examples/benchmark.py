"""Benchmark harness — parity with the reference's
``examples/benchmark/{imagenet.py,bert.py,ncf.py}``: pick a model family and
a strategy by flag, train on synthetic data, report examples/sec.

  python examples/benchmark.py --model resnet50 --autodist_strategy AllReduce
  python examples/benchmark.py --model bert_base --autodist_strategy Parallax
  python examples/benchmark.py --model vgg16 --autodist_strategy PartitionedPS
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import optax


def build(model_name, seq_len, image_size, streaming_loss=False,
          remat=False, norm="bn"):
    from autodist_tpu.models import (
        BERT_BASE, BERT_LARGE, DenseNet121, InceptionV3, LMConfig, NCFConfig,
        ResNet50, ResNet101, VGG16,
    )
    from autodist_tpu.models import train_lib

    r = np.random.RandomState(0)
    if (streaming_loss or remat) and model_name not in (
            "gpt_small", "gpt_tiny", "llama_small", "llama_tiny"):
        raise SystemExit(
            f"--streaming_loss/--remat only apply to GPT/Llama, not "
            f"{model_name} — refusing to measure a configuration that "
            f"would not take effect")
    if norm != "bn" and model_name not in ("resnet50", "resnet101"):
        raise SystemExit(
            f"':fused_norm'/':gn' swap the ResNet normalization layer, "
            f"not {model_name}'s — refusing to measure a configuration "
            f"that would not take effect")
    if model_name in ("resnet50", "resnet101", "vgg16", "densenet121", "inception_v3"):
        cls = {"resnet50": ResNet50, "resnet101": ResNet101, "vgg16": VGG16,
               "densenet121": DenseNet121, "inception_v3": InceptionV3}[model_name]
        model = cls(norm=norm) if model_name in ("resnet50",
                                                 "resnet101") else cls()
        loss_fn, params, state = train_lib.classifier_capture(
            model, (image_size, image_size, 3))

        def batch_fn(B):
            return {"image": r.randn(B, image_size, image_size, 3).astype(np.float32),
                    "label": r.randint(0, 1000, B)}

        return dict(loss_fn=loss_fn, params=params, mutable_state=state,
                    sparse_vars=None, has_rng=False, cfg=None,
                    optimizer=train_lib.sgd_momentum(0.1), batch_fn=batch_fn)
    if model_name in ("bert_tiny", "bert_base", "bert_large"):
        from autodist_tpu.models import BERT_TINY

        cfg = {"bert_tiny": BERT_TINY, "bert_base": BERT_BASE,
               "bert_large": BERT_LARGE}[model_name]
        loss_fn, params, sparse = train_lib.bert_capture(cfg, seq_len)

        def batch_fn(B):
            return {
                "input_ids": r.randint(0, cfg.vocab_size, (B, seq_len)).astype(np.int32),
                "labels": np.where(r.rand(B, seq_len) < 0.15,
                                   r.randint(0, cfg.vocab_size, (B, seq_len)),
                                   -100).astype(np.int32),
                "next_sentence_label": r.randint(0, 2, (B,)).astype(np.int32),
            }

        return dict(loss_fn=loss_fn, params=params, mutable_state=None,
                    sparse_vars=sparse, has_rng=True, cfg=cfg,
                    optimizer=optax.adamw(1e-4), batch_fn=batch_fn)
    if model_name == "ncf":
        from autodist_tpu.models import train_lib as tl

        cfg = NCFConfig()
        loss_fn, params, sparse = tl.ncf_capture(cfg)

        def batch_fn(B):
            return {"user": r.randint(0, cfg.num_users, (B,)).astype(np.int32),
                    "item": r.randint(0, cfg.num_items, (B,)).astype(np.int32),
                    "label": (r.rand(B) < 0.5).astype(np.float32)}

        return dict(loss_fn=loss_fn, params=params, mutable_state=None,
                    sparse_vars=sparse, has_rng=False, cfg=cfg,
                    optimizer=optax.adam(1e-3), batch_fn=batch_fn)
    if model_name in ("gpt_small", "gpt_tiny", "llama_small", "llama_tiny"):
        import dataclasses

        if model_name.startswith("gpt"):
            from autodist_tpu.models import GPT_SMALL, GPT_TINY

            cfg = GPT_SMALL if model_name == "gpt_small" else GPT_TINY
            capture, has_rng = train_lib.gpt_capture, True  # dropout rng
        else:
            from autodist_tpu.models import LLAMA_TINY, LlamaConfig

            cfg = LlamaConfig() if model_name == "llama_small" else LLAMA_TINY
            capture, has_rng = train_lib.llama_capture, False
        if seq_len > cfg.max_position or remat:
            cfg = dataclasses.replace(
                cfg, max_position=max(seq_len, cfg.max_position),
                remat=remat or cfg.remat)
        loss_fn, params, sparse = capture(cfg, seq_len,
                                          streaming_loss=streaming_loss)

        def batch_fn(B):
            toks = r.randint(0, cfg.vocab_size, (B, seq_len + 1)).astype(np.int32)
            return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

        return dict(loss_fn=loss_fn, params=params, mutable_state=None,
                    sparse_vars=sparse, has_rng=has_rng, cfg=cfg,
                    optimizer=optax.adamw(1e-4), batch_fn=batch_fn)
    if model_name == "lm1b":
        from autodist_tpu.models import train_lib as tl

        cfg = LMConfig(vocab_size=793470 // 8, embed_dim=512, hidden_dim=2048)
        loss_fn, params, sparse = tl.lm_capture(cfg, seq_len)

        def batch_fn(B):
            return {"tokens": r.randint(0, cfg.vocab_size, (B, seq_len)).astype(np.int32),
                    "targets": r.randint(0, cfg.vocab_size, (B, seq_len)).astype(np.int32)}

        return dict(loss_fn=loss_fn, params=params, mutable_state=None,
                    sparse_vars=sparse, has_rng=False, cfg=cfg,
                    optimizer=optax.adagrad(0.2), batch_fn=batch_fn)
    raise SystemExit(f"unknown model {model_name}")


# forward FLOPs per example for conv families (standard 2-FLOPs-per-MAC
# counts at 224px); transformer/LM families are computed from the actual
# parameter count + seq_len by _fwd_flops_per_example (the table's fixed
# seq=128 guesses under-counted attention and ignored --seq_len)
FLOPS_PER_EXAMPLE = {
    "resnet50": 4.1e9, "resnet101": 7.8e9, "vgg16": 15.5e9,
    "densenet121": 2.9e9, "inception_v3": 5.7e9,
}


def _matmul_param_count(params, exclude=()):
    """Total size of leaves, skipping names matching ``exclude`` — position/
    type embedding tables do no matmul work (pure lookups)."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if any(e in name for e in exclude):
            continue
        total += int(np.prod(leaf.shape))
    return total


def _fwd_flops_per_example(model_name, params, seq_len, cfg=None):
    """Forward FLOPs/example.  Transformers: 2*N_matmul*S for the dense
    matmuls (the tied input-embedding table counts once — its lookup is
    free, its output projection is a matmul) + 4*L*S^2*hidden for the
    QK^T / PV attention matmuls.  MFU numerator = 3x this (bwd ~ 2x fwd)."""
    if model_name in FLOPS_PER_EXAMPLE:
        return FLOPS_PER_EXAMPLE[model_name]
    if model_name in ("bert_tiny", "bert_base", "bert_large"):
        n = _matmul_param_count(params, ("position_embeddings",
                                        "type_embeddings"))
        return 2.0 * n * seq_len + 4.0 * cfg.num_layers * seq_len ** 2 * cfg.hidden_size
    if model_name in ("gpt_small", "gpt_tiny", "llama_small", "llama_tiny"):
        # lookup-only tables do no matmul work: gpt's learned positions /
        # llama's untied input table (gpt's wte counts — tied output head)
        lookup_only = ("wpe",) if model_name.startswith("gpt") else ("embed",)
        n = _matmul_param_count(params, lookup_only)
        # causal: the S^2 attention matmuls do half the work
        return 2.0 * n * seq_len + 2.0 * cfg.num_layers * seq_len ** 2 * cfg.hidden_size
    if model_name == "lm1b":
        # the untied input table is lookup-only (the output head is a
        # separate Dense) — exclude it like the other lookup tables
        n = _matmul_param_count(params, ("embedding",))
        return 2.0 * n * seq_len
    return None


def _real_pipeline(args, cap, B, sess):
    """Disk -> C++ loader -> DevicePrefetcher input pipeline (reference
    analog: ``examples/benchmark/imagenet.py`` trains from real input
    pipelines, not device-resident tensors).  The dataset is materialized
    once into the native record format; batches then flow through the mmap
    loader's worker threads and the device double-buffer — so the measured
    step includes (overlapped) host IO + H2D transfer.

    Returns an endless iterator of device-resident global batches.
    """
    import tempfile

    from autodist_tpu.data.loader import (BatchLoader, DevicePrefetcher,
                                          RecordDataset, write_records)

    sample = cap["batch_fn"](1)
    keys = sorted(sample)  # one flat f32 record per example: concat leaves
    sizes = {k: int(np.prod(np.asarray(sample[k]).shape[1:]) or 1)
             for k in keys}
    rec_len = sum(sizes.values())
    n_records = max(4 * B, 1024)
    host = cap["batch_fn"](n_records)
    flat = np.concatenate(
        [np.asarray(host[k]).reshape(n_records, -1).astype(np.float32)
         for k in keys], axis=1)
    workdir = tempfile.mkdtemp(prefix="adio_bench_")
    import atexit
    import shutil

    atexit.register(shutil.rmtree, workdir, ignore_errors=True)
    path = os.path.join(workdir, "data.adio")
    write_records(path, flat)
    ds = RecordDataset(path, (rec_len,), np.float32)
    loader = BatchLoader(ds, B, shuffle=True, seed=0,
                         threads=args.loader_threads, prefetch=2)

    def rebuild():
        for arr in loader:
            out, off = {}, 0
            for k in keys:
                n = sizes[k]
                leaf = arr[:, off:off + n].reshape(
                    (B,) + np.asarray(sample[k]).shape[1:])
                ref_dtype = np.asarray(host[k]).dtype
                out[k] = leaf.astype(ref_dtype) if ref_dtype != np.float32 else leaf
                off += n
            yield out

    return DevicePrefetcher(rebuild(), sess, depth=2)


# MODEL-level strategy-string variants: consumed by build(norm=...), not
# by the strategy builder — ':fused_norm' swaps ResNet's nn.BatchNorm for
# the single-VMEM-pass Pallas kernel (the F008 memory-bound remediation),
# ':gn' for the stat-free fused GroupNorm
MODEL_VARIANTS = {"fused_norm": "bn_fused", "gn": "gn"}


def _model_norm(strategy_name):
    """The norm knob a ``Name:variant`` strategy string selects (the last
    model-level variant wins; ``"bn"`` when none present)."""
    _, _, variants = strategy_name.partition(":")
    norms = [MODEL_VARIANTS[v] for v in variants.split(":")
             if v in MODEL_VARIANTS]
    return norms[-1] if norms else "bn"


def _make_builder(args, strategy_name, resource_spec=None):
    """``Name`` or ``Name:variant[:variant]`` — AllReduce-family variants:
    ``overlap``/``barrier`` (sync schedule), ``two_level``/``flat``
    (sync hierarchy), ``sharded_update`` (ZeRO-style sharded weight
    update), ``bf16_master`` (bf16-compute/f32-master mixed precision —
    implies the sharded update), ``equarx`` (the fused block-quantized
    EQuARX codec on the DCN hop — requires the factored mesh, like
    ``searched_schedule``) and ``searched_schedule`` (the schedule
    synthesizer's top program for the spec — requires a ``replica_dcn x
    replica_ici`` factorization, e.g. ``--mesh
    "replica_dcn=2,replica_ici=4"``), e.g. ``AllReduce:two_level``,
    ``AllReduce:bf16_master`` or ``AllReduce:overlap:sharded_update``;
    the MODEL-level variants ``fused_norm``/``gn`` (ResNet norm knob —
    see ``MODEL_VARIANTS``) ride the same string but are consumed by
    ``build(norm=...)``; ``--ar_chunk_size`` sets the family's
    bucket-group granularity so the overlap term has buckets to
    pipeline."""
    from autodist_tpu import strategy as S

    name, _, variants = strategy_name.partition(":")
    builder_cls = getattr(S, name)
    kwargs = {}
    for variant in (v for v in variants.split(":") if v):
        if variant in ("overlap", "barrier"):
            kwargs["schedule"] = variant
        elif variant in ("two_level", "flat"):
            kwargs["hierarchy"] = variant
        elif variant in ("sharded_update", "sharded"):
            kwargs["sharded_update"] = "sharded"
        elif variant in ("bf16_master", "mixed"):
            kwargs["precision"] = "bf16_master"
        elif variant in ("equarx", "equarx_int8"):
            if resource_spec is None or not getattr(
                    resource_spec, "mesh_request", None):
                raise SystemExit(
                    "equarx: the fused quantized codec rides the DCN hop "
                    "of the two-level schedule — factor the mesh with "
                    "--mesh \"replica_dcn=N,replica_ici=M\"")
            kwargs["dcn_compressor"] = "equarx_int8"
            kwargs.setdefault("hierarchy", "two_level")
        elif variant in ("searched_schedule", "searched"):
            from autodist_tpu.strategy.schedule_search import search

            entries = search(resource_spec, top_k=1) \
                if resource_spec is not None else []
            if not entries:
                raise SystemExit(
                    "searched_schedule: the spec does not factor into "
                    "replica_dcn x replica_ici (multi-node hosts or an "
                    "explicit --mesh \"replica_dcn=N,replica_ici=M\" "
                    "request required)")
            kwargs["schedule_ir"] = entries[0]["ir"]
            kwargs.setdefault("hierarchy", "two_level")
        elif variant in MODEL_VARIANTS:
            pass  # model-level: consumed by build(norm=...), not the builder
        else:
            raise SystemExit(f"unknown strategy variant {variant!r} in "
                             f"{strategy_name!r} (overlap | barrier | "
                             f"two_level | flat | sharded_update | "
                             f"bf16_master | equarx | searched_schedule | "
                             f"fused_norm | gn)")
    if args.ar_chunk_size and issubclass(builder_cls, S.AllReduce):
        kwargs["chunk_size"] = args.ar_chunk_size
    return builder_cls(**kwargs)


def run_one(args, strategy_name, cap, n_chips):
    """Build a session under one strategy; measure; return (eps, record)."""
    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.simulator.cost_model import measure_and_record

    B = args.batch_per_chip * n_chips
    spec = _spec(n_chips, mesh=_parse_mesh(args.mesh))
    builder = _make_builder(args, strategy_name, resource_spec=spec)
    ad = AutoDist(resource_spec=spec, strategy_builder=builder)
    sess = ad.distribute(cap["loss_fn"], cap["params"], cap["optimizer"],
                         sparse_vars=cap["sparse_vars"], has_rng=cap["has_rng"],
                         mutable_state=cap["mutable_state"])
    batch = cap["batch_fn"](B)
    gbatch = sess._shard_batch(batch)  # device-resident: measure the step
    record = measure_and_record(sess, gbatch, steps=args.steps,
                                warmup=args.warmup)
    eps = B / record.step_time_s
    extra = ""
    fpe = _fwd_flops_per_example(args.model, cap["params"], args.seq_len,
                                 cap.get("cfg"))
    if fpe:
        from autodist_tpu.utils.timing import peak_flops

        try:
            mfu = 3.0 * fpe * (eps / n_chips) / peak_flops()
            extra += f" mfu={mfu:.3f}"
        except KeyError:   # no peak known for this device: no MFU
            pass
    if args.data == "real":
        # same step, batches arriving through the full input pipeline;
        # compares against the device-resident number to report whether
        # the run is input-bound (r2 verdict item 9)
        from autodist_tpu.utils.timing import fetch_scalar, seconds_per_step

        pre = _real_pipeline(args, cap, B, sess)
        fetch_scalar(sess.run(next(pre))["loss"])  # warm

        def run_steps(n):
            m = None
            for _ in range(n):
                m = sess.run(next(pre))
            return m["loss"]

        real_dt = seconds_per_step(run_steps, k=args.steps)
        overhead = real_dt / record.step_time_s - 1.0
        extra = (f" real_eps={B / real_dt:.1f} "
                 f"input_overhead={100 * overhead:.1f}% "
                 f"{'INPUT-BOUND' if overhead > 0.2 else 'compute-bound'}")
    print(f"model={args.model} strategy={strategy_name} chips={n_chips} "
          f"global_batch={B} examples/sec={eps:.1f} per_chip={eps / n_chips:.1f} "
          f"step_ms={1000 * record.step_time_s:.2f}{extra}")
    return eps, record, sess


def sweep(args):
    """Per-strategy sweep + cost-model validation (the AutoDist thesis:
    different models peak under different strategies — reference
    ``docs/usage/performance.md`` figure1; r1 verdict item 2).  Dumps an
    AutoSync-style RuntimeRecord per strategy and compares the analytic
    cost model's ranking against measured step times."""
    import json

    from autodist_tpu.simulator.cost_model import calibrate, estimate

    os.environ["AUTODIST_IS_TESTING"] = "True"  # several AutoDist instances
    n_chips = jax.device_count()
    strategies = [s.strip() for s in args.strategies.split(",") if s.strip()]
    measured, estimated, pairs = {}, {}, []
    records_dir = args.records_dir
    if records_dir:
        os.makedirs(records_dir, exist_ok=True)
    for name in strategies:
        cap = build(args.model, args.seq_len, args.image_size,
                    streaming_loss=args.streaming_loss, remat=args.remat,
                    norm=_model_norm(name))
        eps, record, sess = run_one(args, name, cap, n_chips)
        measured[name] = record.step_time_s
        est = estimate(sess._t.strategy, sess._t.model_item,
                       _spec(n_chips, mesh=_parse_mesh(args.mesh)),
                       flops_per_example=_fwd_flops_per_example(
                           args.model, cap["params"], args.seq_len,
                           cap.get("cfg")) or 0.0,
                       batch_per_chip=args.batch_per_chip)
        estimated[name] = est.total_s
        pairs.append((est, record.step_time_s))
        if records_dir:
            record.dump(os.path.join(
                records_dir,
                f"{args.model}_{name.replace(':', '_')}.json"))
        del sess

    measured_rank = sorted(measured, key=measured.get)
    estimated_rank = sorted(estimated, key=estimated.get)
    summary = {
        "model": args.model, "chips": n_chips,
        "backend": jax.default_backend(),   # "cpu" = pipeline validation
        "batch_per_chip": args.batch_per_chip,
        "ar_chunk_size": args.ar_chunk_size or None,
        "measured_step_s": measured, "estimated_step_s": estimated,
        "measured_rank": measured_rank, "estimated_rank": estimated_rank,
        "top_choice_agrees": measured_rank[0] == estimated_rank[0],
        # measured-grounded correction for future AutoStrategy rankings
        "calibration": calibrate(pairs),
    }
    print(json.dumps(summary))
    if records_dir:
        with open(os.path.join(records_dir,
                               f"{args.model}_summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
    return summary


def serve(args):
    """Serving decode variant (``--serve``): continuous batching through
    the ServingEngine vs static per-request ``generate()`` rollouts on
    the same request set; writes the ``gpt_tiny_serve_decode`` record
    ``make perf-gate`` diffs against its blessed baseline."""
    import json

    from autodist_tpu.serving.benchmark import (SERVE_RECORD_NAME,
                                                measure_serve_decode)

    if args.model not in ("resnet50", "gpt_tiny"):  # resnet50 = default
        raise SystemExit(f"--serve measures the gpt_tiny decode service, "
                         f"not {args.model}")
    os.environ["AUTODIST_IS_TESTING"] = "True"  # engine + rollout sessions
    rec = measure_serve_decode()
    print(json.dumps(rec))
    if args.records_dir:
        os.makedirs(args.records_dir, exist_ok=True)
        path = os.path.join(args.records_dir, f"{SERVE_RECORD_NAME}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}")


def _parse_mesh(mesh_arg):
    """``"replica_dcn=2,replica_ici=4"`` -> {axis: size} or None."""
    if not mesh_arg:
        return None
    axes = {}
    for part in mesh_arg.split(","):
        name, _, size = part.partition("=")
        if not size:
            raise SystemExit(f"--mesh entry {part!r} is not name=size")
        axes[name.strip()] = int(size)
    return axes


def _spec(n_chips, mesh=None):
    from autodist_tpu.resource_spec import ResourceSpec

    if mesh:
        return ResourceSpec(resource_info={
            "nodes": [{"address": "localhost",
                       "chips": list(range(n_chips)), "chief": True}],
            "mesh": mesh})
    return ResourceSpec.from_num_chips(n_chips)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--autodist_strategy", default="AllReduce",
                    help="PS | PSLoadBalancing | PartitionedPS | UnevenPartitionedPS | "
                         "AllReduce | PartitionedAR | RandomAxisPartitionAR | Parallax")
    ap.add_argument("--strategies", default="",
                    help="comma list -> per-strategy sweep + cost-model "
                         "validation (e.g. 'AllReduce,PS,PartitionedPS,"
                         "Parallax'); an AllReduce-family entry takes "
                         "optional ':overlap'/':barrier' (sync schedule) "
                         "and ':two_level'/':flat' (sync hierarchy) "
                         "suffixes")
    ap.add_argument("--ar_chunk_size", type=int, default=0,
                    help="bucket-group granularity (vars per group) for "
                         "AllReduce-family builders; 0 = builder default")
    ap.add_argument("--mesh", default="",
                    help="explicit mesh request, e.g. "
                         "'replica_dcn=2,replica_ici=4' — factor the "
                         "replica axis so ':two_level' strategies realize "
                         "the hierarchical sync schedule")
    ap.add_argument("--records_dir", default="",
                    help="dump AutoSync-style RuntimeRecords + summary here")
    ap.add_argument("--data", choices=("synthetic", "real"),
                    default="synthetic",
                    help="real: feed batches from the native mmap loader + "
                         "DevicePrefetcher (reports input-bound vs "
                         "compute-bound against the device-resident step)")
    ap.add_argument("--loader_threads", type=int, default=2)
    ap.add_argument("--batch_per_chip", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--seq_len", type=int, default=128)
    ap.add_argument("--image_size", type=int, default=224)
    ap.add_argument("--streaming_loss", action="store_true",
                    help="GPT/Llama: streaming vocab cross-entropy "
                         "(ops/losses.py) — no (B,S,V) logits allocation")
    ap.add_argument("--remat", action="store_true",
                    help="GPT/Llama: per-block rematerialization")
    ap.add_argument("--serve", action="store_true",
                    help="serving decode variant: continuous batching "
                         "through the ServingEngine vs static generate() "
                         "rollouts (writes gpt_tiny_serve_decode.json "
                         "under --records_dir)")
    args = ap.parse_args()

    if args.serve:
        serve(args)
        return
    if args.strategies:
        sweep(args)
        return

    n_chips = jax.device_count()
    cap = build(args.model, args.seq_len, args.image_size,
                streaming_loss=args.streaming_loss, remat=args.remat,
                norm=_model_norm(args.autodist_strategy))
    _, record, sess = run_one(args, args.autodist_strategy, cap, n_chips)
    if args.records_dir:
        os.makedirs(args.records_dir, exist_ok=True)
        record.dump(os.path.join(
            args.records_dir,
            f"{args.model}_{args.autodist_strategy.replace(':', '_')}.json"))


if __name__ == "__main__":
    main()
