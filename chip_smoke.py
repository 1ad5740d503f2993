"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process that owns the chip from start to end and drives the main paths
through the entry points a user calls::

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # one host, four chips: the sharded path only

Default run, in order: device check; GPT-2-small at published width trained
through ``AutoDist(...).distribute(...)`` -> ``sess.run(batch)`` on data read
back through the native loader, checked against a plain ``jax.jit`` reference;
ResNet-50 B=256 trained the same way.  (No serving phase: on the chip a bf16
decode step's low bits depend on the batch shape, so ``serve()``'s tokens
leave ``generate()``'s at near-tied logits — ROADMAP S0.)

``--chips 4`` runs GPT-2-small data-parallel over a ``replica: 4`` mesh under
``AllReduce()`` and ``AllReduce(sharded_update="sharded")`` against the same
one-device reference, and no other phase.

Every earlier stdout line is one JSON object naming the device it ran on.  The
last line is ``{"ok": true, "device": {...}}`` and is printed only when every
phase passed; any failure ends the process with a non-zero exit code.  The
seconds printed here say whether the path runs, not how fast it is: they are
not benchmark results.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Engine against reference, both bf16 forward passes of one seeded model on one
# batch: flash against XLA attention and another reduction order move the
# f32-accumulated mean loss by a few bf16 ulps (2**-8 relative each).
LOSS_RTOL = 2e-2
TRAIN_STEPS = 8        # checked steps per training phase
TIMING_K = 4           # steps in the timed window
DP_STEPS = 4           # steps per variant of the four-chip phase


def emit(rec):
    print(json.dumps(rec), flush=True)


def require(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def device_fields(devices):
    d = devices[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(devices)}


def memory_field(devices, field):
    """``memory_stats()[field]`` of each device (None where the backend
    keeps no such statistics)."""
    return [(d.memory_stats() or {}).get(field) for d in devices]


class CacheEvents:
    """Counts JAX's persistent-compile-cache events, so a phase can say
    what its second compile of the same program was spared."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        self.saved_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_secs(self, event, secs, **_):
        if event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved_s += secs

    def snapshot(self):
        return {"hits": self.hits, "misses": self.misses,
                "saved_s": round(self.saved_s, 3)}


class SwitchableBuilder:
    """A strategy builder that defers to ``current``: a process may hold one
    AutoDist, and the four-chip phase compares two builders on it."""

    def __init__(self, current):
        self.current = current

    def build(self, model_item, resource_spec):
        return self.current.build(model_item, resource_spec)


# ------------------------------------------------------------------ data --

def write_token_corpus(path, n_records, seq_len, vocab_size, seed):
    """A seeded corpus with Zipf-like token frequencies.  Uniform tokens
    cannot be learnt; here the unigram distribution can, so a falling loss
    means the update was applied."""
    import numpy as np

    from autodist_tpu.data.loader import write_records

    r = np.random.RandomState(seed)
    p = 1.0 / np.arange(1, vocab_size + 1)
    toks = r.choice(vocab_size, size=(n_records, seq_len + 1), p=p / p.sum())
    os.makedirs(os.path.dirname(path), exist_ok=True)
    write_records(path, toks.astype(np.int32))


class TokenStream:
    """The corpus read back through RecordDataset -> BatchLoader; keeps every
    host batch it hands out so a reference can be given the same ones."""

    def __init__(self, path, seq_len, batch, seed):
        import numpy as np

        from autodist_tpu.data.loader import BatchLoader, RecordDataset

        self._ds = RecordDataset(path, (seq_len + 1,), np.int32)
        self._loader = BatchLoader(self._ds, batch, seed=seed)
        self.seen = []

    def __iter__(self):
        return self

    def __next__(self):
        recs = next(self._loader)
        b = {"tokens": recs[:, :-1], "targets": recs[:, 1:]}
        self.seen.append(b)
        return b

    def close(self):
        self._loader.close()
        self._ds.close()


# ------------------------------------------------------------- reference --

def gpt_setup(cfg, *, batch, seq_len, seed, out_dir):
    """What both GPT phases start from: the seeded corpus on disk, and the
    seeded capture ``(corpus path, loss_fn, params, sparse_vars,
    optimizer)``."""
    import optax

    from autodist_tpu.models.train_lib import gpt_capture
    from autodist_tpu.utils.rng import host_key

    corpus = os.path.join(out_dir, "gpt_corpus.bin")
    write_token_corpus(corpus, 4 * batch, seq_len, cfg.vocab_size, seed)
    loss_fn, params, sparse = gpt_capture(cfg, seq_len, rng=host_key(seed),
                                          streaming_loss=True)
    return corpus, loss_fn, params, sparse, optax.adamw(1e-4)


def reference_losses(cfg, seq_len, params, optimizer, batches, device):
    """Losses of a plain train step on ``batches`` from the same ``params``:
    one ``jax.jit`` of ``value_and_grad`` + optax on one device, XLA
    attention, no engine in the loop."""
    import jax
    import optax

    from autodist_tpu.models.train_lib import gpt_capture
    from autodist_tpu.utils.rng import host_key

    loss_fn, _, _ = gpt_capture(
        dataclasses.replace(cfg, attention_impl="xla"), seq_len,
        streaming_loss=True)
    rng = host_key(0)      # dropout is off: the key only fills the signature

    @jax.jit
    def raw_step(p, s, b):
        loss, grads = jax.value_and_grad(loss_fn)(p, b, rng)
        updates, s = optimizer.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    p = jax.device_put(params, device)
    s = optimizer.init(p)
    losses = []
    for b in batches:
        p, s, loss = raw_step(p, s, jax.device_put(b, device))
        losses.append(float(loss))
    return losses


def check_losses(name, losses, ref=None):
    import math

    require(all(math.isfinite(v) for v in losses),
            f"{name}: non-finite loss in {losses}")
    require(losses[-1] < losses[0],
            f"{name}: loss did not fall: {losses}")
    if ref is not None:
        for i, (got, want) in enumerate(zip(losses, ref)):
            require(abs(got - want) <= LOSS_RTOL * abs(want),
                    f"{name}: loss {i} is {got}, the reference has {want} "
                    f"(rtol {LOSS_RTOL})")


# --------------------------------------------------------- shared pieces --

def compile_step(sess, gbatch, events):
    """Lower and compile the session's step for ``gbatch``: the compiled
    text, and for the record the seconds and what the persistent cache did.
    JAX keeps the executable for the session's own calls, so the first
    ``sess.run`` does not compile again."""
    before = events.snapshot()
    t0 = time.perf_counter()
    lowered = sess._step.lower(sess.state, gbatch)
    t1 = time.perf_counter()
    text = lowered.compile().as_text()
    t2 = time.perf_counter()
    after = events.snapshot()
    return text, {"lower_s": round(t1 - t0, 3),
                  "compile_s": round(t2 - t1, 3),
                  "cache_hits": after["hits"] - before["hits"],
                  "cache_misses": after["misses"] - before["misses"],
                  "cache_saved_s": round(
                      after["saved_s"] - before["saved_s"], 3)}


def compile_step_again(sess, gbatch, events):
    """The same compile a second time in this run, with JAX's in-memory
    caches dropped first: what is left is what the persistent cache saves
    a later process."""
    import jax

    jax.clear_caches()
    return compile_step(sess, gbatch, events)[1]


def time_steps(sess, next_batch, k):
    """Seconds per step over ``k`` steps of ``sess.run``, the window closed
    by a host fetch of the last step's loss."""
    from autodist_tpu.utils.timing import seconds_per_step

    def run_steps(n):
        m = None
        for _ in range(n):
            m = sess.run(next_batch())
        return m["loss"]

    return {"k": k, "s_per_step": seconds_per_step(run_steps, k)}


def release(devices):
    """Drop what the finished phase left behind (the caller has already
    dropped its references); returns bytes in use per device."""
    import jax

    jax.clear_caches()
    gc.collect()
    return memory_field(devices, "bytes_in_use")


# ---------------------------------------------------------------- phases --

def phase_gpt_train(ad, cfg, *, batch, seq_len, steps, timing_k, seed,
                    out_dir, devices, events):
    """Train the GPT of ``cfg`` on ``devices[0]`` through
    ``ad.distribute`` on loader-fed data and compare the first two losses
    with the plain reference."""
    from autodist_tpu.data.loader import DevicePrefetcher

    rec = {"phase": "gpt_train", **device_fields(devices), "batch": batch,
           "seq_len": seq_len,
           "bytes_in_use_at_start": memory_field(devices, "bytes_in_use")}
    corpus, loss_fn, params, sparse, optimizer = gpt_setup(
        cfg, batch=batch, seq_len=seq_len, seed=seed, out_dir=out_dir)
    sess = ad.distribute(loss_fn, params, optimizer, sparse_vars=sparse,
                         has_rng=True)
    stream = TokenStream(corpus, seq_len, batch, seed)
    prefetch = DevicePrefetcher(stream, sess, depth=2)

    gbatch = next(prefetch)
    text, rec["compile_cold"] = compile_step(sess, gbatch, events)
    rec["tpu_custom_call"] = "tpu_custom_call" in text
    losses = [float(sess.run(gbatch)["loss"])]
    for _ in range(steps - 1):
        losses.append(float(sess.run(next(prefetch))["loss"]))
    rec["losses"] = losses
    rec["timing"] = time_steps(sess, lambda: next(prefetch), timing_k)
    rec["peak_bytes_in_use"] = memory_field(devices, "peak_bytes_in_use")
    rec["compile_again"] = compile_step_again(sess, gbatch, events)
    stream.close()

    del sess, prefetch, gbatch    # the session's state, before the reference
    rec["reference_losses"] = reference_losses(
        cfg, seq_len, params, optimizer, stream.seen[:2], devices[0])
    check_losses("gpt_train", losses, rec["reference_losses"])
    return rec


def phase_resnet_train(ad, model, *, image_size, num_classes, batch, steps,
                       timing_k, seed, devices, events):
    """Train ``model`` (a ResNet) on one fixed seeded batch on
    ``devices[0]`` through ``ad.distribute``."""
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.models import train_lib
    from autodist_tpu.utils.rng import host_key

    rec = {"phase": "resnet_train", **device_fields(devices), "batch": batch,
           "image_size": image_size,
           "bytes_in_use_at_start": memory_field(devices, "bytes_in_use")}
    loss_fn, params, state = train_lib.classifier_capture(
        model, (image_size, image_size, 3), rng=host_key(seed))
    sess = ad.distribute(loss_fn, params, train_lib.sgd_momentum(0.1),
                         mutable_state=state)
    r = np.random.RandomState(seed)
    gbatch = sess._shard_batch({
        "image": r.randn(batch, image_size, image_size, 3).astype(np.float32),
        "label": r.randint(0, num_classes, batch)})
    gbatch["image"] = jnp.asarray(gbatch["image"], jnp.bfloat16)

    _, rec["compile_cold"] = compile_step(sess, gbatch, events)
    losses = [float(sess.run(gbatch)["loss"]) for _ in range(steps)]
    rec["losses"] = losses
    rec["timing"] = time_steps(sess, lambda: gbatch, timing_k)
    rec["peak_bytes_in_use"] = memory_field(devices, "peak_bytes_in_use")
    rec["compile_again"] = compile_step_again(sess, gbatch, events)
    check_losses("resnet_train", losses)
    return rec


def phase_data_parallel(ad, builder, cfg, *, batch, seq_len, steps, seed,
                        out_dir, devices, events):
    """GPT data-parallel over ``devices`` under ``AllReduce()`` and under
    the sharded (ZeRO) update, both on ``ad``, against the one-device
    reference on ``devices[0]``; ``builder`` is the SwitchableBuilder ``ad``
    was made with."""
    import jax

    from autodist_tpu.data.loader import DevicePrefetcher
    from autodist_tpu.strategy import AllReduce

    n = len(devices)
    rec = {"phase": "data_parallel", **device_fields(devices), "batch": batch,
           "seq_len": seq_len, "variants": {}}
    mesh_devices = list(ad.mesh.devices.flat)
    require(len({d.id for d in mesh_devices}) == n
            and set(mesh_devices) == set(devices),
            f"mesh does not hold the {n} devices: {mesh_devices}")
    rec["mesh"] = {"shape": dict(ad.mesh.shape),
                   "device_ids": [d.id for d in mesh_devices]}

    corpus, loss_fn, params, sparse, optimizer = gpt_setup(
        cfg, batch=batch, seq_len=seq_len, seed=seed, out_dir=out_dir)
    stream = TokenStream(corpus, seq_len, batch, seed)
    host_batches = [next(stream) for _ in range(steps)]
    stream.close()

    for name, variant in (
            ("replicated_update", AllReduce()),
            ("sharded_update", AllReduce(sharded_update="sharded"))):
        builder.current = variant
        v = {"bytes_in_use_at_start": memory_field(devices, "bytes_in_use")}
        sess = ad.distribute(loss_fn, params, optimizer, sparse_vars=sparse,
                             has_rng=True)
        prefetch = DevicePrefetcher(iter(host_batches), sess, depth=2)
        gbatch = next(prefetch)
        require(gbatch["tokens"].sharding.device_set == set(devices)
                and len({s.device for s in
                         gbatch["tokens"].addressable_shards}) == n,
                f"{name}: the batch is not sharded over all {n} devices")
        opt_leaves = jax.tree.leaves(sess.state["opt_state"])
        require(all(x.sharding.device_set == set(devices)
                    for x in opt_leaves),
                f"{name}: optimizer state is not on all {n} devices")
        sharded = [x for x in opt_leaves
                   if not x.sharding.is_fully_replicated]
        v["opt_state_sharded_leaves"] = len(sharded)
        v["opt_state_leaves"] = len(opt_leaves)
        if name == "sharded_update":
            require(sharded and all(
                len({s.device for s in x.addressable_shards}) == n
                for x in sharded),
                "sharded_update: no optimizer state leaf is sharded over "
                f"all {n} devices")
        text, v["compile_cold"] = compile_step(sess, gbatch, events)
        v["tpu_custom_call"] = "tpu_custom_call" in text
        v["collectives"] = {op: text.count(f" {op}(") + text.count(
            f" {op}-start(") for op in
            ("all-reduce", "reduce-scatter", "all-gather")}
        losses = [float(sess.run(gbatch)["loss"])]
        for gb in prefetch:
            losses.append(float(sess.run(gb)["loss"]))
        v["losses"] = losses
        v["bytes_in_use_after_steps"] = memory_field(devices, "bytes_in_use")
        v["peak_bytes_in_use"] = memory_field(devices, "peak_bytes_in_use")
        rec["variants"][name] = v
        del sess, prefetch, gbatch, opt_leaves, sharded
        release(devices)

    rec["reference_losses"] = reference_losses(
        cfg, seq_len, params, optimizer, host_batches[:2], devices[0])
    for name, v in rec["variants"].items():
        check_losses(name, v["losses"], rec["reference_losses"])
    return rec


# ------------------------------------------------------------------ main --

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the data-parallel path over four chips, and "
                         "no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and of the data")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for the data this run writes")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0] is {devices[0]}",
              file=sys.stderr)
        return 2
    require(len(devices) >= args.chips,
            f"--chips {args.chips} needs {args.chips} devices, "
            f"found {len(devices)}")

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.data import loader
    from autodist_tpu.models import GPT_SMALL, ResNet50
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.utils.compile_cache import ensure_compile_cache

    os.makedirs(args.out, exist_ok=True)
    events = CacheEvents()
    kind = loader.loader_kind()
    emit({"phase": "start", **device_fields(devices), "chips": args.chips,
          "seed": args.seed, "loader": kind,
          "compile_cache_dir": ensure_compile_cache()})
    require(kind == "native (built in this process)",
            f"the native loader was not built in this run (got: {kind}); "
            "remove native/libautodist_io.so so it is built from source")
    cfg = dataclasses.replace(GPT_SMALL, remat=True)

    if args.chips == 4:
        spec = ResourceSpec()
        require(len(devices) == 4 and jax.process_count() == 1
                and spec.num_accelerators == 4
                and len(spec.node_addresses) == 1,
                f"--chips 4 wants one process, one node, four chips; "
                f"ResourceSpec() describes {spec.num_accelerators} chips on "
                f"{len(spec.node_addresses)} node(s), jax has {len(devices)}")
        builder = SwitchableBuilder(AllReduce())
        ad = AutoDist(resource_spec=spec, strategy_builder=builder)
        rec = phase_data_parallel(
            ad, builder, cfg, batch=32, seq_len=1024, steps=DP_STEPS,
            seed=args.seed, out_dir=args.out, devices=devices, events=events)
        emit(rec)
        for name, v in rec["variants"].items():
            require(v["tpu_custom_call"],
                    f"{name}: no tpu_custom_call in the compiled step: "
                    "flash attention is not the Pallas kernel")
            require(all(v["bytes_in_use_after_steps"]),
                    f"{name}: a device holds no bytes after the steps: "
                    f"{v['bytes_in_use_after_steps']}")
    else:
        one = devices[:1]
        ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(1),
                      strategy_builder=AllReduce())
        rec = phase_gpt_train(
            ad, cfg, batch=32, seq_len=1024, steps=TRAIN_STEPS,
            timing_k=TIMING_K, seed=args.seed, out_dir=args.out,
            devices=one, events=events)
        emit(rec)
        require(rec["tpu_custom_call"],
                "gpt_train: no tpu_custom_call in the compiled step: flash "
                "attention is not the Pallas kernel")
        del rec
        emit({"phase": "release", **device_fields(one),
              "bytes_in_use": release(one)})
        emit(phase_resnet_train(
            ad, ResNet50(num_classes=1000, norm="bn"), image_size=224,
            num_classes=1000, batch=256, steps=TRAIN_STEPS,
            timing_k=TIMING_K, seed=args.seed, devices=one, events=events))

    emit({"phase": "compile_cache", **device_fields(devices),
          **events.snapshot()})
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
