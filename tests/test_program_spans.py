"""The program's own scopes and spans (PERF.md section 3).

Device side: every numbered stage of ``GraphTransformer._spmd_step`` sits in
a ``jax.named_scope`` ``ad.<stage>``, read here from the ``op_name`` metadata
of the step compiled for the CPU mesh.  Host side: the runner, the
prefetcher and the loader open ``jax.profiler.TraceAnnotation``s
``ad.<span>``, read here from a profile taken on the CPU
(``jax.profiler.ProfileData``): names, nesting by time, and the arguments
the benchmark's readers use.
"""
import glob
import logging as pylogging
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu import telemetry
from autodist_tpu.autodist import AutoDist
from autodist_tpu.data import loader as loader_mod
from autodist_tpu.data.loader import (BatchLoader, DevicePrefetcher,
                                      RecordDataset, write_records)
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import PS, AllReduce

RS = np.random.RandomState(0)


def _loss(p, batch):
    h = jnp.tanh(batch["x"] @ p["w1"])
    return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)


def _params():
    r = np.random.RandomState(7)
    return {"w1": jnp.asarray(r.randn(8, 16), jnp.float32),
            "w2": jnp.asarray(r.randn(16), jnp.float32)}


def _batch(n=16):
    return {"x": RS.randn(n, 8).astype(np.float32),
            "y": RS.randn(n).astype(np.float32)}


def _session(chips, builder, **kwargs):
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(chips),
                  strategy_builder=builder)
    return ad.distribute(_loss, _params(), optax.adam(1e-2), **kwargs)


@pytest.fixture(autouse=True)
def _telemetry_off_afterwards():
    yield
    telemetry.disable()
    telemetry._STATE["run_dir"] = None
    telemetry.reset_registry()


# -- device scopes ----------------------------------------------------------

def _step_hlo(sess):
    """The session's step, compiled here: HLO text whose instructions carry
    ``metadata={op_name="..."}`` (the plain lowering's text drops it)."""
    gbatch = sess._shard_batch(_batch())
    return sess._step.lower(sess.state, gbatch).compile().as_text()


def _op_names(sess):
    return re.findall(r'op_name="([^"]+)"', _step_hlo(sess))


def _innermost(op_name):
    found = re.findall(r"ad\.[a-z_]+", op_name)
    return found[-1] if found else None


@pytest.mark.parametrize("chips,builder,kwargs,expected", [
    # on one chip nothing is reduced, and a codec-free bucket is not even
    # packed (kernel/synchronization/all_reduce.py): no ``ad.sync`` operation
    (1, lambda: AllReduce(), {},
     {"ad.grad", "ad.update"}),
    (1, lambda: AllReduce(), {"clip_global_norm": 1.0},
     {"ad.grad", "ad.clip", "ad.update"}),
    (8, lambda: AllReduce(sharded_update="sharded"), {},
     {"ad.grad", "ad.sync", "ad.update", "ad.gather"}),
    (8, lambda: PS(), {},
     {"ad.grad", "ad.sync", "ad.update", "ad.gather"}),
    (8, lambda: AllReduce(precision="bf16_master",
                          sharded_update="sharded"), {},
     {"ad.materialize", "ad.grad", "ad.sync", "ad.update"}),
], ids=["allreduce-1", "allreduce-1-clip", "sharded-update-8", "ps-8",
        "bf16-master-8"])
def test_lowered_step_holds_the_scopes(chips, builder, kwargs, expected):
    names = _op_names(_session(chips, builder(), **kwargs))
    scopes = {_innermost(n) for n in names} - {None}
    assert scopes >= expected, scopes
    assert chips > 1 or "ad.sync" not in scopes, scopes
    assert scopes <= {"ad.materialize", "ad.grad", "ad.sync", "ad.clip",
                      "ad.update", "ad.gather"}
    grad = [n for n in names if _innermost(n) == "ad.grad"]
    # forward and backward are told apart inside the one scope
    assert any("transpose(" in n for n in grad)
    assert any("transpose(" not in n for n in grad)
    assert not any("transpose(" in n for n in names
                   if _innermost(n) in ("ad.update", "ad.clip"))


def test_collectives_sit_in_sync_and_gather():
    sess = _session(8, AllReduce(sharded_update="sharded"))
    seen = {}
    for line in _step_hlo(sess).split("\n"):
        m = re.search(r" (reduce-scatter|all-gather|all-reduce)\(", line)
        op = re.search(r'op_name="([^"]+)"', line)
        if m and op:
            seen.setdefault(m.group(1), set()).add(_innermost(op.group(1)))
    assert seen["reduce-scatter"] == {"ad.sync"}
    assert seen["all-gather"] == {"ad.gather"}
    # the loss's own pmean is the one collective outside a stage
    assert seen.get("all-reduce", set()) <= {"ad.sync", None}


def test_overlapped_sync_nests_under_grad():
    sess = _session(8, AllReduce(schedule="overlap"), accum_steps=2)
    names = _op_names(sess)
    nested = [n for n in names
              if re.search(r"ad\.grad/.*ad\.sync", n)]
    assert nested and all(_innermost(n) == "ad.sync" for n in nested)


def test_cached_step_is_keyed_by_its_scope_names(tmp_path):
    """Two programs that differ in a ``named_scope`` only get two entries in
    the persistent cache once ``ensure_compile_cache`` has keyed it, so a
    profile never shows another commit's ``op_name``s."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from autodist_tpu.utils.compile_cache import ensure_compile_cache

    def scoped(name):
        def f(x):
            with jax.named_scope(name):
                return jnp.sin(x) * 2.0
        return jax.jit(f)

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    before = {n: getattr(jax.config, n) for n in names}
    try:
        ensure_compile_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        def entries():
            return {e for e in os.listdir(tmp_path)
                    if e.startswith("jit_f-") and e.endswith("-cache")}

        x = jnp.ones((4,))
        for _ in range(3):                    # same names: one entry
            scoped("ad.one")(x).block_until_ready()
        assert len(entries()) == 1
        scoped("ad.two")(x).block_until_ready()
        assert len(entries()) == 2
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        cc.reset_cache()


# -- host spans -------------------------------------------------------------

def _fed_session(tmp_path, monkeypatch=None, native=True):
    path = str(tmp_path / "records.bin")
    write_records(path, RS.randn(64, 9).astype(np.float32))
    if not native:
        monkeypatch.setattr(loader_mod, "_lib", False)
    ds = RecordDataset(path, (9,), np.float32)
    ld = BatchLoader(ds, 16, seed=3, prefetch=2)

    def stream():
        for recs in ld:
            yield {"x": recs[:, :8], "y": recs[:, 8]}

    sess = _session(8, AllReduce())
    return sess, DevicePrefetcher(stream(), sess, depth=2), ld, ds


def _profiled_spans(tmp_path, fn):
    """``fn()`` under the profiler; the ``ad.*`` events of the thread that
    ran it as ``(name, start, end, stats)``, by start time."""
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(trace_dir)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ad."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _children(spans, parent):
    return [s for s in spans if s is not parent
            and parent[1] <= s[1] and s[2] <= parent[2]]


def _check_three_fed_steps(spans, native=True):
    runs = [s for s in spans if s[0] == "ad.run"]
    assert [r[3]["step_num"] for r in runs] == [0, 1, 2]
    assert [r[3]["variants"] for r in runs] == [1, 1, 1]
    for run in runs:
        inside = [c[0] for c in _children(spans, run)]
        assert inside == ["ad.shard_batch", "ad.pre_step", "ad.dispatch"]
    nexts = [s for s in spans if s[0] == "ad.prefetch.next"]
    assert [n[3]["batch"] for n in nexts] == [0, 1, 2]
    assert all(n[3]["ready"] in (0, 1) for n in nexts)
    for i, nxt in enumerate(nexts):
        (push,) = [c for c in _children(spans, nxt)
                   if c[0] == "ad.prefetch.push"]
        assert push[3]["batch"] == i + 2          # depth 2 runs ahead
        under_push = _children(spans, push)
        assert [c[0] for c in under_push][0] == "ad.loader.next"
        assert under_push[-1][0] == "ad.shard_batch"
        (ld_next,) = [c for c in under_push if c[0] == "ad.loader.next"]
        in_loader = [c[0] for c in _children(spans, ld_next)]
        if native:
            assert in_loader == ["ad.loader.wait", "ad.loader.copy"]
            assert 0 <= ld_next[3]["ring"] <= 3   # prefetch + 1 slots
        else:
            assert in_loader == [] and "ring" not in ld_next[3]
    # batch i is consumed by step i: each hand-over ends before its run
    assert all(n[2] <= r[1] for n, r in zip(nexts, runs))


def _three_steps(sess, prefetcher):
    def go():
        for _ in range(3):
            metrics = sess.run(next(prefetcher))
        jax.block_until_ready(metrics)
    return go


def test_profile_holds_the_nested_host_spans(tmp_path):
    if not loader_mod._load_native():
        pytest.skip("no native loader here")
    sess, prefetcher, ld, ds = _fed_session(tmp_path)
    spans = _profiled_spans(tmp_path, _three_steps(sess, prefetcher))
    _check_three_fed_steps(spans)
    ld.close()
    ds.close()


def test_same_spans_with_telemetry_enabled(tmp_path):
    if not loader_mod._load_native():
        pytest.skip("no native loader here")
    telemetry.enable(run_dir=str(tmp_path / "run"))
    sess, prefetcher, ld, ds = _fed_session(tmp_path)
    assert sess._telemetry is not None
    spans = _profiled_spans(tmp_path, _three_steps(sess, prefetcher))
    _check_three_fed_steps(spans)
    # and the registry holds the same names as span records
    recorded = {r["name"] for r in telemetry.get_registry().events("span")}
    assert recorded >= {"ad.shard_batch", "ad.pre_step", "ad.dispatch",
                        "ad.prefetch.next", "ad.prefetch.push",
                        "ad.loader.next", "ad.loader.wait", "ad.loader.copy"}
    push = next(r for r in telemetry.get_registry().events("span")
                if r["name"] == "ad.prefetch.push")
    assert "batch" in push["args"]
    ld.close()
    ds.close()


def test_a_run_with_trace_dir_profiles_its_own_spans(tmp_path):
    """``run(batch, trace_dir=...)`` starts its profile before ``ad.run``
    opens, so the profile holds the runner's spans, the first step's and a
    steady one's."""
    from jax.profiler import ProfileData

    sess = _session(8, AllReduce())
    for step in range(2):
        metrics = sess.run(_batch(), trace_dir=str(tmp_path))
        assert metrics["trace_dir"] == str(tmp_path / f"step_{step}")
        (path,) = glob.glob(os.path.join(metrics["trace_dir"], "**",
                                         "*.xplane.pb"), recursive=True)
        names = {ev.name for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert names >= {"ad.run", "ad.shard_batch", "ad.pre_step",
                         "ad.dispatch"}


def test_numpy_fallback_has_no_ring(tmp_path, monkeypatch):
    sess, prefetcher, ld, ds = _fed_session(tmp_path, monkeypatch,
                                            native=False)
    assert not ld._native
    spans = _profiled_spans(tmp_path, _three_steps(sess, prefetcher))
    _check_three_fed_steps(spans, native=False)


def test_second_variant_is_counted_and_warned_once(tmp_path):
    sess = _session(8, AllReduce())
    records = []

    class Keep(pylogging.Handler):
        def emit(self, record):
            records.append(record)

    from autodist_tpu.utils import logging as ad_logging

    handler = Keep(level=pylogging.WARNING)
    logger = ad_logging.get_logger()
    logger.addHandler(handler)
    try:
        def go():
            for n in (16, 16, 32, 32, 16):
                metrics = sess.run(_batch(n))
            jax.block_until_ready(metrics)
        spans = _profiled_spans(tmp_path, go)
    finally:
        logger.removeHandler(handler)
    runs = [s for s in spans if s[0] == "ad.run"]
    assert [r[3]["step_num"] for r in runs] == [0, 1, 2, 3, 4]
    assert [r[3]["variants"] for r in runs] == [1, 1, 2, 2, 2]
    warned = [r.getMessage() for r in records
              if "compiled again" in r.getMessage()]
    assert len(warned) == 1 and "dispatch 2" in warned[0]


def _counting_loss(p, batch):
    """A loss with auxiliary outputs, as a model with routing counters."""
    return _loss(p, batch), {"rows_seen": jnp.sum(batch["y"] > 0) * 1.0,
                             "a_vector": batch["y"][:2]}


def test_auxiliary_counters_ride_on_a_later_run_span(tmp_path):
    """A step's auxiliary scalars are written on the ``ad.run`` span of a
    LATER dispatch, once the step has finished (its own ``run`` returns
    while it still runs), with ``aux_step`` naming the step they are of;
    with telemetry on they are registry gauges besides."""
    telemetry.enable(run_dir=str(tmp_path / "run"))
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(8),
                  strategy_builder=AllReduce())
    sess = ad.distribute(_counting_loss, _params(), optax.adam(1e-2),
                         has_aux=True)
    batches = [_batch() for _ in range(4)]
    seen = []

    def go():
        for b in batches:
            metrics = sess.run(b)
            jax.block_until_ready(metrics)      # so the next span has them
            seen.append(float(metrics["rows_seen"]))

    spans = _profiled_spans(tmp_path, go)
    runs = [s for s in spans if s[0] == "ad.run"]
    assert "aux_step" not in runs[0][3] and "rows_seen" not in runs[0][3]
    for i, run in enumerate(runs[1:], start=1):
        assert run[3]["aux_step"] == i - 1
        assert run[3]["rows_seen"] == pytest.approx(seen[i - 1])
        assert "a_vector" not in run[3]         # scalars only
    # the engine's mean over the eight devices of each one's count
    assert seen == [float(np.sum(b["y"] > 0)) / 8 for b in batches]
    assert telemetry.get_registry().gauge_value("step.rows_seen") \
        == pytest.approx(seen[-1])
    # a loss without auxiliary outputs keeps nothing and writes nothing
    plain = ad.distribute(_loss, _params(), optax.adam(1e-2))
    plain.run(_batch())
    assert not plain._aux_pending


def test_loader_ready_counts_the_ring(tmp_path):
    import time

    lib = loader_mod._load_native()
    if not lib:
        pytest.skip("no native loader here")
    path = str(tmp_path / "records.bin")
    write_records(path, RS.randn(64, 9).astype(np.float32))
    ds = RecordDataset(path, (9,), np.float32)
    ld = BatchLoader(ds, 16, seed=3, threads=1, prefetch=2)

    def settled(want):
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if lib.adio_loader_ready(ld._ld) == want:
                return True
            time.sleep(0.005)
        return False

    assert settled(3)                     # the ring fills: prefetch + 1
    buf = lib.adio_loader_next(ld._ld)    # held: its slot stays taken
    assert settled(2)
    buf2 = lib.adio_loader_next(ld._ld)
    buf3 = lib.adio_loader_next(ld._ld)
    assert lib.adio_loader_ready(ld._ld) == 0
    for b in (buf, buf2, buf3):
        lib.adio_loader_release(ld._ld, b)
    assert settled(3)
    assert lib.adio_loader_ready(None) == 0
    ld.close()
    ds.close()


def test_span_is_an_annotation_when_telemetry_is_off(monkeypatch):
    assert not telemetry.enabled()

    def boom(*a, **k):
        raise AssertionError("the registry was touched with telemetry off")

    monkeypatch.setattr(telemetry.SpanRecorder, "span", boom)
    ctx = telemetry.span("ad.test", batch=3)
    assert isinstance(ctx, jax.profiler.TraceAnnotation)
    with ctx:
        pass
