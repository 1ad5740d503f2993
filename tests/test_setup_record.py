"""The program's set-up record (``autodist_tpu/telemetry/setup.py``): the
spans of ``distribute()`` and a session's first step, the compiler's
seconds and the cache's events put down to them, the state's and step's
bytes, and the totals every later ``ad.run`` carries.
"""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from autodist_tpu.autodist import AutoDist
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce
from autodist_tpu.telemetry import setup

BATCH = np.random.RandomState(0).randn(16, 8).astype(np.float32)
SECONDS = ("trace_s", "lower_s", "compile_s")


def _loss(p, batch):
    return jnp.mean((jnp.tanh(batch @ p["w"]) - 0.5) ** 2)


def _params():
    return {"w": jnp.asarray(np.random.RandomState(7).randn(8, 4),
                             jnp.float32)}


@pytest.fixture(scope="module")
def ad():
    return AutoDist(resource_spec=ResourceSpec.from_num_chips(4),
                    strategy_builder=AllReduce())


def _set_up(ad, loss=_loss):
    """``distribute`` and the first ``run``; the set-up records they wrote,
    in the order the spans opened."""
    since_us = time.time_ns() // 1000
    sess = ad.distribute(loss, _params(), optax.adam(1e-2))
    sess.run(BATCH)
    return sess, _records_since(since_us)


def _records_since(since_us):
    records = [r for r in setup.record() if r["ts"] >= since_us]
    return sorted(records, key=lambda r: r["ts"])


def _one(records, name):
    (rec,) = [r for r in records if r["name"] == name]
    return rec


class _CompileEvents:
    """Every compile event JAX reports while it is registered."""

    def __init__(self):
        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, **kwargs):
        self.spans.append((event.rsplit("/", 1)[-1], start, end,
                           kwargs.get("fun_name")))

    def close(self):
        jax.monitoring.unregister_event_time_span_listener(self._on_span)


def test_span_tree_and_parents_of_distribute_and_the_first_run(ad):
    sess, records = _set_up(ad)
    assert [(r["name"], r.get("parent")) for r in records] == [
        ("ad.distribute", None),
        ("ad.capture", "ad.distribute"),
        ("ad.strategy", "ad.distribute"),
        ("ad.strategy_compile", "ad.distribute"),
        ("ad.transform", "ad.distribute"),
        ("ad.init_state", "ad.distribute"),
        ("ad.first_run", None)]
    distribute = _one(records, "ad.distribute")
    for child in records[1:6]:              # inside its parent in time too
        assert distribute["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= \
            distribute["ts"] + distribute["dur"] + 1
    # the session's totals are the record's, over its own spans
    totals = sess._setup_totals
    assert totals["setup_program_s"] == pytest.approx(
        (distribute["dur"] + _one(records, "ad.first_run")["dur"]) / 1e6,
        rel=1e-3)
    assert totals["setup_state_s"] == pytest.approx(
        _one(records, "ad.init_state")["dur"] / 1e6, rel=1e-3)
    for kind in SECONDS:
        assert totals["setup_" + kind] == pytest.approx(sum(
            r.get("args", {}).get(kind, 0.0) for r in records))
    assert "step_reserved_bytes" not in totals     # the CPU keeps no count
    assert setup.current() is None


def test_a_session_built_on_its_own_counts_its_state_in_the_program(ad):
    from autodist_tpu.kernel.graph_transformer import GraphTransformer
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.runner import DistributedSession

    item = ModelItem(_loss, _params(), optax.adam(1e-2))
    transformer = GraphTransformer(ad.build_strategy(item), item, ad.mesh)
    since_us = time.time_ns() // 1000
    sess = DistributedSession(transformer)
    sess.run(BATCH)
    records = _records_since(since_us)
    assert [(r["name"], r.get("parent")) for r in records] == [
        ("ad.init_state", None), ("ad.first_run", None)]
    assert sess._setup_totals["setup_program_s"] == pytest.approx(
        sum(r["dur"] for r in records) / 1e6, rel=1e-3)
    assert sess._setup_totals["setup_state_s"] == pytest.approx(
        records[0]["dur"] / 1e6, rel=1e-3)


def test_the_step_compiles_under_the_first_run_and_an_earlier_jit_nowhere(ad):
    events = _CompileEvents()
    try:
        def early(x):
            return jnp.cos(x) * 7.0

        jax.jit(early)(np.ones((5,), np.float32)).block_until_ready()
        sess, records = _set_up(ad)
    finally:
        events.close()
    distribute, first = _one(records, "ad.distribute"), \
        _one(records, "ad.first_run")
    start_s = distribute["ts"] / 1e6
    compiles = [(s, e, f) for kind, s, e, f in events.spans
                if kind == "backend_compile_duration"]
    (step,) = [(s, e) for s, e, f in compiles if "step_fn" in f]
    lo, hi = first["ts"] / 1e6, (first["ts"] + first["dur"]) / 1e6
    assert lo <= step[0] and step[1] <= hi + 1e-3
    assert first["args"]["compile_s"] == pytest.approx(
        step[1] - step[0], rel=1e-3, abs=1e-5)
    # the jit before distribute() compiled outside every set-up span, and
    # what the record counts is exactly the compiles from there on
    (early,) = [(s, e) for s, e, f in compiles if "early" in f]
    assert 0 < early[1] - early[0] and early[1] < start_s
    inside = sum(e - s for s, e, f in compiles if s >= start_s)
    assert sum(r.get("args", {}).get("compile_s", 0.0) for r in records) \
        == pytest.approx(inside, rel=1e-3, abs=1e-5)
    assert sess._setup_totals["setup_compile_s"] == pytest.approx(
        inside, rel=1e-3, abs=1e-5)


def _nested(depth):
    """A loss whose trace holds ``depth`` jits one inside the other, each
    of whose traces takes 50 ms of its own."""
    def level(n):
        def f(x):
            time.sleep(0.05)
            return jnp.sin(level(n - 1)(x)) if n > 1 else jnp.sin(x)
        return jax.jit(f)

    def loss(p, batch):
        return jnp.mean(level(depth)(batch @ p["w"]) ** 2)
    return loss


def test_nested_traces_are_counted_once(ad):
    events = _CompileEvents()
    try:
        sess, records = _set_up(ad, _nested(5))
    finally:
        events.close()
    first = _one(records, "ad.first_run")
    lo, hi = first["ts"] / 1e6, (first["ts"] + first["dur"]) / 1e6
    traces = [e - s for kind, s, e, f in events.spans
              if kind == "jaxpr_trace_duration" and lo <= s and e <= hi]
    span_s = first["dur"] / 1e6
    # the five nested traces report 0.25, 0.2, ... s inside the step's own:
    # summed they read more seconds than the first run took
    assert sum(traces) > span_s
    assert 0.25 <= first["args"]["trace_s"] <= span_s
    assert sum(first["args"][k] for k in SECONDS) <= span_s
    assert sess._setup_totals["setup_trace_s"] >= first["args"]["trace_s"]


@pytest.fixture
def persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_cache_misses_in_a_first_session_and_hits_in_a_second(
        ad, persistent_cache):
    def loss(p, batch):         # a program no other test has cached
        return jnp.mean(jnp.cosh(batch @ p["w"]) * 0.25)

    # one call site: the key holds the program's metadata, stack included
    (cold, cold_records), (warm, warm_records) = [
        _set_up(ad, loss) for _ in range(2)]
    first = _one(cold_records, "ad.first_run")["args"]
    assert first["cache_misses"] >= 1 and "cache_hits" not in first
    assert cold._setup_totals["setup_cache_misses"] >= first["cache_misses"]
    first = _one(warm_records, "ad.first_run")["args"]
    assert first["cache_hits"] >= 1 and "cache_misses" not in first
    assert warm._setup_totals["setup_cache_misses"] == 0
    assert glob.glob(os.path.join(str(persistent_cache), "jit_step_fn-*"))


def test_state_bytes_are_the_fullest_devices_shard_bytes():
    devices = jax.devices()[:4]
    mesh = Mesh(np.asarray(devices), ("replica",))
    pair = Mesh(np.asarray(devices[:2]), ("replica",))
    state = {
        "sharded": jax.device_put(jnp.ones((8, 6), jnp.float32),
                                  NamedSharding(mesh, P("replica"))),
        "replicated": jax.device_put(jnp.ones((3,), jnp.bfloat16),
                                     NamedSharding(mesh, P())),
        "on_two": jax.device_put(jnp.ones((4, 5), jnp.int32),
                                 NamedSharding(pair, P(None, None))),
        "key": jax.device_put(jax.random.key(0), NamedSharding(mesh, P())),
        "none": None}
    per_device = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            data = shard.data
            per_device[shard.device] += (
                jax.random.key_data(data).nbytes
                if jnp.issubdtype(data.dtype, jax.dtypes.prng_key)
                else data.nbytes)
    # 2 x 6 x 4 + 3 x 2 + 4 x 5 x 4 + 8 on the first two devices
    assert max(per_device.values()) == 48 + 6 + 80 + 8
    assert setup.fullest_device_bytes(state) == max(per_device.values())


def test_the_session_counts_its_state(ad):
    sess, records = _set_up(ad)
    expected = setup.fullest_device_bytes(sess.state)
    assert expected > 0
    assert _one(records, "ad.init_state")["args"]["state_bytes"] == expected
    assert sess._setup_totals["state_bytes"] == expected


def _profile(tmp_path, fn):
    """``fn()`` under the profiler: the host's ``ad.*`` events as
    ``(name, start_ns, end_ns, stats)``, by start."""
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(trace_dir)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            {k: v for k, v in ev.stats})
                           for ev in line.events if ev.name.startswith("ad.")]
    return sorted(events, key=lambda e: (e[1], -e[2]))


def test_steady_runs_grow_no_record_and_carry_the_same_totals(ad, tmp_path):
    sess, _ = _set_up(ad)
    size = len(setup.record())

    def ten():
        for _ in range(10):
            metrics = sess.run(BATCH)
        jax.block_until_ready(metrics)

    runs = [e for e in _profile(tmp_path, ten) if e[0] == "ad.run"]
    assert len(setup.record()) == size
    assert [r[3]["step_num"] for r in runs] == list(range(1, 11))
    for run in runs:
        assert {k: run[3][k] for k in sess._setup_totals} == pytest.approx(
            sess._setup_totals)
    assert "ad.first_run" not in {e[0] for e in _profile(
        tmp_path / "again", lambda: sess.run(BATCH))}


def test_a_profile_around_distribute_holds_the_record(ad, tmp_path):
    out = {}

    def go():
        out["sess"], out["records"] = _set_up(ad)

    events = _profile(tmp_path, go)
    records = out["records"]
    names = {r["name"] for r in records}
    found = [e for e in events if e[0] in names]
    assert [e[0] for e in found] == [r["name"] for r in records]

    def parent_of(i, spans, start, end):
        held = [j for j, s in enumerate(spans) if j != i
                and start(s) <= start(spans[i]) and end(spans[i]) <= end(s)]
        return spans[held[-1]][0] if held else None

    for i, (rec, ev) in enumerate(zip(records, found)):
        assert parent_of(i, found, lambda e: e[1], lambda e: e[2]) == \
            rec.get("parent")
        dur_ms, ev_ms = rec["dur"] / 1e3, (ev[2] - ev[1]) / 1e6
        assert abs(ev_ms - dur_ms) <= max(1.0, 0.05 * dur_ms)
        # the counters ride on the profile's event too
        for key, value in rec.get("args", {}).items():
            assert ev[3][key] == pytest.approx(value)
