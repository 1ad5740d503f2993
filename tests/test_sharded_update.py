"""ZeRO-style cross-replica sharded weight update (ShardedUpdate.SHARDED).

The AR family's ``sharded_update`` knob rewrites the step as
reduce-scatter of grads -> per-shard optimizer update (opt state
permanently sharded 1/R, bucket-aligned flat shards with a per-var
padding plan) -> all-gather of FRESH PARAMS (replacing the gradient
all-gather).  Pinned here, mirroring tests/test_hierarchical_sync.py:

- resolve_sharded_update follows the PR 2 name/value-table error
  convention with raw-int validation,
- proto/builder/plan/transformer threading + bucket shard plans,
- block-codec ineligibility (replicated-update fallback) and scalar
  exclusion,
- engine equivalence vs the replicated update across optimizers
  (sgd/momentum/adam), every elementwise codec, barrier+overlap,
  FLAT+TWO_LEVEL (fused: the ICI scatter's shard feeds the update, no
  gradient re-gather), and under grad-accum scan,
- cost model: 1/R opt-state HBM (with the async-PS regression guard),
  scatter+gather wire pricing, AutoStrategy ranking a sharded candidate
  first on an HBM-bound multi-node spec,
- analysis: Y007/Y008 warnings + Y009 summary; clean end-to-end verify,
- checkpoint round-trip of the sharded opt state (gather-on-save
  canonical form; cross-strategy restore),
- telemetry meta/gauges (sync.sharded_update),
- the live ``records/cpu_mesh/gpt_tiny_AllReduce_sharded_update.json``
  record audits clean with X006 realized bytes matching the cost
  model's scatter/gather predictions within the 25% tolerance.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import AXIS_REPLICA_DCN, AXIS_REPLICA_ICI
from autodist_tpu.kernel import partitioner as part
from autodist_tpu.model_item import ModelItem
from autodist_tpu.proto import synchronizers_pb2
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce, Parallax
from autodist_tpu.strategy.base import resolve_sharded_update

_C = synchronizers_pb2.AllReduceSynchronizer
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC_FLAT4 = ResourceSpec(resource_info={
    "nodes": [{"address": "localhost", "chips": [0, 1, 2, 3]}]})
SPEC_2x2 = ResourceSpec(resource_info={
    "nodes": [{"address": "localhost", "chips": [0, 1, 2, 3]}],
    "mesh": {AXIS_REPLICA_DCN: 2, AXIS_REPLICA_ICI: 2}})
SPEC_2NODE = ResourceSpec(resource_info={"nodes": [
    {"address": "10.0.0.1", "chips": [0, 1, 2, 3], "chief": True,
     "network_bandwidth": 100},
    {"address": "10.0.0.2", "chips": [0, 1, 2, 3],
     "network_bandwidth": 100}]})


def _item(scale=1):
    params = {"w1": jnp.zeros((32 * scale, 16)), "b1": jnp.zeros((16,)),
              "w2": jnp.zeros((16, 4))}
    return ModelItem(lambda p, b: 0.0, params)


# -- knob resolution + proto threading --------------------------------------

def test_resolve_sharded_update_names_and_ints():
    assert resolve_sharded_update("replicated") == _C.REPLICATED_UPDATE
    assert resolve_sharded_update("sharded") == _C.SHARDED
    assert resolve_sharded_update("SHARDED") == _C.SHARDED
    assert resolve_sharded_update("zero") == _C.SHARDED
    assert resolve_sharded_update(_C.SHARDED) == _C.SHARDED
    assert resolve_sharded_update(True) == _C.SHARDED
    assert resolve_sharded_update(False) == _C.REPLICATED_UPDATE
    # PR 2 convention: errors enumerate the accepted name/value table and
    # raw ints are validated
    with pytest.raises(ValueError) as e:
        resolve_sharded_update("fsdp")
    assert "'sharded'" in str(e.value) and "'replicated'" in str(e.value)
    with pytest.raises(ValueError) as e:
        resolve_sharded_update(99)
    assert "accepted names/values" in str(e.value)
    with pytest.raises(ValueError):
        AllReduce(sharded_update="bogus")


def test_sharded_update_threads_builder_to_buckets():
    from autodist_tpu.kernel.graph_transformer import GraphTransformer

    item = _item()
    s = AllReduce(sharded_update="sharded").build(item, SPEC_FLAT4)
    for n in s.node_config:
        assert n.AllReduceSynchronizer.sharded_update == _C.SHARDED
    plans = part.build_var_plans(s, item, 4)
    assert all(p.sharded_update == _C.SHARDED for p in plans.values())
    mesh = Mesh(np.array(jax.devices()[:4]), ("replica",))
    t = GraphTransformer(s, item, mesh)
    assert t.sync_sharded_update
    assert len(t.sharded_buckets) == 1
    (b,) = t.sharded_buckets
    assert b.sharded_update == _C.SHARDED and b.num_shards == 4
    # per-var padding plan: shard lengths are ceil(size / R)
    assert b.shard_sizes == tuple(-(-sz // 4) for sz in b.sizes)
    assert b.padded_total == sum(b.shard_sizes) * 4
    assert "sharded_update(ss=" in t.plan_summary()
    summary = t.sharded_update_summary()
    assert summary["enabled"] and summary["num_shards"] == 4
    assert summary["shard_bytes"] == b.shard_total * 4  # f32


def test_block_codec_falls_back_to_replicated_update():
    from autodist_tpu.kernel.graph_transformer import GraphTransformer

    item = _item()
    for kw in (dict(compressor="Int8Compressor"),
               dict(compressor="PowerSGDCompressor"),
               dict(hierarchy="two_level", dcn_compressor="Int8Compressor")):
        spec = SPEC_2x2 if "hierarchy" in kw else SPEC_FLAT4
        mesh = (Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                     (AXIS_REPLICA_DCN, AXIS_REPLICA_ICI))
                if "hierarchy" in kw
                else Mesh(np.array(jax.devices()[:4]), ("replica",)))
        s = AllReduce(sharded_update="sharded", **kw).build(item, spec)
        t = GraphTransformer(s, item, mesh)
        assert not t.sync_sharded_update, kw
        assert all(not p.sharded_update for p in t.plans.values()), kw


def test_scalar_vars_never_shard_their_update():
    item = ModelItem(lambda p, b: 0.0,
                     {"w": jnp.zeros((32, 8)), "temp": jnp.zeros(())})
    s = AllReduce(sharded_update="sharded").build(item, SPEC_FLAT4)
    plans = part.build_var_plans(s, item, 4)
    assert plans["temp"].sharded_update == 0
    assert plans["w"].sharded_update == _C.SHARDED
    # update-space shapes: flat padded shard for w, untouched scalar
    assert part.update_space_shape(plans["w"], 4) == (256,)
    assert part.update_space_shape(plans["temp"], 4) == ()
    assert part.update_space_spec(plans["w"], "replica") == P("replica")
    assert part.update_space_spec(plans["temp"], "replica") == P()


# -- engine equivalence (the acceptance matrix) ------------------------------

_OPTS = {"sgd": lambda: optax.sgd(0.1),
         "momentum": lambda: optax.sgd(0.1, momentum=0.9),
         "adam": lambda: optax.adam(0.05)}


def _train(spec, opt="sgd", schedule="barrier", hierarchy="auto",
           compressor="NoneCompressor", sharded="replicated", accum=1,
           steps=2):
    from autodist_tpu.autodist import AutoDist

    r = np.random.RandomState(0)
    params = {"w1": jnp.asarray(r.randn(32, 16), jnp.float32),
              "b1": jnp.zeros((16,), jnp.float32),
              "w2": jnp.asarray(r.randn(16, 4), jnp.float32)}

    def loss(p, b):
        h = jnp.tanh(b["x"] @ p["w1"] + p["b1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    batch = {"x": r.randn(32, 32).astype(np.float32),
             "y": r.randn(32, 4).astype(np.float32)}
    ad = AutoDist(resource_spec=spec, strategy_builder=AllReduce(
        compressor=compressor, schedule=schedule, hierarchy=hierarchy,
        sharded_update=sharded))
    sess = ad.distribute(loss, params, _OPTS[opt](), accum_steps=accum)
    for _ in range(steps):
        m = sess.run(batch)
    return sess, float(m["loss"])


@pytest.mark.parametrize("opt", sorted(_OPTS))
def test_engine_sharded_matches_replicated_per_optimizer(opt):
    """Acceptance: sgd / momentum / adam — the sharded update trains
    identically to the replicated one (allclose; the reduce-scatter sums
    the same terms as the allreduce up to re-association)."""
    s0, l0 = _train(SPEC_FLAT4, opt=opt)
    s1, l1 = _train(SPEC_FLAT4, opt=opt, sharded="sharded")
    assert s1._t.sync_sharded_update and not s0._t.sync_sharded_update
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 s0.params(), s1.params())
    assert abs(l0 - l1) < 1e-4


_ELEMENTWISE = [("NoneCompressor", 1e-5), ("BF16Compressor", 2e-2),
                ("BF16CompressorEF", 2e-2)]


@pytest.mark.parametrize("schedule", ["barrier", "overlap"])
@pytest.mark.parametrize("comp,tol", _ELEMENTWISE)
def test_engine_sharded_matches_replicated_per_codec(schedule, comp, tol):
    """Acceptance: every elementwise codec, both issue schedules, FLAT."""
    s0, _ = _train(SPEC_FLAT4, schedule=schedule, compressor=comp)
    s1, _ = _train(SPEC_FLAT4, schedule=schedule, compressor=comp,
                   sharded="sharded")
    assert s1._t.sync_sharded_update
    assert s1._t.sync_schedule == schedule
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=tol),
                 s0.params(), s1.params())


@pytest.mark.parametrize("comp,tol", _ELEMENTWISE)
def test_engine_two_level_fused_sharded_matches_flat(comp, tol):
    """Acceptance: fused TWO_LEVEL x SHARDED — the ICI reduce-scatter's
    shard feeds the update directly and the param gather retraces the
    hops — matches the flat replicated baseline."""
    s0, _ = _train(SPEC_FLAT4, compressor=comp)
    s1, _ = _train(SPEC_2x2, hierarchy="two_level", compressor=comp,
                   sharded="sharded")
    t = s1._t
    assert t.sync_hierarchy == "two_level" and t.sync_sharded_update
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=tol),
                 s0.params(), s1.params())


@pytest.mark.parametrize("schedule", ["barrier", "overlap"])
def test_engine_sharded_under_grad_accum(schedule):
    """Acceptance: grad accumulation — under overlap the per-microbatch
    scatter runs INSIDE the scan (the shard accumulator carries (ss,)
    shapes) and the param gather still happens once per step."""
    s0, _ = _train(SPEC_FLAT4, opt="adam", schedule=schedule, accum=4)
    s1, _ = _train(SPEC_FLAT4, opt="adam", schedule=schedule, accum=4,
                   sharded="sharded")
    assert s1._t.sync_sharded_update
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 s0.params(), s1.params())


def test_engine_two_level_sharded_ef_overlap_accum():
    """The deepest composition: TWO_LEVEL x SHARDED x bf16-EF DCN wire x
    overlap x accumulation — the per-region EF residual (ici-major padded
    row layout) threads the scan and stays allclose to the flat EF run."""
    s0, _ = _train(SPEC_FLAT4, opt="adam", schedule="overlap",
                   compressor="BF16CompressorEF", accum=2)
    s1, _ = _train(SPEC_2x2, opt="adam", schedule="overlap",
                   hierarchy="two_level", compressor="BF16CompressorEF",
                   accum=2, sharded="sharded")
    t = s1._t
    assert t.sync_hierarchy == "two_level" and t.sync_sharded_update
    # the EF residual lives in the padded row layout for two-level buckets
    (b,) = t.sharded_buckets
    assert t.init_comp_states()[b.key].shape == (4, b.padded_total)
    # bf16-EF rounding takes a different path through the scatter than
    # through the flat reduce; 1e-2 is still half the codec family's
    # 2e-2 equivalence tolerance
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-2),
                 s0.params(), s1.params())


def test_sharded_update_with_global_norm_clip():
    """The mesh-aware global-norm assembly treats sharded-update shards
    as disjoint (full-axis psum), matching the replicated clip."""
    from autodist_tpu.autodist import AutoDist

    r = np.random.RandomState(1)
    params = {"w": jnp.asarray(r.randn(32, 8) * 3, jnp.float32)}
    batch = {"x": r.randn(16, 32).astype(np.float32)}

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    outs = []
    for sharded in ("replicated", "sharded"):
        ad = AutoDist(resource_spec=SPEC_FLAT4,
                      strategy_builder=AllReduce(sharded_update=sharded))
        sess = ad.distribute(loss, params, optax.sgd(0.1),
                             clip_global_norm=0.5)
        m = sess.run(batch)
        outs.append((sess.params(), float(m["grad_norm"])))
    (p0, n0), (p1, n1) = outs
    assert n0 == pytest.approx(n1, rel=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 p0, p1)


# -- cost model (acceptance) -------------------------------------------------

def _big_item():
    return ModelItem(lambda p, b: 0.0, {"w": jnp.zeros((512, 512))},
                     optax.adam(1e-3))


def test_hbm_footprint_sharded_update_is_one_over_r():
    """Pin: the sharded-update placement gets the 1/R opt-state footprint
    — and async PS still does NOT (regression guard on the PR 1 fix)."""
    from autodist_tpu.simulator.cost_model import hbm_footprint
    from autodist_tpu.strategy import PS

    item = _big_item()
    pb = 512 * 512 * 4
    ar_fp = hbm_footprint(AllReduce().build(item, SPEC_FLAT4), item, 8)
    sh_fp = hbm_footprint(
        AllReduce(sharded_update="sharded").build(item, SPEC_FLAT4),
        item, 8)
    assert abs(ar_fp["opt_bytes"] - 2 * pb) < 0.05 * pb
    assert abs(sh_fp["opt_bytes"] - 2 * pb / 8) < 0.05 * pb
    # params + grads stay full (gathered copy on every chip)
    assert sh_fp["param_bytes"] == ar_fp["param_bytes"]
    assert sh_fp["grad_bytes"] == ar_fp["grad_bytes"]
    # a block-codec sharded request earns NO discount (engine falls back)
    int8_fp = hbm_footprint(
        AllReduce(sharded_update="sharded",
                  compressor="Int8Compressor").build(item, SPEC_FLAT4),
        item, 8)
    assert abs(int8_fp["opt_bytes"] - 2 * pb) < 0.05 * pb
    # async PS: full opt state on the server — never the 1/R discount
    async_fp = hbm_footprint(PS(sync=False).build(item, SPEC_FLAT4),
                             item, 8)
    assert abs(async_fp["opt_bytes"] - 2 * pb) < 0.05 * pb


def test_cost_model_prices_scatter_gather_and_sharded_update():
    from autodist_tpu.simulator.cost_model import (estimate,
                                                   predicted_comm_bytes)

    item = _big_item()
    nbytes = 512 * 512 * 4
    repl = estimate(AllReduce().build(item, SPEC_FLAT4), item, SPEC_FLAT4,
                    flops_per_example=1e9)
    shard = estimate(
        AllReduce(sharded_update="sharded").build(item, SPEC_FLAT4),
        item, SPEC_FLAT4, flops_per_example=1e9)
    bd = shard.breakdown
    assert bd["ar_bytes"] == 0
    assert bd["sharded_scatter_bytes"] == pytest.approx(nbytes)
    assert bd["sharded_gather_bytes"] == pytest.approx(nbytes)
    # scatter+gather == the allreduce ring's wire volume at NoneCompressor
    assert (bd["sharded_scatter_s"] + bd["sharded_gather_s"]
            == pytest.approx(repl.breakdown and
                             2.0 * bd["sharded_scatter_s"]))
    # 1/R optimizer phase: strictly cheaper overall
    assert bd["update_bytes"] == pytest.approx(nbytes / 4)
    assert shard.total_s < repl.total_s
    assert predicted_comm_bytes(shard)["flat"] == pytest.approx(2 * nbytes)
    # a gradient codec shrinks ONLY the scatter leg (params ride native)
    bf16 = estimate(
        AllReduce(sharded_update="sharded",
                  compressor="BF16Compressor").build(item, SPEC_FLAT4),
        item, SPEC_FLAT4, flops_per_example=1e9)
    assert bf16.breakdown["sharded_scatter_bytes"] == \
        pytest.approx(nbytes / 2)
    assert bf16.breakdown["sharded_gather_bytes"] == pytest.approx(nbytes)


def test_cost_model_two_level_sharded_dcn_hop():
    """Fused TWO_LEVEL x SHARDED: the DCN hop pays grad-scatter +
    param-gather ONE-WAY (priced (n-1)/n) instead of the shard ring."""
    from autodist_tpu.simulator.cost_model import estimate

    item = _big_item()
    nbytes = 512 * 512 * 4
    repl = estimate(AllReduce(hierarchy="two_level").build(item, SPEC_2NODE),
                    item, SPEC_2NODE, flops_per_example=1e9)
    shard = estimate(
        AllReduce(hierarchy="two_level",
                  sharded_update="sharded").build(item, SPEC_2NODE),
        item, SPEC_2NODE, flops_per_example=1e9)
    bd = shard.breakdown
    assert bd["hier_ici_bytes"] == pytest.approx(2 * nbytes)
    # dcn: shard * (grad factor 1 + param 1) vs replicated shard * 1
    assert bd["hier_dcn_bytes"] == pytest.approx(
        repl.breakdown["hier_dcn_bytes"] * 2)
    # ...but one-way pricing + 1/R update keeps it strictly cheaper
    assert shard.total_s < repl.total_s


def test_auto_strategy_ranks_sharded_first_on_hbm_bound_spec():
    """Acceptance: on an HBM-bound multi-node spec AutoStrategy ranks a
    sharded-update candidate first; replicated-update AR candidates are
    H001-rejected and the BUILT winner carries the SHARDED proto knob."""
    from autodist_tpu.strategy.auto_strategy import (AutoStrategy,
                                                     default_candidates)

    assert any(getattr(b, "sharded_update", None) == "sharded"
               for b in default_candidates(SPEC_FLAT4))
    cands = default_candidates(SPEC_2NODE)
    assert any(getattr(b, "sharded_update", None) == "sharded"
               and getattr(b, "hierarchy", None) == "two_level"
               for b in cands)

    item = _big_item()
    pb = 512 * 512 * 4
    # fits params + grads + SHARDED opt state (2pb/8) but not the
    # replicated 2pb of Adam moments
    budget = int(pb + pb + 2 * pb / 8 + 0.3 * pb)
    auto = AutoStrategy(flops_per_example=1e9,
                        hbm_bytes_per_device=budget)
    s = auto.build(item, SPEC_2NODE)
    winner = auto.last_ranking[0][0]
    assert "sharded" in winner, auto.last_ranking
    rejected = {n for n, _ in auto.last_rejected}
    assert "AllReduce" in rejected  # the replicated-update baseline
    assert any(
        n.AllReduceSynchronizer.sharded_update == _C.SHARDED
        for n in s.node_config
        if n.WhichOneof("synchronizer") == "AllReduceSynchronizer")


# -- analysis (acceptance) ---------------------------------------------------

def test_analysis_warns_block_codec_sharded_update():
    from autodist_tpu.analysis import verify_strategy

    item = _item()
    s = AllReduce(sharded_update="sharded",
                  compressor="Int8Compressor").build(item, SPEC_FLAT4)
    report = verify_strategy(s, item, SPEC_FLAT4, passes=("hierarchy",))
    assert report.ok  # a fallback, not a failure
    codes = [f.code for f in report.findings]
    assert "Y007" in codes
    assert any(f.code == "Y009" and "fall back" in f.message
               for f in report.findings)


def test_analysis_warns_var_smaller_than_shard_count():
    from autodist_tpu.analysis import verify_strategy

    item = ModelItem(lambda p, b: 0.0,
                     {"w": jnp.zeros((64, 8)), "tiny": jnp.zeros((2,))})
    s = AllReduce(sharded_update="sharded").build(item, SPEC_FLAT4)
    report = verify_strategy(s, item, SPEC_FLAT4, passes=("hierarchy",))
    y8 = [f for f in report.findings if f.code == "Y008"]
    assert len(y8) == 1 and y8[0].subject == "tiny"


def test_analysis_clean_sharded_verifies_end_to_end():
    """The full pass chain (static + traced) on real sharded strategies
    comes back clean — the records/cpu_mesh gate relies on this."""
    from autodist_tpu.analysis import verify_strategy

    def quad_loss(p, b):
        total = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(p):
            total = total + jnp.sum(jnp.square(leaf))
        return total * jnp.mean(jnp.ones_like(b["x"]))

    item = ModelItem(quad_loss,
                     {"w1": jnp.zeros((32, 16)), "b1": jnp.zeros((16,)),
                      "w2": jnp.zeros((16, 4))}, optax.adam(1e-3))
    for builder in (AllReduce(sharded_update="sharded"),
                    AllReduce(sharded_update="sharded",
                              schedule="overlap")):
        s = builder.build(item, SPEC_FLAT4)
        report = verify_strategy(
            s, item, SPEC_FLAT4, batch_shapes={"x": ((8, 4), "float32")},
            hbm_bytes_per_device=16 << 30)
        assert report.ok, [str(f) for f in report.errors]
        assert any(f.code == "Y009" for f in report.findings)
    s = AllReduce(sharded_update="sharded",
                  hierarchy="two_level").build(item, SPEC_2x2)
    report = verify_strategy(
        s, item, SPEC_2x2, batch_shapes={"x": ((8, 4), "float32")},
        hbm_bytes_per_device=16 << 30)
    assert report.ok, [str(f) for f in report.errors]


def test_audit_sharded_schedule_is_scatter_then_gather():
    """The HLO audit confirms the realized schedule: reduce-scatter of
    grads + all-gather of params, ZERO unintended collectives (no
    X001/X002), and under TWO_LEVEL the four-hop fused trio with no
    gradient re-gather between the ICI scatter and the shard update."""
    from autodist_tpu.analysis import (LOWERED_PASSES, STATIC_PASSES,
                                       TRACE_PASSES, verify_strategy)

    def quad_loss(p, b):
        total = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(p):
            total = total + jnp.sum(jnp.square(leaf))
        return total * jnp.mean(jnp.ones_like(b["x"]))

    # big enough that every hop (incl. the 1/R_ici DCN shard) clears the
    # audit's control-plane threshold and must match its channel
    item = ModelItem(quad_loss, {"w": jnp.zeros((256, 128))},
                     optax.adam(1e-3))
    s = AllReduce(sharded_update="sharded",
                  hierarchy="two_level").build(item, SPEC_2x2)
    report = verify_strategy(
        s, item, SPEC_2x2, batch_shapes={"x": ((8, 4), "float32")},
        hbm_bytes_per_device=16 << 30,
        passes=STATIC_PASSES + TRACE_PASSES + LOWERED_PASSES)
    assert report.ok, [str(f) for f in report.errors]
    x6 = next(f for f in report.findings if f.code == "X006")
    by_label = {c["label"]: c for c in x6.data["channels"]}
    hops = [k.split("/", 1)[1] for k in by_label]
    assert set(hops) == {"ici-scatter", "dcn-scatter", "dcn-param-gather",
                         "ici-param-gather"}
    for c in by_label.values():
        assert c["ops"] >= 1, c  # every hop realized, nothing extra
    assert x6.data["n_unmatched"] == 0


def test_live_record_x006_matches_cost_model_within_tolerance():
    """CI/tooling acceptance: the shipped live record's realized bytes
    match the cost model's scatter/gather predictions within the audit's
    25% tolerance (mirrors the two-level record pin in
    tests/test_hlo_audit.py)."""
    from autodist_tpu.analysis import (LOWERED_PASSES, STATIC_PASSES,
                                       TRACE_PASSES, verify_strategy)
    from autodist_tpu.analysis.hlo_audit import BYTES_TOL
    from autodist_tpu.simulator.cost_model import (RuntimeRecord, estimate,
                                                   rebuild_record_case)

    path = os.path.join(REPO, "records", "cpu_mesh",
                        "gpt_tiny_AllReduce_sharded_update.json")
    assert os.path.exists(path), "live sharded-update record missing"
    rec = RuntimeRecord.load(path)
    strategy, item, R = rebuild_record_case(rec)
    spec = ResourceSpec.from_num_chips(R)
    report = verify_strategy(
        strategy, item, spec, batch_shapes={"x": ((2 * R, 4), "float32")},
        hbm_bytes_per_device=16 << 30,
        passes=STATIC_PASSES + TRACE_PASSES + LOWERED_PASSES)
    assert report.ok, [str(f) for f in report.errors]
    x6 = next(f for f in report.findings if f.code == "X006")
    realized_flat = x6.data["realized"]["flat"]
    est = estimate(strategy, item, spec)
    predicted = (est.breakdown["sharded_scatter_bytes"]
                 + est.breakdown["sharded_gather_bytes"])
    assert predicted > 0
    assert realized_flat == pytest.approx(predicted, rel=BYTES_TOL)


# -- checkpoint round-trip ---------------------------------------------------

def test_checkpoint_roundtrip_sharded_opt_state(tmp_path):
    """Sharded opt state canonicalizes to the single-device shape on save
    (gather-on-save) and restores both into a sharded session AND across
    strategies into a replicated one — resumed training matches."""
    from autodist_tpu.checkpoint.saver import Saver

    sess, _ = _train(SPEC_FLAT4, opt="adam", sharded="sharded", steps=2)
    path = str(tmp_path / "ckpt")
    Saver(sess).save(path)

    # canonical (single-device) contract: original param shapes
    restored = Saver.restore_single_device(path)
    for name, leaf in restored["params"].items():
        assert leaf.shape == np.asarray(sess.params()[name]).shape

    # same-strategy restore: continue training == uninterrupted training
    sess_resume, _ = _train(SPEC_FLAT4, opt="adam", sharded="sharded",
                            steps=2)
    Saver(sess_resume).restore(path)
    ref, _ = _train(SPEC_FLAT4, opt="adam", sharded="sharded", steps=3)
    # the exact batch _train uses: same RandomState(0) stream, params
    # drawn first
    r = np.random.RandomState(0)
    r.randn(32, 16)
    r.randn(16, 4)
    batch = {"x": r.randn(32, 32).astype(np.float32),
             "y": r.randn(32, 4).astype(np.float32)}
    sess_resume.run(batch)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 ref.params(), sess_resume.params())

    # cross-strategy restore (sharded -> replicated): params + opt state
    # land in the replicated layout and training continues equivalently
    sess_repl, _ = _train(SPEC_FLAT4, opt="adam", steps=2)
    Saver(sess_repl).restore(path)
    sess_repl.run(batch)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 ref.params(), sess_repl.params())


# -- telemetry ---------------------------------------------------------------

def test_telemetry_records_sharded_update(tmp_path):
    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.telemetry import load_manifest
    from autodist_tpu.telemetry.session import SessionTelemetry

    r = np.random.RandomState(0)
    params = {"w": jnp.asarray(r.randn(32, 8), jnp.float32)}
    batch = {"x": r.randn(16, 32).astype(np.float32)}
    ad = AutoDist(resource_spec=SPEC_FLAT4,
                  strategy_builder=AllReduce(sharded_update="sharded"))
    sess = ad.distribute(lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2),
                         params, optax.sgd(0.1))
    tel = SessionTelemetry(sess._t, run_dir=str(tmp_path))
    sess._telemetry = tel
    for _ in range(2):
        sess.run(batch)
    sess.finalize_telemetry()
    records = load_manifest(str(tmp_path))
    meta = next(rec for rec in records if rec.get("kind") == "meta")
    shup = meta["sharded_update"]
    assert shup["enabled"] and shup["num_shards"] == 4
    assert shup["param_gather_bytes"] > 0
    gauges = next(rec for rec in records
                  if rec.get("kind") == "summary")["aggregates"]["gauges"]
    assert "sync.sharded_update" in gauges
    assert "sync.param_gather_bytes" in gauges


def test_parallax_inherits_sharded_update():
    item = _item()
    s = Parallax(sharded_update="sharded").build(item, SPEC_FLAT4)
    ar_nodes = [n for n in s.node_config
                if n.WhichOneof("synchronizer") == "AllReduceSynchronizer"]
    assert ar_nodes
    assert all(n.AllReduceSynchronizer.sharded_update == _C.SHARDED
               for n in ar_nodes)
