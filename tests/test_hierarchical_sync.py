"""Hierarchical (two-level) topology-aware gradient sync.

The TWO_LEVEL schedule (``AllReduceSynchronizer.Hierarchy``) decomposes
the AR family's collective on a ``replica_dcn x replica_ici`` factored
mesh: intra-slice reduce-scatter over ICI -> cross-slice ring allreduce
of the 1/R_ici shard over DCN (optionally through the DCN-hop codec) ->
intra-slice all-gather.  Pinned here:

- proto/builder/plan/transformer threading + resolve_hierarchy errors,
- mesh factoring from host boundaries and the YAML override,
- tuple-axis collective helpers,
- CPU-mesh equivalence: TWO_LEVEL == FLAT (allclose) for the elementwise
  codec family, with and without DCN-hop compression, under barrier and
  overlap schedules and under grad accumulation,
- cost model: per-hop pricing makes TWO_LEVEL strictly cheaper than FLAT
  on a DCN-bottlenecked multi-node spec, and AutoStrategy selects it,
- analysis: PowerSGD as DCN-hop codec and bad sub-axis factorizations
  are rejected (ERROR).
"""
import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from autodist_tpu.const import AXIS_REPLICA_DCN, AXIS_REPLICA_ICI
from autodist_tpu.kernel import partitioner as part
from autodist_tpu.kernel.synchronization import all_reduce as ar
from autodist_tpu.model_item import ModelItem
from autodist_tpu.proto import synchronizers_pb2
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce, Parallax
from autodist_tpu.strategy.base import resolve_hierarchy

_C = synchronizers_pb2.AllReduceSynchronizer

SPEC_FLAT4 = ResourceSpec(resource_info={
    "nodes": [{"address": "localhost", "chips": [0, 1, 2, 3]}]})
# the acceptance mesh: 2 x 2 factored over 4 virtual CPU devices
SPEC_2x2 = ResourceSpec(resource_info={
    "nodes": [{"address": "localhost", "chips": [0, 1, 2, 3]}],
    "mesh": {AXIS_REPLICA_DCN: 2, AXIS_REPLICA_ICI: 2}})
# two hosts x 4 chips with explicit DCN bandwidth (multi-node pricing)
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

def test_resolve_hierarchy_names_and_ints():
    assert resolve_hierarchy("auto") == _C.AUTO_HIERARCHY
    assert resolve_hierarchy("flat") == _C.FLAT
    assert resolve_hierarchy("two_level") == _C.TWO_LEVEL
    assert resolve_hierarchy("TWO_LEVEL") == _C.TWO_LEVEL
    assert resolve_hierarchy(_C.TWO_LEVEL) == _C.TWO_LEVEL
    # PR 2 convention: errors enumerate the accepted name/value table and
    # raw ints are validated
    with pytest.raises(ValueError) as e:
        resolve_hierarchy("pyramid")
    assert "'two_level'" in str(e.value) and "'flat'" in str(e.value)
    with pytest.raises(ValueError) as e:
        resolve_hierarchy(99)
    assert "accepted names/values" in str(e.value)
    with pytest.raises(ValueError):
        AllReduce(hierarchy="bogus")


def test_hierarchy_threads_builder_to_plans_and_transformer():
    from autodist_tpu.kernel.graph_transformer import GraphTransformer

    item = _item()
    s = AllReduce(hierarchy="two_level",
                  dcn_compressor="Int8Compressor").build(item, SPEC_2x2)
    for n in s.node_config:
        assert n.AllReduceSynchronizer.hierarchy == _C.TWO_LEVEL
        assert n.AllReduceSynchronizer.dcn_compressor == _C.Int8Compressor
    plans = part.build_var_plans(s, item, 4)
    assert all(p.hierarchy == _C.TWO_LEVEL for p in plans.values())
    assert all(p.dcn_compressor == _C.Int8Compressor for p in plans.values())
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                (AXIS_REPLICA_DCN, AXIS_REPLICA_ICI))
    t = GraphTransformer(s, item, mesh)
    assert t.sync_hierarchy == "two_level"
    assert t.hier_spec is not None and t.hier_spec.ici == AXIS_REPLICA_ICI
    assert all(b.hierarchy == _C.TWO_LEVEL for b in t.buckets)
    assert "sync_hierarchy: two_level" in t.plan_summary()
    # the summary's per-hop accounting: DCN rides 1/R_ici of the volume,
    # further int8-compressed — wire_byte_factor's honest int8 pricing,
    # 0.25x payload plus the per-256-block f32 scale rows
    from autodist_tpu.kernel.synchronization.compressor import \
        wire_byte_factor
    hs = t.hierarchy_summary()
    assert hs["mode"] == "two_level"
    assert hs["replica_dcn"] == 2 and hs["replica_ici"] == 2
    assert hs["dcn_compressors"] == ["int8"]
    assert hs["dcn_hop_bytes"] == pytest.approx(
        hs["ici_hop_bytes"] / 2 * wire_byte_factor(_C.Int8Compressor) / 2)


def test_two_level_without_factored_mesh_raises():
    from autodist_tpu.kernel.graph_transformer import GraphTransformer

    item = _item()
    s = AllReduce(hierarchy="two_level").build(item, SPEC_FLAT4)
    # builder factored graph_config off host boundaries: single node ->
    # nothing to factor, mesh stays 1-D
    mesh = Mesh(np.array(jax.devices()[:4]), ("replica",))
    with pytest.raises(ValueError, match="replica_dcn"):
        GraphTransformer(s, item, mesh)


def test_auto_resolves_by_mesh_and_default_stays_flat():
    from autodist_tpu.kernel.graph_transformer import GraphTransformer

    item = _item()
    s = AllReduce().build(item, SPEC_FLAT4)  # hierarchy="auto"
    t_flat = GraphTransformer(
        s, item, Mesh(np.array(jax.devices()[:4]), ("replica",)))
    assert t_flat.sync_hierarchy == "flat"
    s2 = AllReduce().build(item, SPEC_2x2)
    t_two = GraphTransformer(
        s2, item, Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                       (AXIS_REPLICA_DCN, AXIS_REPLICA_ICI)))
    assert t_two.sync_hierarchy == "two_level"


def test_powersgd_main_codec_falls_back_flat():
    from autodist_tpu.kernel.graph_transformer import GraphTransformer

    item = _item()
    s = AllReduce(compressor="PowerSGDCompressor",
                  hierarchy="two_level").build(item, SPEC_2x2)
    t = GraphTransformer(
        s, item, Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                      (AXIS_REPLICA_DCN, AXIS_REPLICA_ICI)))
    assert t.sync_hierarchy == "flat"
    assert all(b.hierarchy == _C.FLAT for b in t.buckets)


# -- mesh factoring ----------------------------------------------------------

def test_build_mesh_hierarchy_factors_host_boundaries():
    from autodist_tpu.parallel.mesh import build_mesh, hierarchical_axes

    assert hierarchical_axes(SPEC_2NODE, 8) == {
        AXIS_REPLICA_DCN: 2, AXIS_REPLICA_ICI: 4}
    mesh = build_mesh(SPEC_2NODE, hierarchy=True,
                      devices=jax.devices()[:8])
    assert mesh.axis_names == (AXIS_REPLICA_DCN, AXIS_REPLICA_ICI)
    assert dict(mesh.shape) == {AXIS_REPLICA_DCN: 2, AXIS_REPLICA_ICI: 4}
    # single node: nothing to factor
    assert hierarchical_axes(SPEC_FLAT4, 4) == {"replica": 4}
    flat = build_mesh(SPEC_FLAT4, hierarchy=True, devices=jax.devices()[:4])
    assert flat.axis_names == ("replica",)
    # the YAML mesh: request overrides the automatic factorization
    mesh22 = build_mesh(SPEC_2x2, devices=jax.devices()[:4])
    assert dict(mesh22.shape) == {AXIS_REPLICA_DCN: 2, AXIS_REPLICA_ICI: 2}


def test_two_level_builder_writes_factored_graph_mesh():
    item = _item()
    s = AllReduce(hierarchy="two_level").build(item, SPEC_2NODE)
    assert list(s.graph_config.mesh.axis_names) == [AXIS_REPLICA_DCN,
                                                    AXIS_REPLICA_ICI]
    assert list(s.graph_config.mesh.axis_sizes) == [2, 4]
    # flat/auto builders keep the 1-D mesh
    s0 = AllReduce().build(item, SPEC_2NODE)
    assert list(s0.graph_config.mesh.axis_names) == ["replica"]


# -- tuple-axis collective helpers (satellite) -------------------------------

def test_collective_helpers_accept_axis_tuples():
    from autodist_tpu.parallel import collectives as coll

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
    x = np.arange(32, dtype=np.float32).reshape(4, 8)

    def body(xs):
        v = xs[0]                                   # (8,) per device
        return (coll.all_reduce_mean(v, ("a", "b")),
                coll.all_reduce_sum(v, ["a", "b"]),
                coll.all_gather(coll.reduce_scatter(v, ("a", "b")),
                                ("a", "b")),
                coll.reduce_scatter(v, ("a",)),     # 1-tuple == bare name
                coll.axis_size(("a", "b")))

    mean, total, rt, rs1, size = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(("a", "b")),
        out_specs=(P(), P(), P(), P("a"), P()), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(mean), x.mean(axis=0))
    np.testing.assert_allclose(np.asarray(total), x.sum(axis=0))
    # reduce_scatter then all_gather over the same tuple round-trips the
    # cross-device sum
    np.testing.assert_allclose(np.asarray(rt), x.sum(axis=0))
    assert int(np.asarray(size)) == 4
    assert np.asarray(rs1).shape == (8,)  # scattered over "a" only


# -- kernel-level equivalence ------------------------------------------------

_SHAPES = {"a": (33,), "b": (17, 3), "c": (41,), "d": (8, 8)}


def _hier_buckets(comp_enum, hierarchy, dcn=0):
    dtypes = {n: np.dtype(np.float32) for n in _SHAPES}
    plans = {}
    for i, name in enumerate(sorted(_SHAPES)):
        plans[name] = part.VarPlan(
            name=name, shape=_SHAPES[name], dtype=np.float32,
            placement=part.Placement.REPLICATED,
            sync=part.SyncKind.ALL_REDUCE,
            group=i // 2, compressor=comp_enum, hierarchy=hierarchy,
            dcn_compressor=dcn)
    return ar.plan_buckets(plans, _SHAPES, dtypes)


def _run_sync(buckets, sync_fn, **kw):
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                (AXIS_REPLICA_DCN, AXIS_REPLICA_ICI))
    axis = (AXIS_REPLICA_DCN, AXIS_REPLICA_ICI)
    r = np.random.RandomState(0)
    gstack = {n: r.randn(4, int(np.prod(s))).astype(np.float32)
              for n, s in _SHAPES.items()}

    def body(gs):
        g1 = {n: gs[n][0].reshape(_SHAPES[n]) for n in _SHAPES}
        g2 = {n: (gs[n][0] * 1.7 - 0.3).reshape(_SHAPES[n]) for n in _SHAPES}
        states = ar.init_compressor_states(buckets)
        s1, states = sync_fn(g1, buckets, states, axis, **kw)
        s2, _ = sync_fn(g2, buckets, states, axis, **kw)
        return s1, s2

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P((AXIS_REPLICA_DCN, AXIS_REPLICA_ICI)),
        out_specs=P(), check_vma=False))(gstack)


_HIER = ar.HierAxes(ici=AXIS_REPLICA_ICI, dcn=(AXIS_REPLICA_DCN,))

_CASES = [
    ("NoneCompressor", 0, 1e-6),
    ("BF16Compressor", 0, 2e-2),
    ("BF16CompressorEF", 0, 2e-2),
    ("Int8Compressor", 0, 5e-2),
    # DCN-hop override: uncompressed bucket, int8 only on the slow wire
    ("NoneCompressor", _C.Int8Compressor, 5e-2),
    ("NoneCompressor", _C.BF16Compressor, 2e-2),
]


@pytest.mark.parametrize("comp,dcn,tol", _CASES)
def test_sync_hierarchical_matches_flat(comp, dcn, tol):
    """Two consecutive two-level steps (state threading included) match
    the flat barrier sync within the DCN-hop codec's rounding."""
    comp_enum = getattr(_C, comp)
    flat = _run_sync(_hier_buckets(comp_enum, _C.FLAT), ar.sync_bucketed)
    two = _run_sync(_hier_buckets(comp_enum, _C.TWO_LEVEL, dcn),
                    ar.sync_hierarchical, hier=_HIER)
    for step in (0, 1):
        for n in _SHAPES:
            np.testing.assert_allclose(
                np.asarray(flat[step][n]), np.asarray(two[step][n]),
                rtol=0, atol=tol, err_msg=f"{comp}/dcn={dcn}/{n}/step{step}")


@pytest.mark.parametrize("comp,dcn,tol", _CASES)
def test_sync_overlapped_hier_matches_flat(comp, dcn, tol):
    """The overlap issue order (chunked, for elementwise wire codecs)
    composes with the hierarchy: still allclose to the flat barrier."""
    comp_enum = getattr(_C, comp)
    flat = _run_sync(_hier_buckets(comp_enum, _C.FLAT), ar.sync_bucketed)
    buckets = _hier_buckets(comp_enum, _C.TWO_LEVEL, dcn)
    kw = {"max_chunk_bytes": 64} if ar.elementwise(buckets[0]) else {}
    two = _run_sync(buckets, ar.sync_overlapped, hier=_HIER, **kw)
    for step in (0, 1):
        for n in _SHAPES:
            np.testing.assert_allclose(
                np.asarray(flat[step][n]), np.asarray(two[step][n]),
                rtol=0, atol=tol, err_msg=f"{comp}/dcn={dcn}/{n}/step{step}")


def test_sync_hierarchical_requires_hier_axes():
    buckets = _hier_buckets(_C.NoneCompressor, _C.TWO_LEVEL)
    with pytest.raises(ValueError, match="replica_dcn"):
        ar.sync_hierarchical({}, buckets, {}, "replica", hier=None)


def test_two_level_wire_codec_and_state():
    """TWO_LEVEL buckets carry the DCN-hop codec's state: a stateless
    bucket with an EF DCN codec gains a residual, an EF bucket with an
    int8 DCN override drops its own."""
    b_gain = _hier_buckets(_C.NoneCompressor, _C.TWO_LEVEL,
                           _C.BF16CompressorEF)
    assert ar.wire_codec(b_gain[0]) == _C.BF16CompressorEF
    st = ar.init_compressor_states(b_gain)
    assert all(s.shape == (b.total,) for b, s in
               zip(b_gain, (st[b.key] for b in b_gain)))
    b_drop = _hier_buckets(_C.BF16CompressorEF, _C.TWO_LEVEL,
                           _C.Int8Compressor)
    assert ar.wire_codec(b_drop[0]) == _C.Int8Compressor
    assert all(s == () for s in ar.init_compressor_states(b_drop).values())
    # elementwise() (chunking / in-scan eligibility) demands the WIRE
    # codec be elementwise too: an int8 DCN hop must not chunk — per-chunk
    # re-blocking would change the approximation vs the barrier
    assert ar.elementwise(b_gain[0])      # none bucket, bf16_ef wire: OK
    assert ar.elementwise(_hier_buckets(_C.BF16Compressor,
                                        _C.TWO_LEVEL)[0])
    assert not ar.elementwise(_hier_buckets(_C.NoneCompressor,
                                            _C.TWO_LEVEL,
                                            _C.Int8Compressor)[0])


# -- engine-level equivalence (the acceptance matrix) ------------------------

def _train(spec, schedule="barrier", hierarchy="auto",
           compressor="NoneCompressor", dcn=None, accum=1, steps=2):
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
        dcn_compressor=dcn))
    sess = ad.distribute(loss, params, optax.sgd(0.1), accum_steps=accum)
    for _ in range(steps):
        m = sess.run(batch)
    return sess.params(), float(m["loss"]), sess._t


_ELEMENTWISE = [("NoneCompressor", 1e-5), ("BF16Compressor", 2e-2),
                ("BF16CompressorEF", 2e-2)]


@pytest.mark.parametrize("schedule", ["barrier", "overlap"])
@pytest.mark.parametrize("comp,tol", _ELEMENTWISE)
def test_engine_two_level_matches_flat(schedule, comp, tol):
    """Acceptance: every elementwise codec, TWO_LEVEL on the factored
    2x2 mesh == FLAT on the 1-D mesh, both schedules."""
    pf, lf, _ = _train(SPEC_FLAT4, schedule=schedule, compressor=comp)
    ph, lh, t = _train(SPEC_2x2, schedule=schedule, hierarchy="two_level",
                       compressor=comp)
    assert t.sync_hierarchy == "two_level"
    assert t.sync_schedule == schedule
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=tol),
                 pf, ph)
    assert abs(lf - lh) < max(tol, 1e-4)


@pytest.mark.parametrize("schedule", ["barrier", "overlap"])
def test_engine_two_level_matches_flat_under_accum(schedule):
    """Acceptance: grad accumulation (the in-scan overlap path included)
    preserves the equivalence."""
    pf, _, _ = _train(SPEC_FLAT4, schedule=schedule, accum=4)
    ph, _, t = _train(SPEC_2x2, schedule=schedule, hierarchy="two_level",
                      accum=4)
    assert t.sync_hierarchy == "two_level"
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 pf, ph)


def test_engine_two_level_stateful_dcn_codec_in_scan():
    """bf16+error-feedback as the DCN-hop codec, through the in-scan
    overlap path: the per-shard residual (dynamic-sliced at ICI-index
    offsets) threads the scan carry and stays allclose to the flat EF
    run."""
    pf, _, _ = _train(SPEC_FLAT4, schedule="overlap",
                      compressor="BF16CompressorEF", accum=2)
    ph, _, t = _train(SPEC_2x2, schedule="overlap", hierarchy="two_level",
                      compressor="BF16CompressorEF", accum=2)
    assert t.sync_hierarchy == "two_level"
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=5e-3),
                 pf, ph)


def test_engine_two_level_with_dcn_compression():
    """DCN-hop wire compression (int8 on the cross-slice hop only) stays
    allclose to the uncompressed flat baseline."""
    pf, _, _ = _train(SPEC_FLAT4)
    ph, _, t = _train(SPEC_2x2, hierarchy="two_level",
                      dcn=_C.Int8Compressor)
    assert t.sync_hierarchy == "two_level"
    hs = t.hierarchy_summary()
    assert hs["dcn_compressors"] == ["int8"]
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=5e-2),
                 pf, ph)


def test_engine_flat_on_factored_mesh_is_flat_sync():
    """hierarchy="flat" pins the one-collective schedule even on a
    factored mesh — and still trains identically (tuple-axis pmean)."""
    pf, _, _ = _train(SPEC_FLAT4)
    p2, _, t = _train(SPEC_2x2, hierarchy="flat")
    assert t.sync_hierarchy == "flat"
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 pf, p2)


# -- cost model + AutoStrategy (acceptance) ----------------------------------

def _gpt_class_item():
    """A DCN-bottlenecked dense model: ~8M params, trivial compute."""
    r = np.random.RandomState(0)
    params = {"emb": jnp.asarray(r.randn(4096, 512), jnp.float32),
              "w1": jnp.asarray(r.randn(1024, 1024), jnp.float32),
              "w2": jnp.asarray(r.randn(1024, 1024), jnp.float32),
              "head": jnp.asarray(r.randn(512, 4096), jnp.float32)}
    return ModelItem(lambda p, b: 0.0, params)


def test_two_level_prices_strictly_cheaper_on_multi_node():
    from autodist_tpu.simulator.cost_model import estimate

    item = _gpt_class_item()
    flat = estimate(AllReduce(hierarchy="flat").build(item, SPEC_2NODE),
                    item, SPEC_2NODE, flops_per_example=1e9)
    two = estimate(AllReduce(hierarchy="two_level").build(item, SPEC_2NODE),
                   item, SPEC_2NODE, flops_per_example=1e9)
    assert two.total_s < flat.total_s
    assert two.comm_s < flat.comm_s
    bd = two.breakdown
    assert bd["hier_replica_dcn"] == 2 and bd["hier_replica_ici"] == 4
    assert bd["hier_ici_s"] > 0 and bd["hier_dcn_s"] > 0
    assert bd["ar_bytes"] == 0  # everything moved to the two-hop terms
    # the DCN ring carries only the 1/R_ici shard
    assert bd["hier_dcn_bytes"] == pytest.approx(bd["hier_ici_bytes"] / 8)
    # DCN-hop compression shrinks only the DCN term
    two_c = estimate(
        AllReduce(hierarchy="two_level",
                  dcn_compressor="BF16Compressor").build(item, SPEC_2NODE),
        item, SPEC_2NODE, flops_per_example=1e9)
    assert two_c.breakdown["hier_dcn_bytes"] == pytest.approx(
        bd["hier_dcn_bytes"] / 2)
    assert two_c.breakdown["hier_ici_bytes"] == bd["hier_ici_bytes"]
    assert two_c.comm_s < two.comm_s
    # single-node spec: no factorization declared -> flat pricing
    single = estimate(AllReduce().build(item, SPEC_FLAT4), item, SPEC_FLAT4)
    assert single.breakdown["hier_ici_bytes"] == 0


def test_auto_strategy_selects_two_level_on_multi_node():
    """Acceptance: AutoStrategy enumerates TWO_LEVEL candidates on a
    multi-node spec and ranks one first for a DCN-bottlenecked model."""
    from autodist_tpu.strategy.auto_strategy import (AutoStrategy,
                                                     default_candidates)

    assert not any(
        getattr(b, "hierarchy", "auto") == "two_level"
        for b in default_candidates(SPEC_FLAT4))
    cands = default_candidates(SPEC_2NODE)
    assert any(getattr(b, "hierarchy", None) == "two_level" for b in cands)

    item = _gpt_class_item()
    auto = AutoStrategy(flops_per_example=1e9)
    s = auto.build(item, SPEC_2NODE)
    winner = auto.last_ranking[0][0]
    assert "AllReduce" in winner or "Parallax" in winner
    # the built strategy really is two-level: factored mesh + proto knob
    assert AXIS_REPLICA_DCN in list(s.graph_config.mesh.axis_names)
    assert any(
        n.AllReduceSynchronizer.hierarchy == _C.TWO_LEVEL
        for n in s.node_config
        if n.WhichOneof("synchronizer") == "AllReduceSynchronizer")


# -- analysis pass (acceptance) ----------------------------------------------

def test_analysis_rejects_powersgd_dcn_compressor():
    from autodist_tpu.analysis import verify_strategy

    item = _item()
    s = AllReduce(hierarchy="two_level").build(item, SPEC_2x2)
    for n in s.node_config:
        n.AllReduceSynchronizer.dcn_compressor = _C.PowerSGDCompressor
    report = verify_strategy(s, item, SPEC_2x2, passes=("hierarchy",))
    assert not report.ok
    assert "Y001" in report.error_codes()


def test_analysis_rejects_bad_subaxis_factorization():
    from autodist_tpu.analysis import verify_strategy

    item = _item()
    s = AllReduce(hierarchy="two_level").build(item, SPEC_2x2)
    # corrupt the factorization: 2 x 3 != 4 devices
    s.graph_config.mesh.axis_sizes[:] = [2, 3]
    report = verify_strategy(s, item, SPEC_2x2, passes=("hierarchy",))
    assert not report.ok
    assert "Y003" in report.error_codes()


def test_analysis_rejects_two_level_without_subaxes():
    from autodist_tpu.analysis import verify_strategy

    item = _item()
    s = AllReduce(hierarchy="two_level").build(item, SPEC_2x2)
    s.graph_config.mesh.axis_names[:] = ["replica"]
    s.graph_config.mesh.axis_sizes[:] = [4]
    report = verify_strategy(s, item, SPEC_2x2, mesh=None,
                             passes=("hierarchy",))
    assert "Y002" in report.error_codes()


def test_analysis_clean_two_level_verifies_end_to_end():
    """The full pass chain (static + traced) on a real two-level strategy
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
    s = AllReduce(hierarchy="two_level",
                  dcn_compressor="BF16Compressor").build(item, SPEC_2x2)
    report = verify_strategy(
        s, item, SPEC_2x2, batch_shapes={"x": ((8, 4), "float32")},
        hbm_bytes_per_device=16 << 30)
    assert report.ok, [str(f) for f in report.errors]
    assert any(f.code == "Y006" for f in report.findings)


def test_engine_rejects_powersgd_dcn_compressor():
    from autodist_tpu.kernel.graph_transformer import GraphTransformer

    item = _item()
    s = AllReduce(hierarchy="two_level").build(item, SPEC_2x2)
    for n in s.node_config:
        n.AllReduceSynchronizer.dcn_compressor = _C.PowerSGDCompressor
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                (AXIS_REPLICA_DCN, AXIS_REPLICA_ICI))
    with pytest.raises(ValueError, match="DCN-hop"):
        GraphTransformer(s, item, mesh)


# -- telemetry records the chosen hierarchy + per-hop bytes ------------------

def test_telemetry_records_hierarchy_and_per_hop_bytes(tmp_path):
    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.telemetry import load_manifest
    from autodist_tpu.telemetry.session import SessionTelemetry

    r = np.random.RandomState(0)
    params = {"w": jnp.asarray(r.randn(32, 8), jnp.float32)}
    batch = {"x": r.randn(16, 32).astype(np.float32)}
    ad = AutoDist(resource_spec=SPEC_2x2, strategy_builder=AllReduce(
        hierarchy="two_level", dcn_compressor="BF16Compressor"))
    sess = ad.distribute(lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2),
                         params, optax.sgd(0.1))
    tel = SessionTelemetry(sess._t, run_dir=str(tmp_path))
    sess._telemetry = tel
    for _ in range(2):
        sess.run(batch)
    sess.finalize_telemetry()
    records = load_manifest(str(tmp_path))
    meta = next(rec for rec in records if rec.get("kind") == "meta")
    hier = meta["hierarchy"]
    assert hier["mode"] == "two_level"
    assert hier["replica_dcn"] == 2 and hier["replica_ici"] == 2
    assert hier["dcn_compressors"] == ["bf16"]
    # DCN hop = 1/R_ici of one phase's volume, bf16-halved
    assert hier["dcn_hop_bytes"] == pytest.approx(
        hier["ici_hop_bytes"] / 2 / 2 * 0.5)
    # the report surfaces it (predicted per-hop next to measured walls)
    import tools.telemetry_report as tr

    summary = tr.summarize_manifest(records)
    assert summary["hierarchy"]["mode"] == "two_level"
    rendered = tr.render(summary)
    assert "sync hierarchy: two_level" in rendered
    # per-hop gauges landed in the registry aggregates
    gauges = next(rec for rec in records
                  if rec.get("kind") == "summary")["aggregates"]["gauges"]
    assert "sync.dcn_hop_bytes" in gauges and "sync.ici_hop_bytes" in gauges


# -- Parallax inherits the knob ---------------------------------------------

def test_parallax_two_level_builds_factored():
    item = _item()
    s = Parallax(hierarchy="two_level").build(item, SPEC_2NODE)
    assert AXIS_REPLICA_DCN in list(s.graph_config.mesh.axis_names)
    ar_nodes = [n for n in s.node_config
                if n.WhichOneof("synchronizer") == "AllReduceSynchronizer"]
    assert ar_nodes
    assert all(n.AllReduceSynchronizer.hierarchy == _C.TWO_LEVEL
               for n in ar_nodes)
