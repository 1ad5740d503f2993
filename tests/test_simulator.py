"""Cost-model + AutoStrategy tests."""
import jax.numpy as jnp
import numpy as np

from autodist_tpu.model_item import ModelItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.simulator.cost_model import CostEstimate, estimate, rank_strategies
from autodist_tpu.strategy import AllReduce, Parallax, PS
from autodist_tpu.strategy.auto_strategy import AutoStrategy


def _item(sparse=False):
    params = {"emb": jnp.zeros((10000, 64)), "w": jnp.zeros((64, 64))}
    return ModelItem(lambda p, b: 0.0, params,
                     sparse_vars=["emb"] if sparse else None)


SPEC8 = ResourceSpec(resource_info={
    "nodes": [{"address": "localhost", "chips": list(range(8))}]})


def test_estimate_single_chip_no_comm():
    spec1 = ResourceSpec.from_num_chips(1)
    est = estimate(AllReduce().build(_item(), spec1), _item(), spec1)
    assert est.comm_s == 0.0


def test_compressed_allreduce_cheaper():
    item = _item()
    full = estimate(AllReduce().build(item, SPEC8), item, SPEC8)
    comp = estimate(AllReduce(compressor="BF16Compressor").build(item, SPEC8),
                    item, SPEC8)
    assert comp.comm_s < full.comm_s


def test_sparse_routing_cheaper_for_embeddings():
    """Parallax (sparse rows all-gathered) should beat pure AllReduce
    (dense table reduced) when the table dwarfs the touched rows."""
    item = _item(sparse=True)
    dense_item = _item(False)
    ar_dense = estimate(AllReduce().build(dense_item, SPEC8), dense_item, SPEC8)
    px = estimate(Parallax().build(item, SPEC8), item, SPEC8)
    assert px.breakdown["sparse_bytes"] < ar_dense.breakdown["ar_bytes"]


def test_rank_strategies_orders_by_cost():
    item = _item(sparse=True)
    ranking = rank_strategies([AllReduce(), Parallax(), PS()], item, SPEC8)
    costs = [c for c, *_ in ranking]
    assert costs == sorted(costs)


def test_auto_strategy_builds_winner():
    item = _item(sparse=True)
    auto = AutoStrategy()
    s = auto.build(item, SPEC8)
    assert len(s.node_config) == 2
    assert auto.last_ranking and len(auto.last_ranking) >= 5
    # embedding-heavy model: winner must route the sparse var off dense AR
    assert np.isfinite(auto.last_ranking[0][1])


def test_total_overlap_model():
    e = CostEstimate(compute_s=1.0, comm_s=0.5, breakdown={})
    assert 1.0 < e.total_s < 1.5


def test_calibration_recovers_coefficients():
    """calibrate() fits measured ~= a*compute + b*comm + c and
    calibrated_total applies it (AutoSync loop: measurements ground the
    analytic model)."""
    from autodist_tpu.simulator.cost_model import CostEstimate, calibrate

    ests = [CostEstimate(compute_s=c, comm_s=m, breakdown={})
            for c, m in [(1.0, 0.1), (1.0, 0.5), (2.0, 0.2), (3.0, 1.0)]]
    a, b, c0 = 2.0, 5.0, 0.01
    pairs = [(e, a * e.compute_s + b * e.comm_s + c0) for e in ests]
    cal = calibrate(pairs)
    assert abs(cal["compute_scale"] - a) < 1e-6
    assert abs(cal["comm_scale"] - b) < 1e-6
    assert abs(cal["overhead_s"] - c0) < 1e-6
    got = ests[0].calibrated_total(cal)
    assert abs(got - pairs[0][1]) < 1e-9


def test_calibration_degenerate():
    from autodist_tpu.simulator.cost_model import calibrate

    cal = calibrate([])
    assert cal == {"compute_scale": 1.0, "comm_scale": 1.0, "overhead_s": 0.0}


def test_update_phase_separates_dense_strategies():
    """Ring-AR and RS+AG wire volumes are identical by construction (that
    equivalence IS the engine's PS realization), so the optimizer-update
    term — full params per chip when replicated, 1/R when weight-update
    sharded — is what ranks the dense strategies.  PartitionedPS must
    price strictly below AllReduce on a multi-chip mesh, and the two must
    no longer tie."""
    from autodist_tpu.strategy import PartitionedPS

    item = _item()
    ar = estimate(AllReduce().build(item, SPEC8), item, SPEC8)
    pps = estimate(PartitionedPS(max_shards=8).build(item, SPEC8),
                   item, SPEC8)
    assert ar.breakdown["update_s"] > pps.breakdown["update_s"]
    assert pps.total_s < ar.total_s
    # comm volumes genuinely tie; the separation is the update phase
    assert abs(ar.comm_s - pps.comm_s) / max(ar.comm_s, 1e-30) < 0.2


def test_record_measure_calibrate_rank_pipeline(tmp_path):
    """The full AutoSync loop on the CPU mesh: measure real sessions under three strategies,
    dump/load RuntimeRecords (backend-labeled), fit a calibration from
    the (estimate, measured) pairs, and rank with it — every stage of
    the record→calibrate→rank pipeline exercised end-to-end.  The
    committed ``records/cpu_mesh/`` artifacts are the script-level run
    of this same pipeline (examples/benchmark.py --strategies)."""
    import optax

    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.simulator.cost_model import (RuntimeRecord, calibrate,
                                                   measure_and_record)

    r = np.random.RandomState(0)
    params = {"emb": jnp.asarray(r.randn(512, 16), jnp.float32),
              "w": jnp.asarray(r.randn(16, 8), jnp.float32)}

    def loss(p, b):
        h = p["emb"][b["ids"]] @ p["w"]
        return jnp.mean(h ** 2)

    batch = {"ids": r.randint(0, 512, (16,))}
    pairs, measured = [], {}
    for builder_cls in (AllReduce, PS, Parallax):
        item = ModelItem(loss, params, optimizer=optax.sgd(0.01),
                         sparse_vars=["emb"])
        ad = AutoDist(resource_spec=SPEC8, strategy_builder=builder_cls())
        sess = ad.distribute(loss, params, optax.sgd(0.01),
                             sparse_vars=["emb"])
        # nine steps, not three: with windows of one step each a single
        # slow step on a busy CPU pushes the fit below the raw estimate
        # (seen once in a whole tier-1 run, never when run alone)
        rec = measure_and_record(sess, sess._shard_batch(batch), steps=9,
                                 warmup=2)
        assert rec.backend == "cpu"           # labeled, never a hw claim
        path = rec.dump(str(tmp_path / f"{builder_cls.__name__}.json"))
        loaded = RuntimeRecord.load(path)
        assert loaded.backend == "cpu"
        assert loaded.step_time_s == rec.step_time_s
        assert loaded.strategy_pb == rec.strategy_pb
        est = estimate(sess._t.strategy, item, SPEC8)
        pairs.append((est, rec.step_time_s))
        measured[builder_cls.__name__] = rec.step_time_s
    cal = calibrate(pairs)
    assert set(cal) == {"compute_scale", "comm_scale", "overhead_s"}
    assert all(v >= 0.0 for v in cal.values())
    # the calibrated model must reproduce the measured times better than
    # (or as well as) the raw analytic estimate on its own training set
    raw_err = sum(abs(e.total_s - m) for e, m in pairs)
    cal_err = sum(abs(e.calibrated_total(cal) - m) for e, m in pairs)
    assert cal_err <= raw_err + 1e-9
    # and ranking with the calibration runs end-to-end
    order = rank_strategies([AllReduce(), PS(), Parallax()],
                            _item(sparse=True), SPEC8, calibration=cal)
    assert len(order) == 3


def test_committed_cpu_records_load_and_are_labeled():
    """The committed records/cpu_mesh artifacts stay loadable and
    cpu-labeled (the dataset-consumption path of the AutoSync analog)."""
    import glob
    import json
    import os

    root = os.path.join(os.path.dirname(__file__), "..", "records",
                        "cpu_mesh")
    from autodist_tpu.simulator.cost_model import RuntimeRecord

    def _is_runtime_record(p):
        # sweep dirs also hold non-RuntimeRecord artifacts (the serving
        # decode record perf_gate owns)
        with open(p) as f:
            return {"model_def", "strategy"} <= set(json.load(f))

    recs = [p for p in glob.glob(os.path.join(root, "*.json"))
            if not p.endswith("summary.json") and _is_runtime_record(p)]
    assert len(recs) >= 3
    for p in recs:
        rec = RuntimeRecord.load(p)
        assert rec.backend == "cpu"
        assert rec.step_time_s > 0
        assert len(rec.strategy_pb) > 0 and len(rec.model_def) > 0
    with open(os.path.join(root, "gpt_tiny_summary.json")) as f:
        s = json.load(f)
    assert s["backend"] == "cpu"
    assert set(s["measured_rank"]) == set(s["estimated_rank"])


def test_committed_v5e_aot_sweep_loads():
    """The committed v5e AOT sweep (records/v5e_aot/summary.json — model x
    strategy compiled by the real TPU toolchain, tools/aot_sweep.py) stays
    well-formed: every strategy entry carries XLA stats + a roofline
    prediction, and the per-model ranking covers all four strategies."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "records",
                        "v5e_aot", "summary.json")
    with open(path) as f:
        d = json.load(f)
    assert d["n_devices"] >= 4
    assert "not an on-chip measurement" in d["method"]
    for model, v in d["models"].items():
        assert set(v["predicted_rank"]) == {"AllReduce", "PS",
                                            "PartitionedPS", "Parallax"}
        for sname, st in v["strategies"].items():
            assert st["xla_flops"] > 0, (model, sname)
            assert st["step_pred_s"] > 0
            assert st["analytic_comm_s"] >= 0


def test_committed_v5e_capacity_proof_loads():
    """The committed HBM capacity proof (records/v5e_aot/capacity.json):
    both headline bench configs compiled full-size for v5e and fitting
    the 16 GiB budget."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "records",
                        "v5e_aot", "capacity.json")
    with open(path) as f:
        d = json.load(f)
    assert d["ok"] is True
    assert set(d["configs"]) == {"gpt_small_s1024_b8_flash_streaming_remat",
                                 "resnet50_224_b256_bf16",
                                 "gpt_small_s8192_b2_ring_seq4"}
    for name, c in d["configs"].items():
        assert c["ok"] and c["fits_hbm"], (name, c)
        assert 0 < c["demand_bytes"] <= d["hbm_bytes"]


def test_auto_strategy_with_calibration_file(tmp_path):
    """AutoStrategy loads a sweep summary JSON and ranks with the
    measured-grounded coefficients."""
    import json

    from autodist_tpu.strategy.auto_strategy import AutoStrategy

    summary = {"calibration": {"compute_scale": 2.0, "comm_scale": 4.0,
                               "overhead_s": 0.001}}
    path = tmp_path / "summary.json"
    path.write_text(json.dumps(summary))
    item = _item(sparse=True)
    auto = AutoStrategy(calibration=str(path))
    s = auto.build(item, SPEC8)
    assert len(s.node_config) == 2
    assert auto.last_ranking
    # calibrated totals include the fixed overhead term
    assert all(c >= 0.001 for _, c in auto.last_ranking)
