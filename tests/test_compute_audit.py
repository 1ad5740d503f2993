"""HLO compute auditor (autodist_tpu/analysis/compute_audit.py).

Covers the compute-op extractor (golden-file pins on a conv fusion and a
remat-duplicated dot inside a scan body + live-lowering drift checks),
the single-source FLOP rules in the cost model, the F-code auditor unit
level, the lowered donation check (F004), the jaxpr-vs-HLO FLOP
reconciliation contract over the recorded sweep, the seeded recompute /
dropped-donation cases, the engine verify gates, the AutoStrategy
predicted-MFU-ceiling export, and the AD03 lint rule.

Also covers the HBM byte view: the traffic extractor + hbm_traffic pins
on the conv-fusion fixture (F007 table), the memory-bound flip (F008)
in both directions plus its absolute-bytes floor, the roofline
reconciliation against the measured v5e ResNet-50 step, the
``predicted_mfu_ceiling(hbm_bytes=...)`` roofline clamp, the
F008 -> fused-norm remediation knob, the committed GPT roofline-lever
record, and the AD13 byte-arithmetic lint rule.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P  # noqa: F401

from autodist_tpu.analysis import (LOWERED_PASSES, STATIC_PASSES,
                                   TRACE_PASSES, Severity, verify_strategy)
from autodist_tpu.analysis.cases import (EXPECTED_DONATION_CODE,
                                         EXPECTED_RECOMPUTE_CODE,
                                         build_dropped_donation_case,
                                         build_recompute_case)
from autodist_tpu.analysis.compute_audit import (FLOPS_ABS_SLACK, FLOPS_TOL,
                                                 RECOMPUTE_MIN_FLOPS,
                                                 ComputeOp, audit_compute,
                                                 audit_donation,
                                                 extract_compute_ops,
                                                 parse_main_signature)
from autodist_tpu.model_item import ModelItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.simulator.cost_model import (DEFAULT_MXU_EFF, conv_flops,
                                               dot_flops, elementwise_flops,
                                               predicted_mfu_ceiling)
from autodist_tpu.strategy import AllReduce

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "data", "hlo")

ALL_PASSES = STATIC_PASSES + TRACE_PASSES + LOWERED_PASSES
SPEC8 = ResourceSpec(resource_info={
    "nodes": [{"address": "localhost", "chips": list(range(8))}]})


def _fixture(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


def _codes(findings):
    return [f.code for f in findings]


# -- single-source FLOP rules (cost_model) ----------------------------------


def test_flop_rules_are_single_sourced():
    assert dot_flops([4, 16], 16) == 2 * 4 * 16 * 16
    assert dot_flops([7], 0) == 2 * 7          # contraction floor of 1
    assert conv_flops([2, 8, 8, 16], 3, [3, 3]) == 2 * 2048 * 3 * 9
    assert elementwise_flops([8, 32]) == 256


def test_predicted_mfu_ceiling_discounts_lowering_overhead():
    # 2x realized work halves the ceiling; never above the raw efficiency
    assert predicted_mfu_ceiling(1e6, 2e6) == pytest.approx(
        DEFAULT_MXU_EFF / 2)
    assert predicted_mfu_ceiling(1e6, 1e6) == pytest.approx(DEFAULT_MXU_EFF)
    assert predicted_mfu_ceiling(2e6, 1e6) == pytest.approx(DEFAULT_MXU_EFF)
    # no contraction work (the records sweep) -> the raw efficiency
    assert predicted_mfu_ceiling(0.0, 0.0) == pytest.approx(DEFAULT_MXU_EFF)
    assert predicted_mfu_ceiling(None, 1e6) == pytest.approx(DEFAULT_MXU_EFF)


# -- extractor: golden-file pins --------------------------------------------


def test_extract_conv_fixture():
    """Golden pin: a NHWC conv fusion (conv + bias + relu).  The conv's
    FLOPs follow the conv rule off the ``dim_numbers`` rhs spec (the 'i'
    dim is per-group in_channels); the bias/relu ride as elementwise."""
    ops = extract_compute_ops(_fixture("conv_fusion.stablehlo.txt"))
    (conv,) = [o for o in ops if o.is_contraction]
    assert conv.kind == "convolution"
    assert conv.flops == conv_flops([2, 8, 8, 16], 3, [3, 3])
    assert conv.count == 1.0 and not conv.in_loop
    assert conv.region == "fwd"
    assert "(2x8x8x3xf32, 3x3x3x16xf32) -> 2x8x8x16xf32" in conv.signature
    elementwise = [o for o in ops if not o.is_contraction]
    assert len(elementwise) == 2               # bias add + relu maximum
    assert all(o.flops == 2 * 8 * 8 * 16 for o in elementwise)


def test_extract_remat_scan_dot_fixture():
    """Golden pin: grad of a scan whose remat'd body dot is re-run in the
    backward — three textually identical dot signatures (fwd, recompute,
    dx transpose), each carried with the loop's static trip count."""
    ops = extract_compute_ops(_fixture("remat_scan_dot.stablehlo.txt"))
    dots = [o for o in ops if o.is_contraction]
    assert len(dots) == 3
    assert len({o.signature for o in dots}) == 1   # identical signatures
    for o in dots:
        assert o.flops == dot_flops([4, 16], 16)
        assert o.count == 3.0 and o.in_loop
        assert o.region == "in-scan"


def test_extract_live_conv_matches_golden_shape():
    """Drift check: a fresh lowering of the fixture's conv program parses
    to the same contraction (jax upgrades changing the textual format
    break HERE, not in some downstream audit)."""
    def convy(x, k, b):
        y = jax.lax.conv_general_dilated(
            x, k, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + b)

    txt = jax.jit(convy).trace(
        jax.ShapeDtypeStruct((2, 8, 8, 3), "float32"),
        jax.ShapeDtypeStruct((3, 3, 3, 16), "float32"),
        jax.ShapeDtypeStruct((16,), "float32")).lower().as_text()
    live = [(o.kind, o.flops, o.count, o.in_loop)
            for o in extract_compute_ops(txt) if o.is_contraction]
    gold = [(o.kind, o.flops, o.count, o.in_loop)
            for o in extract_compute_ops(
                _fixture("conv_fusion.stablehlo.txt")) if o.is_contraction]
    assert live == gold


def test_extract_live_remat_scan_matches_golden_shape():
    def scan_remat(x, w):
        @jax.checkpoint
        def layer(c):
            return jnp.tanh(c @ w)

        def body(c, _):
            c = layer(c)
            return c, jnp.sum(c)
        c, ys = jax.lax.scan(body, x, None, length=3)
        return jnp.sum(c) + jnp.sum(ys)

    txt = jax.jit(jax.grad(scan_remat)).trace(
        jax.ShapeDtypeStruct((4, 16), "float32"),
        jax.ShapeDtypeStruct((16, 16), "float32")).lower().as_text()
    live = sorted((o.kind, o.flops, o.count, o.in_loop)
                  for o in extract_compute_ops(txt) if o.is_contraction)
    gold = sorted((o.kind, o.flops, o.count, o.in_loop)
                  for o in extract_compute_ops(
                      _fixture("remat_scan_dot.stablehlo.txt"))
                  if o.is_contraction)
    assert live == gold


# -- the auditor (F-codes), unit level --------------------------------------


def _cop(flops, kind="dot_general", dtype="bf16", sig="dot A", count=1.0,
         **kw):
    return ComputeOp(kind=kind, flops=flops, dtype=dtype, signature=sig,
                     shape_key=sig, count=count, **kw)


def test_clean_table_is_only_f006():
    findings = audit_compute([_cop(1e6)], model_flops=1e6)
    assert _codes(findings) == ["F006"]
    assert findings[0].data["flop_ratio"] == pytest.approx(1.0)


def test_f001_realized_beyond_tolerance_is_error():
    findings = audit_compute([_cop(2e6, sig="big")], model_flops=1e6)
    (f1,) = [f for f in findings if f.code == "F001"]
    assert f1.severity == Severity.ERROR
    assert "big" in f1.message                 # attribution table
    within = audit_compute([_cop(1e6 * (1 + FLOPS_TOL / 2))],
                           model_flops=1e6)
    assert "F001" not in _codes(within)


def test_f001_abs_slack_protects_elementwise_only_programs():
    # the records sweep's quadratic loss: ~0 contraction FLOPs both sides
    findings = audit_compute([_cop(FLOPS_ABS_SLACK / 2)], model_flops=1.0)
    assert "F001" not in _codes(findings)
    assert "F001" not in _codes(audit_compute([], model_flops=None))


def test_f002_duplicated_signature_fires_above_threshold():
    dup = [_cop(RECOMPUTE_MIN_FLOPS, sig="same", out_bytes=1024.0),
           _cop(RECOMPUTE_MIN_FLOPS, sig="same", out_bytes=1024.0)]
    findings = audit_compute(dup, model_flops=None)
    (f2,) = [f for f in findings if f.code == "F002"]
    assert "x2" in f2.message
    (f6,) = [f for f in findings if f.code == "F006"]
    (grp,) = f6.data["recompute"]
    assert grp["multiplicity"] == 2
    assert grp["flops_paid"] == RECOMPUTE_MIN_FLOPS
    assert grp["hbm_saved_bytes"] == 1024.0
    tiny = [_cop(RECOMPUTE_MIN_FLOPS / 4, sig="s"),
            _cop(RECOMPUTE_MIN_FLOPS / 4, sig="s")]
    assert "F002" not in _codes(audit_compute(tiny, model_flops=None))


def test_f003_f32_contractions_warn_bf16_is_clean():
    findings = audit_compute([_cop(1e6, dtype="f32")], model_flops=1e6)
    assert "F003" in _codes(findings)
    assert "F003" not in _codes(
        audit_compute([_cop(1e6, dtype="bf16")], model_flops=1e6))


def test_f005_elementwise_share_needs_some_contraction_work():
    ops = [_cop(1e5), _cop(1e6, kind="add")]
    findings = audit_compute(ops, model_flops=None)
    assert "F005" in _codes(findings)
    # elementwise-ONLY programs (the records sweep) never fire it
    assert "F005" not in _codes(
        audit_compute([_cop(1e6, kind="add")], model_flops=None))


def test_f006_payload_prices_the_mfu_ceiling():
    findings = audit_compute(
        [_cop(2e6, sig="a"), _cop(1e5, kind="add", sig="e")],
        model_flops=1e6)
    (f6,) = [f for f in findings if f.code == "F006"]
    d = f6.data
    assert d["realized_flops"] == 2e6 and d["model_flops"] == 1e6
    assert d["flop_ratio"] == pytest.approx(2.0)
    assert d["per_class"]["dot"] == 2e6
    assert d["per_class"]["elementwise"] == 1e5
    assert d["predicted_mfu_ceiling"] == pytest.approx(DEFAULT_MXU_EFF / 2)
    assert d["n_contractions"] == 1


# -- lowered donation check (F004) ------------------------------------------


def test_parse_main_signature_live_lowering():
    def f(s, x):
        return s + x, jnp.sum(x)

    txt = jax.jit(f, donate_argnums=(0,)).trace(
        jax.ShapeDtypeStruct((8,), "float32"),
        jax.ShapeDtypeStruct((8,), "float32")).lower().as_text()
    args, outs = parse_main_signature(txt)
    assert [ty for ty, _ in args] == ["8xf32", "8xf32"]
    # single-program path pins the alias at lowering
    assert "tf.aliasing_output" in args[0][1]
    assert "8xf32" in outs
    assert audit_donation(args, outs, [True, False]) == []


def test_f004_dropped_donation_attribute():
    args = [("7xf32", ': tensor<7xf32> {mhlo.sharding = "{replicated}"}')]
    (f4,) = audit_donation(args, ["7xf32"], [True])
    assert f4.code == "F004" and f4.severity == Severity.WARNING
    assert "dropped at lowering" in f4.message


def test_f004_deferred_donor_without_type_compatible_output():
    args = [("7xf32", ": tensor<7xf32> {jax.buffer_donor = true}")]
    (f4,) = audit_donation(args, ["7xbf16", "256x256xf32"], [True])
    assert f4.code == "F004" and f4.subject == "7xf32"
    # a matching output type realizes the alias: clean
    assert audit_donation(args, ["7xf32"], [True]) == []
    # undonated args are never checked
    assert audit_donation(args, ["7xbf16"], [False]) == []


# -- end to end: parity, records reconciliation -----------------------------


def _item(shape=(64, 64), **kw):
    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2) + sum(
            jnp.sum(jnp.square(x)) for x in jax.tree.leaves(p))

    return ModelItem(loss, {"w": jnp.zeros(shape)}, optax.adam(1e-3), **kw)


def _batch_shapes(d=64, n=16):
    return {"x": ((n, d), "float32")}


def test_clean_mlp_realized_flops_match_jaxpr_exactly():
    """The reconciliation pin for real contraction work: the HLO-level
    counter and ``jaxpr_flops`` share the same FLOP rules and the same
    remat convention, so on a clean engine step they agree EXACTLY (a
    drift here means one side changed its accounting)."""
    item = _item((128, 128))
    s = AllReduce().build(item, SPEC8)
    report = verify_strategy(s, item, SPEC8, passes=ALL_PASSES,
                             batch_shapes=_batch_shapes(128))
    assert report.ok, str(report)
    (f6,) = [f for f in report.findings if f.code == "F006"]
    assert f6.data["realized_flops"] > 0
    assert f6.data["realized_flops"] == pytest.approx(
        f6.data["model_flops"], rel=1e-6)
    assert f6.data["flop_ratio"] == pytest.approx(1.0, abs=1e-6)


def test_record_sweep_reconciles_against_jaxpr_flops():
    """The acceptance contract over the recorded sweep: every strategy's
    F006 total agrees with ``jaxpr_flops`` within the documented
    tolerance (``FLOPS_TOL`` relative + ``FLOPS_ABS_SLACK`` absolute —
    the synthetic quadratic loss counts ~0 contraction FLOPs on BOTH
    sides) and none trips F001.  A representative strategy per family;
    ``make audit`` sweeps them all."""
    import importlib.util

    path = os.path.join(REPO, "tools", "verify_strategy.py")
    spec = importlib.util.spec_from_file_location("verify_strategy_cli", path)
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)

    for rec in ("bert_tiny_AllReduce.json", "gpt_tiny_PS.json",
                "gpt_tiny_AllReduce_two_level.json",
                "gpt_tiny_AllReduce_sharded_update.json"):
        case = cli._record_case(
            os.path.join(REPO, "records", "cpu_mesh", rec), 16 * 1024 ** 3)
        report = verify_strategy(passes=("compute-audit",), **case)
        assert "F001" not in _codes(report.findings), rec
        (f6,) = [f for f in report.findings if f.code == "F006"]
        model = f6.data["model_flops"] or 0.0
        assert abs(f6.data["realized_flops"] - model) <= \
            model * FLOPS_TOL + FLOPS_ABS_SLACK, rec


# -- seeded cases ------------------------------------------------------------


def test_seeded_recompute_case_is_caught_only_as_f002():
    case = build_recompute_case()
    # the jaxpr tier is blind to remat waste (it counts the recompute as
    # model work) ...
    jaxpr_report = verify_strategy(passes=STATIC_PASSES + TRACE_PASSES,
                                   **case)
    assert jaxpr_report.ok
    assert not jaxpr_report.warnings
    # ... the compute audit attributes it
    report = verify_strategy(passes=ALL_PASSES, **case)
    assert report.ok, str(report)
    warn = {f.code for f in report.findings if int(f.severity) > 0}
    assert warn == {EXPECTED_RECOMPUTE_CODE}
    f2 = report.by_code(EXPECTED_RECOMPUTE_CODE)
    assert f2 and all("recompute" in f.message for f in f2)
    (f6,) = [f for f in report.findings if f.code == "F006"]
    assert f6.data["recompute"]
    # both sides count the remat: no F001, ratio stays ~1
    assert f6.data["flop_ratio"] == pytest.approx(1.0, abs=0.01)


def test_seeded_dropped_donation_case_fires_f004():
    report = verify_strategy(passes=ALL_PASSES,
                             **build_dropped_donation_case())
    assert report.ok, str(report)
    f4 = report.by_code(EXPECTED_DONATION_CODE)
    assert f4 and any("full copy per step" in f.message for f in f4)


# -- engine gates ------------------------------------------------------------


def test_session_verify_surfaces_compute_table_before_first_step():
    from autodist_tpu.autodist import AutoDist

    item = _item((128, 128))
    ad = AutoDist(resource_spec=SPEC8, strategy_builder=AllReduce())
    sess = ad.distribute(item.loss_fn, item.params, optax.adam(1e-3),
                         verify=True)
    report = sess.verify({"x": np.ones((16, 128), np.float32)},
                         raise_on_error=False)
    assert "F006" in _codes(report.findings)
    m = sess.run({"x": np.ones((16, 128), np.float32)})
    assert np.isfinite(float(m["loss"]))


def test_aot_gate_feeds_the_preattached_tpu_lowering():
    """``aot_compile_step(verify=True)`` iterates STATIC+TRACE+LOWERED
    over a context carrying the real TPU lowering in ``lowered_text`` —
    the compute audit must consume THAT text (not re-lower) and stamp
    its table on the context."""
    from autodist_tpu.analysis.compute_audit import compute_audit_pass
    from autodist_tpu.analysis.passes import PASS_REGISTRY
    from autodist_tpu.analysis.verify import AnalysisContext

    assert "compute-audit" in LOWERED_PASSES     # the gate's pass list
    assert PASS_REGISTRY["compute-audit"] is not None
    ctx = AnalysisContext(strategy=None)
    ctx.lowered_text = _fixture("remat_scan_dot.stablehlo.txt")
    ctx.lowered_source = "TPU lowering for v5e:2x2"
    findings = compute_audit_pass(ctx)
    (f6,) = [f for f in findings if f.code == "F006"]
    assert f6.data["source"] == "TPU lowering for v5e:2x2"
    assert f6.data["n_contractions"] == 3
    assert ctx.compute_summary == f6.data


def test_compute_audit_without_lowering_is_f000_info():
    from autodist_tpu.analysis.compute_audit import compute_audit_pass
    from autodist_tpu.analysis.verify import AnalysisContext

    findings = compute_audit_pass(AnalysisContext(strategy=None))
    assert _codes(findings) == ["F000"]
    assert all(f.severity == Severity.INFO for f in findings)


def test_auto_strategy_exports_predicted_mfu_ceiling():
    from autodist_tpu.strategy.auto_strategy import AutoStrategy

    item = _item((128, 128))
    auto = AutoStrategy(audit_batch_shapes=_batch_shapes(128))
    auto.build(item, SPEC8)
    assert auto.last_compute_audit is not None
    assert auto.last_compute_audit["strategy"] == auto.last_ranking[0][0]
    assert 0.0 < auto.last_compute_audit["predicted_mfu_ceiling"] <= \
        auto.last_compute_audit["mxu_eff"]
    assert auto.last_compute_audit["realized_flops"] > 0


# -- AD03 lint rule ----------------------------------------------------------


def _lint_snippet(tmp_path, relpath, source):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint", os.path.join(REPO, "tools", "lint.py"))
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(source)
    return [code for _p, _ln, code, _m in lint.lint_file(p)]


_AD03_BAD = ("import math\n"
             "def layer_flops(x, w):\n"
             "    return 2 * math.prod(x.shape) * w.shape[-1]\n")
_AD03_ASSIGN = "import numpy as np\nflops = 2 * np.prod(x.shape)\n"


def test_ad03_flags_adhoc_flop_arithmetic_in_engine_code(tmp_path):
    assert "AD03" in _lint_snippet(tmp_path, "autodist_tpu/x.py", _AD03_BAD)
    assert "AD03" in _lint_snippet(tmp_path, "tools/y.py", _AD03_ASSIGN)


def test_ad03_exempts_cost_model_tests_and_non_flop_products(tmp_path):
    assert "AD03" not in _lint_snippet(
        tmp_path, "autodist_tpu/simulator/cost_model.py", _AD03_BAD)
    assert "AD03" not in _lint_snippet(tmp_path, "tests/t.py", _AD03_BAD)
    # a shape product NOT named flops (e.g. byte sizing) is fine
    ok = "import math\nnbytes = 4 * math.prod(x.shape)\n"
    assert "AD03" not in _lint_snippet(tmp_path, "autodist_tpu/ok.py", ok)
    # a flops computation routed through cost_model carries no prod call
    routed = ("from autodist_tpu.simulator.cost_model import dot_flops\n"
              "def step_flops(out, k):\n"
              "    return dot_flops(out, k)\n")
    assert "AD03" not in _lint_snippet(tmp_path, "autodist_tpu/r.py", routed)


# -- HBM byte view: traffic extractor, F007/F008, roofline -------------------


def test_traffic_extractor_pins_conv_fusion_fixture():
    from autodist_tpu.analysis.compute_audit import extract_traffic_ops
    from autodist_tpu.simulator.cost_model import hbm_traffic

    traffic = hbm_traffic(_fixture("conv_fusion.stablehlo.txt"))
    assert traffic["total_bytes"] == pytest.approx(44224.0)
    assert traffic["by_class"] == {"contraction": pytest.approx(11456.0),
                                   "fused": pytest.approx(32768.0)}
    assert traffic["n_ops"] == 3
    # the extractor feeds the same walker: one op per traffic site
    ops = extract_traffic_ops(_fixture("conv_fusion.stablehlo.txt"))
    assert len(ops) == 3
    assert {o.kind for o in ops} == {"convolution", "elementwise"}


def test_f007_table_always_present_with_roofline_fields():
    from autodist_tpu.analysis.compute_audit import audit_traffic

    ops = [_cop(1e9, kind="add", sig="add big", in_bytes=2e9, out_bytes=1e9,
                in_types=("f32",), out_type="f32")]
    findings = audit_traffic(ops, peak_flops=100e12, hbm_gbps=819.0)
    f007 = next(f for f in findings if f.code == "F007")
    assert f007.severity is Severity.INFO
    for key in ("hbm_bytes", "by_class", "arithmetic_intensity", "compute_s",
                "hbm_s", "roofline_s", "roofline_bound",
                "predicted_mfu_ceiling_roofline", "top_sites"):
        assert key in f007.data, key
    assert f007.data["roofline_bound"] == "memory"
    assert f007.data["hbm_bytes"] == pytest.approx(3e9)


def test_f008_flips_on_bytes_dominated_and_stays_quiet_when_compute_bound():
    from autodist_tpu.analysis.compute_audit import audit_traffic

    # bytes dominate: 3 GB at 819 GB/s >> 1 GFLOP of MXU time
    memory = [_cop(1e9, kind="add", sig="add big", in_bytes=2e9,
                   out_bytes=1e9, in_types=("f32",), out_type="f32")]
    codes = _codes(audit_traffic(memory, peak_flops=100e12, hbm_gbps=819.0))
    assert codes.count("F008") == 1
    f008 = next(f for f in audit_traffic(memory, peak_flops=100e12,
                                         hbm_gbps=819.0) if f.code == "F008")
    assert f008.severity is Severity.WARNING
    assert "memory-bound" in f008.message
    assert "add big" in f008.message  # names the top HBM site

    # flops dominate: 1 PFLOP on a 100-TFLOP/s part vs 1.5 GB of traffic
    compute = [_cop(1e15, sig="dot big", in_bytes=1e9, out_bytes=5e8,
                    in_types=("bf16", "bf16"), out_type="f32")]
    assert "F008" not in _codes(
        audit_traffic(compute, peak_flops=100e12, hbm_gbps=819.0))


def test_f008_respects_absolute_bytes_floor():
    from autodist_tpu.analysis.compute_audit import (MEMORY_BOUND_MIN_BYTES,
                                                     audit_traffic)

    # heavily bytes-dominated ratio, but 3 MB total -- under the floor, so
    # a toy step never carries the memory-bound warning
    tiny = [_cop(1e3, kind="add", sig="add tiny", in_bytes=2e6, out_bytes=1e6,
                 in_types=("f32",), out_type="f32")]
    assert 3e6 < MEMORY_BOUND_MIN_BYTES
    assert "F008" not in _codes(
        audit_traffic(tiny, peak_flops=100e12, hbm_gbps=819.0))


def test_roofline_reconciles_measured_v5e_resnet_step():
    from autodist_tpu.simulator.cost_model import roofline_bound, roofline_s

    # A round-3 chip reading of 99.8 ms/step (to be re-measured, ROADMAP
    # S2), XLA-counted 6.12 TFLOP, 83.4 GB
    # of HBM traffic, 197 bf16 TFLOP/s peak, 819 GB/s HBM.  The byte leg
    # is what explains the wall -- the step is memory-bound, and the
    # roofline lands within 25% of the measured step time.
    measured_s = 0.0998
    pred = roofline_s(6.12e12, 83.4e9, peak_flops=197e12, hbm_gbps=819.0)
    assert abs(pred - measured_s) / measured_s < 0.25
    assert roofline_bound(6.12e12, 83.4e9,
                          peak_flops=197e12, hbm_gbps=819.0) == "memory"
    # and the bytes leg, not the flops leg, is the binding one
    assert pred == pytest.approx(83.4e9 / (819.0 * 1e9))


def test_predicted_mfu_ceiling_roofline_clamp():
    # 2-arg behaviour is unchanged (pinned elsewhere); the opt-in
    # hbm_bytes kwarg lowers the ceiling when the step is memory-bound
    plain = predicted_mfu_ceiling(3.14e12, 6.12e12)
    clamped = predicted_mfu_ceiling(3.14e12, 6.12e12, hbm_bytes=83.4e9,
                                    peak_flops=197e12, hbm_gbps=819.0)
    assert plain == pytest.approx(0.2309, abs=1e-4)
    assert clamped == pytest.approx(0.1565, abs=1e-4)
    assert clamped < plain
    # compute-bound traffic leaves the ceiling alone
    assert predicted_mfu_ceiling(
        3.14e12, 6.12e12, hbm_bytes=1e6,
        peak_flops=197e12, hbm_gbps=819.0) == pytest.approx(plain)


def test_f008_maps_to_fused_norm_knob():
    import types

    from autodist_tpu.analysis.compute_audit import audit_traffic
    from autodist_tpu.analysis.remediation import suggest_remediations

    ops = [_cop(1e9, kind="add", sig="add big", in_bytes=2e9, out_bytes=1e9,
                in_types=("f32",), out_type="f32")]
    findings = audit_traffic(ops, peak_flops=100e12, hbm_gbps=819.0)
    rems = {r.code: r for r in suggest_remediations(
        types.SimpleNamespace(findings=findings))}
    assert "F008" in rems
    assert rems["F008"].kind == "model"
    assert rems["F008"].knob == {"norm": "bn_fused"}
    assert "bn_fused" in rems["F008"].action
    assert rems["F008"].expected_gain


def test_gpt_b32_lever_record_is_roofline_priced():
    import json

    from autodist_tpu.simulator.cost_model import (DEFAULT_HBM_GBPS,
                                                   DEFAULT_MXU_EFF,
                                                   DEFAULT_PEAK_FLOPS,
                                                   roofline_s)

    path = os.path.join(REPO, "records", "v5e_aot", "gpt_b32_lever.json")
    with open(path) as f:
        lever = json.load(f)
    pred = roofline_s(lever["xla_flops"], lever["xla_bytes_accessed"],
                      peak_flops=DEFAULT_PEAK_FLOPS * DEFAULT_MXU_EFF,
                      hbm_gbps=DEFAULT_HBM_GBPS)
    assert round(pred * 1e3, 2) == lever["roofline_pred_step_ms"]
    assert lever["roofline_bound"] == "memory"
    assert (lever["predicted_mfu_ceiling_roofline"]
            < lever["predicted_mfu_ceiling"])


# -- AD13: byte arithmetic routed through cost_model -------------------------


_AD13_ITEMSIZE = ("def hbm_step_bytes(x):\n"
                  "    return x.size * x.dtype.itemsize\n")
_AD13_PROD = ("import math\n"
              "def traffic_for(x):\n"
              "    return 4 * math.prod(x.shape)\n")
_AD13_ASSIGN = ("import numpy as np\n"
                "roofline_bytes = x.size * x.dtype.itemsize\n")


def test_ad13_flags_adhoc_byte_arithmetic_in_traffic_contexts(tmp_path):
    assert "AD13" in _lint_snippet(tmp_path, "autodist_tpu/x.py",
                                   _AD13_ITEMSIZE)
    assert "AD13" in _lint_snippet(tmp_path, "tools/y.py", _AD13_PROD)
    assert "AD13" in _lint_snippet(tmp_path, "autodist_tpu/z.py",
                                   _AD13_ASSIGN)


def test_ad13_exempts_blessed_walkers_tests_and_plain_byte_code(tmp_path):
    # the single-source byte walkers are the blessed homes
    for rel in ("autodist_tpu/simulator/cost_model.py",
                "autodist_tpu/analysis/hlo_audit.py",
                "autodist_tpu/analysis/compute_audit.py"):
        assert "AD13" not in _lint_snippet(tmp_path, rel, _AD13_ITEMSIZE)
    assert "AD13" not in _lint_snippet(tmp_path, "tests/t.py", _AD13_ITEMSIZE)
    # byte arithmetic OUTSIDE an hbm/roofline/traffic-named context is the
    # ordinary buffer-sizing idiom, not roofline accounting
    ok = ("def bucket_bytes(x):\n"
          "    return x.size * x.dtype.itemsize\n")
    assert "AD13" not in _lint_snippet(tmp_path, "autodist_tpu/ok.py", ok)
