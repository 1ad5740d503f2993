"""The Nemotron-H cell's own pieces, on the CPU: the family's loop at a tiny
size through the functions ``run.py`` calls, its copy of the reference against
the tests' original, the cost arithmetic against hand numbers, the new readers
on made-up lanes.

    python -m pytest benchmark/tests -q
"""
import json
import os

import pytest

from benchmark.harness import (cells, model_scopes, nemotron_h_cost, peaks,
                               ssd_scopes)
from benchmark.tests.test_benchmark import run_tiny, tiny_cell, tiny_manifest

CELL = "nemotron3_nano_30b_a3b.train_fed"
NEW = ("ssd_scan_ms", "ssd_scan_roofline", "ssd_proj_ms",
       "relu2_experts_roofline")

TINY = {
    "family": "nemotron_h", "chunk_size": 16, "conv_kernel": 4,
    "head_dim": 16, "hidden_size": 64,
    "hybrid_override_pattern": "MEM*EMEM", "layer_norm_epsilon": 1e-5,
    "mamba_head_dim": 8, "mamba_num_heads": 4, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 64, "n_groups": 2,
    "n_routed_experts": 4, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 5,
    "num_key_value_heads": 2, "routed_scaling_factor": 2.5,
    "ssm_state_size": 16, "time_step_floor": 1e-4, "time_step_max": 0.1,
    "time_step_min": 0.001, "vocab_size": 128,
    "deployment": {"n_routed_experts_published": 8, "first_expert": 0,
                   "num_hidden_layers_published": 8},
    "assumed": {"compute_dtype": "float32", "attention_impl": "xla",
                "remat": True, "learning_rate": 1e-3, "warmup_steps": 1}}


def test_loop_on_the_cpu(tmp_path):
    cell = tiny_cell("tiny.nemotron_h", 1, batch=4, seq_len=48)
    cell["feed"]["rank_offset"] = 10
    result, lines = run_tiny(cell, TINY, tmp_path,
                             tiny_manifest("tiny.nemotron_h", "tokens"),
                             seconds=3.0)
    assert result["correct"], lines[-1]["not_correct_because"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    setup = next(x for x in lines if x["phase"] == "setup")
    # float32 on both sides: the chunked scan, the packed experts and the
    # streaming loss against the recurrence, masks and whole logits
    for got, want in zip(setup["first_losses"], setup["reference_losses"]):
        assert got == pytest.approx(want, rel=2e-5)
    assert lines[-1]["compilations_in_window"] == 0
    assert lines[-1]["model_flops_per_unit"] > 0


def test_overflow_of_the_packed_rows_is_not_correct(tmp_path):
    cell = tiny_cell("tiny.nemotron_h", 1, batch=4, seq_len=48,
                     moe_rows_bound=8)
    cell["feed"]["rank_offset"] = 10
    result, lines = run_tiny(cell, TINY, tmp_path,
                             tiny_manifest("tiny.nemotron_h", "tokens"),
                             seconds=1.0)
    assert not result["correct"]
    assert "non-finite loss" in lines[-1]["not_correct_because"]


def test_the_seeded_selection_bias_balances_the_routing():
    """Random weights whose hidden states share a large component (here put
    in by hand: one vector added to every embedding) send most tokens to a
    few experts; with the family's bias every expert of every routed layer
    is chosen about equally often, on tokens the bias was not made from."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    family = cells.load_family("nemotron_h")
    config = {**TINY, "n_routed_experts": 8}        # hold all eight
    cell = tiny_cell("tiny.nemotron_h", 1, batch=4, seq_len=256)
    cell["feed"]["rank_offset"] = 10
    cfg = family.model_config(config, cell)
    ref = family.reference_config(config)

    from autodist_tpu.models.train_lib import nemotron_h_capture

    params = jax.jit(lambda key: nemotron_h_capture(cfg, 256, rng=key)[1])(
        jax.random.PRNGKey(0))
    shared = 0.05 * jnp.asarray(np.random.RandomState(1).randn(64),
                                jnp.float32)
    params = {**params, "embed": params["embed"] + shared}
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 128, (4, 256)))

    def worst_load(p):
        counts = jax.vmap(lambda t: family.hidden_states(p, t, ref)[1])(
            tokens).sum(0)                          # [routed layers, 8]
        return float(jnp.max(counts.max(1) / counts.mean(1)))

    made = jax.jit(lambda p: family.balancing_bias(
        p, family.calibration_tokens(cell, cfg, 5), ref, 8))(params)
    assert sorted(made) == ["l_1", "l_4"]
    assert all(float(jnp.abs(jnp.mean(m["moe"]["router_bias"]))) < 1e-6
               for m in made.values())
    assert worst_load(params) > 2.0
    assert worst_load({**params, **made}) < 1.3


def test_the_family_cuts_the_pattern_to_the_layers_kept():
    family = cells.load_family("nemotron_h")
    assert family.pattern_here(TINY) == "MEM*E"
    _, config = cells.load_cell(CELL)
    assert family.pattern_here(config) == "MEMEM*EME"
    assert family.reference_config(config)["hybrid_override_pattern"] \
        == "MEMEM*EME"


def test_the_family_holds_a_copy_of_the_reference():
    here = os.path.join(cells.BENCH_DIR, "families", "nemotron_h.py")
    there = os.path.join(cells.REPO_DIR, "tests", "nemotron_h_reference.py")
    mark = "SCAN_BLOCK = "
    with open(here) as f, open(there) as g:
        mine, original = f.read(), g.read()
    assert mine[mine.index(mark):] == original[original.index(mark):]


def test_cost_arithmetic_against_hand_numbers():
    # the issue's count: 303.5 M dense weights a token passes (the
    # convolutions' 0.1 M on top), four routed layers of 8 experts of
    # 9.978 M at 6 / 128 of the tokens, the causal half of the scores, the
    # scan: about 2.15 GFLOP a trained token
    mamba = 2688 * 10304 + 4 * 6144 + 4096 * 2688
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    ffn = 2688 * 128 + 2 * 2688 * 3712            # router, shared expert
    n_dense = 4 * mamba + attn + 4 * ffn + 2688 * 16384
    assert n_dense == pytest.approx(303.5e6, rel=1e-3)
    n_exp = 4 * 8 * 2 * 2688 * 1856
    per_token = nemotron_h_cost.train_flops_per_token(
        n_dense, n_exp, 6, 128, 8192, 1, 32, 128, 4, 64, 64, 128)
    assert per_token == 3 * (2 * n_dense + 2 * n_exp * 6 / 128
                             + 2 * 8192 * 4096 + 4 * 5 * 64 * 128 * 64)
    assert per_token == pytest.approx(2.15e9, rel=5e-3)
    assert nemotron_h_cost.ssd_scan_flops_per_token(64, 64, 128) \
        == 5 * 64 * 128 * 64
    f, b = nemotron_h_cost.ssd_scan_cost("fwd", 10, 64, 64, 8, 128)
    assert f == 10 * 5 * 64 * 128 * 64
    assert b == 10 * 2 * (4096 + 2 * 8 * 128 + 64 * 2 + 4096)
    f2, b2 = nemotron_h_cost.ssd_scan_cost("bwd", 10, 64, 64, 8, 128)
    assert f2 == 2 * f
    assert b2 == 10 * 2 * (2 * (4096 + 2 * 8 * 128 + 64 * 2) + 4096)
    # bytes-bound at the cell's 16,384 tokens: about 0.4 ms forward a layer
    pk = peaks.peaks_for("TPU v5 lite")
    f, b = nemotron_h_cost.ssd_scan_cost("fwd", 16384, 64, 64, 8, 128)
    assert b / pk["hbm_bytes_per_s"] > f / pk["bf16_flops_per_s"]
    assert b / pk["hbm_bytes_per_s"] == pytest.approx(0.415e-3, rel=1e-2)
    f, b = nemotron_h_cost.relu2_experts_cost("fwd", 100, 8, 2688, 1856)
    assert f == 100 * 2 * 2 * 2688 * 1856
    assert b == 2 * (2 * 8 * 2688 * 1856 + 2 * 100 * 2688)
    f2, b2 = nemotron_h_cost.relu2_experts_cost("bwd", 100, 8, 2688, 1856)
    assert f2 == 2 * f
    assert b2 == 2 * (2 * 2 * 8 * 2688 * 1856 + 3 * 100 * 2688)


def made_up_run():
    ms = 1e6
    step = "jit_step_fn(1)"
    g = "jit(step_fn)/ad.grad/"
    fwd = g + "jvp(NemotronH)/l_0/"
    again = g + "transpose(jvp(NemotronH))/ad.grad/jvp(NemotronH)/" \
        "checkpoint/rematted_computation/l_0/"
    bwd = g + "transpose(jvp(NemotronH))/ad.grad/jvp(NemotronH)/" \
        "checkpoint/l_0/"
    # one steady step of 100 ms from t = 100 ms; [name, start, dur, op_name]
    ops = [
        ["%fusion.1", 100, 10, fwd + "ssd/ssd.proj/dot_general"],
        ["%while.2", 110, 20, fwd + "ssd/ssd.scan/while"],
        ["%fusion.3", 112, 6, fwd + "ssd/ssd.scan/while/body/dot_general"],
        ["%fusion.4", 130, 4, fwd.replace("l_0", "l_1")
         + "moe/moe.route/sort"],
        ["%ragged-dot-none.5", 134, 2, "ragged-dot-none:"],
        ["%fusion.6", 136, 1, fwd.replace("l_0", "l_1")
         + "moe/moe.shared/dot_general"],
        ["%fusion.8", 140, 5, None],
        ["%fusion.9", 145, 10, again + "ssd/ssd.scan/dot_general"],
        ["%fusion.11", 158, 22, bwd + "ssd/ssd.scan/transpose"],
        ["%ragged-dot.14", 190, 6, bwd.replace("l_0", "l_1")
         + "moe/moe.experts/ragged_dot"],
        ["%fusion.15", 196, 4, bwd + "ssd/ssd.proj/transpose"]]
    events, model_ops, ssd_ops = [], [], []
    for name, start, dur, op_name in ops:
        events.append([name, start * ms, dur * ms])
        model_ops.append([name, start * ms, dur * ms,
                          *model_scopes.classify_op(name, op_name)])
        ssd_ops.append([name, start * ms, dur * ms,
                        ssd_scopes.classify(op_name)])
    lanes = [
        {"plane": "/device:TPU:0", "line": "XLA Modules", "events": [
            [step, 0, 90 * ms], [step, 100 * ms, 100 * ms],
            [step, 200 * ms, 50 * ms]]},
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": events}]
    from benchmark.harness import trace

    waits = [["bench.input_wait", t * ms, 1 * ms, {}, "python"]
             for t in (95, 195, 295, 395)]
    runs = [["ad.run", t * ms, 2 * ms, {"variants": 1, "aux_step": i,
                                        "moe_rows_here": rows,
                                        "moe_load_max_over_mean": 1.5},
             "python"]
            for i, (t, rows) in enumerate([(197, 6000.0), (297, 6200.0)])]
    family = cells.load_family("nemotron_h")
    cell, config = cells.load_cell(CELL)
    return {"lanes": lanes, "summary": trace.summarize(lanes),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "shapes": family.layer_shapes(cell, config),
            "model_trace": {"ops": model_ops}, "ssd_trace": {"ops": ssd_ops},
            "program_trace": {"spans": sorted(waits + runs,
                                              key=lambda s: s[1]),
                              "ops": []}}


def test_scopes_are_read_from_op_names():
    assert ssd_scopes.classify(
        "jit(step_fn)/ad.grad/jvp(NemotronH)/l_2/ssd/ssd.scan/while/body/"
        "dot_general:") == "ssd.scan"
    assert ssd_scopes.classify(
        "jit(step_fn)/ad.grad/transpose(jvp(NemotronH))/l_0/ssd/ssd.proj/"
        "checkpoint/mul") == "ssd.proj"
    # a scope is a whole path component; the flax module ``ssd`` is none
    assert ssd_scopes.classify("jit(f)/l_0/ssd/dot_general") is None
    assert ssd_scopes.classify("jit(f)/ssd.scanner/mul") is None
    assert ssd_scopes.classify(None) is None


def test_new_readers_on_made_up_lanes():
    run = made_up_run()
    read = {m: cells.load_reader("per_layer", m)(run) for m in NEW}
    # the while of 20 ms holds 6 ms of its body: self time adds up to 20
    assert read["ssd_scan_ms"] == pytest.approx(20 + 10 + 22)
    assert read["ssd_proj_ms"] == pytest.approx(10 + 4)
    pk = peaks.peaks_for("TPU v5 lite")
    scan = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
               for f, b in (nemotron_h_cost.ssd_scan_cost(
                   k, 2 * 8192, 64, 64, 8, 128) for k in ("fwd", "bwd")))
    assert read["ssd_scan_roofline"] == pytest.approx(100 * 4 * scan / 0.052)
    experts = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                  for f, b in (nemotron_h_cost.relu2_experts_cost(
                      k, 6100.0, 8, 2688, 1856) for k in ("fwd", "bwd")))
    assert read["relu2_experts_roofline"] == pytest.approx(
        100 * 4 * experts / 0.008)
    assert 0 < read["ssd_scan_roofline"] < 100
    # the accepted readers the cell is listed under find their scopes too,
    # and the gated experts' share finds nothing to count its rows by
    assert cells.load_reader("per_layer", "moe_route_ms")(run) \
        == pytest.approx(4)
    assert cells.load_reader("per_layer", "moe_experts_ms")(run) \
        == pytest.approx(8)
    assert cells.load_reader("per_layer", "moe_experts_roofline")(run) is None
    assert cells.load_reader("per_layer", "gdn_rule_ms")(run) is None


def test_new_readers_find_nothing_in_a_program_without_the_scopes():
    run = made_up_run()
    run["ssd_trace"] = {"ops": [op[:3] + [None]
                                for op in run["ssd_trace"]["ops"]]}
    run["model_trace"] = {"ops": [[op[0], op[1], op[2], None, op[4]]
                                  for op in run["model_trace"]["ops"]]}
    for m in NEW:
        assert cells.load_reader("per_layer", m)(run) is None, m
    run["ssd_trace"] = None
    run["cell"] = None
    assert cells.load_reader("per_layer", "ssd_scan_ms")(run) is None
    # another family's shapes: the shares have nothing to reckon with
    run = made_up_run()
    run["shapes"] = {"batch_per_chip": 2, "seq_len": 8192}
    assert cells.load_reader("per_layer", "ssd_scan_roofline")(run) is None
    assert cells.load_reader("per_layer", "relu2_experts_roofline")(run) \
        is None


def test_the_cell_is_listed_under_what_it_reports():
    per_layer = {m["name"]: m for m in cells.load_manifest()["per_layer"]}
    mine = {n for n, m in per_layer.items() if CELL in m.get("workloads", [])}
    assert set(NEW) <= mine
    assert {"moe_route_ms", "moe_experts_ms", "moe_rows_here",
            "moe_load_max_over_mean", "full_attn_ms", "full_attn_roofline",
            "attn_layout_ms"} <= mine
    assert {n for n in per_layer if n.endswith(".tokens")} <= mine
    assert not mine & {"flash_attn_ms", "flash_attn_roofline",
                       "moe_experts_roofline"}
    assert not any(n.startswith("gdn_") for n in mine)
    for n in NEW:
        assert per_layer[n]["workloads"] == [CELL]
        assert per_layer[n]["moves"] == "tokens_per_s"


def test_configuration_keeps_the_published_widths():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    _, config = cells.load_cell(CELL)
    entry = next(c for c in cells.load_manifest()["configs"]
                 if c["name"] == "nemotron3_nano_30b_a3b")
    assert entry["source"] == row["source_url"] == config["source"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"]) \
        == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    d = config["deployment"]
    assert d["n_routed_experts_published"] \
        == row["config"]["n_routed_experts"]
    assert d["chips_sharing_a_layer"] * config["n_routed_experts"] \
        == row["config"]["n_routed_experts"]
    assert d["num_hidden_layers_published"] \
        == row["config"]["num_hidden_layers"] \
        == len(config["hybrid_override_pattern"])
    assert config["vocab_size"] * d["chips_sharing_the_vocabulary"] \
        == row["config"]["vocab_size"]
    # the floors: a whole period's kinds, 8 experts a routed layer, an
    # eighth of the vocabulary
    kinds = cells.load_family("nemotron_h").pattern_here(config)
    assert set(kinds) == set(config["hybrid_override_pattern"])
    assert config["n_routed_experts"] >= 8
