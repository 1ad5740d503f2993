"""The LFM2 cell's own pieces, on the CPU: the family's loop at a tiny size
through the functions ``run.py`` calls, its copy of the reference against the
tests' original, the cost arithmetic against hand numbers, the new readers on
made-up lanes.

    python -m pytest benchmark/tests -q
"""
import json
import os

import pytest

from benchmark.harness import (cells, lfm2_cost, lfm2_scopes, model_scopes,
                               peaks, qwen3_next_cost)
from benchmark.tests.test_benchmark import run_tiny, tiny_cell, tiny_manifest

CELL = "lfm2_8b_a1b.train_fed"
NEW = ("sconv_proj_ms", "sconv_mix_ms", "sconv_mix_roofline")

# published layers 0, 2, 3 of five: conv + dense, attention + routed, conv +
# routed; 4 of 8 experts held
TINY = {
    "family": "lfm2", "conv_L_cache": 3, "hidden_size": 64,
    "intermediate_size": 96,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "moe_intermediate_size": 32, "norm_eps": 1e-5, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3,
    "num_key_value_heads": 2, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 128,
    "deployment": {"num_experts_published": 8, "first_expert": 0,
                   "num_dense_layers_published": 2},
    "assumed": {"compute_dtype": "float32", "attention_impl": "xla",
                "remat": True, "learning_rate": 1e-3, "warmup_steps": 1}}


def test_loop_on_the_cpu(tmp_path):
    cell = tiny_cell("tiny.lfm2", 1, batch=4, seq_len=48)
    cell["feed"]["rank_offset"] = 10
    result, lines = run_tiny(cell, TINY, tmp_path,
                             tiny_manifest("tiny.lfm2", "tokens"),
                             seconds=3.0)
    assert result["correct"], lines[-1]["not_correct_because"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    setup = next(x for x in lines if x["phase"] == "setup")
    # float32 on both sides: the packed experts and the streaming loss over
    # the tied embedding against masks and whole logits
    for got, want in zip(setup["first_losses"], setup["reference_losses"]):
        assert got == pytest.approx(want, rel=2e-5)
    assert lines[-1]["compilations_in_window"] == 0
    assert lines[-1]["model_flops_per_unit"] > 0


def test_overflow_of_the_packed_rows_is_not_correct(tmp_path):
    cell = tiny_cell("tiny.lfm2", 1, batch=4, seq_len=48, moe_rows_bound=8)
    cell["feed"]["rank_offset"] = 10
    result, lines = run_tiny(cell, TINY, tmp_path,
                             tiny_manifest("tiny.lfm2", "tokens"),
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

    family = cells.load_family("lfm2")
    config = {**TINY, "num_experts": 8}             # hold all eight
    cell = tiny_cell("tiny.lfm2", 1, batch=4, seq_len=256)
    cell["feed"]["rank_offset"] = 10
    cfg = family.model_config(config, cell)
    ref = family.reference_config(config)

    from autodist_tpu.models.train_lib import lfm2_capture

    params = jax.jit(lambda key: lfm2_capture(cfg, 256, rng=key)[1])(
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
        p, cells.load_family("nemotron_h").calibration_tokens(cell, cfg, 5),
        ref, 8))(params)
    assert sorted(made) == ["l_1", "l_2"]
    assert all(float(jnp.abs(jnp.mean(m["moe"]["expert_bias"]))) < 1e-6
               for m in made.values())
    assert worst_load(params) > 1.5
    assert worst_load({**params, **made}) < 1.3


def test_the_family_cuts_the_layers_to_those_kept():
    family = cells.load_family("lfm2")
    assert family.layers_here(TINY) == (0, 2, 3)
    assert family.reference_config(TINY)["layer_types"] \
        == ("conv", "full_attention", "conv")
    cell, config = cells.load_cell(CELL)
    assert family.layers_here(config) == (0, 2, 3, 4, 5)
    ref = family.reference_config(config)
    assert ref["layer_types"] == ("conv", "full_attention", "conv", "conv",
                                  "conv")
    assert ref["num_dense_layers"] == 1 and ref["first_expert"] == 0
    cfg = family.model_config(config, cell)
    assert cfg.layer_kinds == (
        ("conv", "dense"), ("full_attention", "moe"), ("conv", "moe"),
        ("conv", "moe"), ("conv", "moe"))
    assert cfg.num_experts == 32 and cfg.experts_held == 8
    assert cfg.rows_bound == 49152 == 3 * 4 * 8192 * 4 * 8 // 32 // 2
    assert family.layer_shapes(cell, config) == {
        "batch_per_chip": 4, "seq_len": 8192, "heads": 32, "kv_heads": 8,
        "head_dim": 64, "sconv_layers": 4, "sconv_channels": 2048,
        "sconv_taps": 3, "moe_layers": 4, "experts_held": 8, "hidden": 2048,
        "expert_width": 1792}


def test_the_family_holds_a_copy_of_the_reference():
    here = os.path.join(cells.BENCH_DIR, "families", "lfm2.py")
    there = os.path.join(cells.REPO_DIR, "tests", "lfm2_reference.py")
    mark = "TOKEN_BLOCK = "
    with open(here) as f, open(there) as g:
        mine, original = f.read(), g.read()
    assert mine[mine.index(mark):] == original[original.index(mark):]


def test_cost_arithmetic_against_hand_numbers():
    # the issue's count: 4 short convolutions of 16.78 M, attention 10.49 M
    # and its scores at S = 8,192, the dense feed-forward 44.04 M, four
    # routers and four times 8 experts of 11.01 M at 4 / 32 of the tokens,
    # the tied head 33.55 M: about 1.30 GFLOP a trained token
    d = 2048
    sconv = d * 3 * d + d * d
    attn = 2 * d * d + 2 * d * 512
    n_dense = 4 * sconv + attn + 3 * d * 7168 + 4 * d * 32 + 16384 * d
    n_exp = 4 * 8 * 3 * d * 1792
    per_token = lfm2_cost.train_flops_per_token(
        n_dense, n_exp, 4, 32, 8192, 1, 2048, 4, 2048, 3)
    assert per_token == 3 * (2 * n_dense + 2 * n_exp * 4 / 32
                             + 2 * 8192 * 2048 + 4 * 8 * 2048)
    assert per_token == pytest.approx(1.30e9, rel=5e-3)
    assert lfm2_cost.short_conv_flops_per_token(2048, 3) == 8 * 2048
    f, b = lfm2_cost.short_conv_cost("fwd", 10, 2048, 3)
    assert f == 10 * 8 * 2048 and b == 10 * 2 * 4 * 2048
    f2, b2 = lfm2_cost.short_conv_cost("bwd", 10, 2048, 3)
    assert f2 == 2 * f and b2 == 10 * 2 * 7 * 2048
    # bytes-bound at the cell's 32,768 tokens: about 0.65 ms forward a layer
    pk = peaks.peaks_for("TPU v5 lite")
    f, b = lfm2_cost.short_conv_cost("fwd", 32768, 2048, 3)
    assert b / pk["hbm_bytes_per_s"] > f / pk["bf16_flops_per_s"]
    assert b / pk["hbm_bytes_per_s"] == pytest.approx(0.655e-3, rel=1e-2)
    # the accepted expert roofline's cost at this cell's widths: 2.16 TFLOP
    # a layer forward and backward at the mean of 32,768 rows
    f = sum(qwen3_next_cost.moe_experts_cost(k, 32768, 8, 2048, 1792)[0]
            for k in ("fwd", "bwd"))
    assert f == pytest.approx(2.16e12, rel=5e-3)


def made_up_run():
    ms = 1e6
    step = "jit_step_fn(1)"
    g = "jit(step_fn)/ad.grad/"
    fwd = g + "jvp(Lfm2)/l_0/"
    again = g + "transpose(jvp(Lfm2))/ad.grad/jvp(Lfm2)/" \
        "checkpoint/rematted_computation/l_0/"
    bwd = g + "transpose(jvp(Lfm2))/ad.grad/jvp(Lfm2)/checkpoint/l_0/"
    # one steady step of 100 ms from t = 100 ms; [name, start, dur, op_name]
    ops = [
        ["%fusion.1", 100, 10, fwd + "sconv/sconv.proj/dot_general"],
        ["%fusion.2", 110, 6, fwd + "sconv/sconv.mix/checkpoint/mul"],
        ["%fusion.3", 116, 3, fwd + "ffn/ffn.dense/dot_general"],
        ["%fusion.4", 130, 4, fwd.replace("l_0", "l_1")
         + "moe/moe.route/sort"],
        ["%moe.experts.5", 134, 2, fwd.replace("l_0", "l_1")
         + "moe/moe.experts/pallas_call"],
        ["%fusion.8", 140, 5, None],
        ["%fusion.9", 145, 6, again + "sconv/sconv.mix/checkpoint/mul"],
        ["%fusion.11", 158, 12, bwd + "sconv/sconv.mix/checkpoint/"
         "rematted_computation/mul"],
        ["%moe.experts.14", 190, 6, bwd.replace("l_0", "l_1")
         + "moe/moe.experts/pallas_call"],
        ["%fusion.15", 196, 4, bwd + "sconv/sconv.proj/transpose"]]
    events, model_ops, sconv_ops = [], [], []
    for name, start, dur, op_name in ops:
        events.append([name, start * ms, dur * ms])
        model_ops.append([name, start * ms, dur * ms,
                          *model_scopes.classify_op(name, op_name)])
        sconv_ops.append([name, start * ms, dur * ms,
                          lfm2_scopes.classify(op_name)])
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
                                        "moe_load_max_over_mean": 1.1},
             "python"]
            for i, (t, rows) in enumerate([(197, 32000.0), (297, 33000.0)])]
    family = cells.load_family("lfm2")
    cell, config = cells.load_cell(CELL)
    return {"lanes": lanes, "summary": trace.summarize(lanes),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "shapes": family.layer_shapes(cell, config),
            "model_trace": {"ops": model_ops},
            "sconv_trace": {"ops": sconv_ops},
            "program_trace": {"spans": sorted(waits + runs,
                                              key=lambda s: s[1]),
                              "ops": []}}


def test_scopes_are_read_from_op_names():
    assert lfm2_scopes.classify(
        "jit(step_fn)/ad.grad/jvp(Lfm2)/l_2/sconv/sconv.mix/checkpoint/"
        "mul:") == "sconv.mix"
    assert lfm2_scopes.classify(
        "jit(step_fn)/ad.grad/transpose(jvp(Lfm2))/l_0/sconv/sconv.proj/"
        "dot_general") == "sconv.proj"
    # a scope is a whole path component; the flax module ``sconv`` is none
    assert lfm2_scopes.classify("jit(f)/l_0/sconv/dot_general") is None
    assert lfm2_scopes.classify("jit(f)/sconv.mixer/mul") is None
    assert lfm2_scopes.classify("jit(f)/l_0/ffn/ffn.dense/mul") is None
    assert lfm2_scopes.classify(None) is None


def test_new_readers_on_made_up_lanes():
    run = made_up_run()
    read = {m: cells.load_reader("per_layer", m)(run) for m in NEW}
    assert read["sconv_mix_ms"] == pytest.approx(6 + 6 + 12)
    assert read["sconv_proj_ms"] == pytest.approx(10 + 4)
    pk = peaks.peaks_for("TPU v5 lite")
    mix = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
              for f, b in (lfm2_cost.short_conv_cost(k, 4 * 8192, 2048, 3)
                           for k in ("fwd", "bwd")))
    assert read["sconv_mix_roofline"] == pytest.approx(100 * 4 * mix / 0.024)
    assert 0 < read["sconv_mix_roofline"] < 100
    # the accepted readers the cell is listed under find their scopes too,
    # the gated experts' share among them; the other models' find nothing
    assert cells.load_reader("per_layer", "moe_route_ms")(run) \
        == pytest.approx(4)
    assert cells.load_reader("per_layer", "moe_experts_ms")(run) \
        == pytest.approx(8)
    experts = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                  for f, b in (qwen3_next_cost.moe_experts_cost(
                      k, 32500.0, 8, 2048, 1792) for k in ("fwd", "bwd")))
    assert cells.load_reader("per_layer", "moe_experts_roofline")(run) \
        == pytest.approx(100 * 4 * experts / 0.008)
    for other in ("relu2_experts_roofline", "gdn_rule_ms", "ssd_scan_ms"):
        assert cells.load_reader("per_layer", other)(run) is None, other


def test_new_readers_find_nothing_in_a_program_without_the_scopes():
    run = made_up_run()
    run["sconv_trace"] = {"ops": [op[:3] + [None]
                                  for op in run["sconv_trace"]["ops"]]}
    for m in NEW:
        assert cells.load_reader("per_layer", m)(run) is None, m
    run["sconv_trace"] = None
    run["cell"] = None
    assert cells.load_reader("per_layer", "sconv_mix_ms")(run) is None
    # another family's shapes: the share has nothing to reckon with
    run = made_up_run()
    run["shapes"] = {"batch_per_chip": 2, "seq_len": 8192}
    assert cells.load_reader("per_layer", "sconv_mix_roofline")(run) is None


def test_the_cell_is_listed_under_what_it_reports():
    manifest = cells.load_manifest()
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    mine = {n for n, m in per_layer.items() if CELL in m.get("workloads", [])}
    assert set(NEW) <= mine
    assert {"moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
            "moe_experts_kernel_share", "moe_rows_here",
            "moe_load_max_over_mean", "full_attn_ms", "full_attn_roofline",
            "attn_layout_ms"} <= mine
    assert {n for n in per_layer if n.endswith(".tokens")} <= mine
    assert not mine & {"flash_attn_ms", "flash_attn_roofline",
                       "relu2_experts_roofline"}
    assert not any(n.startswith(("gdn_", "ssd_")) for n in mine)
    for n in NEW:
        assert per_layer[n]["workloads"] == [CELL]
        assert per_layer[n]["moves"] == "tokens_per_s"
        assert per_layer[n]["layer"] == "short-convolution mixer"
    e2e = {m["name"] for m in cells.metrics_of(CELL, "end_to_end", manifest)}
    assert e2e == {"tokens_per_s", "peak_hbm_gb", "setup_s"}
    assert len(manifest["configs"]) == len(manifest["workloads"]) == 5
    assert all(w["chips"] == 1 for w in manifest["workloads"])


def test_configuration_keeps_the_published_widths():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    _, config = cells.load_cell(CELL)
    entry = next(c for c in cells.load_manifest()["configs"]
                 if c["name"] == "lfm2_8b_a1b")
    assert entry["source"] == row["source_url"] == config["source"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"]) \
        == {"num_hidden_layers", "num_dense_layers", "num_experts",
            "vocab_size"}
    d = config["deployment"]
    assert d["num_experts_published"] == row["config"]["num_experts"]
    assert d["chips_sharing_a_layer"] * config["num_experts"] \
        == row["config"]["num_experts"]
    assert d["num_hidden_layers_published"] \
        == row["config"]["num_hidden_layers"] == len(config["layer_types"])
    assert d["num_dense_layers_published"] \
        == row["config"]["num_dense_layers"]
    assert config["vocab_size"] * d["chips_sharing_the_vocabulary"] \
        == row["config"]["vocab_size"]
    # the floors: a whole period and four layers after the dense ones, 8
    # experts a routed layer, an eighth of the vocabulary
    kept = cells.load_family("lfm2").reference_config(config)
    routed = kept["layer_types"][kept["num_dense_layers"]:]
    assert len(routed) >= 4 and set(routed) == set(config["layer_types"])
    assert routed.count("conv") == 3 * routed.count("full_attention")
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= row["config"]["vocab_size"]
    for key in ("tie_word_embeddings", "selection_bias", "norm_topk_epsilon",
                "initializer", "warmup_steps"):
        assert key in config["assumed"], key
