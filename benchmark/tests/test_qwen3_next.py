"""The Qwen3-Next cell's own pieces, on the CPU: the family's loop at a tiny
size through the functions ``run.py`` calls, its copy of the reference against
the tests' original, the cost arithmetic against hand numbers, the new readers
on made-up lanes.

    python -m pytest benchmark/tests -q
"""
import json
import os

import pytest

from benchmark.harness import cells, model_scopes, peaks, qwen3_next_cost
from benchmark.tests.test_benchmark import run_tiny, tiny_cell, tiny_manifest

CELL = "qwen3_next_80b_a3b.train_fed"

TINY = {
    "family": "qwen3_next", "full_attention_interval": 4, "head_dim": 16,
    "hidden_size": 64, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 8,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 8, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 32, "vocab_size": 128,
    "deployment": {"num_experts_published": 8, "first_expert": 0},
    "assumed": {"compute_dtype": "float32", "attention_impl": "xla",
                "delta_rule_chunk": 16, "remat": True,
                "learning_rate": 1e-3}}


def test_loop_on_the_cpu(tmp_path):
    cell = tiny_cell("tiny.qwen3_next", 1, batch=4, seq_len=48)
    cell["feed"]["rank_offset"] = 10
    result, lines = run_tiny(cell, TINY, tmp_path,
                             tiny_manifest("tiny.qwen3_next", "tokens"),
                             seconds=3.0)
    assert result["correct"], lines[-1]["not_correct_because"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    setup = next(x for x in lines if x["phase"] == "setup")
    # float32 on both sides: the chunked rule, the packed experts and the
    # streaming loss against recurrences, masks and whole logits
    for got, want in zip(setup["first_losses"], setup["reference_losses"]):
        assert got == pytest.approx(want, rel=2e-5)
    assert lines[-1]["compilations_in_window"] == 0
    assert lines[-1]["model_flops_per_unit"] > 0


def test_overflow_of_the_packed_rows_is_not_correct(tmp_path):
    cell = tiny_cell("tiny.qwen3_next", 1, batch=4, seq_len=48,
                     moe_rows_bound=8)
    cell["feed"]["rank_offset"] = 10
    result, lines = run_tiny(cell, TINY, tmp_path,
                             tiny_manifest("tiny.qwen3_next", "tokens"),
                             seconds=1.0)
    assert not result["correct"]
    assert "non-finite loss" in lines[-1]["not_correct_because"]


def test_the_family_holds_a_copy_of_the_reference():
    here = os.path.join(cells.BENCH_DIR, "families", "qwen3_next.py")
    there = os.path.join(cells.REPO_DIR, "tests", "qwen3_next_reference.py")
    mark = "SCAN_BLOCK = "
    with open(here) as f, open(there) as g:
        mine, original = f.read(), g.read()
    assert mine[mine.index(mark):] == original[original.index(mark):]


def test_cost_arithmetic_against_hand_numbers():
    # the issue's count: DeltaNet projections 202 M, rule 11 M, attention
    # projections 55 M and scores 67 M, feed-forwards 42 M, head 78 M
    gdn = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048          # router, shared expert
    n_dense = 3 * gdn + attn + 4 * ffn + 2048 * 18992
    n_exp = 4 * 16 * 3 * 2048 * 512
    per_token = qwen3_next_cost.train_flops_per_token(
        n_dense, n_exp, 10, 512, 8192, 1, 16, 256, 3, 32, 128, 128)
    assert per_token == pytest.approx(3 * 0.454e9, rel=2e-3)    # 1.36 G
    assert qwen3_next_cost.gdn_rule_flops_per_token(32, 128, 128) \
        == 7 * 128 * 128 * 32
    f, b = qwen3_next_cost.gdn_rule_cost("fwd", 10, 16, 32, 128, 128)
    assert f == 10 * 7 * 128 * 128 * 32
    assert b == 10 * 2 * (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32 * 2)
    f2, b2 = qwen3_next_cost.gdn_rule_cost("bwd", 10, 16, 32, 128, 128)
    assert f2 == 2 * f and b2 > b
    f, b = qwen3_next_cost.moe_experts_cost("fwd", 100, 16, 2048, 512)
    assert f == 100 * 3 * 2 * 2048 * 512
    assert b == 2 * (3 * 16 * 2048 * 512 + 2 * 100 * 2048)
    f, b = qwen3_next_cost.gqa_attention_call_cost("fwd", 4, 16, 2, 8192, 256)
    assert f == 2 * 8192 * 8192 * 256 * 64
    assert b == 4 * 8192 * 256 * 2 * (2 * 16 + 2 * 2)
    f, b = qwen3_next_cost.gqa_attention_call_cost("bwd", 1, 16, 16, 1024, 64)
    from benchmark.harness import flops
    assert (f, b) == flops.flash_attention_call_cost("bwd", 1, 16, 1024, 64)


def made_up_run():
    ms = 1e6
    step = "jit_step_fn(1)"
    g = "jit(step_fn)/ad.grad/"
    fwd = g + "jvp(Qwen3Next)/l_0/"
    again = g + "transpose(jvp(Qwen3Next))/ad.grad/jvp(Qwen3Next)/" \
        "checkpoint/rematted_computation/l_0/"
    bwd = g + "transpose(jvp(Qwen3Next))/ad.grad/jvp(Qwen3Next)/" \
        "checkpoint/l_0/"
    fwd_out = "(bf16[64,8192,256]{2,1,0}, f32[64,1,8192]{2,1,0})"
    dkdv_out = "(f32[64,8192,256]{2,1,0}, f32[64,8192,256]{2,1,0})"
    dq_out = "bf16[64,8192,256]{2,1,0}"
    # one steady step of 100 ms from t = 100 ms; [name, start, dur, op_name]
    ops = [
        ["%fusion.1", 100, 10, fwd + "gdn/gdn.proj/dot_general"],
        ["%while.2", 110, 20, fwd + "gdn/gdn.rule/while"],
        ["%fusion.3", 112, 6, fwd + "gdn/gdn.rule/while/body/dot_general"],
        ["%fusion.4", 130, 4, fwd + "moe/moe.route/sort"],
        ["%ragged-dot-none.5", 134, 2, "ragged-dot-none:"],
        ["%fusion.6", 136, 1, fwd + "moe/moe.shared/dot_general"],
        ["%attn.7", 137, 3, fwd.replace("l_0", "l_3") + "attn/pallas_call",
         fwd_out],
        ["%fusion.8", 140, 5, None],
        ["%fusion.9", 145, 10, again + "gdn/gdn.rule/dot_general"],
        ["%attn.10", 155, 3, again.replace("l_0", "l_3")
         + "attn/pallas_call", fwd_out],
        ["%fusion.11", 158, 22, bwd + "gdn/gdn.rule/transpose"],
        ["%attn.12", 180, 4, bwd.replace("l_0", "l_3") + "attn/pallas_call",
         dq_out],
        ["%attn.13", 184, 6, bwd.replace("l_0", "l_3") + "attn/pallas_call",
         dkdv_out],
        ["%ragged-dot.14", 190, 6, bwd + "moe/moe.experts/ragged_dot"],
        ["%fusion.15", 196, 4, bwd + "gdn/gdn.proj/transpose"]]
    events, model_ops = [], []
    for name, start, dur, op_name, *result in ops:
        row = [name, start * ms, dur * ms]
        if result:
            row.append(result[0] + " -> tpu_custom_call")
        events.append(row)
        model_ops.append([name, start * ms, dur * ms,
                          *model_scopes.classify_op(name, op_name)])
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
            for i, (t, rows) in enumerate([(197, 9000.0), (297, 11000.0)])]
    family = cells.load_family("qwen3_next")
    cell, config = cells.load_cell(CELL)
    return {"lanes": lanes, "summary": trace.summarize(lanes),
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "shapes": family.layer_shapes(cell, config),
            "model_trace": {"ops": model_ops},
            "program_trace": {"spans": sorted(waits + runs,
                                              key=lambda s: s[1]),
                              "ops": []}}


def test_scopes_are_read_from_op_names():
    assert model_scopes.classify(
        "jit(step_fn)/ad.grad/jvp(Qwen3Next)/l_1/gdn/gdn.rule/while/body/"
        "dot_general:") == ("gdn.rule", "forward")
    assert model_scopes.classify(
        "jit(step_fn)/ad.grad/transpose(jvp(Qwen3Next))/ad.grad/"
        "jvp(Qwen3Next)/checkpoint/rematted_computation/l_3/attn/"
        "pallas_call:") == ("attn", "recompute")
    assert model_scopes.classify(
        "jit(step_fn)/ad.grad/transpose(jvp(Qwen3Next))/l_0/moe/"
        "moe.experts/ragged_dot") == ("moe.experts", "backward")
    # a scope is a whole path component: no model scope in these
    assert model_scopes.classify("jit(step_fn)/ad.update/mul")[0] is None
    assert model_scopes.classify("jit(f)/attn_norm/mul")[0] is None
    assert model_scopes.classify(None) == (None, "forward")
    # the compiler's grouped-matmul kernels carry its own name and no scope
    assert model_scopes.classify_op("%ragged-dot-none.3", "ragged-dot-none:") \
        == ("moe.experts", "forward")
    assert model_scopes.classify_op("%fusion.3", "ragged-dot-none:")[0] is None


def test_new_readers_on_made_up_lanes():
    run = made_up_run()
    names = ("gdn_rule_ms", "gdn_proj_ms", "gdn_rule_roofline",
             "moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
             "moe_rows_here", "moe_load_max_over_mean", "full_attn_ms",
             "full_attn_roofline")
    read = {m: cells.load_reader("per_layer", m)(run) for m in names}
    # the while of 20 ms holds 6 ms of its body: self time adds up to 20
    assert read["gdn_rule_ms"] == pytest.approx(20 + 10 + 22)
    assert read["gdn_proj_ms"] == pytest.approx(10 + 4)
    assert read["moe_route_ms"] == pytest.approx(4)
    assert read["moe_experts_ms"] == pytest.approx(2 + 6)
    assert read["full_attn_ms"] == pytest.approx(3 + 3 + 4 + 6)
    assert read["moe_rows_here"] == pytest.approx(10000.0)
    assert read["moe_load_max_over_mean"] == pytest.approx(1.5)
    pk = peaks.peaks_for("TPU v5 lite")
    tokens = 4 * 8192
    rule = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
               for f, b in (qwen3_next_cost.gdn_rule_cost(
                   k, tokens, 16, 32, 128, 128) for k in ("fwd", "bwd")))
    assert read["gdn_rule_roofline"] == pytest.approx(
        100 * 3 * rule / 0.052)
    experts = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                  for f, b in (qwen3_next_cost.moe_experts_cost(
                      k, 10000.0, 16, 2048, 512) for k in ("fwd", "bwd")))
    assert read["moe_experts_roofline"] == pytest.approx(
        100 * 4 * experts / 0.008)
    attn = sum(n * max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
               for n, (f, b) in (
                   (2, qwen3_next_cost.gqa_attention_call_cost(
                       "fwd", 4, 16, 2, 8192, 256)),
                   (1, qwen3_next_cost.gqa_attention_call_cost(
                       "bwd", 4, 16, 2, 8192, 256))))
    assert read["full_attn_roofline"] == pytest.approx(100 * attn / 0.016)
    for m in ("gdn_rule_roofline", "moe_experts_roofline"):
        assert 0 < read[m]


def test_new_readers_find_nothing_in_a_program_without_the_scopes():
    run = made_up_run()
    run["model_trace"] = {"ops": [[op[0], op[1], op[2], None, op[4]]
                                  for op in run["model_trace"]["ops"]]}
    run["program_trace"]["spans"] = [
        [s[0], s[1], s[2], {"variants": 1}, s[4]]
        for s in run["program_trace"]["spans"]]
    for m in ("gdn_rule_ms", "gdn_proj_ms", "gdn_rule_roofline",
              "moe_route_ms", "moe_experts_ms", "moe_experts_roofline",
              "moe_rows_here", "moe_load_max_over_mean", "full_attn_ms",
              "full_attn_roofline"):
        assert cells.load_reader("per_layer", m)(run) is None, m
    run["model_trace"] = None
    run["cell"] = None
    assert cells.load_reader("per_layer", "gdn_rule_ms")(run) is None


def test_configuration_keeps_the_published_widths():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    _, config = cells.load_cell(CELL)
    entry = next(c for c in cells.load_manifest()["configs"]
                 if c["name"] == "qwen3_next_80b_a3b")
    assert entry["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"])
    d = config["deployment"]
    assert d["num_experts_published"] == row["config"]["num_experts"]
    assert d["chips_sharing_a_layer"] * config["num_experts"] \
        == row["config"]["num_experts"]
    assert config["vocab_size"] * d["chips_sharing_the_vocabulary"] \
        == row["config"]["vocab_size"]
