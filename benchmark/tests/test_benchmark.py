"""Fast checks of the benchmark itself, on the CPU: the manifest against the
files, the loop at tiny sizes through the functions ``run.py`` calls, the
arithmetic against hand numbers, the trace reduction on a recorded chip trace.

    python -m pytest benchmark/tests -q
"""
import json
import math
import os
import re
import subprocess
import sys

import pytest

from benchmark import run as bench_run
from benchmark.harness import cells, flops, peaks, timing, trace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

TINY_GPT = {
    "family": "gpt", "n_vocab": 512, "n_ctx": 64, "n_embd": 64, "n_head": 2,
    "n_layer": 2,
    "assumed": {"mlp_ratio": 2, "compute_dtype": "float32",
                "attention_impl": "xla", "remat": True, "learning_rate": 1e-3}}
TINY_RESNET = {
    "family": "resnet", "depth": 50, "stage_sizes": [1, 1, 1, 1],
    "num_filters": 8, "image_size": 28, "num_classes": 10,
    "assumed": {"compute_dtype": "float32", "norm": "bn",
                "learning_rate": 0.05, "momentum": 0.9}}


def tiny_cell(name, chips, **extra):
    return {"name": name, "chips": chips,
            "feed": {"records": 32, "prefetch_depth": 2, "loader_threads": 2,
                     "loader_prefetch": 2},
            "strategy": {"builder": "AllReduce", "args": {}},
            "resource_spec": {"from_num_chips": chips}, "warmup_steps": 3,
            "reference": {"steps": 2, "micro_batches": 2, "train": True},
            "trace_steps": 3, **extra}


def run_tiny(cell, config, tmp_path, manifest, trace_on=False, seconds=1.5):
    import jax

    lines = []
    result = bench_run.run_cell(
        cell, config, manifest, seed=2 ** 31 + 77, seconds=seconds,
        trace=trace_on, devices=jax.devices()[:cell["chips"]],
        emit=lines.append, work_dir=str(tmp_path / "work"))
    return result, lines


def tiny_manifest(cell_name, unit):
    return {
        "end_to_end": [
            {"name": f"{unit}_per_s", "unit": f"{unit}/s"},
            {"name": "step_ms_p95", "unit": "ms"},
            {"name": "peak_hbm_gb", "unit": "GB"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": f"input_wait_ms.{unit}", "unit": "ms"},
            {"name": f"dispatch_ms.{unit}", "unit": "ms"},
            {"name": f"device_busy_ms.{unit}", "unit": "ms"}]}


def test_manifest_names_files_that_exist():
    m = cells.load_manifest()
    assert m["command"][1].startswith(m["paths"][0] + "/")
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for c in m["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(cells.REPO_DIR, c["file"]))
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell, config = cells.load_cell(w["name"], m)
        family = cells.load_family(config["family"])
        assert hasattr(family, "Job") and hasattr(family, "layer_shapes")
        mine = {x["name"] for x in cells.metrics_of(w["name"], "end_to_end", m)}
        assert "setup_s" in mine and len(mine) >= 2
        layer = cells.metrics_of(w["name"], "per_layer", m)
        assert layer
        for x in layer:       # a cell reports the metric its layer metric moves
            assert x["moves"] in mine, (w["name"], x["name"])
    for section in ("end_to_end", "per_layer"):
        for x in m[section]:
            assert NAME.match(x["name"]) and UNIT.match(x["unit"])
            assert x["better"] in ("lower", "higher")
            assert callable(cells.load_reader(section, x["name"]))
            for w in x.get("workloads", []):
                assert any(w == y["name"] for y in m["workloads"])
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)


def test_flop_arithmetic_against_hand_numbers():
    n_all, n_matmul = flops.gpt_param_count(50257, 1024, 24, 4096, 1024)
    assert n_all == 354_823_168
    per_token = flops.gpt_train_flops_per_token(n_matmul, 24, 1024, 1024)
    assert per_token == pytest.approx(2.27e9, rel=2e-3)
    n_all, n_matmul = flops.gpt_param_count(50257, 1280, 36, 5120, 1024)
    assert n_all == pytest.approx(774e6, rel=1e-3)
    assert flops.gpt_train_flops_per_token(
        n_matmul, 36, 1024, 1280) == pytest.approx(4.92e9, rel=2e-3)
    assert flops.resnet50_train_flops_per_image() == pytest.approx(12.27e9,
                                                                   rel=1e-3)
    # one head, S=1024, D=64: 2 and 5 causal matmuls of 1024*1024*64
    f, b = flops.flash_attention_call_cost("fwd", 1, 1, 1024, 64)
    assert (f, b) == (2 * 1024 * 1024 * 64, 4 * 1024 * 64 * 2)
    f, b = flops.flash_attention_call_cost("bwd", 2, 3, 1024, 64)
    assert (f, b) == (6 * 5 * 1024 * 1024 * 64, 6 * 8 * 1024 * 64 * 2)
    least, bound = flops.roofline_least_seconds(
        197e9, 1e9, peaks.peaks_for("TPU v5 lite"))
    assert least == pytest.approx(1e9 / 819e9) and bound == "bytes"


def test_unknown_chip_has_no_peaks():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


def test_throughput_and_p95_on_made_up_finishes():
    finish = [10.0 + 0.1 * i for i in range(21)]      # 20 steps in 2 s
    finish[7] += 0.05                                  # one late finish
    assert timing.throughput(finish, 256) == pytest.approx(20 * 256 / 2.0)
    steps = timing.intervals_ms(finish)
    assert len(steps) == 20 and max(steps) == pytest.approx(150.0)
    assert timing.percentile(steps, 95) == pytest.approx(100.0)
    assert timing.percentile(steps, 100) == pytest.approx(150.0)
    assert timing.percentile(list(range(1, 201)), 95) == 190
    assert timing.throughput([1.0], 256) is None


@pytest.mark.parametrize("losses,ref,ok", [
    ([5.0, 4.9, 4.8, 4.7, 4.6, 4.5, 4.4, 4.3, 4.2, 4.1], [5.0, 4.9], True),
    ([5.0, 4.9, float("nan"), 4.7], [5.0, 4.9], False),
    ([5.0, 4.9, 4.8, 4.7], [5.0, 4.5], False),        # leaves the reference
    ([5.0, 4.9, 5.0, 5.1], [5.0, 4.9], False),        # does not fall
    ([5.0], [5.0, 4.9], False),
])
def test_correct_needs_finite_matching_falling_losses(losses, ref, ok):
    assert timing.loss_checks(losses, ref, 2e-2)[0] is ok


def test_gpt_loop_on_the_cpu(tmp_path):
    cell = tiny_cell("tiny.gpt", 1, batch=8, seq_len=64)
    result, lines = run_tiny(cell, TINY_GPT, tmp_path,
                             tiny_manifest("tiny.gpt", "tokens"))
    assert result["correct"], lines[-1]["not_correct_because"]
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) >= {"tokens_per_s", "setup_s"}
    assert result["metrics"]["tokens_per_s"]["value"] > 0
    setup = next(x for x in lines if x["phase"] == "setup")
    assert len(setup["reference_losses"]) == 2
    assert lines[-1]["compilations_in_window"] == 0


def test_resnet_loop_on_the_cpu(tmp_path):
    cell = tiny_cell("tiny.resnet", 1, batch=16)
    result, lines = run_tiny(cell, TINY_RESNET, tmp_path,
                             tiny_manifest("tiny.resnet", "images"))
    assert result["correct"], lines[-1]["not_correct_because"]
    assert set(result["metrics"]) >= {"images_per_s", "step_ms_p95", "setup_s"}
    assert lines[-1]["compilations_in_window"] == 0


def test_four_device_path_on_virtual_devices(tmp_path):
    cell = tiny_cell("tiny.dp4", 4, batch=8, seq_len=64,
                     strategy={"builder": "AllReduce",
                               "args": {"sharded_update": "sharded"}},
                     reference={"steps": 1, "micro_batches": 2,
                                "train": False}, params_on="host")
    result, lines = run_tiny(cell, TINY_GPT, tmp_path,
                             tiny_manifest("tiny.dp4", "tokens"))
    assert result["correct"], lines[-1]["not_correct_because"]
    assert result["device"]["count"] == 4
    setup = next(x for x in lines if x["phase"] == "setup")
    assert setup["mesh_holds_the_devices"] and setup["batch_devices"] == 4
    assert setup["opt_state_sharded_leaves"] > 0
    replicas = next(x for x in lines if x["phase"] == "replicas")
    assert replicas["replicated_leaves_bit_equal"]


def test_traced_run_reports_host_spans(tmp_path):
    # a CPU trace has no device plane: the device readers return nothing
    # and are left out, the host-span readers report
    cell = tiny_cell("tiny.gpt", 1, batch=8, seq_len=64)
    result, _ = run_tiny(cell, TINY_GPT, tmp_path,
                         tiny_manifest("tiny.gpt", "tokens"), trace_on=True,
                         seconds=3.0)
    assert set(result["metrics"]) == {"input_wait_ms.tokens",
                                      "dispatch_ms.tokens"}
    assert "busy_s" not in result["device"]


def test_command_stops_at_the_device_check_on_a_cpu():
    m = cells.load_manifest()
    out = subprocess.run(
        [sys.executable, os.path.join(cells.REPO_DIR, m["command"][1]),
         "--workload", m["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def chip_trace_run():
    lanes = trace.load_lanes(os.path.join(
        DATA, "gpt2_medium_chip_trace.json.gz"))
    cell, config = cells.load_cell("gpt2_medium.train_fed")
    family = cells.load_family(config["family"])
    return {"lanes": lanes, "summary": trace.summarize(lanes),
            "peaks": peaks.peaks_for("TPU v5 lite"), "spans": {},
            "shapes": family.layer_shapes(cell, config)}


def test_trace_reduction_on_the_recorded_chip_trace():
    # cut from gpt2_medium.train_fed's own traced run on a v5e (PR 24): a
    # cut step, three whole ones; the reduction keeps the middle two
    run = chip_trace_run()
    s = run["summary"]
    assert s["planes"] == ["/device:TPU:0"] and s["steps"] == 2
    assert s["window_s"] == pytest.approx(2.8685, rel=1e-4)
    assert 1.0 - s["busy_s"] / s["window_s"] < 1e-3          # idle share
    assert s["per_device"][0]["collective_s"] == 0.0
    assert s["device_ops"][0][0] == "attn"
    # self time: the ops add up to the busy time, the whiles' bodies once
    assert sum(trace.self_seconds(
        trace.events_of(run["lanes"], s["planes"][0], trace.OPS_LINE),
        *s["window"]).values()) == pytest.approx(s["busy_s"], rel=1e-6)
    assert all(g[0] == "bench.block" for g in s["idle_gaps"][:3])
    read = {m: cells.load_reader("per_layer", m)(run) for m in (
        "device_busy_ms.tokens", "flash_attn_ms", "flash_attn_roofline",
        "collective_ms", "input_wait_ms.tokens")}
    assert read["device_busy_ms.tokens"] == pytest.approx(1434.2, rel=1e-4)
    assert read["flash_attn_ms"] == pytest.approx(694.5, rel=1e-3)
    assert read["flash_attn_roofline"] == pytest.approx(5.42, rel=1e-2)
    assert read["collective_ms"] == 0.0
    assert read["input_wait_ms.tokens"] is None      # no spans handed in


def test_exposed_collective_time_on_made_up_lanes():
    ms = 1e6
    step = "jit_step_fn(1)"
    lanes = [
        {"plane": "/device:TPU:0", "line": trace.MODULES_LINE, "events": [
            [step, 0, 50 * ms], [step, 100 * ms, 100 * ms],
            [step, 210 * ms, 100 * ms], [step, 320 * ms, 30 * ms]]},
        {"plane": "/device:TPU:0", "line": trace.OPS_LINE, "events": [
            ["%fusion.1", 100 * ms, 60 * ms],
            ["%all-reduce.1", 160 * ms, 20 * ms],          # all exposed
            ["%fusion.2", 180 * ms, 20 * ms],
            ["%fusion.1", 210 * ms, 60 * ms],
            ["%all-gather-done.3", 270 * ms, 30 * ms]]},
        {"plane": "/device:TPU:0", "line": trace.ASYNC_LINE, "events": [
            ["%all-gather-start.3", 250 * ms, 50 * ms]]},   # 20 hidden
        {"plane": "/host:CPU", "line": "python", "events": [
            ["bench.input_wait", 200 * ms, 9 * ms],
            ["bench.block", 270 * ms, 45 * ms]]}]
    s = trace.summarize(lanes)
    d = s["per_device"][0]
    assert s["steps"] == 2 and s["window_s"] == pytest.approx(0.210)
    assert d["busy_s"] == pytest.approx(0.190)
    assert d["collective_s"] == pytest.approx(0.070)
    assert d["collective_exposed_s"] == pytest.approx(0.050)
    assert s["idle_gaps"][0] == ["bench.input_wait", pytest.approx(0.010)]
    assert s["idle_gaps"][1] == ["bench.block", pytest.approx(0.010)]
    assert dict(s["device_ops"])["fusion"] == pytest.approx(0.140)
    assert dict(s["device_ops"])["all-gather-done"] == pytest.approx(0.030)
    assert trace.summarize(lanes[1:]) is None        # no step events
