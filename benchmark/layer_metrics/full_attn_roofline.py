"""Pallas kernels: the least time the chip could take for a step's
softmax-attention calls (``qwen3_next_cost.gqa_attention_call_cost``: K and
V at their own head count) over the time the kernels took, in percent.

The kernels are told apart by their results: the forward returns the output
and the row statistics (two results of different shapes), dq one result,
dk/dv two results of one shape (float32 per query head under grouped K/V
heads).  The backward pair is charged one backward's cost, at the dk/dv
call; every call is charged, a recomputed forward too, as
``flash_attn_roofline`` does: the share is the kernels' own."""
from benchmark.harness.cells import load_module
from benchmark.harness.flops import roofline_least_seconds
from benchmark.harness.qwen3_next_cost import gqa_attention_call_cost


def kind_of(result):
    parts = result.strip("()").split("}, ")
    if len(parts) != 2:
        return None                     # dq: charged with its dk/dv call
    shapes = [p.split("{")[0] for p in parts]
    return "bwd" if shapes[0] == shapes[1] else "fwd"


def read(run):
    calls = load_module("layer_metrics", "full_attn_ms").attn_calls(run)
    sh = run.get("shapes") or {}
    if not calls or not run.get("peaks") or "kv_heads" not in sh:
        return None
    least = 0.0
    for _, _, result in calls:
        kind = kind_of(result)
        if kind is None:
            continue
        flops, moved = gqa_attention_call_cost(
            kind, sh["batch_per_chip"], sh["heads"], sh["kv_heads"],
            sh["seq_len"], sh["head_dim"])
        least += roofline_least_seconds(flops, moved, run["peaks"])[0]
    return 100.0 * least / sum(c[1] for c in calls)
