"""Pallas kernels: the least time the chip could take for the attention
calls of a step (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, from the shapes) over the time the flash kernels took, in percent.

A forward kernel returns ``(out bf16, row statistics f32)``; the backward is
two kernels, dq (one result) and dk/dv (two bf16 results), and the pair is
charged one backward's cost, at the dk/dv call."""
from benchmark.harness.cells import load_module
from benchmark.harness.flops import (flash_attention_call_cost,
                                     roofline_least_seconds)


def read(run):
    calls = load_module("layer_metrics", "flash_attn_ms").flash_calls(run)
    if not calls or not run["peaks"]:
        return None
    sh = run["shapes"]
    least = 0.0
    for _, _, result in calls:
        parts = result.strip("()").split("}, ")
        if len(parts) == 2 and parts[1].startswith("f32"):
            kind = "fwd"
        elif len(parts) == 2:
            kind = "bwd"
        else:
            continue            # dq: charged with its dk/dv call
        flops, moved = flash_attention_call_cost(
            kind, sh["batch_per_chip"], sh["heads"], sh["seq_len"],
            sh["head_dim"])
        least += roofline_least_seconds(flops, moved, run["peaks"])[0]
    return 100.0 * least / sum(c[1] for c in calls)
