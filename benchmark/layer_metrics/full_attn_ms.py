"""Pallas kernels: milliseconds per step in the flash attention kernels of
the softmax-attention layers on device 0, the custom calls to
``tpu_custom_call`` under the model's ``attn`` scope.  (``flash_attn_ms``
takes the same calls by their instruction's name; its roofline sibling
cannot tell the kernels apart under grouped K/V heads, so this pair reads
that configuration.)"""
from benchmark.harness import model_scopes, trace


def attn_calls(run):
    """``[name, seconds, result type]`` of the kernels under ``attn`` that
    run whole inside the steady window."""
    rec, s = model_scopes.of(run), run.get("summary")
    if not rec or not s or not run.get("lanes"):
        return []
    under = {(op[0], op[1]) for op in rec["ops"] if op[3] == "attn"}
    lo, hi = s["window"]
    return [[e[0], e[2] / 1e9, e[3].rsplit(" -> ", 1)[0]]
            for e in trace.events_of(run["lanes"], s["planes"][0],
                                     trace.OPS_LINE)
            if len(e) > 3 and e[3].endswith("-> tpu_custom_call")
            and (e[0], e[1]) in under and e[1] >= lo and e[1] + e[2] <= hi]


def read(run):
    calls = attn_calls(run)
    if not calls:
        return None
    return 1e3 * sum(c[1] for c in calls) / run["summary"]["steps"]
