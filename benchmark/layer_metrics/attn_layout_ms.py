"""Pallas kernels: milliseconds per step on device 0 in ``copy`` operations
under the model's ``attn`` scope: what the program pays to lay the attention
kernels' operands and results out anew (the fold to ``(B*H, S, D)`` and back,
slices of a fused projection made S-minor), which is data movement and no
arithmetic.  0 where the scope is there and no copy runs under it; nothing
where no operation carries the scope (another model, or a program without
the model's scopes)."""
from benchmark.harness import model_scopes, trace

SCOPE = "attn"


def read(run):
    rec, s = model_scopes.of(run), run.get("summary")
    if not rec or not s:
        return None
    under = [op for op in rec["ops"] if op[3] == SCOPE]
    if not under:
        return None
    lo, hi = s["window"]
    copies = [op for op in under if trace.stem(op[0]) == "copy"]
    busy = sum(b - a for a, b in trace.clip(copies, lo, hi))
    return busy / 1e6 / s["steps"]
