"""Expert layer: the least time the chip could take for a step's grouped
products (a forward and a backward pass a layer over ``moe_rows_here``
assignments: FLOPs from the rows, bytes from the held experts' weights and
the rows) over the time under ``moe.experts``, in percent."""
from benchmark.harness import model_scopes
from benchmark.harness.flops import roofline_least_seconds
from benchmark.harness.qwen3_next_cost import moe_experts_cost


def read(run):
    ms = model_scopes.scope_ms(run, "moe.experts")
    rows = model_scopes.run_argument_mean(run, "moe_rows_here")
    sh = run.get("shapes") or {}
    if not ms or rows is None or not run.get("peaks") \
            or "moe_layers" not in sh:
        return None
    least = sum(
        roofline_least_seconds(*moe_experts_cost(
            kind, rows, sh["experts_held"], sh["hidden"],
            sh["expert_width"]), run["peaks"])[0]
        for kind in ("fwd", "bwd"))
    return 100.0 * sh["moe_layers"] * least / (1e-3 * ms)
