"""Expert layer: the least time the chip could take for a step's grouped
products where an expert is two matrices round ``relu(.)^2`` (a forward and
a backward pass a routed layer over ``moe_rows_here`` assignments: FLOPs from
the rows, bytes from the held experts' weights and the rows,
``nemotron_h_cost.relu2_experts_cost``) over the time under ``moe.experts``,
in percent.  ``moe_experts_roofline`` counts three products a row and reads
the gated experts' cells."""
from benchmark.harness import model_scopes
from benchmark.harness.flops import roofline_least_seconds
from benchmark.harness.nemotron_h_cost import relu2_experts_cost


def read(run):
    ms = model_scopes.scope_ms(run, "moe.experts")
    rows = model_scopes.run_argument_mean(run, "moe_rows_here")
    sh = run.get("shapes") or {}
    if not ms or rows is None or not run.get("peaks") \
            or "relu2_layers" not in sh:
        return None
    least = sum(
        roofline_least_seconds(*relu2_experts_cost(
            kind, rows, sh["experts_held"], sh["hidden"],
            sh["expert_width"]), run["peaks"])[0]
        for kind in ("fwd", "bwd"))
    return 100.0 * sh["relu2_layers"] * least / (1e-3 * ms)
