"""Gated DeltaNet: the least time the chip could take for a step's delta
rule (one forward and one backward pass of each DeltaNet layer, the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the shapes: the
recurrence itself, whatever computes it) over the time under ``gdn.rule``,
in percent.  Work run again under recomputation adds to the time only."""
from benchmark.harness import model_scopes
from benchmark.harness.flops import roofline_least_seconds
from benchmark.harness.qwen3_next_cost import gdn_rule_cost


def read(run):
    ms = model_scopes.scope_ms(run, "gdn.rule")
    sh = run.get("shapes") or {}
    if not ms or not run.get("peaks") or "gdn_layers" not in sh:
        return None
    tokens = sh["batch_per_chip"] * sh["seq_len"]
    least = sum(
        roofline_least_seconds(*gdn_rule_cost(
            kind, tokens, sh["gdn_key_heads"], sh["gdn_value_heads"],
            sh["gdn_key_dim"], sh["gdn_value_dim"]), run["peaks"])[0]
        for kind in ("fwd", "bwd"))
    return 100.0 * sh["gdn_layers"] * least / (1e-3 * ms)
