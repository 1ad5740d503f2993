"""State-space mixer: the least time the chip could take for a step's
selective scan (one forward and one backward pass of each Mamba-2 layer, the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
shapes: the recurrence itself, whatever computes it) over the time under
``ssd.scan``, in percent.  Work run again under recomputation adds to the
time only."""
from benchmark.harness import ssd_scopes
from benchmark.harness.flops import roofline_least_seconds
from benchmark.harness.nemotron_h_cost import ssd_scan_cost


def read(run):
    ms = ssd_scopes.scope_ms(run, "ssd.scan")
    sh = run.get("shapes") or {}
    if not ms or not run.get("peaks") or "ssd_layers" not in sh:
        return None
    tokens = sh["batch_per_chip"] * sh["seq_len"]
    least = sum(
        roofline_least_seconds(*ssd_scan_cost(
            kind, tokens, sh["ssd_heads"], sh["ssd_head_dim"],
            sh["ssd_groups"], sh["ssd_state"]), run["peaks"])[0]
        for kind in ("fwd", "bwd"))
    return 100.0 * sh["ssd_layers"] * least / (1e-3 * ms)
