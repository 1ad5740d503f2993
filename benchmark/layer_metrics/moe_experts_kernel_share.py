"""Expert layer: the share of ``moe_experts_ms`` spent inside the repo's own
Pallas kernels (``ops/pallas/grouped_matmul.py``), in percent: the custom
calls to ``tpu_custom_call`` that carry the model's ``moe.experts`` scope in
their ``op_name`` (as ``gdn_rule_kernel_share`` finds those under
``gdn.rule``) over the self time of everything under that scope.  What is
left is the work round the kernels (the activation, the casts, the rows'
weights, the lists of visits).  The compiler's own grouped kernels
(``lax.ragged_dot``) are custom calls too, but carry no ``op_name``:
``model_scopes`` counts them to the scope by their name, and they are not
counted here.  Nothing where none of the repo's kernels runs under the
scope: the ``ragged_dot`` path, or no such scope at all."""
from benchmark.harness import model_scopes, trace

SCOPE = "moe.experts"


def kernel_seconds(run):
    """Seconds inside the steady window in the repo's kernels under
    ``SCOPE``."""
    rec, s = model_scopes.of(run), run.get("summary")
    if not rec or not s or not run.get("lanes"):
        return 0.0
    under = {(op[0], op[1]) for op in rec["ops"] if op[3] == SCOPE
             and not trace.stem(op[0]).startswith(model_scopes.GROUPED)}
    lo, hi = s["window"]
    return sum(e[2] / 1e9
               for e in trace.events_of(run["lanes"], s["planes"][0],
                                        trace.OPS_LINE)
               if len(e) > 3 and e[3].endswith("-> tpu_custom_call")
               and (e[0], e[1]) in under and e[1] >= lo and e[1] + e[2] <= hi)


def read(run):
    kernels = kernel_seconds(run)
    ms = model_scopes.scope_ms(run, SCOPE)
    if not kernels or not ms:
        return None
    return 100.0 * (1e3 * kernels / run["summary"]["steps"]) / ms
