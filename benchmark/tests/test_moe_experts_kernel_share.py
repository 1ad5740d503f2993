"""``moe_experts_kernel_share`` on a made-up ``model_trace``: the repo's
kernels under ``moe.experts``, a fusion under it, the compiler's own grouped
kernel (a custom call without an ``op_name``) and a flash kernel under
``attn``.

    python -m pytest benchmark/tests -q
"""
import pytest

from benchmark.harness import cells, model_scopes, trace

MS = 1e6


def made_up_run(own_kernels=True):
    """One steady step of 100 ms from t = 100 ms.  Under ``moe.experts``:
    the up and the down product (3 ms and 2 ms), the rows' cotangent and the
    weights' gradient of one of them (3 ms and 4 ms) and a fusion of 4 ms
    (the activation); under ``attn`` a flash kernel (5 ms); one product of
    the step before the window.  Without the repo's kernels the four
    products are the compiler's ``ragged-dot-none``, which carry no
    ``op_name``."""
    g = "jit(step_fn)/ad.grad/"
    fwd = g + "jvp(NemotronH)/l_1/ffn/moe.experts/"
    bwd = g + "transpose(jvp(NemotronH))/ad.grad/jvp(NemotronH)/" \
        "checkpoint/l_1/ffn/moe.experts/"
    up, down, dw = ("f32[18432,1856]{1,0}", "f32[18432,2688]{1,0}",
                    "f32[8,2688,1856]{2,1,0}")
    name = "%moe.experts" if own_kernels else "%ragged-dot-none"
    ops = [
        [name + ".0", 60, 3, fwd + "pallas_call", up],  # before the window
        [name + ".1", 100, 3, fwd + "pallas_call", up],
        ["%fusion.2", 103, 4, fwd + "integer_pow", None],
        [name + ".3", 107, 2, fwd + "pallas_call", down],
        ["%attn.4", 110, 5, g + "jvp(NemotronH)/l_5/attn/pallas_call",
         "(bf16[2,8192,4096]{2,1,0}, f32[64,1,8192]{2,1,0})"],
        [name + ".5", 120, 3, bwd + "pallas_call",
         "bf16[18432,2688]{1,0}"],
        [name + ".6", 123, 4, bwd + "pallas_call", dw]]
    events, model_ops = [], []
    for op, start, dur, op_name, result in ops:
        if not own_kernels and op.startswith("%ragged"):
            op_name = None
        events.append([op, start * MS, dur * MS]
                      + ([result + " -> tpu_custom_call"] if result else []))
        model_ops.append([op, start * MS, dur * MS,
                          *model_scopes.classify_op(op, op_name)])
    step = "jit_step_fn(1)"
    lanes = [
        {"plane": "/device:TPU:0", "line": "XLA Modules", "events": [
            [step, 0, 90 * MS], [step, 100 * MS, 100 * MS],
            [step, 200 * MS, 50 * MS]]},
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": events}]
    return {"lanes": lanes, "summary": trace.summarize(lanes),
            "model_trace": {"ops": model_ops}}


def test_the_share_is_the_repos_kernels_under_the_scope_over_the_scope():
    run = made_up_run()
    assert run["summary"]["steps"] == 1
    # 3 + 2 + 3 + 4 ms of kernels of the 16 ms under moe.experts; the flash
    # kernel and the product outside the window count for neither
    assert cells.load_reader("per_layer", "moe_experts_ms")(run) \
        == pytest.approx(16.0)
    assert cells.load_reader("per_layer", "moe_experts_kernel_share")(run) \
        == pytest.approx(100.0 * 12 / 16)


def test_the_compilers_grouped_kernels_are_not_the_repos():
    read = cells.load_reader("per_layer", "moe_experts_kernel_share")
    # the ragged_dot path: the same time under the scope (by the kernels'
    # name), custom calls all, none of them the repo's
    run = made_up_run(own_kernels=False)
    assert cells.load_reader("per_layer", "moe_experts_ms")(run) \
        == pytest.approx(16.0)
    assert read(run) is None
    # a program without the model's scopes, and a run without a trace
    run = made_up_run()
    run["model_trace"] = {"ops": [[*op[:3], None, op[4]]
                                  for op in run["model_trace"]["ops"]]}
    assert read(run) is None
    assert read({"model_trace": None, "cell": None}) is None


def test_the_metric_lists_the_cells_whose_kernels_run():
    per_layer = {m["name"]: m for m in cells.load_manifest()["per_layer"]}
    mine = per_layer["moe_experts_kernel_share"]
    assert mine["layer"] == per_layer["moe_experts_ms"]["layer"]
    assert mine["moves"] == "tokens_per_s" and mine["unit"] == "%"
    # the kernels' tile rule takes both routed cells' shapes
    assert mine["workloads"] == per_layer["moe_experts_ms"]["workloads"]
