"""``gdn_rule_kernel_share`` on a made-up ``model_trace``: custom calls under
``gdn.rule`` and under ``attn``, a fusion under ``gdn.rule``.

    python -m pytest benchmark/tests -q
"""
import pytest

from benchmark.harness import cells, model_scopes, trace

MS = 1e6


def made_up_run(rule_kernels=True):
    """One steady step of 100 ms from t = 100 ms.  Under ``gdn.rule``: the
    forward kernel (8 ms), the same recomputed (8 ms), the backward kernel
    (12 ms), and a fusion of 2 ms that lays the gates out; under ``attn`` a
    flash kernel (5 ms); one kernel of the step before the window."""
    g = "jit(step_fn)/ad.grad/"
    fwd = g + "jvp(Qwen3Next)/l_0/gdn/gdn.rule/"
    again = g + "transpose(jvp(Qwen3Next))/ad.grad/jvp(Qwen3Next)/" \
        "checkpoint/rematted_computation/l_0/gdn/gdn.rule/"
    bwd = g + "transpose(jvp(Qwen3Next))/ad.grad/jvp(Qwen3Next)/" \
        "checkpoint/l_0/gdn/gdn.rule/"
    o = "(bf16[4,8192,4096]{2,1,0}, f32[4,32,8,128,128]{4,3,2,1,0})"
    grads = "(bf16[4,8192,2048]{2,1,0}, bf16[4,8192,2048]{2,1,0}, " \
        "bf16[4,8192,4096]{2,1,0}, f32[4,32,64,8,128]{4,3,2,1,0})"
    call = "pallas_call" if rule_kernels else "while"
    ops = [
        ["%gdn.0", 60, 8, fwd + call, o],            # before the window
        ["%fusion.1", 100, 2, fwd + "cumsum", None],
        ["%gdn.2", 102, 8, fwd + call, o],
        ["%attn.3", 110, 5, g + "jvp(Qwen3Next)/l_3/attn/pallas_call",
         "(bf16[64,8192,256]{2,1,0}, f32[64,1,8192]{2,1,0})"],
        ["%fusion.4", 115, 30, g + "jvp(Qwen3Next)/l_0/gdn/gdn.proj/dot",
         None],
        ["%gdn.5", 145, 8, again + call, o],
        ["%gdn.6", 153, 12, bwd + call, grads]]
    events, model_ops = [], []
    for name, start, dur, op_name, result in ops:
        kernel = result and (rule_kernels or "attn" in name)
        events.append([name, start * MS, dur * MS]
                      + ([result + " -> tpu_custom_call"] if kernel else []))
        model_ops.append([name, start * MS, dur * MS,
                          *model_scopes.classify_op(name, op_name)])
    step = "jit_step_fn(1)"
    lanes = [
        {"plane": "/device:TPU:0", "line": "XLA Modules", "events": [
            [step, 0, 90 * MS], [step, 100 * MS, 100 * MS],
            [step, 200 * MS, 50 * MS]]},
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": events}]
    return {"lanes": lanes, "summary": trace.summarize(lanes),
            "model_trace": {"ops": model_ops}}


def test_the_share_is_the_kernels_under_the_rule_scope_over_the_scope():
    run = made_up_run()
    assert run["summary"]["steps"] == 1
    read = cells.load_reader("per_layer", "gdn_rule_kernel_share")
    # 8 + 8 + 12 ms of kernels of the 30 ms under gdn.rule; the attention
    # kernel and the kernel outside the window count for neither
    assert cells.load_reader("per_layer", "gdn_rule_ms")(run) \
        == pytest.approx(30.0)
    assert read(run) == pytest.approx(100.0 * 28 / 30)


def test_nothing_is_read_where_no_kernel_runs_under_the_scope():
    read = cells.load_reader("per_layer", "gdn_rule_kernel_share")
    # the scan form: the same time under the scope, no custom call
    run = made_up_run(rule_kernels=False)
    assert cells.load_reader("per_layer", "gdn_rule_ms")(run) \
        == pytest.approx(30.0)
    assert read(run) is None
    # a program without the model's scopes, and a run without a trace
    run = made_up_run()
    run["model_trace"] = {"ops": [[*op[:3], None, op[4]]
                                  for op in run["model_trace"]["ops"]]}
    assert read(run) is None
    assert read({"model_trace": None, "cell": None}) is None
