"""``attn_layout_ms`` on a made-up ``model_trace``: a ``copy`` under the
model's ``attn`` scope, one outside it, a kernel call under the scope.

    python -m pytest benchmark/tests -q
"""
import pytest

from benchmark.harness import cells, model_scopes, trace

MS = 1e6


def made_up_run(fold=True, scoped=True):
    """One steady step of 100 ms from t = 100 ms.  Under ``h_0/attn``: the
    fold of q before the kernel (3 ms), the kernel (5 ms), the output's
    unfold in the backward pass (2 ms) and a fusion (4 ms); a copy of the
    MLP's (7 ms); a fold in the step before the window."""
    g = "jit(step_fn)/ad.grad/"
    attn = (g + "jvp(GPT)/h_0/attn/") if scoped else (g + "jvp(GPT)/h_0/")
    back = g + "transpose(jvp(GPT))/ad.grad/jvp(GPT)/checkpoint/h_0/" \
        + ("attn/" if scoped else "")
    out = "(bf16[512,1024,64]{2,1,0}, f32[512,1,1024]{2,1,0})"
    ops = [
        ["%copy.1", 60, 3, attn + "transpose", None],   # before the window
        ["%copy.2", 100, 3, attn + "transpose", None],
        ["%attn.3", 103, 5, attn + "pallas_call", out],
        ["%copy.4", 108, 7, g + "jvp(GPT)/h_0/mlp_in/dot_general", None],
        ["%fusion.5", 115, 4, attn + "qkv/dot_general", None],
        ["%copy.6", 150, 2, back + "reshape", None]]
    if not fold:
        ops = [op for op in ops if "copy" not in op[0] or "mlp" in op[3]]
    events, model_ops = [], []
    for name, start, dur, op_name, result in ops:
        events.append([name, start * MS, dur * MS]
                      + ([result + " -> tpu_custom_call"] if result else []))
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


def test_the_copies_under_the_attention_scope_are_counted():
    run = made_up_run()
    assert run["summary"]["steps"] == 1
    read = cells.load_reader("per_layer", "attn_layout_ms")
    # 3 + 2 ms: not the MLP's copy, the kernel, the fusion, nor the copy of
    # the step before the window
    assert read(run) == pytest.approx(5.0)


def test_zero_where_the_scope_is_there_and_nothing_is_copied_under_it():
    read = cells.load_reader("per_layer", "attn_layout_ms")
    assert read(made_up_run(fold=False)) == 0.0


def test_nothing_is_read_without_the_scope_or_without_a_trace():
    read = cells.load_reader("per_layer", "attn_layout_ms")
    assert read(made_up_run(scoped=False)) is None
    assert read({"model_trace": None, "cell": None}) is None
