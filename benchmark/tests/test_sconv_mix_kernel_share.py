"""``sconv_mix_kernel_share`` on a made-up ``sconv_trace``: custom calls
under ``sconv.mix`` and under ``attn``, fusions under ``sconv.mix`` and
``sconv.proj``.

    python -m pytest benchmark/tests -q
"""
import pytest

from benchmark.harness import cells, lfm2_scopes, trace

MS = 1e6
CELL = "lfm2_8b_a1b.train_fed"


def made_up_run(mix_kernels=True):
    """One steady step of 100 ms from t = 100 ms.  Under ``sconv.mix``: the
    forward kernel (2 ms), the same recomputed (2 ms), the backward kernel
    (4 ms), and a fusion of 0.5 ms that sums the taps' partial gradients;
    under ``sconv.proj`` a product (10 ms); under ``attn`` a flash kernel
    (5 ms); one kernel of the step before the window."""
    g = "jit(step_fn)/ad.grad/"
    fwd = g + "jvp(Lfm2)/l_0/sconv/sconv.mix/"
    again = g + "transpose(jvp(Lfm2))/ad.grad/jvp(Lfm2)/checkpoint/" \
        "rematted_computation/l_0/sconv/sconv.mix/"
    bwd = g + "transpose(jvp(Lfm2))/ad.grad/jvp(Lfm2)/checkpoint/l_0/" \
        "sconv/sconv.mix/"
    y = "bf16[4,8192,2048]{2,1,0}"
    grads = "(bf16[4,8192,2048]{2,1,0}, bf16[4,8192,2048]{2,1,0}, " \
        "bf16[4,8192,2048]{2,1,0}, f32[4,16,3,2048]{3,2,1,0})"
    call = "pallas_call" if mix_kernels else "checkpoint/mul"
    ops = [
        ["%sconv.mix.0", 60, 2, fwd + call, y],         # before the window
        ["%fusion.1", 100, 10, g + "jvp(Lfm2)/l_0/sconv/sconv.proj/"
         "dot_general", None],
        ["%sconv.mix.2", 110, 2, fwd + call, y],
        ["%attn.3", 112, 5, g + "jvp(Lfm2)/l_1/attn/pallas_call",
         "(bf16[128,8192,64]{2,1,0}, f32[128,1,8192]{2,1,0})"],
        ["%sconv.mix.4", 150, 2, again + call, y],
        ["%sconv.mix.5", 160, 4, bwd + call, grads],
        ["%fusion.6", 164, 0.5, bwd + "reduce_sum", None]]
    events, sconv_ops = [], []
    for name, start, dur, op_name, result in ops:
        kernel = result and (mix_kernels or "attn" in name)
        events.append([name, start * MS, dur * MS]
                      + ([result + " -> tpu_custom_call"] if kernel else []))
        sconv_ops.append([name, start * MS, dur * MS,
                          lfm2_scopes.classify(op_name)])
    step = "jit_step_fn(1)"
    lanes = [
        {"plane": "/device:TPU:0", "line": "XLA Modules", "events": [
            [step, 0, 90 * MS], [step, 100 * MS, 100 * MS],
            [step, 200 * MS, 50 * MS]]},
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": events}]
    return {"lanes": lanes, "summary": trace.summarize(lanes),
            "sconv_trace": {"ops": sconv_ops}}


def test_the_share_is_the_kernels_under_the_mix_scope_over_the_scope():
    run = made_up_run()
    assert run["summary"]["steps"] == 1
    read = cells.load_reader("per_layer", "sconv_mix_kernel_share")
    # 2 + 2 + 4 ms of kernels of the 8.5 ms under sconv.mix; the attention
    # kernel, the projection and the kernel outside the window count for
    # neither
    assert cells.load_reader("per_layer", "sconv_mix_ms")(run) \
        == pytest.approx(8.5)
    assert read(run) == pytest.approx(100.0 * 8 / 8.5)


def test_nothing_is_read_where_no_kernel_runs_under_the_scope():
    read = cells.load_reader("per_layer", "sconv_mix_kernel_share")
    # the XLA form: the same time under the scope, no custom call
    run = made_up_run(mix_kernels=False)
    assert cells.load_reader("per_layer", "sconv_mix_ms")(run) \
        == pytest.approx(8.5)
    assert read(run) is None
    # a program without the mixer's scopes, and a run without a trace
    run = made_up_run()
    run["sconv_trace"] = {"ops": [[*op[:3], None]
                                  for op in run["sconv_trace"]["ops"]]}
    assert read(run) is None
    assert read({"sconv_trace": None, "cell": None}) is None


def test_the_cell_is_listed_under_the_share():
    per_layer = {m["name"]: m for m in cells.load_manifest()["per_layer"]}
    share = per_layer["sconv_mix_kernel_share"]
    assert share["workloads"] == [CELL]
    assert share["layer"] == per_layer["sconv_mix_ms"]["layer"]
    assert share["moves"] == "tokens_per_s"
