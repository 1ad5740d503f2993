"""``ssd_scan_kernel_share`` on a made-up ``ssd_trace``: custom calls under
``ssd.scan`` and under ``attn``, fusions under ``ssd.scan``.

    python -m pytest benchmark/tests -q
"""
import pytest

from benchmark.harness import cells, ssd_scopes, trace

MS = 1e6


def made_up_run(scan_kernels=True):
    """One steady step of 100 ms from t = 100 ms.  Under ``ssd.scan``: the
    forward kernel (3 ms), the same recomputed (3 ms), the backward kernel
    (8 ms), and a fusion of 1 ms that lays the step rows out; under ``attn``
    a flash kernel (5 ms); one kernel of the step before the window."""
    g = "jit(step_fn)/ad.grad/"
    fwd = g + "jvp(NemotronH)/l_0/ssd/ssd.scan/"
    again = g + "transpose(jvp(NemotronH))/ad.grad/jvp(NemotronH)/" \
        "checkpoint/rematted_computation/l_0/ssd/ssd.scan/"
    bwd = g + "transpose(jvp(NemotronH))/ad.grad/jvp(NemotronH)/" \
        "checkpoint/l_0/ssd/ssd.scan/"
    y = "(bf16[2,8192,4096]{2,1,0}, f32[2,8,8,512,128]{4,3,2,1,0})"
    grads = "(bf16[2,8192,4096]{2,1,0}, bf16[2,8192,1024]{2,1,0}, " \
        "bf16[2,8192,1024]{2,1,0}, f32[2,64,64,8,128]{4,3,2,1,0})"
    call = "pallas_call" if scan_kernels else "while"
    ops = [
        ["%ssd.scan.0", 60, 3, fwd + call, y],          # before the window
        ["%fusion.1", 100, 1, fwd + "_rows/reduce_window_sum", None],
        ["%ssd.scan.2", 101, 3, fwd + call, y],
        ["%attn.3", 110, 5, g + "jvp(NemotronH)/l_5/attn/pallas_call",
         "(bf16[2,8192,4096]{2,1,0}, f32[32,1,8192]{2,1,0})"],
        ["%fusion.4", 115, 30, g + "jvp(NemotronH)/l_0/ssd/ssd.proj/dot",
         None],
        ["%ssd.scan.5", 145, 3, again + call, y],
        ["%ssd.scan.6", 153, 8, bwd + call, grads]]
    events, ssd_ops = [], []
    for name, start, dur, op_name, result in ops:
        kernel = result and (scan_kernels or "attn" in name)
        events.append([name, start * MS, dur * MS]
                      + ([result + " -> tpu_custom_call"] if kernel else []))
        ssd_ops.append([name, start * MS, dur * MS,
                        ssd_scopes.classify(op_name)])
    step = "jit_step_fn(1)"
    lanes = [
        {"plane": "/device:TPU:0", "line": "XLA Modules", "events": [
            [step, 0, 90 * MS], [step, 100 * MS, 100 * MS],
            [step, 200 * MS, 50 * MS]]},
        {"plane": "/device:TPU:0", "line": "XLA Ops", "events": events}]
    return {"lanes": lanes, "summary": trace.summarize(lanes),
            "ssd_trace": {"ops": ssd_ops}}


def test_the_share_is_the_kernels_under_the_scan_scope_over_the_scope():
    run = made_up_run()
    assert run["summary"]["steps"] == 1
    read = cells.load_reader("per_layer", "ssd_scan_kernel_share")
    # 3 + 3 + 8 ms of kernels of the 15 ms under ssd.scan; the attention
    # kernel and the kernel outside the window count for neither
    assert cells.load_reader("per_layer", "ssd_scan_ms")(run) \
        == pytest.approx(15.0)
    assert read(run) == pytest.approx(100.0 * 14 / 15)


def test_nothing_is_read_where_no_kernel_runs_under_the_scope():
    read = cells.load_reader("per_layer", "ssd_scan_kernel_share")
    # the scan form: the same time under the scope, no custom call
    run = made_up_run(scan_kernels=False)
    assert cells.load_reader("per_layer", "ssd_scan_ms")(run) \
        == pytest.approx(15.0)
    assert read(run) is None
    # a program without the mixer's scopes, and a run without a trace
    run = made_up_run()
    run["ssd_trace"] = {"ops": [[*op[:3], None]
                                for op in run["ssd_trace"]["ops"]]}
    assert read(run) is None
    assert read({"ssd_trace": None, "cell": None}) is None
