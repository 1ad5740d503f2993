"""The readers of what the program itself writes into a profile (``ad.*``
scopes and spans, ``benchmark/harness/program_trace.py``): the ``op_name``
parser, the fifteen readers on a made-up record with hand numbers, on a
reduced recording of ``gpt2_medium.train_fed``'s own traced run on a v5e, and
on records without any ``ad.`` name.

    python -m pytest benchmark/tests -q
"""
import gzip
import json
import os

import pytest

from benchmark.harness import cells, peaks, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

PROGRAM_METRICS = (
    "forward_ms", "backward_ms", "recompute_ms", "update_ms", "sync_ms",
    "step_unscoped_ms", "run_shard_ms", "run_dispatch_ms", "step_variants",
    "loader_wait_ms", "loader_copy_ms", "prefetch_put_ms",
    "loader_ring_depth", "prefetch_ready_share", "idle_in_program_ms")
PHASE_METRICS = ("forward_ms", "backward_ms", "update_ms", "sync_ms",
                 "step_unscoped_ms")


def read_all(run, names=PROGRAM_METRICS):
    return {m: cells.load_reader("per_layer", m)(run) for m in names}


def test_op_name_to_scope_and_direction():
    from benchmark.harness.program_trace import classify

    pre = "jit(step_fn)/shard_map/"
    assert classify(pre + "ad.grad/jvp(GPT)/h_0/attn/pallas_call") == (
        "ad.grad", False, False)
    assert classify(
        pre + "ad.grad/transpose(jvp(GPT))/ad.grad/jvp(GPT)/checkpoint/"
        "rematted_computation/h_1/attn/pallas_call") == (
            "ad.grad", True, True)
    assert classify(pre + "ad.grad/transpose(jvp(GPT))/ad.grad/jvp(GPT)/"
                    "checkpoint/h_1/attn/pallas_call") == (
                        "ad.grad", True, False)
    # a sync nested in the accumulation scan is charged to sync
    assert classify(pre + "ad.grad/while/body/ad.sync/psum")[0] == "ad.sync"
    assert classify(pre + "ad.update/mul") == ("ad.update", False, False)
    assert classify("jit(step_fn)/shard_map/slice.8") == (None, False, False)
    assert classify(None) == (None, False, False)
    assert classify("jit(load.ad)/broadcast")[0] is None    # no such scope


def made_up_program_run():
    """Two steady steps of 100 ms between two cut ones, on one clock: the
    device's operations with their scopes, the benchmark's three spans and
    the program's spans inside them."""
    ms = 1e6
    step = "jit_step_fn(1)"
    lanes = [{"plane": "/device:TPU:0", "line": trace.MODULES_LINE,
              "events": [[step, 0, 50 * ms], [step, 100 * ms, 100 * ms],
                         [step, 200 * ms, 100 * ms], [step, 300 * ms, 30 * ms]]}]
    ops, spans = [], []
    for k, t0 in enumerate((100 * ms, 200 * ms)):
        ops += [
            ["%fusion.1", t0, 20 * ms, "ad.grad", False, False],
            # a while holds its body: 30 ms, 25 of them in two nested ops
            ["%while.2", t0 + 20 * ms, 30 * ms, "ad.grad", True, False],
            ["%fusion.3", t0 + 21 * ms, 10 * ms, "ad.grad", True, True],
            ["%fusion.4", t0 + 32 * ms, 15 * ms, "ad.grad", True, False],
            ["%copy.5", t0 + 50 * ms, 4 * ms, None, False, False],
            ["%all-reduce.6", t0 + 54 * ms, 6 * ms, "ad.sync", False, False],
            ["%fusion.7", t0 + 60 * ms, 2 * ms, "ad.clip", False, False],
            ["%fusion.8", t0 + 62 * ms, 8 * ms, "ad.update", False, False],
            ["%all-gather.9", t0 + 70 * ms, 5 * ms, "ad.gather", False, False],
            # 75..100: idle
        ]
    lanes.append({"plane": "/device:TPU:0", "line": trace.OPS_LINE,
                  "events": [op[:3] for op in ops]})
    # four iterations of the loop; the reduction keeps the middle two
    for i, t0 in enumerate((0, 100 * ms, 200 * ms, 300 * ms)):
        t = t0 + 75 * ms                    # the host works in the idle tail
        spans += [
            ["bench.input_wait", t, 6 * ms, {}, "python"],
            ["ad.prefetch.next", t + 1 * ms, 5 * ms,
             {"batch": i, "ready": 1 if i != 2 else 0}, "python"],
            ["ad.prefetch.push", t + 1.5 * ms, 4 * ms, {"batch": i + 2},
             "python"],
            ["ad.loader.next", t + 2 * ms, 2 * ms, {"ring": 3 - (i % 2)},
             "python"],
            ["ad.loader.wait", t + 2 * ms, 0.5 * ms, {}, "python"],
            ["ad.loader.copy", t + 2.5 * ms, 1.5 * ms, {}, "python"],
            ["ad.shard_batch", t + 4 * ms, 1.5 * ms, {}, "python"],
            ["bench.dispatch", t + 6 * ms, 4 * ms, {}, "python"],
            ["ad.run", t + 6 * ms, 4 * ms, {"step_num": i, "variants": 1},
             "python"],
            ["ad.shard_batch", t + 6 * ms, 0.25 * ms, {}, "python"],
            ["ad.pre_step", t + 6.5 * ms, 0.1 * ms, {}, "python"],
            ["ad.dispatch", t + 7 * ms, 3 * ms, {}, "python"],
            ["bench.block", t + 10 * ms, 15 * ms, {}, "python"],
        ]
    return {"lanes": lanes, "summary": trace.summarize(lanes), "spans": {},
            "program_trace": {"op_name_from": "made up", "ops": ops,
                              "spans": sorted(spans, key=lambda s: s[1])}}


def test_program_readers_on_a_made_up_record():
    run = made_up_program_run()
    assert run["summary"]["steps"] == 2
    read = read_all(run)
    assert read["forward_ms"] == pytest.approx(20.0)
    assert read["backward_ms"] == pytest.approx(30.0)      # the while, once
    assert read["recompute_ms"] == pytest.approx(10.0)
    assert read["update_ms"] == pytest.approx(10.0)        # clip + update
    assert read["sync_ms"] == pytest.approx(11.0)          # sync + gather
    assert read["step_unscoped_ms"] == pytest.approx(4.0)
    busy = cells.load_reader("per_layer", "device_busy_ms.tokens")(run)
    assert sum(read[m] for m in PHASE_METRICS) == pytest.approx(busy)
    assert read["run_shard_ms"] == pytest.approx(0.25)
    assert read["prefetch_put_ms"] == pytest.approx(1.5)
    assert read["run_dispatch_ms"] == pytest.approx(3.0)
    assert read["loader_wait_ms"] == pytest.approx(0.5)
    assert read["loader_copy_ms"] == pytest.approx(1.5)
    assert read["step_variants"] == 1
    assert read["loader_ring_depth"] == pytest.approx(2.5)  # steps 1 and 2
    assert read["prefetch_ready_share"] == pytest.approx(50.0)
    # idle 75..100 of each step; the program's spans cover 76..81 and 81..85
    assert read["idle_in_program_ms"] == pytest.approx(9.0)


def test_program_readers_find_nothing_without_ad_names():
    run = made_up_program_run()
    rec = run["program_trace"]
    run["program_trace"] = {
        "op_name_from": None,
        "ops": [op[:3] + [None, False, False] for op in rec["ops"]],
        "spans": [s for s in rec["spans"] if s[0].startswith("bench.")]}
    assert set(read_all(run).values()) == {None}
    # and with no profile at all
    run = made_up_program_run()
    del run["program_trace"]
    run["cell"] = {"name": "no.such.cell"}
    assert set(read_all(run).values()) == {None}


def recorded_program_run():
    """Cut from ``gpt2_medium.train_fed``'s own traced run on a v5e (PR 25):
    a cut step and three whole ones, so the reduction keeps the middle two.
    The step's module events, the program's and the benchmark's host spans
    with their arguments, and device 0's operations with name, start,
    duration and scope."""
    with gzip.open(os.path.join(
            DATA, "gpt2_medium_program_trace.json.gz"), "rt") as f:
        kept = json.load(f)
    rec = kept["program_trace"]
    lanes = [{"plane": "/device:TPU:0", "line": trace.MODULES_LINE,
              "events": kept["modules"]},
             {"plane": "/device:TPU:0", "line": trace.OPS_LINE,
              "events": [op[:3] for op in rec["ops"]]}]
    return {"lanes": lanes, "summary": trace.summarize(lanes), "spans": {},
            "peaks": peaks.peaks_for("TPU v5 lite"), "program_trace": rec}


def test_program_readers_on_the_recorded_chip_trace():
    run = recorded_program_run()
    assert run["summary"]["steps"] == 2
    assert run["program_trace"]["op_name_from"].startswith("tf_op")
    read = read_all(run)
    busy = cells.load_reader("per_layer", "device_busy_ms.tokens")(run)
    assert busy == pytest.approx(1434.2, rel=1e-4)
    # the phases of the step add up to the time the device is busy
    assert sum(read[m] for m in PHASE_METRICS) == pytest.approx(busy,
                                                                rel=1e-2)
    assert read["forward_ms"] == pytest.approx(407.4, rel=1e-3)
    assert read["backward_ms"] == pytest.approx(1000.5, rel=1e-3)
    assert read["recompute_ms"] == pytest.approx(326.2, rel=1e-3)
    assert read["update_ms"] == pytest.approx(6.87, rel=1e-2)
    assert read["sync_ms"] == pytest.approx(5.60, rel=1e-2)
    assert read["step_unscoped_ms"] / busy < 0.05
    # the flash kernels keep their names under the outer scope; a step has
    # 24 layers x (forward, recomputed forward, dq, dk/dv)
    lo, hi = run["summary"]["window"]
    kinds = [(op[4], op[5]) for op in run["program_trace"]["ops"]
             if trace.stem(op[0]) == "attn" and lo <= op[1] < hi]
    assert all(op[3] == "ad.grad" for op in run["program_trace"]["ops"]
               if trace.stem(op[0]) == "attn")
    assert {k: kinds.count(k) for k in set(kinds)} == {
        (False, False): 48, (True, True): 48, (True, False): 96}
    assert read["step_variants"] == 1
    assert read["loader_ring_depth"] == 3.0
    assert read["prefetch_ready_share"] == 100.0
    assert 0 < read["run_shard_ms"] < read["run_dispatch_ms"] < 10
    assert 0 < read["loader_wait_ms"] < read["loader_copy_ms"] \
        < read["prefetch_put_ms"] < 2
    assert read["idle_in_program_ms"] < 0.01


def test_metadata_table_is_read_from_the_wire_format(tmp_path):
    """A hand-made ``XSpace`` with one device plane: two operations, one
    whose metadata carries ``tf_op`` as a string and one as a reference."""
    from benchmark.harness.program_trace import op_names_by_event_name

    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(number, payload):
        if isinstance(payload, int):
            return varint(number << 3) + varint(payload)
        return varint(number << 3 | 2) + varint(len(payload)) + payload

    def stat_metadata(sid, name):
        return field(5, field(1, sid) + field(2, field(1, sid)
                                              + field(2, name.encode())))

    def event_metadata(eid, name, stat):
        return field(4, field(1, eid) + field(2, field(1, eid) + field(
            2, name.encode()) + field(5, stat)))

    plane = (field(1, 7) + field(2, b"/device:TPU:0")
             + field(3, field(2, b"XLA Ops"))           # a line, skipped
             + stat_metadata(1, "tf_op") + stat_metadata(2, "flops")
             + stat_metadata(3, "jit(f)/ad.update/mul:")
             + event_metadata(10, "%fusion.1 = f32[8] fusion()",
                              field(1, 1) + field(
                                  5, b"jit(f)/ad.grad/jvp()/dot_general:"))
             + event_metadata(11, "%fusion.2 = f32[8] fusion()",
                              field(1, 2) + field(3, 99))
             + event_metadata(12, "%fusion.3 = f32[8] fusion()",
                              field(1, 1) + field(7, 3)))
    host = field(1, 8) + field(2, b"/host:CPU")
    path = tmp_path / "made_up.xplane.pb"
    path.write_bytes(field(1, host) + field(1, plane))
    assert op_names_by_event_name(str(path)) == {
        "%fusion.1 = f32[8] fusion()": "jit(f)/ad.grad/jvp()/dot_general:",
        "%fusion.3 = f32[8] fusion()": "jit(f)/ad.update/mul:"}
    path.write_bytes(field(1, host))
    assert op_names_by_event_name(str(path)) == {}
