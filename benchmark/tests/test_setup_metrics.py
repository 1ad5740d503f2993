"""The eight set-up and device-memory readers on a made-up ``ad.run`` lane:
four steps, the first of which (the session's first) carries no totals, the
later ones the totals the runner writes on every ``ad.run`` after it.

    python -m pytest benchmark/tests -q
"""
import pytest

from benchmark.harness import cells

MS = 1e6
TOTALS = {"setup_program_s": 41.25, "setup_state_s": 3.5,
          "setup_trace_s": 20.125, "setup_lower_s": 6.75,
          "setup_compile_s": 9.0, "setup_cache_misses": 0,
          "state_bytes": 7_961_000_000, "step_reserved_bytes": 3_970_000_000}


def made_up_run(totals=TOTALS):
    """Four iterations of the loop; the reduction keeps the middle two."""
    spans = []
    for i in range(4):
        t = i * 100 * MS
        args = {"step_num": i, "variants": 1, **(totals if i else {})}
        spans += [["bench.input_wait", t, 1 * MS, {}, "python"],
                  ["ad.run", t + 2 * MS, 4 * MS, args, "python"],
                  ["ad.dispatch", t + 3 * MS, 2 * MS, {}, "python"]]
    return {"cell": None, "program_trace": {
        "op_name_from": None, "ops": [], "spans": spans}}


@pytest.mark.parametrize("metric,expected", [
    ("setup_program_s", 41.25), ("setup_state_s", 3.5),
    ("setup_trace_s", 20.125), ("setup_lower_s", 6.75),
    ("setup_compile_s", 9.0), ("setup_cache_misses", 0.0),
    ("state_gb", 7.961), ("step_temp_gb", 3.97)])
def test_a_reader_takes_the_totals_and_finds_nothing_without_them(
        metric, expected):
    read = cells.load_reader("per_layer", metric)
    assert read(made_up_run()) == pytest.approx(expected)
    # the parent's program writes no totals on its ``ad.run``, and a run
    # without a profile has no lane at all
    assert read(made_up_run(totals={})) is None
    assert read({"cell": None}) is None
