"""Every cell of ``BENCHMARK.json`` finds its files and its family imports
under tier-1's CPU platform.  ``benchmark/tests`` run outside tier-1, so this
is where a program PR that renames or deletes what a family imports learns
it before a chip run.  Nothing is built, run or written."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import cells  # noqa: E402

WORKLOADS = [w["name"] for w in cells.load_manifest()["workloads"]]


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_files_load_and_family_imports(name):
    cell, config = cells.load_cell(name)
    assert cell["name"] == name and cell["chips"] >= 1
    family = cells.load_family(config["family"])
    assert isinstance(family.Job, type)
    assert callable(family.layer_shapes)
