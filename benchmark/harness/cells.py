"""Finds a cell's files by the names in ``BENCHMARK.json``.

``workloads/<cell>.json`` -> ``configs/<config>.json`` ->
``families/<family>.py``; a metric ``<reader>.<suffix>`` (or plain
``<reader>``) is read by ``end_to_end/<reader>.py`` or
``layer_metrics/<reader>.py``.  Adding a cell, a configuration, a family or
a metric is adding files and entries.
"""
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """The module ``benchmark/<kind>/<name>.py``, loaded by its path."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_manifest():
    return _read_json(os.path.join(REPO_DIR, "BENCHMARK.json"))


def load_cell(name, manifest=None):
    """``(cell, config)`` of the workload ``name``: the manifest's entry
    merged over ``workloads/<name>.json``, and its configuration's file."""
    manifest = manifest or load_manifest()
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    cell = _read_json(os.path.join(BENCH_DIR, "workloads", name + ".json"))
    for key in ("config", "chips", "traffic"):
        if cell.get(key, entry[key]) != entry[key]:
            raise ValueError(f"{name}: {key} differs between BENCHMARK.json "
                             "and the workload file")
    cell = {**cell, **entry}
    conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _read_json(os.path.join(REPO_DIR, conf["file"]))
    return cell, config


def load_family(name):
    return load_module("families", name)


def metrics_of(cell_name, section, manifest=None):
    """The metrics of ``section`` (``end_to_end`` / ``per_layer``) that the
    cell reports: those with no ``workloads`` key, or listing the cell."""
    manifest = manifest or load_manifest()
    return [m for m in manifest[section]
            if cell_name in m.get("workloads", [cell_name])]


def reader_name(metric_name):
    """``input_wait_ms.tokens`` is read by ``layer_metrics/input_wait_ms.py``."""
    return metric_name.split(".", 1)[0]


def load_reader(section, metric_name):
    """The reader of a metric of ``section``: ``end_to_end/<reader>.py`` or
    ``layer_metrics/<reader>.py``."""
    kind = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[section]
    return load_module(kind, reader_name(metric_name)).read
