"""Find a cell's files by name: BENCHMARK.json, the cell, its configuration,
its model family's plug-in, its traffic mix, its reference and its metric
readers."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import types

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell(name: str) -> dict:
    return load_json(BENCH_DIR / "cells" / f"{name}.json")


def config(name: str) -> dict:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def mix(name: str) -> dict:
    return load_json(BENCH_DIR / "mixes" / f"{name}.json")


def _load_module(path: pathlib.Path, modname: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str) -> types.ModuleType:
    """A model family's plug-in (families/<name>.py), loaded once per
    file: the layout, sizes and ``ModelConfig`` of that family."""
    path = BENCH_DIR / "families" / f"{name}.py"
    if path not in _FAMILIES:
        _FAMILIES[path] = _load_module(
            path, "bench_family_" + name.replace(".", "_").replace("-", "_"))
    return _FAMILIES[path]


_FAMILIES: dict = {}


def reference(path: str) -> types.ModuleType:
    """A configuration's plain float32 reference, by the path its file
    names under ``bench/`` (``"reference": "references/dense.py"``)."""
    return _load_module(BENCH_DIR / path,
                        "bench_reference_" + pathlib.Path(path).stem)


def metric_reader(name: str) -> types.ModuleType:
    """A metric's reader (metrics/<name>.py): ``read(ctx) -> float | None``."""
    return _load_module(BENCH_DIR / "metrics" / f"{name}.py",
                        "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def metrics_for(cell_name: str, trace: bool, bench: dict | None = None) -> list:
    """The metric entries a run of ``cell_name`` reports: its end-to-end
    metrics untraced, its per-layer metrics traced."""
    bench = bench or benchmark()
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]
