"""Configuration files: byte counts against the numbers written in them,
the benchmark's weight layout against the program's, and BENCHMARK.json's
entries against the files the harness finds by name."""

import json
import pathlib
import re

import jax
import numpy as np
import pytest

import _benchpath  # noqa: F401
from benchlib import driver, spec, weights

BENCH = spec.benchmark()
# every configuration file the benchmark keeps, listed in BENCHMARK.json
# or kept ready for a cell that a later PR measures
CONFIGS = sorted(p.stem for p in (spec.BENCH_DIR / "configs").glob("*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CONFIGS)
def test_byte_counts_match_the_config_file(name):
    c = spec.config(name)
    n = weights.n_params(c)
    d = weights.dims(c)
    assert n["total"] == c["bytes"]["params"]
    assert 2 * n["total"] == c["bytes"]["weights_bf16"]
    kv = 2 * d["KV"] * d["hd"] * 2 * d["L"]
    assert kv == c["bytes"]["kv_per_token"]


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_layout_is_the_programs(name):
    from repro.models.registry import get_model
    c = spec.config(name)
    abstract = get_model(driver.model_config(c)).init_params(abstract=True)
    leaves = {**{"blocks." + p: (d["L"],) + s
                 for d in [weights.dims(c)]
                 for p, (s, _) in weights.layer_leaves(c).items()},
              **{p: s for p, (s, _) in weights.top_leaves(c).items()}}
    flat = {".".join(str(k.key) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_leaves_with_path(abstract)}
    assert flat == {p: tuple(s) for p, s in leaves.items()}
    assert sum(int(np.prod(s)) for s in flat.values()) == \
        c["bytes"]["params"]


def test_every_name_finds_its_files():
    root = spec.ROOT
    for c in BENCH["configs"]:
        assert (root / c["file"]).is_file()
        assert c["source"] == spec.config(c["name"])["source"]
        assert c["reduced"] == spec.config(c["name"])["reduced"]
        assert (root / "bench" / spec.config(c["name"])["reference"]).is_file()
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        cell = spec.cell(w["name"])
        assert cell["functions"] >= 1 and "check" in cell
        spec.mix(w["traffic"])
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        assert hasattr(spec.metric_reader(m["name"]), "read")
        for cell in m.get("workloads", []):
            spec.workload(cell)


def test_contract_shape():
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            reported = [n for n, e in e2e.items()
                        if cell in e.get("workloads", [cell])]
            assert m["moves"] in reported
    for w in BENCH["workloads"]:
        per = spec.metrics_for(w["name"], True, BENCH)
        assert per, w["name"]
        assert len(spec.metrics_for(w["name"], False, BENCH)) >= 2
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert pathlib.Path(spec.ROOT / "tests" / "bench").is_dir()
