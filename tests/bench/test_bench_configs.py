"""Configuration files: byte counts against the numbers written in them,
the benchmark's weight layout against the program's, and BENCHMARK.json's
entries against the files the harness finds by name."""

import hashlib
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
    fam = weights.family(c)
    layout = sum(len(layers) * int(np.prod(s))
                 for _, layers, leaves in fam.groups(c)
                 for s, _ in leaves.values())
    layout += sum(int(np.prod(s)) for s, _ in fam.top_leaves(c).values())
    assert n["total"] == layout == c["bytes"]["params"]
    assert 2 * n["total"] == c["bytes"]["weights_bf16"]
    assert 0 < n["active"] <= n["total"] + c["vocab_size"] * c["hidden_size"]
    assert fam.kv_bytes_per_token(c) == c["bytes"]["kv_per_token"]


@pytest.mark.parametrize("name", CONFIGS)
def test_weight_layout_is_the_programs(name):
    from repro.models.registry import get_model
    c = spec.config(name)
    fam = weights.family(c)
    abstract = get_model(driver.model_config(c)).init_params(abstract=True)
    leaves = {**{g + "." + p: (len(layers),) + tuple(s)
                 for g, layers, group in fam.groups(c)
                 for p, (s, _) in group.items()},
              **{p: s for p, (s, _) in fam.top_leaves(c).items()}}
    flat = {".".join(str(k.key) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_leaves_with_path(abstract)}
    assert flat == {p: tuple(s) for p, s in leaves.items()}
    assert sum(int(np.prod(s)) for s in flat.values()) == \
        c["bytes"]["params"]
    # every layer is in exactly one group
    held = sorted(i for _, layers, _ in fam.groups(c) for i in layers)
    assert held == list(range(c["num_hidden_layers"]))


# sha256 of the weights at rehearsal sizes (seed 2**33 + 5, function 1),
# computed on the commit before the families became plug-ins: the dense
# layout still draws the very same values
PARENT_DIGESTS = {
    "qwen3-14b-l10": ("43499f525caf0511", "3f8974b8027fdff6",
                      "02040547d872be9c"),
    "smollm-135m": ("9e03f96b465fceb8", "8a4fd43cc4b4d4e7",
                    "512604aab82c24d0"),
}


def _digest(flat: dict) -> str:
    h = hashlib.sha256()
    for p in sorted(flat):
        h.update(p.encode())
        h.update(np.asarray(flat[p]).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT_DIGESTS))
def test_dense_weights_are_bit_identical_to_the_parents(name):
    from benchlib import harness
    c = spec.config(name)
    c, _, _ = harness.rehearsal(c, spec.cell("qwen3-14b-l10.scale-to-zero"),
                                spec.mix("scale-to-zero"))
    seed = 2**33 + 5
    tree = weights.served_params(c, seed, 1)
    served = {".".join(str(k.key) for k in p): v
              for p, v in jax.tree_util.tree_leaves_with_path(tree)}
    layers = {f"{i}.{p}": v for i in range(2)
              for p, v in weights.layer_params(c, seed, 1, i).items()}
    top = weights.top_params(c, seed, 1)
    assert (_digest(served), _digest(layers), _digest(top)) == \
        PARENT_DIGESTS[name]


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
