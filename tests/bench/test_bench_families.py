"""A model family enters the benchmark as new files only.  A toy
mixture-of-experts plug-in (over the program's ``moe`` family, dropless),
its plain reference, its configuration, a mix of concurrent callers and a
cell are added to a copy of the benchmark beside the files it already has,
and a whole rehearsal runs through ``harness.run``: the served tokens pass
the reference and the float8 control fails it."""

import json
import pathlib
import shutil
import time

import jax
import pytest

import _benchpath  # noqa: F401
from benchlib import driver, harness, spec, weights

DATA = pathlib.Path(__file__).parent / "data" / "toy_moe"
CELL = "toy-moe.concurrent"
SEED = 2**33 + 41


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark with the toy family's files added, and
    BENCHMARK.json's entries for them appended; no file changed."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    bench_dir = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = {"families/toy_moe.py": "family.py",
           "references/toy_moe.py": "reference.py",
           "configs/toy-moe.json": "config.json",
           f"cells/{CELL}.json": "cell.json",
           "mixes/toy-concurrent.json": "mix.json"}
    for dst, src in new.items():
        assert not (bench_dir / dst).exists()
        shutil.copy(DATA / src, bench_dir / dst)
    path = tmp_path / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({
        "name": "toy-moe", "source": "the program's moe family",
        "file": "bench/configs/toy-moe.json", "reduced": [],
        "why": "a family added from new files"})
    bench["workloads"].append({
        "name": CELL, "config": "toy-moe", "traffic": "toy-concurrent",
        "chips": 1, "why": "concurrent callers over a toy expert layer"})
    for m in bench["end_to_end"]:
        if m["name"] == "tpot_p50_ms":
            m["workloads"].append(CELL)
    path.write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    monkeypatch.setattr(spec, "BENCH_DIR", bench_dir)
    return tmp_path


def test_the_toy_layout_is_the_programs(checkout):
    from repro.models.registry import get_model
    c = spec.config("toy-moe")
    fam = weights.family(c)
    abstract = get_model(driver.model_config(c)).init_params(abstract=True)
    flat = {".".join(str(k.key) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_leaves_with_path(abstract)}
    want = {g + "." + p: (len(layers),) + tuple(s)
            for g, layers, leaves in fam.groups(c)
            for p, (s, _) in leaves.items()}
    want.update({p: tuple(s) for p, (s, _) in fam.top_leaves(c).items()})
    assert flat == want
    n = weights.n_params(c)
    assert n["total"] == sum(int(jax.numpy.prod(jax.numpy.asarray(s)))
                             for s in flat.values())
    assert n["active"] < n["total"]          # 2 of 8 experts per token


@pytest.mark.parametrize("control", [False, True])
def test_a_family_from_new_files_runs_a_whole_rehearsal(checkout, control):
    out = harness.run(CELL, SEED, 4.0, False, time.perf_counter(),
                      rehearse=True, control=control)
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "tpot_p50_ms"}
    served = out["check"]["max_logit_gap" if not control
                          else "served_max_logit_gap"]
    assert served["value"] <= served["limit"]
    assert out["correct"] is (not control)
    if control:
        gap = out["check"]["control_max_logit_gap"]
        assert gap["value"] > gap["limit"]
