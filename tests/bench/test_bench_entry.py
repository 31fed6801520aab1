"""The entry point refuses to measure without a TPU, and without the
program beside it."""

import importlib.util
import os
import shutil
import subprocess
import sys

import _benchpath  # noqa: F401
from benchlib import spec

CELL = spec.benchmark()["workloads"][0]["name"]


def _entry():
    s = importlib.util.spec_from_file_location(
        "bench_entry", spec.BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_no_tpu_no_result(capsys):
    rc = _entry().main(["--workload", CELL, "--seed",
                        str(2**33), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "needs 1 TPU chip" in out.err


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         CELL, "--seed", "3", "--seconds", "1",
         "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
