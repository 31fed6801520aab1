"""A whole run of each cell at rehearsal sizes on the CPU: the served
tokens pass the reference, the float8 control put in the program's place
fails the same comparison, and the metrics come out under their names."""

import time

import pytest

import _benchpath  # noqa: F401
from benchlib import harness, spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2**31 + 17


def _run(name, control):
    return harness.run(name, SEED, 5.0, False, time.perf_counter(),
                       rehearse=True, control=control)


@pytest.fixture(scope="module", params=CELLS)
def run(request):
    return request.param, _run(request.param, False)


@pytest.fixture(scope="module", params=CELLS)
def control_run(request):
    return request.param, _run(request.param, True)


def test_served_tokens_pass_the_reference(run):
    name, out = run
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["correct"] is True
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_control_reads_a_wider_gap_than_the_program(control_run):
    _, out = control_run
    served = out["check"]["served_max_logit_gap"]["value"]
    control = out["check"]["control_max_logit_gap"]
    assert control["value"] > control["limit"]
    assert control["value"] > 3 * served
    assert out["correct"] is False


def test_result_line_names_the_cells_end_to_end_metrics(run):
    name, out = run
    want = {m["name"] for m in spec.metrics_for(name, False)}
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "check"
    assert out["device"]["count"] == 1


def test_a_window_that_stops_logs_its_compiles_and_raises(monkeypatch,
                                                          capsys):
    """A window that raises, as a serve program that cannot reserve its
    memory does, is not swallowed: the run logs what the window had
    compiled and raises on, and prints no result."""
    from benchlib import driver

    def stops(run, reqs, seconds, tslice):
        raise ValueError("RESOURCE_EXHAUSTED: a stand-in")

    monkeypatch.setattr(driver, "closed_loop", stops)
    monkeypatch.setattr(driver, "open_loop", stops)
    with pytest.raises(ValueError, match="RESOURCE_EXHAUSTED"):
        harness.run(CELLS[0], SEED, 2.0, False, time.perf_counter(),
                    rehearse=True)
    err = capsys.readouterr().err
    assert "window stopped: compile cache 0 hits, 0 misses" in err
