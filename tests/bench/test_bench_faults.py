"""The timed path broken underneath must turn ``correct`` false: a token
altered where the engine produces it, and a decode step that returns its
state (the paged KV arena) unchanged."""

import time

import numpy as np
import pytest

import _benchpath  # noqa: F401
from benchlib import harness, spec

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def _alter_tokens(monkeypatch):
    from repro.runtime import continuous

    greedy = continuous.sample_greedy

    def altered(logits):
        tok = np.asarray(greedy(logits))
        return continuous.jnp.asarray((tok + 1) % logits.shape[-1])

    monkeypatch.setattr(continuous, "sample_greedy", altered)


def _state_unchanged(monkeypatch):
    from repro.models import transformer

    step = transformer.decode_step_paged

    def unchanged(params, cfg, cache, *a, **k):
        logits, _ = step(params, cfg, cache, *a, **k)
        return logits, cache

    monkeypatch.setattr(transformer, "decode_step_paged", unchanged)


FAULTS = {"an_altered_token": _alter_tokens,
          "a_decode_step_that_keeps_its_state": _state_unchanged}


def _assert_caught(out):
    assert out["attempted"] >= 1
    assert out["correct"] is False
    assert (out["check"]["max_logit_gap"]["value"]
            > out["check"]["max_logit_gap"]["limit"])


def test_an_altered_token_is_caught(monkeypatch):
    _alter_tokens(monkeypatch)
    _assert_caught(harness.run(CELLS[0], 23, 4.0, False,
                               time.perf_counter(), rehearse=True))


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_every_fault_is_caught_in_every_cell(monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    _assert_caught(harness.run(cell, 2**33 + 23, 4.0, False,
                               time.perf_counter(), rehearse=True))
