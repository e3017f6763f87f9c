"""A whole run with the timed path broken underneath must read incorrect.

Each test skips the harness's look for a chip and drives the rest of a run
of a tiny cell on the CPU: set-up, warm-up, the window, the check.  The
faults are planted in the program's executor, where the answers are made.
A step that returns its state unchanged and the exchange between chips do
not exist in a one-chip serving cell.
"""
import time

import jax.numpy as jnp
import pytest

import run
from repro.plan.executor import PreparedNetwork

SEED = 2 ** 33 + 11


def _run(checkout, cell="tiny.offline", trace=False):
    return run.run_cell(checkout, checkout / "bench", cell, SEED, 1.5, trace,
                        t_start=time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("cell", ["tiny.offline", "tiny.server"])
def test_sound_run_is_correct(checkout, cell):
    out = _run(checkout, cell)
    assert out["correct"], out["check"]
    assert out["failed"] == 0
    assert out["check"]["max_rel_err"]["value"] < 1e-6
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("cell", ["tiny.offline", "tiny.server"])
def test_control_in_the_programs_place_reads_incorrect(checkout, cell):
    """The reference one precision step down (bf16_3x) in the program's
    place, judged by the run's own comparison."""
    out = run.run_cell(checkout, checkout / "bench", cell, SEED, 1.5, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       control=True)
    assert not out["correct"]
    assert out["check"]["max_rel_err"]["value"] > \
        out["check"]["max_rel_err"]["limit"]


def test_answer_altered_where_produced(checkout, monkeypatch):
    orig = PreparedNetwork.execute_requests

    def altered(self, samples, **kw):
        outs = orig(self, samples, **kw)
        return [outs[0] * (1 + 1e-3)] + list(outs[1:])

    monkeypatch.setattr(PreparedNetwork, "execute_requests", altered)
    out = _run(checkout)
    assert not out["correct"]
    assert out["check"]["max_rel_err"]["value"] > \
        out["check"]["max_rel_err"]["limit"]


def test_half_of_the_batch_left_out(checkout, monkeypatch):
    orig = PreparedNetwork.assemble_batch

    def half(self, samples):
        keep = (len(samples) + 1) // 2
        rest = [jnp.zeros_like(jnp.asarray(s)) for s in samples[keep:]]
        return orig(self, list(samples[:keep]) + rest)

    monkeypatch.setattr(PreparedNetwork, "assemble_batch", half)
    out = _run(checkout)
    assert not out["correct"]


def test_batches_that_fail_are_unanswered(checkout, monkeypatch):
    orig = PreparedNetwork.execute_requests
    calls = {"n": 0}

    def flaky(self, samples, **kw):
        calls["n"] += 1
        if calls["n"] > 40:      # past the warm-up: inside the window
            raise RuntimeError("planted device fault")
        return orig(self, samples, **kw)

    monkeypatch.setattr(PreparedNetwork, "execute_requests", flaky)
    out = _run(checkout)
    assert not out["correct"]
    assert out["failed"] > 0
    assert out["check"]["unanswered"]["value"] > 0
