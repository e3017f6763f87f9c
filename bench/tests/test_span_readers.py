"""The readers of the served batch's host phases: each the mean duration,
in ms, of its one span in the traced half's JSONL, or nothing where the
program emits no such span."""
import types

import pytest

import run
from conftest import REPO

READERS = {"assemble_ms.offline": "exec.assemble",
           "dispatch_ms.offline": "exec.network",
           "split_ms.offline": "exec.split",
           "wait_ms.offline": "serve.wait",
           "fetch_ms.offline": "serve.fetch"}


def _span(name, dur_us):
    return {"ev": "span", "name": name, "ts": 0.0, "dur": dur_us, "tid": 1,
            "depth": 1, "attrs": {}}


@pytest.mark.parametrize("metric,span", sorted(READERS.items()))
def test_reader_is_the_mean_of_its_span(metric, span):
    read = run.load_reader(REPO / "bench", metric)
    others = [_span(s, 9e6) for s in READERS.values() if s != span]
    spans = [_span(span, 2000.0), _span("serve.batch", 5e5),
             _span(span, 4000.0)] + others
    assert read(types.SimpleNamespace(spans=spans)) == pytest.approx(3.0)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_nothing_without_its_span(metric):
    read = run.load_reader(REPO / "bench", metric)
    assert read(types.SimpleNamespace(spans=[])) is None
    others = [_span(s, 1e3) for s in READERS.values() if s != READERS[metric]]
    assert read(types.SimpleNamespace(spans=others)) is None
