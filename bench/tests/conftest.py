"""Fixtures for the benchmark's own tests (``python -m pytest bench/tests``).

They run on the CPU, where the Pallas kernels are interpreted, against a
three-layer stand-in configuration (``tiny.json``): a throwaway checkout
holds a copy of ``bench/``, the program's ``src/`` and a ``BENCHMARK.json``
that adds the stand-in's cells to the real ones.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

# small open-loop and saturating mixes the interpreted kernels keep up with
TINY_MIXES = {
    "tiny_saturate": {"arrivals": "saturate", "queued_batches": 1, "pool": 8},
    "tiny_poisson": {"arrivals": "gamma", "rate": 4.0, "cv": 1.0, "pool": 8},
}
TINY_CELLS = {"tiny.offline": "tiny_saturate", "tiny.server": "tiny_poisson"}


def make_checkout(dest: pathlib.Path) -> pathlib.Path:
    """A checkout at ``dest`` with the tiny cells added; returns its root."""
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "data"))
    (dest / "src").symlink_to(REPO / "src")
    shutil.copy(BENCH / "tests" / "tiny.json",
                dest / "bench" / "configs" / "tiny.json")
    for name, mix in TINY_MIXES.items():
        (dest / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "tests",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "tests on the CPU"})
    for cell, mix in TINY_CELLS.items():
        spec["workloads"].append({"name": cell, "config": "tiny",
                                  "traffic": mix, "chips": 1,
                                  "why": "tests on the CPU"})
    for metric in spec["end_to_end"] + spec["per_layer"]:
        for cell in TINY_CELLS:
            kind = cell.split(".")[1]
            if any(w.endswith("." + kind)
                   for w in metric.get("workloads", ())):
                metric["workloads"].append(cell)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dest


@pytest.fixture(scope="session")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))
