"""The harness's contract: what it refuses, and how it is extended.

A configuration, its system, a traffic mix and a per-layer metric are each
added by new files under ``bench/`` and new entries in ``BENCHMARK.json``,
with no edit to a file that is there; the tests add a throwaway one of
each.  What a system module provides is stated once, in ``bench/run.py``'s
docstring; the throwaway system below implements it for a configuration
that is no conv graph and has a check of its own.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import run
from conftest import REPO


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet50.offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bench_alone_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet50.offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_cells_report_what_benchmark_json_assigns():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = run.load_cell(REPO, REPO / "bench", w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(run.load_reader(REPO / "bench", m["name"]))


# A system that serves no conv graph: each request is a vector, the answer
# is the vector times SCALE, one request a batch, computed as it is
# submitted.  Its reference multiplies by REF_SCALE, and its own gap is
# the largest absolute difference.  ``repro`` is imported at the top, so
# the module loads only once the checkout's ``src/`` is importable.
THROWAWAY_SYSTEM = """
import sys

import numpy as np

import work as work_mod
from repro import obs

print("throwaway system imports repro.obs from", obs.__file__,
      file=sys.stderr)

SCALE = 2.0
REF_SCALE = {ref_scale!r}


class _Done:
    def __init__(self, value):
        self.value = value

    def result(self, timeout=None):
        return self.value


class _Engine:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def queue_depth(self):
        return 0

    def submit(self, payload):
        obs.observe("serve.batch_size", 1)
        obs.inc_counter("throwaway.requests")
        return _Done(np.asarray(payload) * SCALE)


def start(config, mix, seed, chips, plan_dir):
    rng = np.random.default_rng(seed % 2 ** 64)
    pool = list(rng.standard_normal((int(mix["pool"]), config["width"])))
    return _Engine(), pool, None, {{"width": config["width"]}}


def reference(config, inputs, outputs, weights, precision):
    # each sampled request arrives with what was served for it
    assert len(outputs) == len(inputs)
    for x, y in zip(inputs, outputs):
        assert np.array_equal(y, np.asarray(x) * SCALE)
    return [np.asarray(x) * REF_SCALE for x in inputs]


def gap(config, out, ref):
    return float(np.max(np.abs(np.asarray(out) - ref)))


def control_precision(config):
    return "half"


def work(config):
    return work_mod.Work(flops=2.0e9, act_bytes=8.0 * config["width"],
                         weight_bytes=0.0)
"""


def _extend(checkout, root):
    """A copy of ``checkout`` at ``root`` with two throwaway systems (one
    whose reference agrees with its engine, one whose does not), their
    configurations, a mix, a counter reader and their cells, all added as
    new files and entries; returns the contents of every file of
    ``bench/`` before the additions."""
    shutil.copytree(checkout, root, symlinks=True,
                    ignore=shutil.ignore_patterns(".cache"))
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    spec = json.loads((root / "BENCHMARK.json").read_text())
    for name, ref_scale in (("throwaway", 2.0), ("throwaway_wrong", 3.0)):
        (bench / "systems" / f"{name}.py").write_text(
            THROWAWAY_SYSTEM.format(ref_scale=ref_scale))
        (bench / "configs" / f"{name}.json").write_text(json.dumps(
            {"name": name, "system": name, "width": 64,
             "serve": {"max_batch": 1}, "check": {"max_abs_gap": 1e-6}}))
        spec["configs"].append({"name": name, "source": "tests",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "tests"})
        spec["workloads"].append({"name": f"{name}.trickle", "config": name,
                                  "traffic": "trickle", "chips": 1,
                                  "why": "tests"})
    cells = ["throwaway.trickle", "throwaway_wrong.trickle"]
    (bench / "traffic" / "trickle.json").write_text(json.dumps(
        {"arrivals": "gamma", "rate": 40.0, "cv": 1.0, "pool": 4}))
    (bench / "metrics" / "requests_seen.trickle.py").write_text(
        "def read(run):\n"
        "    return run.counters.get('throwaway.requests')\n")
    for q in ("p99", "p50"):
        spec["end_to_end"].append({"name": f"ttft_{q}_ms", "unit": "ms",
                                   "better": "lower", "bound": 0.25,
                                   "source": "host_clock",
                                   "workloads": list(cells)})
    spec["per_layer"].append({"name": "requests_seen.trickle", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "serve", "moves": "ttft_p99_ms",
                              "workloads": list(cells)})
    spec["per_layer"].append({"name": "mfu.trickle", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "model step", "moves": "ttft_p99_ms",
                              "workloads": list(cells)})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return before


@pytest.fixture(scope="module")
def extended(checkout, tmp_path_factory):
    root = tmp_path_factory.mktemp("extended") / "checkout"
    return root, _extend(checkout, root)


# one chip's peak where the CPU has none, so the share readers read
FAKE_PEAK = {"flops_per_s": 1.0e12, "hbm_bytes_per_s": 1.0e11}


def test_a_new_config_mix_and_metric_need_only_new_files(extended,
                                                         monkeypatch):
    root, before = extended
    after = {p: p.read_bytes() for p in before}
    assert after == before           # nothing that was there changed

    monkeypatch.setattr(run, "chip_peak", lambda device: FAKE_PEAK)
    for trace in (False, True):
        out = run.run_cell(root, root / "bench", "throwaway.trickle", 9, 1.5,
                           trace, t_start=time.perf_counter(),
                           require_tpu=False)
        assert out["correct"], out["check"]
        # the system's own number, under the configuration's own key
        assert list(out["check"]) == ["max_abs_gap", "unanswered"]
        assert out["check"]["max_abs_gap"]["value"] == 0.0
        if trace:
            # the counter the system records, through Traced.counters
            assert out["metrics"]["requests_seen.trickle"]["value"] >= 1
            # the system's own Work: 2e9 FLOPs a request against 1e12
            # FLOP/s is 0.2% a request per second of the half-window
            mfu = out["metrics"]["mfu.trickle"]["value"]
            assert 0 < mfu < 0.2 * 40 * 2
        else:
            assert set(out["metrics"]) == {"setup_s", "ttft_p99_ms",
                                           "ttft_p50_ms"}


def test_a_system_whose_reference_disagrees_reads_incorrect(extended):
    root, _ = extended
    out = run.run_cell(root, root / "bench", "throwaway_wrong.trickle", 9,
                       1.5, False, t_start=time.perf_counter(),
                       require_tpu=False)
    assert not out["correct"]
    # served 2x, reference 3x: the gap is the largest |x| sampled
    check = out["check"]["max_abs_gap"]
    assert check["value"] > check["limit"]
    assert out["failed"] == 0


def test_a_configuration_naming_no_system_file_is_refused(extended):
    root, _ = extended
    with pytest.raises(run.SetupError, match="no system"):
        run.load_system(root / "bench", "absent")


def test_a_system_importing_the_program_at_top_loads_from_the_checkout(
        extended):
    """``bench/run.py`` as a script, with no ``PYTHONPATH``: the system
    module's top-level ``import repro`` finds the checkout's ``src/``, and
    the run then stops where the CPU has no TPU."""
    root, _ = extended
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "throwaway.trickle",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr, p.stderr
    assert f"imports repro.obs from {root / 'src' / 'repro'}/" in p.stderr
