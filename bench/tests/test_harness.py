"""The harness's contract: what it refuses, and how it is extended.

A configuration, a traffic mix and a per-layer metric are each added by new
files under ``bench/`` and new entries in ``BENCHMARK.json``, with no edit
to a file that is there; the test adds a throwaway one of each.
"""
import json
import os
import shutil
import subprocess
import sys
import time

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


def test_a_new_config_mix_and_metric_need_only_new_files(checkout, tmp_path):
    root = tmp_path / "extended"
    shutil.copytree(checkout, root, symlinks=True,
                    ignore=shutil.ignore_patterns(".cache"))
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    tiny = json.loads((bench / "configs" / "tiny.json").read_text())
    tiny["name"] = "throwaway"
    (bench / "configs" / "throwaway.json").write_text(json.dumps(tiny))
    (bench / "traffic" / "trickle.json").write_text(json.dumps(
        {"arrivals": "gamma", "rate": 3.0, "cv": 3.0, "pool": 4}))
    (bench / "metrics" / "requests_seen.trickle.py").write_text(
        "def read(run):\n"
        "    return float(len(run.hist.get('serve.time_in_queue_ms', [])))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "throwaway", "source": "tests",
                            "file": "bench/configs/throwaway.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": "throwaway.trickle",
                              "config": "throwaway", "traffic": "trickle",
                              "chips": 1, "why": "tests"})
    for q in ("p99", "p50"):
        spec["end_to_end"].append({"name": f"ttft_{q}_ms", "unit": "ms",
                                   "better": "lower", "bound": 0.25,
                                   "source": "host_clock",
                                   "workloads": ["throwaway.trickle"]})
    spec["per_layer"].append({"name": "requests_seen.trickle", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "serve", "moves": "ttft_p99_ms",
                              "workloads": ["throwaway.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = {p: p.read_bytes() for p in before}
    assert after == before           # nothing that was there changed

    for trace in (False, True):
        out = run.run_cell(root, bench, "throwaway.trickle", 9, 1.5, trace,
                           t_start=time.perf_counter(), require_tpu=False)
        assert out["correct"], out["check"]
        if trace:
            assert "requests_seen.trickle" in out["metrics"]
        else:
            assert set(out["metrics"]) == {"setup_s", "ttft_p99_ms",
                                           "ttft_p50_ms"}
