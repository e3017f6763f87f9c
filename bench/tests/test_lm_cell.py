"""A served-LM cell run whole on the CPU: ``systems/lm_serve.py`` over the
twin of ``configs/nemotron_4_15b.json`` (``lm_twin.py``) on one device,
set-up, warm-up, window and check as ``run.py`` makes them, with the
configuration's own check and limit.  The cell, its mix and its metrics
are added to a throwaway checkout as new files and entries, as a
configuration is.
"""
import json
import shutil
import time

import pytest

import lm_twin
import run

SEED = 2 ** 33 + 5
CELL = "tiny_lm.batch"


@pytest.fixture(scope="module")
def lm_checkout(checkout, tmp_path_factory):
    root = tmp_path_factory.mktemp("lm") / "checkout"
    shutil.copytree(checkout, root, symlinks=True,
                    ignore=shutil.ignore_patterns(".cache"))
    bench = root / "bench"
    config = lm_twin.config(max_batch=2, model_axis=1)
    config["name"] = "tiny_lm"
    (bench / "configs" / "tiny_lm.json").write_text(json.dumps(config))
    (bench / "traffic" / "tiny_lm_batch.json").write_text(json.dumps(
        {"arrivals": "saturate", "queued_batches": 1, "pool": 8,
         "prompt_len": 16, "gen": 32}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_lm", "source": "tests",
                            "file": "bench/configs/tiny_lm.json",
                            "reduced": [], "why": "tests on the CPU"})
    spec["workloads"].append({"name": CELL, "config": "tiny_lm",
                              "traffic": "tiny_lm_batch", "chips": 1,
                              "why": "tests on the CPU"})
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if "nemotron4_15b.batch" in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def twin(monkeypatch):
    lm_twin.patch_arch(monkeypatch.setattr)


def _run(root, trace=False, control=False):
    return run.run_cell(root, root / "bench", CELL, SEED, 1.5, trace,
                        t_start=time.perf_counter(), require_tpu=False,
                        control=control)


@pytest.mark.parametrize("trace", [False, True])
def test_lm_cell_serves_and_reads_correct(lm_checkout, twin, trace):
    out = _run(lm_checkout, trace=trace)
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    check = out["check"]["max_logit_deficit"]
    assert check["value"] <= check["limit"]
    if trace:
        # the LM spans, through their readers; no peak on the CPU
        for name in ("prefill_ms.batch", "decode_ms_per_token.batch",
                     "batch_ms.batch", "fetch_ms.batch"):
            assert out["metrics"][name]["value"] > 0
    else:
        assert set(out["metrics"]) == {"throughput", "setup_s"}


def test_lm_cell_with_the_control_in_the_programs_place_reads_incorrect(
        lm_checkout, twin):
    out = _run(lm_checkout, control=True)
    assert not out["correct"]
    check = out["check"]["max_logit_deficit"]
    assert check["value"] > check["limit"]
    assert out["check"]["unanswered"]["value"] == 0


def test_lm_cell_refuses_a_chip_count_other_than_its_model_axis(
        lm_checkout, twin):
    cell, _ = run.open_cell(lm_checkout, lm_checkout / "bench", CELL,
                            require_tpu=False)
    cell.config["serve"]["model_axis"] = 4
    with pytest.raises(run.SetupError, match="model_axis=4"):
        cell.system.start(cell.config, cell.mix, SEED, cell.chips, None)
