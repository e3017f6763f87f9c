"""A test twin of ``configs/nemotron_4_15b.json`` for the CPU.

It keeps the configuration's per-shard structure at 4 shards, 48 query and
8 KV heads (12 and 2 a shard), and all of the file but the widths: 2
layers, a head of 8, a vocabulary of 1024.  ``patch_arch`` makes the
program's ``nemotron_4_15b`` the twin (bfloat16, as served).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

CONFIG = pathlib.Path(__file__).resolve().parents[1] / "configs" / \
    "nemotron_4_15b.json"
WIDTHS = {"n_layers": 2, "d_model": 384, "head_dim": 8, "d_ff": 1536,
          "vocab": 1024}


def config(max_batch: int = 4, model_axis: int = 1) -> dict:
    """The configuration file with the twin's widths and serve settings."""
    cfg = json.loads(CONFIG.read_text())
    cfg["model"].update(WIDTHS)
    cfg["serve"].update(max_batch=max_batch, model_axis=model_axis)
    return cfg


def arch():
    from repro.configs.nemotron_4_15b import CONFIG as FULL
    return dataclasses.replace(
        FULL, **{k: v for k, v in WIDTHS.items() if k != "head_dim"})


def patch_arch(setattr_fn) -> None:
    """``repro.configs.get_config("nemotron_4_15b")`` returns the twin;
    ``setattr_fn`` is ``monkeypatch.setattr`` or ``setattr``."""
    import repro.configs as configs

    full, twin = configs.get_config, arch()

    def get_config(name, smoke=False):
        return twin if name == "nemotron_4_15b" else full(name, smoke)

    setattr_fn(configs, "get_config", get_config)

