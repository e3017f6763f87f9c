# check: ignore-file[api-boundary]  (operator dev tool: inspects internals by design)
import os
# compiles on placeholder host devices: never take an accelerator, here or
# in the children ``--all`` spawns (they inherit this environment)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: the production
mesh is built from 512 placeholder host devices, every cell's step function
is lowered with ShapeDtypeStruct stand-ins and compiled by XLA SPMD, and the
compiled artifact's memory/cost/collective statistics are recorded for the
roofline analysis (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3p2_3b \
        --shape train_4k [--multi-pod] [--layout-mode coswitch] [--out f.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all --jobs 6
"""
import argparse
import json
import pathlib
import re
import subprocess
import sys
import time

from repro import obs

log = obs.get_logger("dryrun")


def _collective_bytes(hlo: str):
    from repro.core.tpu_cost import collective_bytes_from_hlo
    return collective_bytes_from_hlo(hlo)


def run_cell(arch: str, shape: str, multi_pod: bool,
             layout_mode: str = "coswitch", accum: int = 8) -> dict:
    import jax

    from repro.configs import get_config
    from repro.distributed.stepfn import (jit_prefill, jit_serve_step,
                                          jit_train_step, shardings_for_train)
    from repro.kernels import ops
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs
    from repro.models import build_model
    from repro.optim.adamw import AdamWState
    from jax.sharding import PartitionSpec as P

    ops.use_kernels(False)  # dry-run lowers the pure-XLA path (shardable)
    cfg = get_config(arch)
    cell_kind = ("train" if shape.startswith("train") else
                 "prefill" if shape.startswith("prefill") else "decode")
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = input_specs(arch, shape)
    t0 = time.time()

    with mesh:
        if cell_kind == "train":
            p_sh, _ = shardings_for_train(model, mesh)
            pspecs = model.param_specs()
            opt_specs = AdamWState(
                step=jax.ShapeDtypeStruct((), "int32"),
                mu=jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                    s.shape, "float32"), pspecs),
                nu=jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                    s.shape, "float32"), pspecs),
                master=jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                    s.shape, "float32"), pspecs))
            fn = jit_train_step(model, mesh, specs["batch"],
                                layout_mode=layout_mode, accum=accum)
            lowered = fn.lower(pspecs, opt_specs, specs["batch"])
        elif cell_kind == "prefill":
            from repro.configs.base import shape_by_name
            cell = shape_by_name(shape)
            fn = jit_prefill(model, mesh, cell.global_batch, cell.seq_len,
                             cell.seq_len, frames="frames" in specs)
            args = (model.param_specs(), specs["tokens"])
            if "frames" in specs:
                args = args + (specs["frames"],)
            lowered = fn.lower(*args)
        else:
            from repro.configs.base import shape_by_name
            cell = shape_by_name(shape)
            fn = jit_serve_step(model, mesh, cell.global_batch, cell.seq_len)
            lowered = fn.lower(model.param_specs(), specs["cache"],
                               specs["tokens"])
        compiled = lowered.compile()

    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.core.hlo_cost import analyze_hlo
    walked = analyze_hlo(hlo)   # trip-count-aware (scan bodies multiplied)
    chips = 512 if multi_pod else 256

    result = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "layout_mode": layout_mode,
        "compile_s": round(t_compile, 1),
        "per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        # trip-count-aware per-device totals (core/hlo_cost.py)
        "hlo_flops_per_device": walked.flops,
        "hlo_bytes_per_device": walked.bytes,
        "collective_bytes_per_device": walked.collective_bytes,
        "collective_kinds": walked.collective_kinds,
        # XLA's own (loop-once) numbers, for reference
        "xla_loop_once": {
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
        },
        "chips": chips,
        "n_params": _tree_params(model),
        "n_params_active": _tree_params(model, active_only=True),
    }
    return result


def _tree_params(model, active_only: bool = False) -> float:
    """Parameter count from the spec tree; for MoE, active = top_k/E of the
    4D expert tensors (+ everything else)."""
    import numpy as np
    import jax
    cfg = model.cfg
    total = expert = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            model.param_specs())[0]:
        n = float(np.prod(leaf.shape))
        total += n
        keys = "/".join(str(getattr(p, "key", "")) for p in path)
        if len(leaf.shape) == 4 and "ffn" in keys and "shared" not in keys:
            expert += n
    if not active_only or cfg.family != "moe" or not cfg.n_experts:
        return total
    return total - expert * (1.0 - cfg.top_k / cfg.n_experts)


# --------------------------------------------------------------------- driver
def all_cells():
    from repro.configs import ARCH_IDS, cells_for
    for arch in ARCH_IDS:
        for cell in cells_for(arch):
            yield arch, cell.name


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--layout-mode", default="coswitch",
                    choices=["coswitch", "fixed"])
    ap.add_argument("--accum", type=int, default=8)
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true",
                    help="run every assigned cell (both meshes) as subprocesses")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--results-dir", default="results/dryrun")
    ap.add_argument("--log-level", default=None,
                    choices=["debug", "info", "warning", "error"],
                    help="console log threshold (default: REPRO_LOG or info)")
    args = ap.parse_args()

    obs.configure_from_env()          # REPRO_TRACE=path enables tracing
    if args.log_level:
        obs.set_level(args.log_level)

    if args.all:
        rdir = pathlib.Path(args.results_dir)
        rdir.mkdir(parents=True, exist_ok=True)
        jobs = []
        for arch, shape in all_cells():
            for mp in (False, True):
                tag = f"{arch}-{shape}-{'mp' if mp else 'sp'}"
                out = rdir / f"{tag}.json"
                if out.exists():
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", str(out),
                       "--layout-mode", args.layout_mode]
                if mp:
                    cmd.append("--multi-pod")
                jobs.append((tag, cmd))
        running = []
        while jobs or running:
            while jobs and len(running) < args.jobs:
                tag, cmd = jobs.pop(0)
                log.info("start %s", tag)
                running.append((tag, subprocess.Popen(
                    cmd, stdout=subprocess.DEVNULL,
                    stderr=subprocess.PIPE)))
            done = [r for r in running if r[1].poll() is not None]
            for tag, proc in done:
                running.remove((tag, proc))
                if proc.returncode == 0:
                    log.info("ok %s", tag)
                else:
                    log.error("FAIL %s", tag)
                if proc.returncode != 0:
                    err = proc.stderr.read().decode()[-2000:]
                    (pathlib.Path(args.results_dir) / f"{tag}.err").write_text(err)
            time.sleep(2)
        return

    result = run_cell(args.arch, args.shape, args.multi_pod,
                      args.layout_mode, args.accum)
    text = json.dumps(result, indent=2)
    print(text)
    if args.out:
        pathlib.Path(args.out).write_text(text)


if __name__ == "__main__":
    main()
