#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` on the chip it runs on.

    python3 bench/run.py --workload resnet50.offline --seed 7 --seconds 10 --trace 0

A run reads the cell's configuration (``bench/configs/<config>.json``) and
traffic mix (``bench/traffic/<traffic>.json``).  The configuration names
the system that serves it (``"system": "<name>"``, loaded by path from
``bench/systems/<name>.py`` once the program's ``src/`` is importable;
without the key, ``conv_graph``).  This is the one statement of what a
system module provides; the harness knows nothing else of what it serves:

* ``start(config, mix, seed, chips, plan_dir)``: the engine (not yet
  started), the request pool, an opaque ``weights`` object the reference
  can use once the engine is freed, and a dict of facts for the run's
  stdout.  The engine has ``submit(payload)`` (a ticket whose
  ``result(timeout)`` is the output), ``queue_depth()``, and is a context
  manager that starts and stops it; while ``repro.obs`` is on it records
  each batch's size in the ``serve.batch_size`` histogram.
* ``reference(config, inputs, outputs, weights, precision)``: the plain
  reference's result for each sampled request, from its pool entry and
  the output the program served for it (a reference that depends on the
  input alone ignores the outputs; a served LM's runs over the prompt
  and its served tokens).  ``precision`` is ``"highest"`` for the check.
* ``gap(config, out, ref)``: the number compared for one request, between
  an output and the reference's result for it.  The check takes the
  largest over the sample and holds it to the configuration's one
  ``check`` entry, whose key names it (``max_rel_err`` for the conv
  graphs).
* ``control_precision(config)``: the precision one step below the
  configuration's.  The reference's results there are the control's
  outputs, put in the program's place.
* ``work(config)``: a ``work.Work`` for one request.

A system raises ``work.SetupError`` where the run cannot be made (the
harness's ``SetupError`` is the same class).

Then:

1. set-up: ``start`` from ``--seed`` (plans or other set-up caches go in
   ``bench/.cache/plans``), and a warm-up that serves every batch size from
   1 to ``serve.max_batch`` through the engine.
2. the window: ``--seconds`` of the mix (``loadgen.py``), requests entering
   only through ``engine.submit``.  Compilations inside it are counted.
3. the check (``judge``): a seeded sample of the requests served in the
   window is compared by the system's ``gap`` with its reference at
   highest precision, once the engine's device state is freed.  ``control.py`` drives the same
   run with the control's outputs in the program's place.

``--trace 0`` prints the cell's end-to-end metrics.  ``--trace 1`` runs the
same window traced: its first half under the JAX profiler alone (device
numbers, from ``trace_reduce.py``), its second half with the program's own
spans, counters and histograms on as well (``repro.obs``), and prints the
per-layer metrics, each read by its own reader ``bench/metrics/<name>.py``
from a ``Traced``.  Readers of a share of the chip's peak count the cell's
``chips``: they take the work as split evenly over the chips, and the
trace's busy time as the mean over devices.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``check``); the numbers compared, each beside its limit, are also the
last lines of stderr.  Without a TPU, with fewer chips than the cell asks
for, or on a device kind ``peaks.json`` does not list, the run exits
non-zero before any work and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import loadgen  # noqa: E402
import work as work_mod  # noqa: E402
from work import SetupError  # noqa: E402

# the reference's sample: this many requests served in the window, at most
CHECK_SAMPLE = 64
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def info(**kv) -> None:
    """An earlier stdout line: facts about the run that are not metrics."""
    print("[bench] " + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


# ------------------------------------------------------------------ the spec
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    system: Optional[types.ModuleType] = None   # set by ``open_cell``


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, bench: pathlib.Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(workload, int(w["chips"]), config, mix, e2e, layer)


def _load(path: pathlib.Path, prefix: str, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_system(bench: pathlib.Path, name: str) -> types.ModuleType:
    """The system module a configuration names: ``systems/<name>.py``.
    Its top-level imports run here, so the program's ``src/`` has to be on
    ``sys.path`` first (``open_cell``)."""
    path = bench / "systems" / f"{name}.py"
    if not path.is_file():
        raise SetupError(f"no system {name!r}: {path} does not exist")
    return _load(path, "bench_system_", name)


def load_reader(bench: pathlib.Path, name: str):
    """The reader of a per-layer metric: ``metrics/<name>.py`` where a mix
    needs its own computation, else the one reader of its base name
    (``batch_ms.offline`` is read by ``metrics/batch_ms.py``)."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        path = bench / "metrics" / f"{name.split('.')[0]}.py"
    return _load(path, "bench_metric_", name).read


# ------------------------------------------------------------------ the chip
def enable_caches(bench: pathlib.Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    keeping every program, however fast it compiled: besides each batch
    size's program, the serving path runs small sub-second ones (slices,
    pads, concatenations) that would otherwise compile in every run."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(bench / ".cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_device(chips: int, require_tpu: bool) -> Dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise SetupError(f"JAX found no TPU (platform {dev.platform!r}); "
                             f"the benchmark runs on the chip only")
        if len(devices) < chips:
            raise SetupError(f"the cell asks for {chips} chips; JAX sees "
                             f"{len(devices)}")
        work_mod.peak_for(dev.device_kind)   # an unknown kind is an error
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def chip_peak(device: Dict) -> Optional[Dict]:
    """One chip's published peaks; none off the TPU."""
    if device["platform"] != "tpu":
        return None
    return work_mod.peak_for(device["kind"])


class CompileCounter:
    """Counts XLA compilations (cache hits included) while armed."""

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT and self.armed:
            with self._lock:
                self.count += 1


def memory_peak_bytes(n: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


# ---------------------------------------------------------------- the system
def warm_up(eng, payloads, max_batch: int, tries: int = 4) -> None:
    """Serve every batch size 1..max_batch once.  A one-request blocker
    keeps the worker busy while the k requests queue, so they are
    assembled as one batch of k; sizes the engine's own histogram did not
    see are tried again."""
    from repro import obs

    want = set(range(1, max_batch + 1))
    obs.reset()
    obs.enable()
    try:
        for _ in range(tries):
            for k in sorted(want):
                blocker = eng.submit(payloads[0])
                tickets = [eng.submit(payloads[i % len(payloads)])
                           for i in range(k)]
                blocker.result(timeout=900)
                for t in tickets:
                    t.result(timeout=900)
            want -= {int(s) for s in obs.hist_samples("serve.batch_size")}
            if not want:
                break
    finally:
        obs.reset()
    if want:
        raise SetupError(f"warm-up never assembled batch sizes {sorted(want)}")


# --------------------------------------------------------------- the metrics
def nearest_rank(values: List[float], q: float) -> float:
    """The q-quantile by nearest rank: the smallest value with at least a
    share q of the sample at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def end_to_end(cell: Cell, win: loadgen.WindowResult, setup_s: float
               ) -> Dict[str, Dict]:
    due = win.due()
    lat = [(r.done - r.due) * 1e3 if r.ok else float("inf") for r in due]
    finite = [v for v in lat if math.isfinite(v)]
    values = {
        "setup_s": setup_s,
        "throughput": win.completed_between(win.t0, win.t0 + win.seconds)
        / win.seconds,
    }
    if finite:
        # a failed request misses every limit; with too many failures
        # the tail is the slowest answer the window saw
        for q in (50, 99):
            values[f"ttft_p{q}_ms"] = min(nearest_rank(lat, q / 100),
                                          max(finite))
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise SetupError(f"end-to-end metric {m['name']!r} has no "
                             f"computation in run.py")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def window_facts(win: loadgen.WindowResult) -> Dict:
    due = win.due()
    late = [((r.sent - r.due) * 1e3, r.due - win.t0)
            for r in due if math.isfinite(r.sent)]
    facts = dict(due=len(due), answered=sum(r.ok for r in due),
                 queue_depth_end=win.queue_depth_end)
    if late:
        worst = max(late)
        facts.update(
            generator_late_ms_p50=nearest_rank([v for v, _ in late], 0.5),
            generator_late_ms_p99=nearest_rank([v for v, _ in late], 0.99),
            generator_late_ms_max=worst[0], generator_late_max_at_s=worst[1])
    return facts


class GcPauses:
    """Pauses of Python's garbage collector, every generation, while
    armed: a pause holds every thread of the process, the generator's
    too."""

    def __init__(self):
        self.armed = False
        self.total_s = 0.0
        self.max_s = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None and self.armed:
            dt = time.perf_counter() - self._t
            self.total_s += dt
            self.max_s = max(self.max_s, dt)

    def close(self):
        gc.callbacks.remove(self._on)


# ---------------------------------------------------------------- the check
def check_limit(config: Dict) -> Tuple[str, float]:
    """The name of the number the check compares, and its limit: the
    configuration's one ``check`` entry."""
    if len(config.get("check", {})) != 1:
        raise SetupError("a configuration's \"check\" holds exactly one "
                         "entry: the number compared and its limit")
    (name, limit), = config["check"].items()
    return name, float(limit)


def reference_results(cell: Cell, served: Served,
                      precision: str = "highest") -> list:
    """The system's reference result for each sampled request, in the
    order of ``served.kept``, from its input and its served output."""
    if not served.kept:
        return []
    return list(cell.system.reference(
        cell.config, [served.pool[req.sample] for req, _ in served.kept],
        [out for _, out in served.kept], served.weights, precision))


# ------------------------------------------------------------------- a run
@dataclasses.dataclass
class Traced:
    """What a traced window leaves for the per-layer readers."""

    hist: Dict[str, list]    # every histogram of the second half, by key
    counters: Dict[str, float]   # every counter of the second half, by key
    spans: List[Dict]
    trace: object            # trace_reduce.TraceSummary or None
    window_s: float          # host-clock length of the profiled half
    served: int              # requests completed in it
    work: work_mod.Work      # of one request
    max_batch: int
    peak: Optional[Dict]     # of one chip
    chips: int               # the cell's


def open_cell(root: pathlib.Path, bench: pathlib.Path, workload: str, *,
              require_tpu: bool = True):
    """The cell's spec with its system loaded, and the device it runs on;
    the program importable from this checkout's ``src/`` and JAX's caches
    set.  Raises ``SetupError`` before any work."""
    cell = load_cell(root, bench, workload)
    if not (root / "src" / "repro").is_dir():
        raise SetupError(f"no program under {root / 'src'}: run from a "
                         f"checkout of the repository")
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    cell.system = load_system(bench, cell.config.get("system", "conv_graph"))
    enable_caches(bench)
    return cell, find_device(cell.chips, require_tpu)


@dataclasses.dataclass
class Served:
    """One window served: what the check and the metrics read."""

    win: loadgen.WindowResult
    kept: list               # (request, output) pairs sampled from the seed
    weights: object          # the system's, for its reference
    pool: list               # the request inputs
    setup_s: float
    traced: Optional[Traced]


def start_engine(cell: Cell, bench: pathlib.Path, seed: int,
                 t_start: float):
    """The system's engine (not yet started), request pool and weights,
    from ``seed``."""
    eng, pool, weights, facts = cell.system.start(
        cell.config, cell.mix, seed, cell.chips, bench / ".cache" / "plans")
    info(**facts, start_s=time.perf_counter() - t_start)
    return eng, pool, weights


def serve_cell(cell: Cell, bench: pathlib.Path, device: Dict, seed: int,
               seconds: float, trace: bool, counter: CompileCounter, *,
               t_start: float) -> Served:
    """Set up the engine from ``seed``, warm it, and serve one window."""
    eng, payloads, weights = start_engine(cell, bench, seed, t_start)
    max_batch = int(cell.config["serve"]["max_batch"])
    keep = loadgen.Reservoir(CHECK_SAMPLE, seed)
    traced = None
    pauses = GcPauses()
    with eng:
        warm_up(eng, payloads, max_batch)
        setup_s = time.perf_counter() - t_start
        counter.count = 0
        pauses.armed = True
        if trace:
            win, traced = _traced_window(eng, payloads, cell, max_batch,
                                         seconds, keep, counter, bench,
                                         device)
        else:
            counter.armed = True
            win = loadgen.run_window(eng, payloads, cell.mix, max_batch,
                                     seconds, keep)
            counter.armed = False
        pauses.armed = False
        device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
    pauses.close()
    info(gc_pause_ms_total=pauses.total_s * 1e3,
         gc_pause_ms_max=pauses.max_s * 1e3)
    # the engine's device state goes before the reference runs
    del eng
    gc.collect()
    return Served(win, keep.items, weights, payloads, setup_s, traced)


def control_outputs(cell: Cell, served: Served) -> list:
    """The control in the program's place: for the same sampled requests,
    the reference's results one step below the configuration's precision
    (the system's ``control_precision``)."""
    low = reference_results(cell, served,
                            cell.system.control_precision(cell.config))
    return [(req, out) for (req, _), out in zip(served.kept, low)]


def judge(cell: Cell, served: Served, kept, unanswered: int):
    """``correct``, and each number compared beside its limit: the largest
    of the system's ``gap`` between a sampled output (``kept``, in the
    order of ``served.kept``) and the reference at highest precision over
    the served request, and the due requests left unanswered."""
    name, limit = check_limit(cell.config)
    ref = reference_results(cell, served)
    err = max((float(cell.system.gap(cell.config, out, r))
               for (_, out), r in zip(kept, ref)), default=float("inf"))
    correct = bool(kept) and unanswered == 0 and err <= limit
    return correct, {name: {"value": err, "limit": limit},
                     "unanswered": {"value": unanswered, "limit": 0}}


def run_cell(root: pathlib.Path, bench: pathlib.Path, workload: str,
             seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             control: bool = False) -> Dict:
    """One run of a cell.  ``control`` puts the control's outputs in the
    program's place before the check (``control.py``; never the
    benchmark's own runs)."""
    cell, device = open_cell(root, bench, workload, require_tpu=require_tpu)
    counter = CompileCounter()
    served = serve_cell(cell, bench, device, seed, seconds, trace, counter,
                        t_start=t_start)
    win, traced = served.win, served.traced
    facts = window_facts(win)
    info(setup_s=served.setup_s, compiles_in_window=counter.count,
         sampled=len(served.kept), **facts)
    if counter.count:
        log(f"WARNING: {counter.count} compilations inside the window")

    t_check = time.perf_counter()
    kept = control_outputs(cell, served) if control else served.kept
    correct, compared = judge(cell, served, kept,
                              facts["due"] - facts["answered"])
    info(check_s=time.perf_counter() - t_check)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(bench, m["name"])(traced)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if traced.trace is not None:
            device["busy_s"] = traced.trace.busy_s
            device["window_s"] = traced.trace.window_s
    else:
        metrics = end_to_end(cell, win, served.setup_s)
    out = {"correct": bool(correct), "attempted": len(win.requests),
           "failed": sum(not r.ok for r in win.requests),
           "metrics": metrics, "device": device}
    if trace and traced.trace is not None:
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in traced.trace.top_ops()],
            "idle_gaps": [[k, v] for k, v in traced.trace.top_idle()]}
    out["check"] = compared
    return out


def _traced_window(eng, payloads, cell, max_batch, seconds, keep, counter,
                   bench, device):
    import jax

    import trace_reduce
    from repro import obs

    trace_dir = bench / ".cache" / "trace" / cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    half = {}

    def phases(t0):
        # first half: the profiler alone; second half: the program's own
        # spans and histograms too
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            half["a"] = time.perf_counter()
            time.sleep(max(0.0, t0 + seconds / 2 - time.perf_counter()))
            half["b"] = time.perf_counter()
        obs.reset()
        obs.enable()
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        obs.disable()

    # no Python tracer: it would time every Python call of the serving
    # path's host code, which the window measures
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        counter.armed = True
        win = loadgen.run_window(eng, payloads, cell.mix, max_batch, seconds,
                                 keep, on_start=phases)
        counter.armed = False
    finally:
        jax.profiler.stop_trace()
    spans = [e for e in obs.events() if e.get("ev") == "span"]
    counters, _, hists = obs.registry()
    counters = dict(counters)
    hist = {name: list(samples) for name, samples in hists.items()}
    obs.reset()
    xplane = trace_reduce.find_xplane(trace_dir)
    summary = trace_reduce.reduce_file(xplane) if xplane else None
    shutil.rmtree(trace_dir, ignore_errors=True)
    return win, Traced(
        hist=hist, counters=counters, spans=spans, trace=summary,
        window_s=half["b"] - half["a"],
        served=win.completed_between(half["a"], half["b"]),
        work=cell.system.work(cell.config),
        max_batch=max_batch, peak=chip_peak(device), chips=cell.chips)


def print_result(out: Dict) -> None:
    for name, c in out["check"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(HERE.parent, HERE, args.workload, args.seed,
                       args.seconds, bool(args.trace), t_start=T_PROCESS)
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
