"""repro.api — the blessed, stable surface of the repro stack.

Everything an application (an example, a benchmark, an operator script)
should import lives here; everything else in ``repro.*`` is implementation
and may move without notice.  The contract:

* names in ``__all__`` are stable: they keep their signature and semantics
  across PRs, and removals go through a deprecation cycle;
* the function wrappers take **keyword-only** arguments beyond their
  primary operands, so call sites survive parameter reordering;
* deep imports (``repro.plan.fallback``, ``repro.serve.engine``, ...)
  still work, but new code should not grow them — they are exactly the
  accretion this facade exists to stop.

Typical use::

    from repro import api

    resolved = api.resolve_plan(graph, cfg, opts, cache=api.PlanCache())
    with api.ServeEngine(api.ServeConfig(graph="tiny")) as eng:
        outs = eng.serve(samples)
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Set

# ---- re-exported classes (stable: constructor + documented attrs) --------
from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config
from repro.core.layout import Layout
from repro.core.layoutloop import EvalConfig
from repro.core.workloads import init_graph_weights
from repro.data import DataConfig, SyntheticLMStream, make_stream
from repro.distributed.stepfn import make_train_step
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.optim import adamw_init, adamw_update, wsd_schedule
from repro.plan import (ExecutionPlan, LayerGraph, PlanCache, PlannerOptions,
                        PreparedNetwork, ResolvedPlan, from_arch_config,
                        from_layers, mobilenet_v3_graph, resnet50_graph,
                        step_kernel_blocks)
from repro.plan import execute_network_reference, prepare_network
from repro.plan import resolve_plan as _resolve_plan
from repro.plan import upgrade_plan as _upgrade_plan
from repro.plan import plan_network as _plan_network
from repro.plan import execute_network as _execute_network
from repro.runtime import TrainSupervisor
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import QueueFullError, ServeConfig, ServeEngine, ServeTicket

from repro import obs as _obs

_warned: Set[str] = set()


def warn_deprecated(old: str, new: str) -> None:
    """Log one deprecation warning per process for a legacy entry point."""
    if old in _warned:
        return
    _warned.add(old)
    _obs.get_logger("api").warning(
        "%s is deprecated; import %s from repro.api instead", old, new)


def plan_network(graph: LayerGraph, cfg: EvalConfig, *,
                 opts: Optional[PlannerOptions] = None) -> ExecutionPlan:
    """Stable: full DP/Viterbi network co-search -> ``ExecutionPlan``."""
    from repro.plan import PlannerOptions as _Opts
    return _plan_network(graph, cfg, opts if opts is not None else _Opts())


def resolve_plan(graph: LayerGraph, cfg: EvalConfig, *,
                 opts: Optional[PlannerOptions] = None,
                 cache: Optional[PlanCache] = None,
                 artifact: Optional[str] = None,
                 deadline_s: Optional[float] = None,
                 **kw) -> ResolvedPlan:
    """Stable: degradation-ladder plan resolution — always returns a plan."""
    return _resolve_plan(graph, cfg, opts, cache=cache, artifact=artifact,
                         deadline_s=deadline_s, **kw)


def upgrade_plan(graph: LayerGraph, cfg: EvalConfig, *,
                 opts: Optional[PlannerOptions] = None,
                 cache: Optional[PlanCache] = None,
                 **kw) -> Optional[ResolvedPlan]:
    """Stable: tier-1-only background re-plan; ``None`` means try later."""
    return _upgrade_plan(graph, cfg, opts, cache=cache, **kw)


def execute_network(plan: ExecutionPlan, graph: LayerGraph, x, weights, *,
                    activation: Optional[Callable] = None,
                    use_pallas: bool = True,
                    prepared: Optional[PreparedNetwork] = None,
                    biases: Optional[Sequence] = None):
    """Stable: run a planned network end to end through the RIR executors."""
    return _execute_network(plan, graph, x, weights, activation=activation,
                            use_pallas=use_pallas, prepared=prepared,
                            biases=biases)


__all__ = [
    # planning
    "EvalConfig", "Layout", "LayerGraph", "PlannerOptions", "ExecutionPlan",
    "PlanCache", "ResolvedPlan",
    "from_layers", "resnet50_graph", "mobilenet_v3_graph", "from_arch_config",
    "init_graph_weights",
    "plan_network", "resolve_plan", "upgrade_plan",
    # execution
    "PreparedNetwork", "prepare_network", "execute_network",
    "execute_network_reference",
    "step_kernel_blocks",
    # serving
    "ServeEngine", "ServeConfig", "ServeTicket", "QueueFullError",
    # model zoo + configs (the app-building surface)
    "ARCH_IDS", "get_config", "build_model",
    # training loop: data, step function, optimizer, mesh, checkpoints
    "DataConfig", "SyntheticLMStream", "make_stream", "make_train_step",
    "make_local_mesh", "adamw_init", "adamw_update", "wsd_schedule",
    "CheckpointManager", "TrainSupervisor",
    # entry-point set-up: the persistent compilation cache's directory
    "enable_compile_cache",
    # deprecation helper (for legacy shims, not applications)
    "warn_deprecated",
]
