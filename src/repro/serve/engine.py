"""Continuous-batching serve engine for planned networks.

The request-level serving loop FEATHER's cheap dataflow switching is *for*:
requests enter a bounded admission queue, worker threads assemble dynamic
batches up to the plan tile's batch extent (pad-and-mask — outputs are
bit-identical to serving each request alone, asserted in the tests), and
every batch runs through the per-plan ``PreparedNetwork`` setup that PR 5
hoisted out of the per-batch path.  Plan resolution rides the degradation
ladder (``repro.plan.resolve_plan``) against a warm ``PlanCache`` shared
across workers, and a request admitted at a degraded tier upgrades itself:
a background thread retries the full planner (``repro.plan.upgrade_plan``)
and atomically swaps in the tier-1 prepared network once it recovers —
the serving loop never blocks on planning.

Pipeline::

    submit() -> [bounded queue] -> assembler (<= plan batch extent)
             -> PreparedNetwork / LM prefill+decode -> per-request results
                          ^ background tier upgrader (degraded plans only)

Backpressure is a *typed* contract: a full queue (or an injected
``serve.queue`` admission fault — the chaos schedule's new site) rejects
with ``QueueFullError`` immediately; admission never blocks and never
deadlocks.  Observability: ``serve.queue_depth`` gauge,
``serve.batch_size`` / ``serve.time_in_queue_ms`` / ``serve.e2e_ms``
histograms, ``serve.requests`` / ``serve.rejected{reason=}`` /
``serve.batches`` / ``serve.plan_upgrade`` counters, and a ``serve.batch``
span carrying ``plan_id`` / ``plan_tier`` / ``plan_reason``.  Inside it, a
network batch's host phases are spans of their own: ``exec.assemble``,
``exec.network`` (dispatch), ``exec.split``, ``serve.wait`` (the host
blocked on the device) and ``serve.fetch`` (device-to-host copies).
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.runtime import faults

from .config import ServeConfig

log = obs.get_logger("serve")


class ServeError(Exception):
    """Base class for engine-surface failures.

    Deliberately NOT a ``RuntimeError``: the recovery layers retry
    ``STEP_FAULT_TYPES`` as machine faults, and an engine-surface error
    (bad request shape, stopped engine, typed backpressure) is a caller
    condition to handle, not a fault to retry blindly."""


class QueueFullError(ServeError):
    """Typed backpressure rejection: admission failed, retry later.

    ``reason`` is ``"capacity"`` (bounded queue full), ``"fault"`` (an
    injected/real admission fault at the ``serve.queue`` site), or
    ``"stopped"`` (engine shut down).  Clients treat all three the same
    way: back off and resubmit, or shed the request.
    """

    def __init__(self, msg: str, reason: str):
        super().__init__(msg)
        self.reason = reason


class ServeTicket:
    """A submitted request's handle: blocks on ``result()`` until served."""

    __slots__ = ("rid", "payload", "submit_us", "_event", "_value", "_exc")

    def __init__(self, rid: int, payload):
        self.rid = rid
        self.payload = payload
        self.submit_us = obs.now_us()
        self._event = threading.Event()
        self._value = None
        self._exc: Optional[BaseException] = None

    def _resolve(self, value=None, exc: Optional[BaseException] = None):
        self._value, self._exc = value, exc
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        """The request's output (LM: generated tokens; network: its own
        sample's activation).  Raises the batch's failure, or
        ``TimeoutError`` if not served within ``timeout`` seconds."""
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} not served within "
                               f"{timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._value


# ========================================================================
# Backends: what one assembled batch *does*
# ========================================================================
class _NetworkBackend:
    """Planned conv-network serving through ``PreparedNetwork``."""

    def __init__(self, config: ServeConfig, cache, graph, weights,
                 sleep: Callable[[float], None]):
        from repro.core.layoutloop import EvalConfig
        from repro.core.workloads import init_graph_weights
        from repro.obs.smoke import build_graph
        from repro.plan import prepare_network, resolve_plan

        self.config = config
        self.cache = cache
        self.eval_cfg = EvalConfig()
        self.opts = _planner_options(config)
        base = graph if graph is not None else build_graph(config.graph)
        self.graph = base.with_batch(config.max_batch)
        self.weights = weights if weights is not None else \
            init_graph_weights(list(self.graph.layers), seed=config.seed)
        with obs.span("serve.plan", {"graph": self.graph.name}):
            self.resolved = resolve_plan(
                self.graph, self.eval_cfg, self.opts, cache=cache,
                artifact=config.plan, deadline_s=config.plan_deadline,
                sleep=sleep)
        self.prepared = prepare_network(self.resolved.plan, self.graph,
                                        self.weights)

    @property
    def sample_shape(self):
        return self.prepared.input_shape[1:]

    def validate(self, payload) -> None:
        a = np.asarray(payload)
        if a.shape != self.sample_shape:
            raise ServeError(f"request shape {a.shape} != planned "
                             f"per-sample shape {self.sample_shape}")

    def run(self, prepared, payloads: Sequence) -> List[np.ndarray]:
        import jax
        outs = prepared.execute_requests(
            payloads, use_pallas=self.config.use_pallas)
        with obs.span("serve.wait"):
            outs = jax.block_until_ready(outs)
        with obs.span("serve.fetch"):
            return [np.asarray(o) for o in outs]

    def upgraded(self, resolved):
        """Build the tier-1 prepared network for an upgraded plan."""
        from repro.plan import prepare_network
        return prepare_network(resolved.plan, self.graph, self.weights)


class _LMBackend:
    """LM serving through the existing prefill/decode path.

    The model runs on the first ``model_axis`` local devices, one (1,
    model_axis) mesh: a one-chip deployment holds one chip even on a host
    with more.  Parameters are created in place, laid out by the
    ``distributed.sharding`` rules over that mesh.
    """

    def __init__(self, config: ServeConfig, cache,
                 sleep: Callable[[float], None]):
        import jax

        from repro.configs import get_config
        from repro.distributed.sharding import param_shardings
        from repro.launch.mesh import make_local_mesh
        from repro.models import build_model

        self.config = config
        self.cache = cache
        self.cfg = get_config(config.arch, smoke=config.smoke)
        self.resolved = None
        self.graph = None
        if config.plan is not None:
            from repro.core.layoutloop import EvalConfig
            from repro.plan import from_arch_config, resolve_plan

            self.eval_cfg = EvalConfig()
            self.opts = _planner_options(config)
            self.graph = from_arch_config(
                self.cfg, seq=config.prompt_len + config.gen)
            with obs.span("serve.plan", {"arch": self.cfg.name}):
                self.resolved = resolve_plan(
                    self.graph, self.eval_cfg, self.opts, cache=cache,
                    artifact=config.plan, deadline_s=config.plan_deadline,
                    sleep=sleep)
        self.model = build_model(self.cfg)
        self.mesh = make_local_mesh(
            config.model_axis, jax.devices()[:config.model_axis])
        self.model.mesh = self.mesh
        init_key, _ = jax.random.split(jax.random.PRNGKey(config.seed))
        shardings = param_shardings(self.mesh, self.model.param_specs())
        self.params = jax.jit(self.model.init,
                              out_shardings=shardings)(init_key)
        self.decode = jax.jit(self.model.decode_step)
        self.prefill = jax.jit(self.model.prefill, static_argnums=2)
        self.max_seq = config.prompt_len + config.gen

    @property
    def prepared(self):
        return None   # decode runs through the model's own jitted step

    def validate(self, payload) -> None:
        a = np.asarray(payload)
        if a.shape != (self.config.prompt_len,):
            raise ServeError(f"prompt shape {a.shape} != "
                             f"({self.config.prompt_len},) — requests carry "
                             f"exactly prompt_len tokens")

    def run(self, _prepared, payloads: Sequence) -> List[np.ndarray]:
        import jax
        import jax.numpy as jnp

        B = self.config.max_batch
        k = len(payloads)
        prompts = np.zeros((B, self.config.prompt_len), np.int32)
        for i, p in enumerate(payloads):
            prompts[i] = np.asarray(p, np.int32)
        prompts = jnp.asarray(prompts)
        gen = self.config.gen
        with self.mesh:
            t0 = time.perf_counter()
            if self.cfg.family in ("ssm", "hybrid"):
                cache = self.model.init_cache(B, self.max_seq)
                logits = None
                for t in range(self.config.prompt_len):  # SSM scan-in
                    cache, logits = self.decode(self.params, cache,
                                                prompts[:, t])
            else:
                cache, logits = self.prefill(self.params, prompts,
                                             self.max_seq)
            logits = jax.block_until_ready(logits)
            t_prefill = time.perf_counter() - t0
            obs.observe("serve.prefill_ms", t_prefill * 1e3)
            tokens = jnp.argmax(logits, axis=-1)
            out = [tokens]
            t0 = time.perf_counter()
            for _ in range(gen - 1):
                cache, logits = self.decode(self.params, cache, tokens)
                tokens = jnp.argmax(logits, axis=-1)
                out.append(tokens)
            tokens = jax.block_until_ready(tokens)
            t_decode = time.perf_counter() - t0
        if gen > 1:
            obs.observe("serve.decode_ms_per_token",
                        t_decode * 1e3 / (gen - 1))
        log.debug("batch of %d: prefill %.1f ms; decode %.1f ms/token",
                  k, t_prefill * 1e3, t_decode * 1e3 / max(1, gen - 1))
        toks = np.stack([np.asarray(t) for t in out], axis=1)   # (B, gen)
        return [toks[i] for i in range(k)]

    def upgraded(self, resolved):
        return None


def _planner_options(config: ServeConfig):
    from repro.core.layout import Layout
    from repro.plan import PlannerOptions

    layouts = None
    if config.layouts is not None:
        layouts = tuple(Layout.parse(s) for s in config.layouts)
    return PlannerOptions(switch_modes=("rir",), layouts=layouts,
                          parallel_dims=("C", "P", "Q"))


# ========================================================================
# The engine
# ========================================================================
_SENTINEL = object()


class ServeEngine:
    """Request-level continuous batching over a planned network or LM.

    Construction resolves the plan (degradation ladder + shared cache) and
    hoists all per-plan setup; ``start()`` spawns the assembler workers;
    ``submit()`` is non-blocking admission returning a ``ServeTicket``.
    Use as a context manager::

        with ServeEngine(ServeConfig(graph="tiny", max_batch=4)) as eng:
            outs = eng.serve(samples)
    """

    def __init__(self, config: ServeConfig, *, cache=None, graph=None,
                 weights=None, sleep: Callable[[float], None] = time.sleep):
        from repro.plan import PlanCache

        self.config = config
        self._sleep = sleep
        self.cache = cache if cache is not None else PlanCache()
        if config.log_level:
            obs.set_level(config.log_level)
        if config.arch is not None:
            self._backend = _LMBackend(config, self.cache, sleep)
        else:
            self._backend = _NetworkBackend(config, self.cache, graph,
                                            weights, sleep)
        self._resolved = self._backend.resolved
        self._prepared = self._backend.prepared
        self._swap_lock = threading.Lock()
        self._queue: "queue.Queue" = queue.Queue(maxsize=config.queue_capacity)
        self._rid = itertools.count()
        self._workers: List[threading.Thread] = []
        self._upgrader: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._started = False
        if self._resolved is not None:
            log.info("plan %s tier=%s%s", self._resolved.plan.plan_id,
                     self._resolved.tier_name,
                     f" reason={self._resolved.reason!r}"
                     if self._resolved.reason else "")

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "ServeEngine":
        if self._started:
            return self
        self._started = True
        self._stop.clear()
        for i in range(self.config.workers):
            t = threading.Thread(target=self._worker_loop,
                                 name=f"serve-worker-{i}", daemon=True)
            t.start()
            self._workers.append(t)
        if self.resolved is not None and self.resolved.degraded:
            self._upgrader = threading.Thread(
                target=self._upgrade_loop, name="serve-upgrader", daemon=True)
            self._upgrader.start()
        return self

    def stop(self) -> None:
        if not self._started:
            return
        self._stop.set()
        for _ in self._workers:
            try:
                self._queue.put_nowait(_SENTINEL)
            except queue.Full:
                pass
        for t in self._workers:
            t.join(timeout=30.0)
        if self._upgrader is not None:
            self._upgrader.join(timeout=30.0)
        # fail anything still queued — a stopped engine must not strand
        # callers blocked on result()
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not _SENTINEL:
                item._resolve(exc=ServeError("engine stopped before "
                                             "this request was served"))
        self._workers = []
        self._upgrader = None
        self._started = False

    def __enter__(self) -> "ServeEngine":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # ------------------------------------------------------------ admission
    @property
    def resolved(self):
        """The currently-serving ``ResolvedPlan`` (upgrades swap it)."""
        with self._swap_lock:
            return self._resolved

    @property
    def lm(self):
        """``(model, params, mesh)`` an LM engine serves with, for callers
        that check its outputs against a reference; ``None`` for a network
        engine."""
        b = self._backend
        return (b.model, b.params, b.mesh) if self.config.arch else None

    def queue_depth(self) -> int:
        return self._queue.qsize()

    @property
    def sample_shape(self):
        """Per-request payload shape: the planned per-sample activation
        shape (network mode) or ``(prompt_len,)`` of int32 tokens (LM)."""
        if self.config.arch is not None:
            return (self.config.prompt_len,)
        return self._backend.sample_shape

    def submit(self, payload) -> ServeTicket:
        """Admit one request; non-blocking, typed-rejection backpressure.

        Raises ``QueueFullError`` when the bounded queue is full, admission
        faults (the ``serve.queue`` site), or the engine is stopped —
        admission never blocks, so a saturated engine can never deadlock
        its clients.
        """
        if not self._started or self._stop.is_set():
            obs.inc_counter("serve.rejected", reason="stopped")
            raise QueueFullError("engine is not running", reason="stopped")
        try:
            faults.site(faults.SERVE_QUEUE)
        except faults.STEP_FAULT_TYPES as e:
            obs.inc_counter("serve.rejected", reason="fault")
            raise QueueFullError(
                f"admission fault: {type(e).__name__}: {e}",
                reason="fault") from e
        self._backend.validate(payload)
        ticket = ServeTicket(next(self._rid), payload)
        try:
            self._queue.put_nowait(ticket)
        except queue.Full:
            obs.inc_counter("serve.rejected", reason="capacity")
            raise QueueFullError(
                f"queue at capacity ({self.config.queue_capacity})",
                reason="capacity") from None
        obs.inc_counter("serve.requests")
        obs.set_gauge("serve.queue_depth", self._queue.qsize())
        return ticket

    def serve(self, payloads: Sequence, *, timeout: float = 600.0,
              backoff_s: float = 0.01) -> List:
        """Submit a request list (retrying typed rejections) and collect
        every result in submission order — the convenience loop the CLI,
        smoke and benchmark share."""
        tickets = []
        for p in payloads:
            while True:
                try:
                    tickets.append(self.submit(p))
                    break
                except QueueFullError as e:
                    if e.reason == "stopped":
                        raise
                    self._sleep(backoff_s)
        return [t.result(timeout=timeout) for t in tickets]

    # ------------------------------------------------------------- assembler
    def _worker_loop(self) -> None:
        limit = self.config.batch_limit
        while not self._stop.is_set():
            try:
                first = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            if first is _SENTINEL:
                return
            batch = [first]
            while len(batch) < limit:
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    break
                if item is _SENTINEL:
                    try:
                        # keep the shutdown token visible to sibling workers
                        self._queue.put_nowait(_SENTINEL)
                    except queue.Full:
                        pass   # workers also exit on the stop event
                    break
                batch.append(item)
            obs.set_gauge("serve.queue_depth", self._queue.qsize())
            self._run_batch(batch)

    def _run_batch(self, batch: List[ServeTicket]) -> None:
        with self._swap_lock:
            resolved, prepared = self._resolved, self._prepared
        t_asm = obs.now_us()
        traced = obs.enabled()
        if traced:
            obs.observe("serve.batch_size", len(batch))
            for t in batch:
                obs.observe("serve.time_in_queue_ms",
                            (t_asm - t.submit_us) / 1e3)
        attrs = None
        if obs.active():
            attrs = {"batch": len(batch)}
            if resolved is not None:
                attrs.update(plan_id=resolved.plan.plan_id,
                             plan_tier=resolved.tier_name,
                             plan_reason=resolved.reason)
        try:
            with obs.span("serve.batch", attrs):
                outs = self._backend.run(prepared,
                                         [t.payload for t in batch])
        except Exception as e:   # noqa: BLE001 — fail the batch, keep serving
            obs.inc_counter("serve.batch_failed", type=type(e).__name__)
            log.warning("batch of %d failed (%s: %s)", len(batch),
                        type(e).__name__, e)
            for t in batch:
                t._resolve(exc=e)
            return
        obs.inc_counter("serve.batches")
        done = obs.now_us()
        for t, out in zip(batch, outs):
            t._resolve(value=out)
            if traced:
                obs.observe("serve.e2e_ms", (done - t.submit_us) / 1e3)

    # ---------------------------------------------------------- tier upgrade
    def _upgrade_loop(self) -> None:
        """Background re-planning: degraded tier -> tier 1, never blocking.

        Runs only while the engine serves a degraded plan.  Each round
        waits ``upgrade_interval_s``, retries the full planner via
        ``upgrade_plan`` (cache hit counts — another worker may win the
        race), builds the new prepared network *off* the serving path, and
        swaps it in atomically between batches.
        """
        from repro.plan import upgrade_plan

        b = self._backend
        while not self._stop.wait(self.config.upgrade_interval_s):
            up = upgrade_plan(b.graph, b.eval_cfg, b.opts, cache=self.cache,
                              artifact=self.config.plan, sleep=self._sleep)
            if up is None:
                continue
            prepared = b.upgraded(up)
            with self._swap_lock:
                old = self._resolved
                self._resolved, self._prepared = up, prepared
            obs.inc_counter("serve.plan_upgrade")
            log.info("plan upgraded %s -> %s (plan %s)", old.tier_name,
                     up.tier_name, up.plan.plan_id)
            return
