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
             -> launch: PreparedNetwork dispatch + split, copies to host begun
                        (LM: prefill + decode, run to completion)
             -> finish: wait, fetch -> per-request results
                          ^ background tier upgrader (degraded plans only)

Each worker keeps one network batch in flight: it launches batch k+1, if
one is queued, while the device still runs batch k, and only then finishes
batch k, so the host's part of a batch overlaps the device's part of the
one before (``serve.batches_overlapped`` counts such launches).

Backpressure is a *typed* contract: a full queue (or an injected
``serve.queue`` admission fault — the chaos schedule's new site) rejects
with ``QueueFullError`` immediately; admission never blocks and never
deadlocks.  Observability: ``serve.queue_depth`` gauge,
``serve.batch_size`` / ``serve.time_in_queue_ms`` / ``serve.e2e_ms``
histograms, ``serve.requests`` / ``serve.rejected{reason=}`` /
``serve.batches`` / ``serve.batches_overlapped`` / ``serve.plan_upgrade``
counters, and a ``serve.batch`` span carrying ``plan_id`` / ``plan_tier``
/ ``plan_reason``, from the start of the batch's assembly until its
tickets are resolved (recorded after the fact: a batch's span interleaves
with its neighbour's).  Inside it, a network batch's host phases are spans
of their own: in the launch ``exec.assemble``, ``exec.network``
(dispatch) and ``exec.split`` (dispatch of the split), in the finish
``serve.wait`` (the host blocked on the device) and ``serve.fetch`` (the
rest of the device-to-host copies); an LM batch's are ``lm.prefill``
(through the wait on its logits), ``lm.decode`` (the decode steps through
the wait on the last token) and ``serve.fetch``.
"""
from __future__ import annotations

import dataclasses
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

    def launch(self, prepared, payloads: Sequence) -> list:
        """Assemble and dispatch one batch and its split, and start each
        output's copy to the host; returns before the device is done."""
        outs = prepared.execute_requests(
            payloads, use_pallas=self.config.use_pallas)
        for o in outs:
            o.copy_to_host_async()
        return outs

    @staticmethod
    def finish(outs) -> List[np.ndarray]:
        """Wait for a launched batch and fetch its outputs to the host."""
        import jax
        with obs.span("serve.wait"):
            outs = jax.block_until_ready(outs)
        with obs.span("serve.fetch"):
            return [np.asarray(o) for o in outs]

    def upgraded(self, resolved):
        """Build the tier-1 prepared network for an upgraded plan and
        compile its batch program here, off the serving path, so no
        request batch pays for the compile once it is swapped in."""
        from repro.plan import prepare_network
        prepared = prepare_network(resolved.plan, self.graph, self.weights)
        prepared.warm(use_pallas=self.config.use_pallas)
        return prepared


class _LMBackend:
    """LM serving through the existing prefill/decode path.

    The model runs on the first ``model_axis`` local devices, one (1,
    model_axis) mesh: a one-chip deployment holds one chip even on a host
    with more.  Parameters are laid out by the ``distributed.sharding``
    rules over that mesh: drawn in place by ``LMModel.init`` from the
    seed, or, given ``weights``, made by ``weights(shardings)`` (the
    caller's parameters in that layout, the model's tree, shapes and
    dtypes, or ``ServeError``).
    """

    def __init__(self, config: ServeConfig, cache, weights,
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
        if weights is None:
            self.params = jax.jit(self.model.init,
                                  out_shardings=shardings)(init_key)
        else:
            self.params = weights(shardings)
            _check_params(self.params, jax.eval_shape(self.model.init,
                                                      init_key))
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

    def launch(self, _prepared, payloads: Sequence) -> List[np.ndarray]:
        """Prefill, ``gen - 1`` greedy decode steps and the fetch of the
        tokens, each in its own span (``lm.prefill``, ``lm.decode``,
        ``serve.fetch``); the three add up to the batch.  The decode loop
        waits on every token, so the batch is done when this returns."""
        import jax
        import jax.numpy as jnp

        B = self.config.max_batch
        k = len(payloads)
        gen = self.config.gen
        with self.mesh:
            with obs.span("lm.prefill"):
                prompts = np.zeros((B, self.config.prompt_len), np.int32)
                for i, p in enumerate(payloads):
                    prompts[i] = np.asarray(p, np.int32)
                prompts = jnp.asarray(prompts)
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
            with obs.span("lm.decode", {"steps": gen - 1}):
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
        with obs.span("serve.fetch"):
            toks = np.stack([np.asarray(t) for t in out], axis=1)  # (B, gen)
            return [toks[i] for i in range(k)]

    @staticmethod
    def finish(outs) -> List[np.ndarray]:
        return outs

    def upgraded(self, resolved):
        return None


def _check_params(params, want) -> None:
    """``params`` has ``want``'s tree, and each leaf its shape and dtype."""
    import jax

    if jax.tree.structure(params) != jax.tree.structure(want):
        raise ServeError(f"parameters {jax.tree.structure(params)} are not "
                         f"the model's {jax.tree.structure(want)}")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(want)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ServeError(
                f"parameter {jax.tree_util.keystr(path)} is {a.shape} "
                f"{a.dtype}; the model's is {b.shape} {b.dtype}")


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


def _on_host(outs) -> bool:
    """Whether a launch returned its outputs already on the host."""
    return all(isinstance(o, np.ndarray) for o in outs)


@dataclasses.dataclass
class _Launched:
    """A batch its backend has launched: its tickets, its ``serve.batch``
    span's start and attrs, and the backend's handle on its outputs."""

    batch: List[ServeTicket]
    start_us: float
    attrs: Optional[dict]
    outs: object = None


class ServeEngine:
    """Request-level continuous batching over a planned network or LM.

    Construction resolves the plan (degradation ladder + shared cache) and
    hoists all per-plan setup; ``start()`` spawns the assembler workers;
    ``submit()`` is non-blocking admission returning a ``ServeTicket``.
    Use as a context manager::

        with ServeEngine(ServeConfig(graph="tiny", max_batch=4)) as eng:
            outs = eng.serve(samples)

    ``weights`` replaces the seeded draw: a network's per-layer weights,
    or, for an LM, a function of the parameter shardings that returns the
    parameters to serve, laid out by them.
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
            self._backend = _LMBackend(config, self.cache, weights, sleep)
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
        """Serve batches, launching the next before finishing the last.

        At most one launched, unfinished batch is held: while it runs on
        the device the worker assembles and dispatches the next one, and
        only then waits on, fetches and resolves it.  The next batch is
        taken only if it is already queued, so a lone request never waits
        for a second one.  A batch whose launch returned its outputs on the
        host (an LM batch: its decode loop waits on every token) has
        nothing left to wait for and is finished at once; one whose launch
        returned device arrays is held even if the device is already done,
        so the next batch reaches the device before this one's tickets are
        resolved."""
        in_flight: Optional[_Launched] = None
        try:
            while not self._stop.is_set():
                try:
                    first = self._queue.get(timeout=0.1) \
                        if in_flight is None else self._queue.get_nowait()
                except queue.Empty:
                    if in_flight is not None:
                        self._finish(in_flight)
                        in_flight = None
                    continue
                if first is _SENTINEL:
                    return
                launched = self._launch(self._gather(first),
                                        overlapped=in_flight is not None)
                if in_flight is not None:
                    self._finish(in_flight)
                    in_flight = None
                if launched is not None and _on_host(launched.outs):
                    self._finish(launched)
                else:
                    in_flight = launched
        finally:
            if in_flight is not None:
                self._finish(in_flight)

    def _gather(self, first: ServeTicket) -> List[ServeTicket]:
        """``first`` and whatever else is queued, up to the batch limit."""
        batch = [first]
        while len(batch) < self.config.batch_limit:
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
        return batch

    def _launch(self, batch: List[ServeTicket],
                overlapped: bool) -> Optional[_Launched]:
        """Start one batch on the prepared network of the moment; ``None``
        if its launch raised, which fails its tickets alone."""
        with self._swap_lock:
            resolved, prepared = self._resolved, self._prepared
        t_asm = obs.now_us()
        attrs = None
        if obs.enabled():
            obs.observe("serve.batch_size", len(batch))
            for t in batch:
                obs.observe("serve.time_in_queue_ms",
                            (t_asm - t.submit_us) / 1e3)
            attrs = {"batch": len(batch)}
            if resolved is not None:
                attrs.update(plan_id=resolved.plan.plan_id,
                             plan_tier=resolved.tier_name,
                             plan_reason=resolved.reason)
        launched = _Launched(batch, t_asm, attrs)
        try:
            launched.outs = self._backend.launch(
                prepared, [t.payload for t in batch])
        except Exception as e:   # noqa: BLE001 — fail the batch, keep serving
            self._fail(launched, e)
            return None
        if overlapped:
            obs.inc_counter("serve.batches_overlapped")
        return launched

    def _finish(self, launched: _Launched) -> None:
        """Wait for a launched batch, fetch its outputs and resolve its
        tickets; a failure fails this batch alone."""
        try:
            outs = self._backend.finish(launched.outs)
        except Exception as e:   # noqa: BLE001 — fail the batch, keep serving
            self._fail(launched, e)
            return
        obs.inc_counter("serve.batches")
        done = obs.now_us()
        traced = obs.enabled()
        for t, out in zip(launched.batch, outs):
            t._resolve(value=out)
            if traced:
                obs.observe("serve.e2e_ms", (done - t.submit_us) / 1e3)
        obs.record_span("serve.batch", launched.start_us, launched.attrs)

    def _fail(self, launched: _Launched, e: Exception) -> None:
        obs.inc_counter("serve.batch_failed", type=type(e).__name__)
        log.warning("batch of %d failed (%s: %s)", len(launched.batch),
                    type(e).__name__, e)
        for t in launched.batch:
            t._resolve(exc=e)
        obs.record_span("serve.batch", launched.start_us, launched.attrs)

    # ---------------------------------------------------------- tier upgrade
    def _upgrade_loop(self) -> None:
        """Background re-planning: degraded tier -> tier 1, never blocking.

        Runs only while the engine serves a degraded plan.  Each round
        waits ``upgrade_interval_s``, retries the full planner via
        ``upgrade_plan`` (cache hit counts — another worker may win the
        race), builds the new prepared network and compiles its program *off*
        the serving path, and swaps it in atomically between batches.
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
