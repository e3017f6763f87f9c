"""One load generator for every traffic mix; a mix is a data file.

A mix file under ``traffic/`` names its arrival process and parameters:

* ``"arrivals": "saturate"`` — the offline scenario: the client keeps
  ``(queued_batches + 1) * max_batch`` requests outstanding, so at least
  ``queued_batches`` full batches wait in the queue whenever the engine
  takes one.  Closed loop: a completion releases the next submission.
* ``"arrivals": "gamma"`` with ``rate`` (requests/s) and ``cv`` (the
  coefficient of variation of the gaps; 1 is Poisson) — an open loop on a
  fixed schedule, whatever the system does.

Every seed gets the same work.  An open-loop schedule sends exactly
``round(rate * seconds)`` requests at the same times for every seed: one
fixed draw of gaps, rescaled to fill the window.  (Drawing the order of
the gaps from the seed made the tail a property of the seed: in one set
of six runs at 63.4 req/s on ResNet-50, p99 ranged 346-436 ms by seed.)
The seed draws the inputs: a request's input is one of ``pool`` seeded
samples, taken in turn, so neighbours in a batch differ.  Latency runs
from a request's scheduled send time to its result.
"""
from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Callable, List, Optional

import numpy as np

# the one draw of gaps every seed shares
GAP_DRAW_SEED = 20240513
# how long past the window's close a request may still answer
GRACE_S = 60.0


@dataclasses.dataclass
class Request:
    idx: int
    sample: int                  # index into the input pool
    due: float                   # scheduled send time (perf_counter)
    sent: float = float("nan")
    done: float = float("nan")
    ok: bool = False


class Reservoir:
    """A uniform sample of ``k`` served requests, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(seed % 2 ** 64)
        self.seen = 0
        self.items: List = []

    def offer(self, req: Request, out) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append((req, out))
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.items[j] = (req, out)


def gap_schedule(rate: float, cv: float, seconds: float) -> np.ndarray:
    """Send offsets (s) from the window's start: one fixed draw of gamma
    gaps with mean ``1/rate`` and the given CV, rescaled to span
    ``seconds``."""
    n = max(1, int(round(rate * seconds)))
    shape = 1.0 / (cv * cv)
    gaps = np.random.default_rng(GAP_DRAW_SEED).gamma(
        shape, 1.0 / (shape * rate), n)
    gaps *= seconds / gaps.sum()
    # the first request goes at 0; the last gap closes the window
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


@dataclasses.dataclass
class WindowResult:
    requests: List[Request]
    t0: float
    seconds: float
    queue_depth_end: int

    def due(self) -> List[Request]:
        """Requests scheduled inside the window."""
        return [r for r in self.requests if r.due < self.t0 + self.seconds]

    def completed_between(self, a: float, b: float) -> int:
        return sum(1 for r in self.requests if r.ok and a <= r.done < b)


def _finish(req: Request, ticket, keep: Optional[Reservoir],
            timeout: float) -> None:
    try:
        out = ticket.result(timeout=timeout)
    except Exception:   # noqa: BLE001 — a failed request counts as missing
        req.done = time.perf_counter()
        return
    req.done = time.perf_counter()
    req.ok = True
    if keep is not None:
        keep.offer(req, out)


def run_window(engine, payloads, mix: dict, max_batch: int, seconds: float,
               keep: Optional[Reservoir] = None,
               on_start: Optional[Callable[[float], None]] = None
               ) -> WindowResult:
    """Drive ``engine`` with ``mix`` for ``seconds``; return every request.

    ``on_start(t0)`` is called from a helper thread once the window opens,
    for a caller that switches tracing phases on the window's clock.
    """
    kind = mix["arrivals"]
    if kind == "saturate":
        runner = _saturate
    elif kind == "gamma":
        runner = _open_loop
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return runner(engine, payloads, mix, max_batch, seconds, keep, on_start)


def _start_phases(on_start, t0):
    if on_start is None:
        return None
    th = threading.Thread(target=on_start, args=(t0,), name="bench-phases")
    th.start()
    return th


def _saturate(engine, payloads, mix, max_batch, seconds, keep, on_start):
    outstanding = (int(mix["queued_batches"]) + 1) * max_batch
    n_pool = len(payloads)
    reqs: List[Request] = []
    pending = collections.deque()

    def send(t):
        r = Request(idx=len(reqs), sample=len(reqs) % n_pool, due=t)
        reqs.append(r)
        try:
            ticket = engine.submit(payloads[r.sample])
        except Exception:   # noqa: BLE001 — a refused request counts as missing
            r.done = time.perf_counter()
            return
        r.sent = time.perf_counter()
        pending.append((r, ticket))

    t0 = time.perf_counter()
    for _ in range(outstanding):
        send(t0)
    phases = _start_phases(on_start, t0)
    end = t0 + seconds
    while pending:
        r, ticket = pending.popleft()
        _finish(r, ticket, keep, max(1.0, end + GRACE_S - time.perf_counter()))
        if time.perf_counter() < end:
            send(time.perf_counter())
    depth = engine.queue_depth()
    if phases is not None:
        phases.join()
    return WindowResult(reqs, t0, seconds, depth)


def _open_loop(engine, payloads, mix, max_batch, seconds, keep, on_start):
    offsets = gap_schedule(float(mix["rate"]), float(mix.get("cv", 1.0)),
                           seconds)
    n_pool = len(payloads)
    inbox: "queue.Queue" = queue.Queue()
    reqs: List[Request] = []
    t0 = time.perf_counter() + 0.05
    end = t0 + seconds

    def collect():
        while True:
            item = inbox.get()
            if item is None:
                return
            r, ticket = item
            _finish(r, ticket, keep,
                    max(1.0, end + GRACE_S - time.perf_counter()))

    collector = threading.Thread(target=collect, name="bench-collector")
    collector.start()
    phases = _start_phases(on_start, t0)
    depth = 0
    try:
        for i, off in enumerate(offsets):
            r = Request(idx=i, sample=i % n_pool, due=t0 + float(off))
            reqs.append(r)
            wait = r.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            try:
                ticket = engine.submit(payloads[r.sample])
            except Exception:   # noqa: BLE001 — a refused request is missing
                r.done = time.perf_counter()
                continue
            r.sent = time.perf_counter()
            inbox.put((r, ticket))
        wait = end - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        depth = engine.queue_depth()
    finally:
        inbox.put(None)
        collector.join()
        if phases is not None:
            phases.join()
    return WindowResult(reqs, t0, seconds, depth)
