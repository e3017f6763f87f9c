"""Reduce a JAX profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  The measured window is the host event the
harness wraps around it (``jax.profiler.TraceAnnotation(WINDOW)``); device
events are clipped to it.

* busy: the union of the intervals in which an XLA operation ran on a
  device (its ``XLA Ops`` line), averaged over the devices in the trace;
  idle is the rest of the window.
* per-operation time: device seconds summed by the program each
  operation belongs to (its ``XLA Modules`` event, ``jit_gather(123)`` ->
  ``jit_gather``): the eager executor runs one small program per
  operation, so the program names the operation.
* Mosaic time: device seconds of operations that run a compiled Pallas
  kernel (the HLO text an ``XLA Ops`` event carries as its name holds
  ``custom_call_target="tpu_custom_call"``); the rest of the busy time is
  XLA glue.
* idle gaps: each stretch of the window in which no operation ran, named
  after the host event that overlaps it most (the shorter one on a tie).
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MOSAIC = 'custom_call_target="tpu_custom_call"'
# host events longer than this are envelopes (threads, the window itself),
# not activity that explains a gap
HOST_EVENT_MAX_S = 1.0
NO_HOST = "(no host event)"
_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over devices
    devices: int
    op_s: Dict[str, float]             # by program, summed over devices
    mosaic_s: float                    # mean over devices
    idle_by_host: Dict[str, float]     # mean over devices

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(directory: pathlib.Path) -> Optional[pathlib.Path]:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _window(profile) -> Optional[Tuple[float, float]]:
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def _host_events(profile, lo: float, hi: float):
    starts, ends, names = [], [], []
    cap = HOST_EVENT_MAX_S * 1e9
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, d = ev.start_ns, ev.duration_ns
                if (d <= 0 or d > cap or s >= hi or s + d <= lo
                        or ev.name == WINDOW):
                    continue
                starts.append(s)
                ends.append(s + d)
                names.append(ev.name)
    order = np.argsort(np.asarray(starts, np.float64), kind="stable")
    return (np.asarray(starts, np.float64)[order],
            np.asarray(ends, np.float64)[order],
            [names[i] for i in order])


def _label_gaps(gaps, host) -> Dict[str, float]:
    starts, ends, names = host
    out: Dict[str, float] = {}
    cap = HOST_EVENT_MAX_S * 1e9
    for gs, ge in gaps:
        lo = np.searchsorted(starts, gs - cap)
        hi = np.searchsorted(starts, ge)
        s, e = starts[lo:hi], ends[lo:hi]
        overlap = np.minimum(e, ge) - np.maximum(s, gs)
        label = NO_HOST
        if overlap.size and overlap.max() > 0:
            best = overlap.max()
            cand = np.nonzero(overlap == best)[0]
            pick = cand[np.argmin((e - s)[cand])]
            label = names[lo + pick]
        out[label] = out.get(label, 0.0) + (ge - gs) / 1e9
    return out


def reduce(profile) -> Optional[TraceSummary]:
    """The window's device numbers; ``None`` when the trace has no window
    marker or no device operations in it (nothing to read)."""
    win = _window(profile)
    if win is None:
        return None
    lo, hi = win
    busy_total, mosaic_total = 0.0, 0.0
    op_s: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    devices = 0
    host = None
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e <= s:
                    continue
                if line.name == MODULES_LINE:
                    name = _HASH.sub("", ev.name)
                    op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
                    continue
                intervals.append((s, e))
                if MOSAIC in ev.name:
                    mosaic_total += (e - s) / 1e9
        if not intervals:
            continue
        devices += 1
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged) / 1e9
        gaps, prev = [], lo
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if prev < hi:
            gaps.append((prev, hi))
        if host is None:
            host = _host_events(profile, lo, hi)
        for label, sec in _label_gaps(gaps, host).items():
            idle[label] = idle.get(label, 0.0) + sec
    if devices == 0:
        return None
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / devices,
        devices=devices, op_s=op_s, mosaic_s=mosaic_total / devices,
        idle_by_host={k: v / devices for k, v in idle.items()})


def reduce_file(path) -> Optional[TraceSummary]:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(path)))
