"""The trace reduction, on a hand-built trace and on one recorded on the chip.

``data/mobilenet_v3_two_batches.xplane.pb`` is a profile of two batches of
16 requests of the ``mobilenet_v3`` configuration served through the
engine on one TPU v5e, inside a ``bench.window`` annotation.
"""
import pathlib
from types import SimpleNamespace as NS

import pytest

import trace_reduce

RECORDED = (pathlib.Path(__file__).parent / "data"
            / "mobilenet_v3_two_batches.xplane.pb")


def ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=list(stats))


def profile():
    """Window 0..100 ns; device ops at 10-30 (Mosaic), 20-40 and 60-70; the
    host waits in ``dispatch`` over 40-60 and ``idle_poll`` over 70-100."""
    host = NS(name="/host:CPU", stats=[], lines=[
        NS(name="main", events=[ev(trace_reduce.WINDOW, 0, 100)]),
        NS(name="worker", events=[ev("dispatch", 35, 30),
                                  ev("idle_poll", 70, 30),
                                  ev("prepare", 0, 12)]),
    ])
    kernel = ('%rir_matmul_p.1 = f32[8,128] custom-call(%a, %b), '
              'custom_call_target="tpu_custom_call"')
    dev = NS(name="/device:TPU:0", stats=[], lines=[
        NS(name=trace_reduce.MODULES_LINE, events=[
            ev("jit_rir_matmul_p(123)", 10, 20), ev("jit_gather(9)", 20, 20),
            ev("jit_gather(77)", 60, 10), ev("jit_late(5)", 95, 50)]),
        NS(name=trace_reduce.OPS_LINE, events=[
            ev(kernel, 10, 20), ev("%fusion = gather(...)", 20, 20),
            ev("%fusion.2 = gather(...)", 60, 10), ev("%copy.1", 95, 50)]),
    ])
    return NS(planes=[host, dev])


def test_busy_is_the_union_clipped_to_the_window():
    s = trace_reduce.reduce(profile())
    assert s.window_s == pytest.approx(100e-9)
    # [10, 40] + [60, 70] + [95, 100] = 45 ns
    assert s.busy_s == pytest.approx(45e-9)
    assert s.devices == 1


def test_ops_are_summed_by_program_and_mosaic_is_found_by_its_target():
    s = trace_reduce.reduce(profile())
    assert dict(s.op_s) == pytest.approx(
        {"jit_rir_matmul_p": 20e-9, "jit_gather": 30e-9, "jit_late": 5e-9})
    assert s.mosaic_s == pytest.approx(20e-9)


def test_gaps_are_named_after_the_host_event_that_covers_them():
    s = trace_reduce.reduce(profile())
    # gaps: 0-10 (prepare), 40-60 (dispatch), 70-95 (idle_poll)
    assert s.idle_by_host == pytest.approx(
        {"prepare": 10e-9, "dispatch": 20e-9, "idle_poll": 25e-9})
    assert sum(s.idle_by_host.values()) == pytest.approx(
        s.window_s - s.busy_s)


def test_nothing_to_read_without_a_window_or_a_device():
    p = profile()
    p.planes[0].lines[0].events = []
    assert trace_reduce.reduce(p) is None
    p = profile()
    p.planes = p.planes[:1]
    assert trace_reduce.reduce(p) is None


def _union_by_brute_force(pd, lo, hi):
    """Busy time as the set of microseconds any operation touched."""
    covered = set()
    for plane in pd.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                s = max(e.start_ns, lo)
                t = min(e.start_ns + e.duration_ns, hi)
                covered.update(range(int(s // 1000), int(-(-t // 1000))))
    return covered


def test_recorded_chip_trace():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(RECORDED))
    s = trace_reduce.reduce(pd)
    assert s is not None and s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.mosaic_s < s.busy_s
    assert sum(s.idle_by_host.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    lo = min(e.start_ns for p in pd.planes if not p.name.startswith("/device")
             for ln in p.lines for e in ln.events
             if e.name == trace_reduce.WINDOW)
    buckets = _union_by_brute_force(pd, lo, lo + s.window_s * 1e9)
    # the microsecond grain over-counts each busy stretch by under 2 us
    assert s.busy_s * 1e6 <= len(buckets) <= s.busy_s * 1e6 * 1.2 + 2000
