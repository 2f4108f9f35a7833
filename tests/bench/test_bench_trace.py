"""The reduction from a profiler trace to busy time, kernel time and idle gaps,
on hand-made intervals and on a small trace recorded on a TPU v5e chip (a few
closed-loop rounds at K=4096 on the ``pallas_tiled`` path)."""
import pathlib

import pytest

import bench_tiny  # noqa: F401  puts bench/ on the path

import tracing
from tracing import Op, Span

RECORDED = pathlib.Path(__file__).parent / "data" / "online_k4096.xplane.pb"


def test_union_and_self_times():
    assert tracing.union([(5, 9), (0, 2), (1, 3), (8, 12)]) == [(0, 3), (5, 12)]
    ops = [Op("while", 0, 100, False), Op("fusion.1", 10, 30, False),
           Op("k.1", 40, 90, True), Op("inner", 50, 60, False), Op("copy", 120, 130, False)]
    own = tracing.self_times(ops)
    assert own == {"while": 30, "fusion.1": 20, "k.1": 40, "inner": 10, "copy": 10}


def test_reduce_window_counts_busy_kernel_and_idle():
    ops = [Op("a", 0, 10, False), Op("k", 20, 50, True), Op("b", 60, 70, False),
           Op("k", 90, 140, True)]
    host = [Span("window", 5, 100), Span("fetch", 48, 62), Span("upload", 70, 95)]
    r = tracing.reduce_window([ops], host, host[0], ("fetch", "upload"))
    assert r.window_s == pytest.approx(95e-9)
    assert r.busy_s == pytest.approx((5 + 30 + 10 + 10) * 1e-9)
    assert r.kernel_s == pytest.approx(40e-9)
    assert dict(r.idle_gaps) == pytest.approx(
        {"other": 10e-9, "fetch": 10e-9, "upload": 20e-9})
    assert r.kernels == ["k"]


def test_two_chips_are_averaged():
    one = [Op("a", 0, 40, False)]
    two = [Op("a", 0, 20, False)]
    r = tracing.reduce_window([one, two], [], Span("window", 0, 100), ())
    assert r.busy_s == pytest.approx(30e-9)


def test_recorded_trace():
    devices, host = tracing.read_xplane(str(RECORDED))
    assert len(devices) == 1
    window = tracing.find_span(host, "window")
    r = tracing.reduce_window(devices, host, window, ("upload", "solve", "fetch"))
    assert r.kernels == ["pallas_tiled"]
    assert 0 < r.kernel_s < r.busy_s < r.window_s
    assert r.device_ops[0][0].startswith("pallas_tiled")
    assert {name for name, _ in r.idle_gaps} <= {"upload", "solve", "fetch", "other"}
    solves = [s for s in host if s.name == "solve"]
    kernels = [o for o in devices[0] if o.kernel]
    # each kernel run lies inside a host "solve" span: one clock for both
    assert len(kernels) == len(solves)
    assert all(any(s.start <= k.start and k.end <= s.end for s in solves)
               for k in kernels)
