"""The reduction from a profiler trace to busy time, kernel time and idle gaps,
on hand-made intervals and on a small trace recorded on a TPU v5e chip (a few
closed-loop rounds at K=4096 on the ``pallas_tiled`` path)."""
import pathlib

import pytest

from bench_tiny import harness

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


def _sweeps(counts, lost_after=None):
    """A window of sweep spans 100 ns apart, each running ``counts[i]`` ops of
    1 ns from 10 ns into it; ops that start after ``lost_after`` are dropped,
    as the profiler drops every op event past its bound."""
    host = [Span("window", 0, 100 * len(counts) + 5)]
    ops = []
    for i, n in enumerate(counts):
        host.append(Span("sweep", 100 * i + 2, 100 * i + 95))
        ops += [Op(f"fusion.{k}", 100 * i + 10 + k, 100 * i + 11 + k, False)
                for k in range(n)]
    if lost_after is not None:
        ops = [o for o in ops if o.start <= lost_after]
    return ops, host


@pytest.mark.parametrize("counts, lost_after, cut", [
    ([40, 40, 40], None, None),               # whole
    ([40, 40, 40], 240, 202),                 # the last sweep partly kept
    ([40, 40, 40], 248, 202),                 # it lost its last op alone
    ([40, 40, 40, 40, 40], 120, 102),         # later sweeps hold nothing
    ([40, 40, 40, 40, 40], 149, 202),         # cut at a sweep's end
    ([40, 36, 40, 40], None, 102),            # ops dropped inside the trace
    ([36, 40, 40], None, 2),                  # the first sweep short
    ([40], None, None),                       # one sweep: nothing to compare
])
def test_cut_at_finds_where_the_profiler_dropped_ops(counts, lost_after, cut):
    ops, host = _sweeps(counts, lost_after)
    assert tracing.cut_at([ops], host, host[0], "sweep") == cut


def test_cut_at_reads_every_chip_and_one_clock_offset():
    ops, host = _sweeps([40, 40, 40])
    lost, _ = _sweeps([40, 40, 40], 240)
    assert tracing.cut_at([ops, lost], host, host[0], "sweep") == 202
    # a chip whose events sit late (one offset per trace): ops run past
    # their sweep span's end, or land wholly in the next sweep's interval
    # (the last interval holds two sweeps' ops), and none is lost
    for shift in (50, 95):
        late = [o._replace(start=o.start + shift, end=o.end + shift) for o in ops]
        assert tracing.cut_at([late], host, host[0], "sweep") is None
    # one sweep's ops a whole interval late, the others in place
    jitter = [o._replace(start=o.start + 100, end=o.end + 100)
              if 100 <= o.start < 200 else o for o in ops]
    assert tracing.cut_at([jitter], host, host[0], "sweep") is None
    assert tracing.cut_at([ops], host, host[0], "round") is None


def test_cut_at_on_the_recorded_trace():
    """Six closed-loop rounds recorded on the chip, 21 op events each: the
    whole trace, and the same trace cut inside its last round."""
    devices, host = tracing.read_xplane(str(RECORDED))
    window = tracing.find_span(host, "window")
    assert tracing.cut_at(devices, host, window, "upload") is None
    last = max(s.start for s in host if s.name == "upload")
    ops = sorted(devices[0], key=lambda o: o.start)
    in_last = [o for o in ops if o.start >= last]
    kept = [o for o in ops if o.start < in_last[len(in_last) // 2].start]
    assert tracing.cut_at([kept], host, window, "upload") == last


def test_offline_idle_share_reads_none_where_the_trace_was_cut():
    """The harness decides once whether the trace kept every device op, and
    reduces the window up to the first sweep that lost some: a whole window
    reads 1 - busy / window over all of it; one whose last sweep lost ops
    reads the sweeps before it; one whose first sweep lost ops keeps no
    sweep, and its readers read None."""
    read = harness.reader("device_idle_share.offline")

    def reading(win):
        return harness.Reading(win.reduced if win.kept else None, win.kept,
                               {}, {}, "TPU v5 lite")

    ops, host = _sweeps([40, 40, 40])
    win = harness.reduce_trace([ops], host, ("sweep",), "sweep")
    assert (win.kept, win.units) == (3, 3)
    assert read(reading(win)) == pytest.approx(100 * (1 - 120 / 305))

    ops, host = _sweeps([40, 40, 40], 240)
    win = harness.reduce_trace([ops], host, ("sweep",), "sweep")
    assert (win.kept, win.units) == (2, 3)
    assert win.reduced.window_s == pytest.approx(202e-9)
    assert win.reduced.busy_s == pytest.approx(80e-9)
    assert read(reading(win)) == pytest.approx(100 * (1 - 80 / 202))

    ops, host = _sweeps([36, 40, 40])
    win = harness.reduce_trace([ops], host, ("sweep",), "sweep")
    assert (win.kept, win.units) == (0, 3)
    assert read(reading(win)) is None


def test_a_window_cut_in_its_first_unit_is_traced_again(monkeypatch):
    """The tiny sweep on the CPU, traced, with a reduction that finds the
    first window cut in its first sweep and the second whole: the run
    traces twice and reads the second window, its units scaled to the
    sweeps it kept."""
    from bench_tiny import tiny

    cell = tiny("paper_k10.sweep")
    cell = cell._replace(per_layer=[m for m in cell.per_layer
                                    if m["name"] == "device_idle_share.offline"])
    reduced = tracing.Reduced(0.2, 0.05, 0.0, [["fusion.1", 0.05]],
                              [["sweep", 0.15]], [])
    wins = iter([harness.Window(reduced._replace(busy_s=0.0), 0, 4),
                 harness.Window(reduced, 3, 4)])
    calls = []

    def fake_reduce(dev_ops, host, labels, unit):
        calls.append(unit)
        return next(wins)

    monkeypatch.setattr(harness, "reduce_trace", fake_reduce)
    monkeypatch.setattr(tracing, "latest_xplane", lambda d: d)
    monkeypatch.setattr(tracing, "read_xplane", lambda path: ([], []))
    r = harness.execute(cell, 2**31 + 11, 0.3, True, require_chip=False)
    assert calls == ["sweep", "sweep"]
    assert r["correct"] is True, r["checks"]
    assert r["metrics"]["device_idle_share.offline"]["value"] == pytest.approx(75.0)
    assert (r["device"]["busy_s"], r["device"]["window_s"]) == (0.05, 0.2)
    assert r["breakdown"]["device_ops"] == [["fusion.1", 0.05]]
