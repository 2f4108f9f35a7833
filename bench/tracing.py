"""From a profiler trace to device busy time, kernel time and idle gaps.

The reduction reads the ``.xplane.pb`` file that ``jax.profiler.trace``
writes, through ``jax.profiler.ProfileData``.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed HLO
instruction, named by the instruction's text (``%fusion.6 = (...) fusion(...)``)
and placed on the host's clock.  Instructions nest (a ``while`` holds its
body's ops), so busy time is the union of the intervals and an op's own time
is its duration less that of the ops it contains.

A Pallas kernel is an instruction whose text carries
``custom_call_target="tpu_custom_call"``; its name is the instruction's
(``pallas_tiled.1``).  The benchmark's host spans are ``TraceAnnotation``s on
the host plane, and each idle gap on a device is put down to the host span
that covers its midpoint.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
_NAME = re.compile(r"^%?([^\s=]+)")


class Op(NamedTuple):
    name: str        # HLO instruction name, e.g. "fusion.6"
    start: int       # ns, host clock
    end: int
    kernel: bool


class Span(NamedTuple):
    name: str
    start: int
    end: int


def op_name(text: str) -> str:
    m = _NAME.match(text)
    return m.group(1) if m else text


def read_xplane(path: str):
    """(device ops per chip, host spans) of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[List[Op]] = []
    host: List[Span] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    ops.append(Op(op_name(ev.name), start,
                                  start + int(ev.duration_ns),
                                  KERNEL_MARK in ev.name))
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    start = int(ev.start_ns)
                    host.append(Span(ev.name, start, start + int(ev.duration_ns)))
    return devices, host


def latest_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def self_times(ops: Sequence[Op]) -> Dict[str, int]:
    """ns of each op name's own time: its duration less its nested ops'."""
    out: Dict[str, int] = {}
    stack: List[Tuple[Op, List[int]]] = []   # (op, [ns of direct children])

    def close(entry):
        op, child = entry
        out[op.name] = out.get(op.name, 0) + (op.end - op.start) - child[0]

    for op in sorted(ops, key=lambda o: (o.start, -(o.end - o.start))):
        while stack and op.start >= stack[-1][0].end:
            close(stack.pop())
        if stack:
            stack[-1][1][0] += op.end - op.start
        stack.append((op, [0]))
    while stack:
        close(stack.pop())
    return out


class Reduced(NamedTuple):
    """A traced window, reduced.  Times in seconds, averaged over chips."""

    window_s: float
    busy_s: float
    kernel_s: float
    device_ops: List[List]        # [[op name, seconds of own time], ...]
    idle_gaps: List[List]         # [[host span name, seconds idle], ...]
    kernels: List[str]


def reduce_window(devices: List[List[Op]], host: List[Span], window: Span,
                  labels: Sequence[str]) -> Reduced:
    """Busy, kernel and idle time of every chip inside the host span ``window``.

    ``labels`` names the benchmark's host spans to which idle gaps are put
    down; a gap that none of them covers is put down to ``other``.
    """
    lo, hi = window.start, window.end
    if not devices:
        raise ValueError("the trace holds no device ops")
    marks = sorted((s for s in host if s.name in labels), key=lambda s: s.start)
    busy = kern = 0
    own: Dict[str, int] = {}
    idle: Dict[str, int] = {}
    kernels = set()
    for ops in devices:
        inside = [o for o in ops if o.end > lo and o.start < hi]
        spans = union(clip([(o.start, o.end) for o in inside], lo, hi))
        busy += length(spans)
        kern += length(union(clip([(o.start, o.end) for o in inside if o.kernel],
                                  lo, hi)))
        kernels.update(o.name.rsplit(".", 1)[0] for o in inside if o.kernel)
        for name, ns in self_times(inside).items():
            own[name] = own.get(name, 0) + ns
        prev = lo
        for s, e in spans + [(hi, hi)]:
            if s > prev:
                label = _cover(marks, (prev + s) // 2)
                idle[label] = idle.get(label, 0) + (s - prev)
            prev = max(prev, e)
    n = len(devices)
    top = sorted(own.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / n / 1e9,
        kernel_s=kern / n / 1e9,
        device_ops=[[k, v / n / 1e9] for k, v in top],
        idle_gaps=[[k, v / n / 1e9] for k, v in gaps],
        kernels=sorted(kernels),
    )


def _cover(marks: Sequence[Span], t: int) -> str:
    best: Optional[Span] = None
    for s in marks:
        if s.start > t:
            break
        if s.end >= t and (best is None or s.start >= best.start):
            best = s
    return best.name if best else "other"


def cut_at(devices: Sequence[Sequence[Op]], host: Sequence[Span], window: Span,
           unit: str) -> Optional[int]:
    """Where the trace stops holding every device op: None where it is
    whole, else the start of the first ``unit`` span from which ops are
    missing.

    The profiler drops device op events: every one past its bound (6 273 800
    on a v5e), and now and then some inside a long trace.  A unit (one
    round, one sweep) runs the same programs every time: on a v5e at K = 10,
    2631 op events a round and 907107 a sweep.  Each ``unit`` span in
    ``window`` takes the ops that start from it to the next one, the first
    also those before it and the last those after it, and the count most
    units hold is a unit's.  Device times carry a clock offset of up to a
    few ms, so a round's ops may land in the next round's interval, but
    none is lost: the trace is whole where, on every chip, the units hold
    as many ops as that count times their number.  Ops are missing from
    the first unit after which the running count stays short.  With fewer
    than two units nothing is compared."""
    starts = sorted(s.start for s in host if s.name == unit
                    and window.start <= s.start and s.end <= window.end)
    if len(starts) < 2:
        return None
    cut: Optional[int] = None
    for ops in devices:
        counts = [0] * len(starts)
        for o in ops:
            counts[max(bisect.bisect_right(starts, o.start) - 1, 0)] += 1
        held = [n for n in counts if n]
        per_unit = statistics.mode(held) if held else 0
        short, seen = None, 0
        for i, n in enumerate(counts):
            seen += n
            if seen >= (i + 1) * per_unit:
                short = None
            elif short is None:
                short = i
        if short is not None and (cut is None or starts[short] < cut):
            cut = starts[short]
    return cut


def find_span(host: List[Span], name: str) -> Span:
    spans = [s for s in host if s.name == name]
    if len(spans) != 1:
        raise ValueError(f"expected one host span {name!r}, found {len(spans)}")
    return spans[0]
