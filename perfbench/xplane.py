"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

``load`` turns the file into a plain :class:`Trace` (per-device lists of
operations, the benchmark's own host spans) with nothing but
``jax.profiler.ProfileData``; everything after that is arithmetic on
intervals, so the tests drive it with hand-made traces. All times are
nanoseconds on the trace's one clock.
"""

from __future__ import annotations

import bisect
import itertools
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the benchmark's own host spans (drivers/fit_loop.py), and the program's stage
# spans, which lie inside ``fit`` (spark_rapids_ml_tpu/utils/tracing.py: STAGES)
SPAN_NAMES = ("fit", "model_read", "admit", "densify", "convert", "place", "solve")
NAME_CHARS = 200  # an operation's name is its HLO text: keep the head of it
# operations that only contain other operations of the same line: their time
# is their children's, so they are no leaf of a per-operation sum
CONTAINER = re.compile(r"^%?(while|conditional|call)([.\d]*)( |$)")
# the trace gives an operation its HLO text and no category: on this chip a
# matrix multiplication is an output fusion around a convolution
# (kind=kOutput), a bare convolution or dot, or a Pallas kernel
MATMUL = re.compile(r"kind=kOutput|convolution|[ =]dot\(|tpu_custom_call")


@dataclass
class Op:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # ordinal -> [Op], any order
    spans: list = field(default_factory=list)    # (name, start, end) host spans


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            ops = trace.devices.setdefault(int(match.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.extend(Op(ev.name, float(ev.start_ns), float(ev.duration_ns))
                           for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        start = float(ev.start_ns)
                        trace.spans.append((ev.name, start, start + float(ev.duration_ns)))
    trace.spans.sort(key=lambda s: s[1])
    return trace


# --- interval arithmetic -------------------------------------------------


def union(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(i) for i in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Parts of the disjoint sorted intervals ``a`` that no interval of the
    disjoint sorted ``b`` covers."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def measure(intervals: list):
    """``inside(lo, hi)``: how much of the disjoint sorted ``intervals`` lies in
    (lo, hi), by bisection, for thousands of questions to one list."""
    starts = [s for s, _ in intervals]
    ends = [e for _, e in intervals]
    upto = [0.0, *itertools.accumulate(e - s for s, e in intervals)]

    def inside(lo: float, hi: float) -> float:
        i = bisect.bisect_right(ends, lo)   # the first interval that ends after lo
        j = bisect.bisect_left(starts, hi)  # the first that starts at hi or later
        if i >= j:
            return 0.0
        return upto[j] - upto[i] - max(0.0, lo - starts[i]) - max(0.0, ends[j - 1] - hi)

    return inside


def own_intervals(spans) -> list:
    """(name, intervals) for each (name, start, end) span: the span less the
    spans nested in it, so that a nanosecond belongs to the innermost span that
    holds it and to no other. A span that outlasts the one it starts in is cut
    at that one's end."""
    out, open_spans = [], []  # an open span: [name, end, covered up to, intervals]

    def close(span):
        name, end, at, mine = span
        if at < end:
            mine.append((at, end))
        out.append((name, mine))

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while open_spans and open_spans[-1][1] <= start:
            close(open_spans.pop())
        if open_spans:
            outer = open_spans[-1]
            end = min(end, outer[1])
            if start > outer[2]:
                outer[3].append((outer[2], start))
            outer[2] = max(outer[2], end)
        open_spans.append([name, end, start, []])
    while open_spans:
        close(open_spans.pop())
    return out


def is_container(op: Op) -> bool:
    return bool(CONTAINER.match(op.name))


def is_matmul(op: Op) -> bool:
    return bool(MATMUL.search(op.name))


def leaves(ops: list) -> list:
    return [op for op in ops if not is_container(op)]


# --- the reduced trace ---------------------------------------------------


@dataclass
class Reduced:
    trace: Trace
    chips: int
    window: tuple                  # (start, end): first fit's start to last read's end
    busy: dict                     # ordinal -> disjoint busy intervals inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s_mean(self) -> float:
        return sum(total(b) for b in self.busy.values()) / len(self.busy) / 1e9

    def idle_share_worst(self) -> float:
        least = min(total(b) for b in self.busy.values())
        return 1.0 - least / (self.window[1] - self.window[0])

    def fit_spans(self) -> list:
        return [(s, e) for name, s, e in self.trace.spans if name == "fit"]

    def device_time(self, select=None) -> float:
        """Seconds in which a leaf operation that ``select`` keeps ran inside
        the window (union of their intervals, worst device). Over the number
        of fits it is a fit's share: fits are back to back and alike, and a
        fit's device work outlasts its host call, so none is cut per fit."""
        worst = 0.0
        for ops in self.trace.devices.values():
            mine = union((o.start, o.end) for o in leaves(ops)
                         if select is None or select(o))
            worst = max(worst, total(clip(mine, *self.window)))
        return worst / 1e9

    def host_wait_per_fit(self) -> list:
        """For each ``fit`` span, the nanoseconds of it in which no operation
        ran on any device: conversion, placement and dispatch on the host."""
        everything = union(i for b in self.busy.values() for i in b)
        return [(e - s) - total(clip(everything, s, e)) for s, e in self.fit_spans()]

    def breakdown(self) -> dict:
        sums = {}
        for ops in self.trace.devices.values():
            for op in leaves(ops):
                if self.window[0] <= op.start < self.window[1]:
                    sums[op.name] = sums.get(op.name, 0.0) + op.dur
        device_ops = sorted(sums.items(), key=lambda kv: -kv[1])[:10]
        # idle gaps of the idlest device, by what the host was doing
        ordinal = min(self.busy, key=lambda o: total(self.busy[o]))
        gaps = subtract([self.window], self.busy[ordinal])
        idle_inside = measure(gaps)
        by_span = {}
        for name, mine in own_intervals(self.trace.spans):  # a stage lies inside a fit
            idle = sum(idle_inside(s, e) for s, e in mine)
            by_span[name] = by_span.get(name, 0.0) + idle
        outside = total(gaps) - sum(by_span.values())
        if outside > 0:
            by_span["between_spans"] = outside
        idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
        return {
            "device_ops": [[n[:NAME_CHARS], t / 1e9 / len(self.trace.devices)]
                           for n, t in device_ops],
            "idle_gaps": [[n, t / 1e9] for n, t in idle],
        }


def reduce(trace: Trace, chips: int) -> Reduced:
    if len(trace.devices) < chips:
        raise RuntimeError(
            f"the trace holds {sorted(trace.devices)} device planes, the cell uses {chips}"
        )
    if not trace.spans:
        raise RuntimeError("the trace holds none of the benchmark's host spans")
    window = (min(s for _, s, _ in trace.spans), max(e for _, _, e in trace.spans))
    busy = {
        ordinal: clip(union((o.start, o.end) for o in ops), *window)
        for ordinal, ops in trace.devices.items()
    }
    if not any(total(b) > 0 for b in busy.values()):
        raise RuntimeError("no operation ran on a device inside the traced window")
    return Reduced(trace, chips, window, busy)
