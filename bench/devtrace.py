"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps labelled by what the host was doing.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Everything below works on plain
``Event`` records, so a small recorded trace can test the arithmetic.

  device ops    events on the "XLA Ops" line of each ``/device:TPU:<n>``
                plane (one per operation the device ran);
  host spans    ``TraceAnnotation`` events the harness and its servable
                wrapper write (names starting ``host.``) and the window
                itself (``bench.window``);
  busy          the union of device-op intervals inside the window;
  idle gaps     the window minus busy, each labelled by the innermost host
                span that covers its middle;
  top ops       device time per op, loop and call ops left out (their
                time is their body's).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Iterable

WINDOW = "bench.window"
HOST_PREFIX = "host."
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    meta: str = ""          # op metadata (program name scope, HLO op, ...)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_META_KEYS = ("long_name", "tf_op", "hlo_op", "name", "source", "hlo_module",
              "program_id", "kernel_details")


def read_profile(directory: str) -> list[Event]:
    """All events of the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    events = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            keep_all = is_device and line.name == OPS_LINE
            for ev in line.events:
                if not keep_all and not (
                        ev.name.startswith(HOST_PREFIX) or ev.name == WINDOW):
                    continue
                meta = ""
                if keep_all:
                    meta = " ".join(
                        f"{k}={v}" for k, v in ev.stats if k in _META_KEYS)
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns), float(ev.duration_ns),
                                    meta))
    return events


def window(events: Iterable[Event]) -> tuple[float, float]:
    spans = [e for e in events if e.name == WINDOW]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    return spans[0].start_ns, spans[0].end_ns


def device_ops(events: Iterable[Event]) -> dict[str, list[Event]]:
    """Device-op events per device plane."""
    out: dict[str, list[Event]] = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e.plane) and e.line == OPS_LINE:
            out[e.plane].append(e)
    return dict(out)


def union(intervals: Iterable[tuple[float, float]], lo: float, hi: float):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(merged, lo: float, hi: float):
    """The parts of [lo, hi) that no merged interval covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class HostSpans:
    """Host spans of one thread, sorted by start.  They nest (annotations
    open and close in order), so the innermost span covering a time is the
    one that started last among those that cover it."""

    LOOKBACK = 256

    def __init__(self, spans: Iterable[Event]):
        self.spans = sorted(spans, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.spans]

    def label(self, gap: tuple[float, float]) -> str:
        """Innermost span covering the gap's middle ("host.none" if none)."""
        mid = 0.5 * (gap[0] + gap[1])
        i = bisect.bisect_right(self.starts, mid) - 1
        # Back over the spans that ended before ``mid`` (a parent has a few
        # dozen children at most) to the first one still open at ``mid``.
        for j in range(i, max(i - self.LOOKBACK, -1), -1):
            if self.spans[j].end_ns > mid:
                return self.spans[j].name
        return "host.none"


_CONTAINER = re.compile(r" (while|conditional|call)\(")


def short_name(name: str, width: int = 120) -> str:
    """An op's HLO text without layouts, cut to ``width`` characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def matches(e: Event, patterns: Iterable[str]) -> bool:
    text = f"{e.name} {e.meta}"
    return any(re.search(p, text) for p in patterns)


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers and the ``breakdown`` take from a trace."""

    window_s: float
    busy_s: float                       # mean over the devices used
    ops: list[Event]                    # device ops inside the window
    top_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]

    def kernel_s(self, patterns: Iterable[str]) -> float:
        patterns = list(patterns)
        return sum(e.dur_ns for e in self.ops if matches(e, patterns)) / 1e9


def reduce(events: list[Event], top: int = 10) -> Reduced:
    lo, hi = window(events)
    per_device = device_ops(events)
    busy, ops, gap_list = [], [], []
    for plane, evs in sorted(per_device.items()):
        inside = [e for e in evs if e.end_ns > lo and e.start_ns < hi]
        ops.extend(inside)
        merged = union(((e.start_ns, e.end_ns) for e in inside), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        gap_list.extend(gaps(merged, lo, hi))
    host = HostSpans(e for e in events if e.name.startswith(HOST_PREFIX))
    by_op: dict[str, float] = defaultdict(float)
    for e in ops:
        if _CONTAINER.search(e.name.split(" = ", 1)[-1][:400]):
            continue  # a loop or call op: its time is its body's ops
        by_op[short_name(e.name)] += (
            min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
    by_gap: dict[str, float] = defaultdict(float)
    for g in gap_list:
        by_gap[host.label(g)] += (g[1] - g[0]) / 1e9
    n_dev = max(1, len(per_device))
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / n_dev / 1e9,
        ops=ops,
        top_ops=sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(by_gap.items(), key=lambda kv: -kv[1])[:top],
    )
