"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

The device planes are those named ``/device:TPU:<i>``; on each, the line
``XLA Ops`` holds one event per executed operation.  The host planes hold the
benchmark's own ``TraceAnnotation`` spans, whose names start with ``bench.``;
``bench.window`` spans the measured window.  Host and device events share the
trace's clock.

- busy time: the union of a device's operation intervals inside the window,
  averaged over the devices;
- per-operation time: the summed durations, inside the window, of the events
  of each operation name;
- idle gaps: the longest stretches of the window in which no operation runs
  on a device, each named by the host span that covers most of it.
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass
class Trace:
    """Events in nanoseconds on the trace's clock.

    ``ops``: per device, a list of (name, start, end);
    ``spans``: host spans as (name, start, end).
    """

    ops: dict
    spans: list

    def window(self) -> tuple[float, float]:
        wins = [(s, e) for n, s, e in self.spans if n == WINDOW]
        if len(wins) != 1:
            raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
        return wins[0]


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, found {paths}")
    return paths[0]


def op_name(text: str) -> str:
    """An operation's HLO instruction name: the trace names it by the whole
    instruction, ``%fusion.22 = f32[...] fusion(...)``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events += [(op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
            ops[plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return Trace(ops=ops, spans=spans)


def _clip(events, lo, hi):
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def busy_intervals(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of the events' intervals inside [lo, hi], as sorted disjoint pieces."""
    out: list[list[float]] = []
    for _, s, e in sorted(_clip(events, lo, hi), key=lambda t: t[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: Trace) -> float:
    """Device busy seconds inside the window, averaged over the devices."""
    lo, hi = trace.window()
    if not trace.ops:
        return 0.0
    per_dev = [sum(e - s for s, e in busy_intervals(evs, lo, hi)) for evs in trace.ops.values()]
    return sum(per_dev) / len(per_dev) / 1e9


def window_seconds(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) / 1e9


def op_seconds(trace: Trace, match=None) -> dict:
    """Per operation name, its summed device seconds inside the window,
    averaged over the devices; ``match(name)`` filters."""
    lo, hi = trace.window()
    total: dict = defaultdict(float)
    for evs in trace.ops.values():
        for name, s, e in _clip(evs, lo, hi):
            if match is None or match(name):
                total[name] += (e - s) / 1e9
    n = max(len(trace.ops), 1)
    return {k: v / n for k, v in total.items()}


def idle_gaps(trace: Trace, top: int = 10) -> list[tuple[str, float]]:
    """The ``top`` longest idle stretches of the first device's window, longest
    first, each named by the host span (other than the window) that overlaps it
    most, or ``host.other`` where none does."""
    lo, hi = trace.window()
    if not trace.ops:
        return []
    busy = busy_intervals(next(iter(trace.ops.values())), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = heapq.nlargest(top, ((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s),
                          key=lambda g: g[1] - g[0])
    spans = [sp for sp in trace.spans if sp[0] != WINDOW]
    out = []
    for s, e in gaps:
        cover: dict = defaultdict(float)
        for name, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > 0:
                cover[name] += ov
        out.append((max(cover, key=cover.get) if cover else "host.other", (e - s) / 1e9))
    return out
