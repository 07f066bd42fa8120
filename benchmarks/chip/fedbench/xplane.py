"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

* device busy time: the union of the intervals in which an operation ran
  on a device, inside the traced window;
* idle gaps: the rest of the window, each gap named by the innermost
  ``repro.obs`` host span open at its midpoint (the spans reach the trace
  through ``jax.profiler.TraceAnnotation``);
* per-operation device time, from which the kernel metrics pick their
  kernels by name.

The arithmetic (``union``, ``gaps``, ``attribute``) works on plain
``(start, end)`` nanosecond pairs so that it can be checked without a
trace.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

# host spans of the program and the benchmark that can own an idle gap
HOST_SPANS = ("bench_round", "cohort_build", "local_sgd", "coreset_group",
              "aggregate", "gather", "selection")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(merged: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in merged))


def gaps(merged: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi] that ``merged`` (disjoint, sorted) leaves."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(gap_list: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle nanoseconds per host span.  A gap is cut at the span
    boundaries inside it, and each piece goes to the innermost span (the
    latest-starting one) open over it, or to ``no_span``."""
    out: Dict[str, float] = defaultdict(float)
    for s, e in gap_list:
        cuts = sorted({s, e} | {t for _, t0, t1 in spans for t in (t0, t1)
                                if s < t < e})
        for a, b in zip(cuts[:-1], cuts[1:]):
            mid = 0.5 * (a + b)
            best = None
            for name, t0, t1 in spans:
                if t0 <= mid <= t1 and (best is None or t0 >= best[1]):
                    best = (name, t0)
            out[best[0] if best else "no_span"] += b - a
    return dict(out)


@dataclasses.dataclass
class Reduced:
    window: Interval                      # traced window (ns)
    busy_ns: float                        # mean over the devices used
    n_devices: int
    op_ns: Dict[str, float]               # device time per op name
    op_events: List[Tuple[str, float, float]]    # (name, t0, duration)
    idle_by_span: Dict[str, float]        # idle ns per host span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def events_matching(self, pattern: str):
        rx = re.compile(pattern)
        return [ev for ev in self.op_events if rx.search(ev[0])]

    def breakdown(self, n: int = 10, width: int = 160) -> dict:
        """The device operations that took most time (their HLO text cut
        to ``width``) and the idle time by host span, in seconds."""
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        ops = [(k[:width], v) for k, v in ops]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in idle]}


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.duration_ns)


def _has_hlo_op(ev) -> bool:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return any(k == "hlo_op" for k, _ in ev.stats)


_SHAPE = re.compile(r"\b(?:f|bf|s|u|pred)\d*\[([\d,]*)\]")


def operand_shapes(op_text: str) -> List[Tuple[int, ...]]:
    """Operand shapes of a custom call from its HLO text, as the TPU
    trace names its operations (``%name = <out> custom-call(<operands>),
    custom_call_target=...``)."""
    if "custom-call(" not in op_text:
        return []
    args = op_text.split("custom-call(", 1)[1].split("custom_call_target",
                                                      1)[0]
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in _SHAPE.finditer(args)]


def device_op_lines(planes, host_ops: bool = False
                    ) -> List[Tuple[str, list]]:
    """(plane name, op events) of every accelerator plane: its "XLA Ops"
    line.  ``host_ops`` takes instead the XLA operations that the CPU
    backend runs on host threads (events with an ``hlo_op`` stat), as one
    device: for checking the reduction without an accelerator."""
    out = []
    for plane in planes:
        if host_ops:
            if plane.name.startswith("/host:"):
                evs = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                       for line in plane.lines for ev in line.events
                       if _has_hlo_op(ev)]
                out.append((plane.name, evs))
            continue
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out.append((plane.name, list(_events(line))))
    return [(name, evs) for name, evs in out if evs]


def host_spans(planes, names: Sequence[str] = HOST_SPANS
               ) -> List[Tuple[str, float, float]]:
    want = set(names)
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, t0, dur in _events(line):
                if name in want:
                    out.append((name, t0, t0 + dur))
    return out


def reduce_planes(planes, window_span: str = "bench_round",
                  host_ops: bool = False) -> Reduced:
    planes = list(planes)
    spans = host_spans(planes)
    rounds = [(t0, t1) for name, t0, t1 in spans if name == window_span]
    if not rounds:
        raise ValueError(f"no {window_span!r} span in the trace")
    lo, hi = min(t0 for t0, _ in rounds), max(t1 for _, t1 in rounds)
    devices = device_op_lines(planes, host_ops)
    if not devices:
        raise ValueError("no device operations in the trace")
    op_ns: Dict[str, float] = defaultdict(float)
    busy, idle = 0.0, defaultdict(float)
    events = []
    for _, evs in devices:
        ivs = clip([(t0, t0 + d) for _, t0, d in evs], lo, hi)
        merged = union(ivs)
        busy += covered(merged)
        for k, v in attribute(gaps(merged, lo, hi), spans).items():
            idle[k] += v / len(devices)
        for ev in evs:
            if ev[1] + ev[2] > lo and ev[1] < hi:
                op_ns[ev[0]] += ev[2] / len(devices)
                events.append(ev)
    return Reduced(window=(lo, hi), busy_ns=busy / len(devices),
                   n_devices=len(devices), op_ns=dict(op_ns),
                   op_events=events, idle_by_span=dict(idle))


class Planes(list):
    """The trace's planes; holds the parsed trace they live in."""
    data = None


def load(path) -> Planes:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    planes = Planes(data.planes)
    planes.data = data
    return planes


def find_xplane(trace_dir) -> Optional[str]:
    from pathlib import Path
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    return str(found[-1]) if found else None
