"""Reduction of a profiler trace to the benchmark's device numbers.

The trace is a `jax.profiler.ProfileData`.  Each chip has a plane named
``/device:TPU:<n>`` whose line ``XLA Ops`` holds every operation that ran
on it; the host plane ``/host:CPU`` holds the benchmark's own
`jax.profiler.TraceAnnotation` spans (``window``, ``run``, ``call``,
``sync``, ``analysis``, ``codegen``, ``check``).  Times are in
nanoseconds, but the chip's plane is offset from the host's: by about
1.5 ms in the v5e trace under ``fixtures/``, where the ops lie before the
spans that launched them.  `align` shifts each chip's ops so that its last
op ends where the last ``sync`` span ends (the host returns from waiting
for it); the shift errs late by the host's latency in seeing the op end,
about 0.2 ms there, which keeps every op inside the run span that
launched it.

`summarize` gives, over the ``window`` span:

* ``busy_s``: the union of the intervals in which an operation ran,
  averaged over the chips;
* ``ops_in_runs`` and ``busy_in_runs_s``: the operations that started
  inside a ``run`` span, and the union of their intervals clipped to the
  run spans (per chip, averaged);
* ``top_ops``: the operations that took most time, summed by name;
* ``idle_gaps``: the longest intervals in which no operation ran, each
  named by the innermost benchmark span that held its midpoint (what the
  host was doing).
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPANS = ("window", "run", "call", "sync", "analysis", "codegen", "check")

Interval = Tuple[float, float]


@dataclass(frozen=True)
class Event:
    name: str
    start: float   # ns
    end: float     # ns


@dataclass(frozen=True)
class Summary:
    window_s: float
    busy_s: float
    runs: int
    ops_in_runs: float
    busy_in_runs_s: float
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]


def _is_chip(plane_name: str) -> bool:
    prefix = "/device:TPU:"
    return plane_name.startswith(prefix) and plane_name[len(prefix):].isdigit()


def short_name(hlo: str) -> str:
    """``%name kind[:target]`` of an HLO instruction's text, the name the
    trace gives an op."""
    kind = re.search(r" = \S+ ([\w-]+)\(", hlo)
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    return " ".join([hlo.split(" = ")[0]]
                    + ([kind.group(1)] if kind else [])
                    + ([target.group(1)] if target else []))


def device_ops(profile) -> Dict[str, List[Event]]:
    """Each chip's operations, by plane name, in order of start, on the
    chip's own clock."""
    out = {}
    for plane in profile.planes:
        if not _is_chip(plane.name):
            continue
        ops = [Event(short_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns)
               for line in plane.lines if line.name == OPS_LINE
               for e in line.events]
        out[plane.name] = sorted(ops, key=lambda e: e.start)
    return out


def align(ops: List[Event], spans: Sequence[Event]) -> List[Event]:
    """``ops`` moved onto the host's clock: the last op ends where the last
    ``sync`` span ends."""
    syncs = [s.end for s in spans if s.name == "sync"]
    if not ops or not syncs:
        return ops
    shift = max(syncs) - max(o.end for o in ops)
    return [Event(o.name, o.start + shift, o.end + shift) for o in ops]


def host_spans(profile, names: Sequence[str] = SPANS) -> List[Event]:
    """The benchmark's spans on the host, in order of start."""
    wanted = set(names)
    spans = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in profile.planes if plane.name == HOST_PLANE
             for line in plane.lines for e in line.events
             if e.name in wanted]
    return sorted(spans, key=lambda e: e.start)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that the disjoint ``merged`` intervals cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that the disjoint ``merged`` intervals
    leave uncovered."""
    out, at = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length that two lists of sorted, disjoint intervals share."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _innermost(spans: Sequence[Event], t: float) -> str:
    holding = [s for s in spans if s.start <= t <= s.end]
    return min(holding, key=lambda s: s.end - s.start).name


def summarize(profile, top: int = 10) -> Optional[Summary]:
    """The trace's numbers over its ``window`` span; None where the trace
    has no window span or no chip."""
    spans = host_spans(profile)
    windows = [s for s in spans if s.name == "window"]
    chips = device_ops(profile)
    if not windows or not chips:
        return None
    lo, hi = windows[0].start, windows[0].end
    runs = [(s.start, s.end) for s in spans
            if s.name == "run" and lo <= s.start and s.end <= hi]

    busy = in_runs = run_busy = 0.0
    by_name: Dict[str, float] = {}
    idle: List[Interval] = []
    for ops in chips.values():
        ops = align(ops, spans)
        merged = union((o.start, o.end) for o in ops)
        busy += covered(merged, lo, hi)
        run_busy += overlap(merged, runs)
        starts = [o.start for o in ops]
        in_runs += sum(bisect.bisect_left(starts, e)
                       - bisect.bisect_left(starts, s) for s, e in runs)
        for o in ops:
            if lo <= o.start < hi:
                by_name[o.name] = by_name.get(o.name, 0.0) + o.end - o.start
        idle += gaps(merged, lo, hi)
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    n = len(chips)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy * 1e-9 / n,
        runs=len(runs),
        ops_in_runs=in_runs / n,
        busy_in_runs_s=run_busy * 1e-9 / n,
        top_ops=sorted(((k, v * 1e-9 / n) for k, v in by_name.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=[(_innermost(spans, (s + e) / 2), (e - s) * 1e-9)
                   for s, e in idle])
