"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

A trace is read into plain event lists: the ops and the program executions
that each device ran ("XLA Ops" and "XLA Modules" lines of its
``/device:TPU:n`` plane), and the host's events with their thread.  All
arithmetic below works on those lists, so the tests can feed it events made
by hand.  Times are nanoseconds on the trace's own clock; a window is the
host span the benchmark's loop annotated around the traced steps.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)
HostEvent = Tuple[str, str, float, float]  # (thread, name, start_ns, end_ns)

WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> ops
    modules: Dict[str, List[Event]]    # device plane -> program executions
    host: List[HostEvent]

    def window(self, name: str = WINDOW) -> Tuple[float, float]:
        spans = [(s, e) for _, n, s, e in self.host if n == name]
        if len(spans) != 1:
            raise ValueError(f"{len(spans)} host spans named {name!r}")
        return spans[0]


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that contain no other event of their line.  A ``while``
    (a scanned layer loop) is listed beside the ops it runs; counting both
    would count its time twice."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(order, parent) if not p]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file written by ``jax.profiler`` (or its
    gzip, ``.xplane.pb.gz``): the ops of
    each device's "XLA Ops" line, by HLO op name, and its "XLA Modules"
    line.  A loop is an op too, beside the ops it runs: it keeps the
    device busy between them."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                evs = [(op_name(e.name), e.start_ns,
                        e.start_ns + e.duration_ns) for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(line.name, e.name, e.start_ns,
                          e.start_ns + e.duration_ns) for e in line.events]
    return Trace(ops, modules, host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def merge(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi], sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Iterable[Tuple[float, float]], lo: float,
            hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def subtract(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> float:
    """Length of the disjoint sorted intervals ``a`` outside the disjoint
    sorted intervals ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------


def busy_ns(ops: Sequence[Event], lo: float, hi: float) -> float:
    return covered(((s, e) for _, s, e in ops), lo, hi)


def mean_busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    if not trace.ops:
        return 0.0
    return sum(busy_ns(o, lo, hi) for o in trace.ops.values()) \
        / len(trace.ops) / 1e9


def idle_frac(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """1 - busy / window, averaged over the devices."""
    if not trace.ops:
        return None
    return 1.0 - mean_busy_s(trace, lo, hi) * 1e9 / (hi - lo)


def step_program(trace: Trace, lo: float, hi: float) -> Optional[str]:
    """The program that held the devices longest in the window."""
    total: Dict[str, float] = collections.Counter()
    for mods in trace.modules.values():
        for name, s, e in mods:
            total[name] += max(0.0, min(e, hi) - max(s, lo))
    return max(total, key=total.get) if total else None


def step_gaps_ns(trace: Trace, lo: float, hi: float) -> List[float]:
    """Device idle between the end of one execution of the step program and
    the start of the next, one entry per gap per device."""
    name = step_program(trace, lo, hi)
    gaps = []
    for plane, mods in trace.modules.items():
        runs = sorted((s, e) for n, s, e in mods
                      if n == name and s >= lo and e <= hi)
        busy = merge(((s, e) for _, s, e in trace.ops.get(plane, ())), lo, hi)
        for (_, end), (start, _) in zip(runs, runs[1:]):
            if start > end:
                gaps.append(subtract([(end, start)], busy))
    return gaps


def exposed_collective_frac(trace: Trace, lo: float,
                            hi: float) -> Optional[float]:
    """Share of the window in which a collective runs on a device and no
    other op does, averaged over the devices; None where no collective
    ran."""
    fracs, seen = [], False
    for ops in trace.ops.values():
        ops = leaves(ops)
        coll = merge(((s, e) for n, s, e in ops if is_collective(n)), lo, hi)
        seen = seen or bool(coll)
        other = merge(((s, e) for n, s, e in ops if not is_collective(n)),
                      lo, hi)
        fracs.append(subtract(coll, other) / (hi - lo))
    return sum(fracs) / len(fracs) if seen else None


def top_ops(trace: Trace, lo: float, hi: float,
            k: int = 10) -> List[List]:
    """The ops that took most device time, seconds averaged over devices;
    a loop's time is its ops'."""
    total: Dict[str, float] = collections.Counter()
    for ops in trace.ops.values():
        for name, s, e in leaves(ops):
            total[name] += max(0.0, min(e, hi) - max(s, lo))
    n = max(len(trace.ops), 1)
    return [[name, t / n / 1e9] for name, t in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, lo: float, hi: float,
              k: int = 10) -> List[List]:
    """The longest idle gaps of the first device, each named by the
    innermost host event that spans its middle: a Python frame of the
    profiler's Python tracer (named ``$file:line function``) where one
    does, else any host event, else "none"."""
    if not trace.ops:
        return []
    plane = sorted(trace.ops)[0]
    busy = merge(((s, e) for _, s, e in trace.ops[plane]), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:k]
    out = []
    for length, s, e in gaps:
        mid = (s + e) / 2
        spans = [(not name.startswith("$"), he - hs, name)
                 for _, name, hs, he in trace.host
                 if hs <= mid <= he and name != WINDOW]
        out.append([min(spans)[2] if spans else "none", length / 1e9])
    return out
