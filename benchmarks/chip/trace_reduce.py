"""From a profiler trace to device busy and idle time, per-program device
time and idle gaps named by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes. On a TPU
each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Modules`` holds
one event per program execution, named ``<jit name>(<program id>)``, and
whose line ``XLA Ops`` holds the operations inside them. The benchmark's
own host spans (``jax.profiler.TraceAnnotation`` with a name that starts
with ``bench.``) sit on the host plane, on the same clock. Everything after
``load`` works on plain lists of ``Event`` so that the tests can build a
trace by hand.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int          # ns, on the trace's clock
    end: int

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    modules: Dict[int, List[Event]]     # chip id -> program executions
    ops: Dict[int, List[Event]]         # chip id -> operations
    host: List[Event]                   # the benchmark's host spans


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read a trace file (or the newest one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    modules: Dict[int, List[Event]] = {}
    ops: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Modules", "XLA Ops"):
                dest = modules if line.name == "XLA Modules" else ops
                dest.setdefault(int(m.group(1)), []).extend(
                    Event(e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events)
            elif plane.name.startswith("/host"):
                host.extend(Event(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    for d in (modules, ops):
        for evs in d.values():
            evs.sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(modules, ops, host)


# ------------------------------------------------------------- intervals


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sorted, disjoint union of [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_intervals(trace: Trace, chip: int) -> List[Tuple[int, int]]:
    """Union of the intervals in which an operation ran on ``chip``; the
    program executions stand in where a trace has no operations line."""
    evs = trace.ops.get(chip) or trace.modules.get(chip) or []
    return merge((e.start, e.end) for e in evs)


def busy_ns(trace: Trace, chip: int, lo: int, hi: int) -> int:
    return sum(e - s for s, e in clip(busy_intervals(trace, chip), lo, hi))


def mean_busy_s(trace: Trace, chips: Sequence[int], lo: int, hi: int
                ) -> float:
    """Device busy seconds in [lo, hi), averaged over ``chips``."""
    return sum(busy_ns(trace, c, lo, hi) for c in chips) / len(chips) / 1e9


def idle_gaps(trace: Trace, chip: int, lo: int, hi: int
              ) -> List[Tuple[int, int]]:
    """The intervals of [lo, hi) in which nothing ran on ``chip``."""
    gaps, t = [], lo
    for s, e in clip(busy_intervals(trace, chip), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def host_label(spans: Sequence[Event], t: int, default: str = "host:other"
               ) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best: Optional[Event] = None
    for sp in spans:
        if sp.start <= t < sp.end and (best is None or sp.dur < best.dur):
            best = sp
    return best.name if best else default


def gaps_by_host(trace: Trace, chips: Sequence[int], lo: int, hi: int,
                 top: int = 10) -> List[list]:
    """Idle seconds summed by what the host was doing at each gap's middle,
    averaged over ``chips``, the largest first."""
    spans = [sp for sp in trace.host if sp.end > lo and sp.start < hi]
    tot: Dict[str, float] = {}
    for c in chips:
        for s, e in idle_gaps(trace, c, lo, hi):
            name = host_label(spans, (s + e) // 2)
            tot[name] = tot.get(name, 0.0) + (e - s) / 1e9 / len(chips)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])][:top]


def program_name(module: str) -> str:
    """``jit__lambda(1360...)`` -> ``jit__lambda``: the name without the
    program id, which changes with every shape."""
    return re.sub(r"\(\d+\)$", "", module)


def spans(trace: Trace, name: str) -> List[Event]:
    return [sp for sp in trace.host if sp.name == name]


def modules_in(trace: Trace, chip: int, lo: int, hi: int) -> List[Event]:
    """Program executions on ``chip`` that start in [lo, hi), in order."""
    return [e for e in trace.modules.get(chip, []) if lo <= e.start < hi]
