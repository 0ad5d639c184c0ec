"""What the serving program says of itself, for the per-layer readers:
its process-wide ``serve.*`` counters (``repro.serving.spans``) and the
executions of its named programs ``jit_serve_prefill`` and
``jit_serve_decode`` in the traced window.

The counters cover the whole process. A ``serve_open_loop`` set-up compiles
ahead of time and never serves, so they cover exactly the window; a reader
gets them only where their round count equals the rounds the window ran,
so that a set-up that serves cannot pass unnoticed. A program without the
counters (an older checkout) gives none, and its readers return ``None``.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from benchmarks.chip import trace_reduce

PREFILL, DECODE = "jit_serve_prefill", "jit_serve_decode"
WAIT = "bench.wait_arrival"


def counters(run) -> Optional[dict]:
    """The program's counters, where they count the run's rounds."""
    try:
        from repro.serving import spans
    except ImportError:
        return None
    c = spans.snapshot()
    rounds = getattr(run, "rounds", None)
    if not rounds or c.get("serve.rounds") != len(rounds):
        return None
    return c


def executions(run, name: str, chip: Optional[int] = None
               ) -> List[trace_reduce.Event]:
    """Executions of program ``name`` that start in the traced window, on
    ``chip`` or on every chip of the run."""
    trace = getattr(run, "trace", None)
    lo, hi = getattr(run, "window_ns", (0, 0))
    if trace is None or hi <= lo:
        return []
    chips = run.chips if chip is None else (chip,)
    return [e for c in chips for e in trace_reduce.modules_in(trace, c, lo, hi)
            if trace_reduce.program_name(e.name) == name]


def _overlap(a: int, b: int, spans) -> int:
    return sum(e - s for s, e in trace_reduce.clip(spans, a, b))


def round_idle(run) -> Optional[Tuple[List[int], List[int]]]:
    """(idle ns between rounds, idle ns inside rounds) on the run's chips.

    A round runs from the start of its ``jit_serve_prefill`` to the end of
    the last operation before the next one (the window's end for the last
    round). Between two rounds the device is idle from that end to the next
    prefill's start; the part of it under ``bench.wait_arrival`` (no request
    was due) is taken off, and a boundary whose whole gap lies under it is
    left out. Inside a round it is idle wherever no operation ran. None where
    the window holds no named prefill."""
    trace = getattr(run, "trace", None)
    lo, hi = getattr(run, "window_ns", (0, 0))
    if trace is None or hi <= lo:
        return None
    waits = trace_reduce.merge((sp.start, sp.end) for sp in trace.host
                               if sp.name == WAIT)
    between, inside = [], []
    for chip in run.chips:
        starts = [e.start for e in executions(run, PREFILL, chip)]
        if not starts:
            return None
        busy = trace_reduce.busy_intervals(trace, chip)
        for a, b in zip(starts, starts[1:] + [hi]):
            ran = trace_reduce.clip(busy, a, b)
            last = max((e for _, e in ran), default=a)
            inside.append(last - a - sum(e - s for s, e in ran))
            if b == hi:
                continue
            gap = b - last
            waited = _overlap(last, b, waits)
            if gap > 0 and waited >= gap:
                continue
            between.append(gap - waited)
    return between, inside
