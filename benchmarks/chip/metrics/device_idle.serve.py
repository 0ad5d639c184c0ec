"""Share of the traced window, in %, in which no operation ran on the
serving chip: 1 - union of the device's operation intervals / window."""
from benchmarks.chip import trace_reduce


def read(run):
    trace = getattr(run, "trace", None)
    lo, hi = getattr(run, "window_ns", (0, 0))
    if trace is None or hi <= lo:
        return None
    busy = trace_reduce.mean_busy_s(trace, run.chips, lo, hi)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
