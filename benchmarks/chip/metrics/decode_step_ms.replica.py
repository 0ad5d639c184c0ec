"""Device milliseconds per decode step, read from the program's named decode
step: the summed durations of the ``jit_serve_decode`` executions in the
traced window over their count. None unless that count equals the decode
steps the replica counted (``serve.decode_steps``)."""
from benchmarks.chip import serve_program as P


def read(run):
    c = P.counters(run)
    steps = P.executions(run, P.DECODE)
    if c is None or not steps or len(steps) != c["serve.decode_steps"]:
        return None
    return sum(e.dur for e in steps) / len(steps) / 1e6
