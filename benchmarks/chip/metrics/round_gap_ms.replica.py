"""Device milliseconds idle at a round boundary while the host works: from
the end of a round's last device operation to the start of the next
``jit_serve_prefill``, less any part under ``bench.wait_arrival``, averaged
over the window's round boundaries (``serve_program.round_idle``). The idle
time inside rounds is written to the run's log beside it. None unless the
named prefills match the prefills the replica counted."""
import sys

from benchmarks.chip import serve_program as P


def read(run):
    c = P.counters(run)
    if c is None or len(P.executions(run, P.PREFILL)) != c["serve.prefills"]:
        return None
    idle = P.round_idle(run)
    if idle is None or not idle[0]:
        return None
    between, inside = idle
    print(f"[round_gap_ms.replica] idle between rounds {sum(between) / 1e9:.6f}"
          f" s over {len(between)} boundaries; inside rounds "
          f"{sum(inside) / 1e9:.6f} s over {len(inside)} rounds "
          f"({sum(inside) / len(inside) / 1e6:.3f} ms a round)",
          file=sys.stderr, flush=True)
    return sum(between) / len(between) / 1e6
