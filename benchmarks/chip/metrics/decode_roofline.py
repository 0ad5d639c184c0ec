"""Decode's share of its roofline, in %: the least time the chip could take
for every decode step of the traced window (the larger of its operations
over peak FLOP/s and its bytes over peak bandwidth, from ``flops.py``) over
the device time the decode phases took. The run's log says how many steps
each bound holds ("decode steps bound by memory")."""
from benchmarks.chip import flops


def read(run):
    phases = getattr(run, "phases", None)
    if not phases:
        return None
    least = sum(flops.least_seconds(flops.decode_flops(run.sizes, b, pos),
                                    flops.decode_bytes(run.sizes, b, pos),
                                    run.peaks)[0]
                for b, pos in run.decode_steps())
    took = sum(p["decode_ns"] for p in phases) / 1e9
    return 100.0 * least / took if took > 0 else None
