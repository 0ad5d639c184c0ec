"""The whole serving step's share of the chip's peak, in %: the model
operations of every prefill and decode token served in the traced window
(from ``flops.py``) over the window's length times peak bf16 FLOP/s."""
from benchmarks.chip import flops


def read(run):
    lo, hi = getattr(run, "window_ns", (0, 0))
    if not getattr(run, "rounds", None) or hi <= lo:
        return None
    total = sum(flops.prefill_flops(run.sizes, rd["batch"], rd["prompt"])
                for rd in run.rounds)
    total += sum(flops.decode_flops(run.sizes, b, pos)
                 for b, pos in run.decode_steps())
    return 100.0 * total / ((hi - lo) / 1e9 * run.peaks["bf16_flops_per_s"])
