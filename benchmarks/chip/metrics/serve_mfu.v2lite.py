"""The DeepSeek-V2 cell's serving share of the chip's peak, in %: the
operations of the active parameters of every prefill and decode token
served in the traced window (top-k and shared experts; attention expanded
in prefill, absorbed in decode; ``flops_deepseek_v2``) over the window's
length times peak bf16 FLOP/s."""
from benchmarks.chip import flops_deepseek_v2 as F


def read(run):
    lo, hi = getattr(run, "window_ns", (0, 0))
    if not getattr(run, "rounds", None) or hi <= lo:
        return None
    total = sum(F.prefill_flops(run.sizes, rd["batch"], rd["prompt"])
                for rd in run.rounds)
    total += sum(F.decode_flops(run.sizes, b, pos)
                 for b, pos in run.decode_steps())
    return 100.0 * total / ((hi - lo) / 1e9 * run.peaks["bf16_flops_per_s"])
