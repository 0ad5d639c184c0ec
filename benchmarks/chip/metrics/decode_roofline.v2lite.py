"""The DeepSeek-V2 decode step's share of its roofline, in %: the least
time the chip could take for every decode step of the traced window over
the summed durations of the ``jit_serve_decode`` executions.

The least time is the larger of the window's decode operations over peak
FLOP/s and its decode bytes over peak bandwidth (``flops_deepseek_v2``):
the non-expert weights and the head once a step, the latent rows written
so far, and the experts that got a row, which the program counts over
steps and MoE layers (``serve.moe_experts_hit``). Taken over totals, it is
at most the sum of each step's least time, so the share is not overstated.
None unless the executions match the decode steps counted, or where the
program does not count the experts hit."""
from benchmarks.chip import flops_deepseek_v2 as F
from benchmarks.chip import serve_program as P


def read(run):
    c = P.counters(run)
    steps = P.executions(run, P.DECODE)
    if c is None or not steps or len(steps) != c["serve.decode_steps"] \
            or "serve.moe_experts_hit" not in c:
        return None
    walk = list(run.decode_steps())
    ops = sum(F.decode_flops(run.sizes, b, pos) for b, pos in walk)
    nbytes = sum(F.decode_bytes(run.sizes, b, pos, 0) for b, pos in walk) \
        + c["serve.moe_experts_hit"] * F.expert_params(run.sizes) * 2
    least = max(ops / run.peaks["bf16_flops_per_s"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    took = sum(e.dur for e in steps) / 1e9
    return 100.0 * least / took
