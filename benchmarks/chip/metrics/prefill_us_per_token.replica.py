"""Device microseconds of prefill per prompt token: the summed durations of
the ``jit_serve_prefill`` executions in the traced window over the prompt
tokens the replica counted (``serve.prompt_tokens``, batch x prompt, pads
included). None unless the executions match the prefills counted. At the
chip's peak FLOP/s, ``flops.prefill_flops`` puts a floor under it."""
from benchmarks.chip import serve_program as P


def read(run):
    c = P.counters(run)
    pre = P.executions(run, P.PREFILL)
    if c is None or not pre or len(pre) != c["serve.prefills"] \
            or c["serve.prompt_tokens"] <= 0:
        return None
    return sum(e.dur for e in pre) / c["serve.prompt_tokens"] / 1e3
