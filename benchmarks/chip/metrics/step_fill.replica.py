"""How full each decode step's batch is, in %, weighted by decode steps: the
batch rows the replica decoded (``serve.decode_rows``) over its decode steps
(``serve.decode_steps``) times the batcher's ``max_batch``."""
from benchmarks.chip import serve_program as P


def read(run):
    c = P.counters(run)
    if c is None or c["serve.decode_steps"] <= 0:
        return None
    return 100.0 * c["serve.decode_rows"] / (c["serve.decode_steps"]
                                             * run.max_batch)
