"""Mean seconds a request waits in ``ContinuousBatcher``'s queue, from its
``submit`` to the ``next_round`` that batches it: ``serve.queue_wait_s`` over
``serve.requests_batched``."""
from benchmarks.chip import serve_program as P


def read(run):
    c = P.counters(run)
    if c is None or c["serve.requests_batched"] <= 0:
        return None
    return c["serve.queue_wait_s"] / c["serve.requests_batched"]
