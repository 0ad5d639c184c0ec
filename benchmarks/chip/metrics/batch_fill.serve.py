"""Mean round size over the batcher's ``max_batch``, in %, over every round
of the window: how full ``ContinuousBatcher`` packs the replica's batch."""


def read(run):
    rounds = getattr(run, "rounds", None)
    if not rounds:
        return None
    return 100.0 * sum(r["batch"] for r in rounds) / len(rounds) / run.max_batch
