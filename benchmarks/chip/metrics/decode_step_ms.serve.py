"""Device milliseconds per decode step: the device's busy time in each
round after its prefill, over the round's decode steps (answer length - 1),
summed over the traced window's rounds."""


def read(run):
    phases = getattr(run, "phases", None)
    if not phases:
        return None
    steps = sum(p["steps"] for p in phases)
    if steps <= 0:
        return None
    return sum(p["decode_ns"] for p in phases) / steps / 1e6
