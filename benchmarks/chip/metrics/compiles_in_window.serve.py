"""XLA compilations inside the measured window (``jax.monitoring``'s
backend-compile events): each is a stall that a request waits through."""


def read(run):
    return getattr(run, "compiles_in_window", None)
