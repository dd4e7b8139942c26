"""Runtime / XLA compile: backend compilations inside the measured
window, counted by a ``jax.monitoring`` listener (a persistent-cache load
counts too).  Set-up warms every shape, so it should read 0."""

def read(run):
    return float(run.compiles)
