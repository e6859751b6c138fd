"""Seconds XLA spent compiling (or loading from the persistent cache)
during set-up, by jax.monitoring."""


def read(run):
    return run.compile_setup["compile_s"]
