"""Compile requests inside the window, by jax.monitoring. Has to read 0."""


def read(run):
    return run.compile_window["compiles"]
