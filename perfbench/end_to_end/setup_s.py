"""Process start to the first instant of the window, in seconds: native
build check, cluster up, data written, shapes warmed, compilation."""


def read(run):
    return run.setup_s
