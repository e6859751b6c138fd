"""99th percentile of the time between one batch ready on the device and
the next, over every gap of the window, in ms. What a train step waits
on. A per-layer metric and not an end-to-end one: on a quiet host its
runs spread by 1.3%, through a slow spell of the host by 12%, and no
bound admits both (PERF.md section 2)."""

from perfbench.harness import percentile


def read(run):
    return percentile(run.window.gaps(), 99.0) * 1e3
