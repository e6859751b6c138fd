"""Mean wall time of one load task at the worker, from the task's start to
its report (worker counters load.s / load.tasks)."""


def read(run):
    tasks = run.delta("worker", "load.tasks")
    if "load.s" not in run.after["worker"] or tasks <= 0:
        return None
    return run.delta("worker", "load.s") / tasks * 1e3
