"""Runtime: mean of ``handle.latency_stats()["count"]`` (tasks the engine
dispatched for the query) over the window's answered requests."""


def read(run):
    tasks = [r.tasks for r in run.log if r.tasks]
    return sum(tasks) / len(tasks) if tasks else None
