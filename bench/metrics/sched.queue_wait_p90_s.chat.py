"""Scheduler queue wait, 90th percentile: submit -> admission of every
finished request due in the window, from the program's request trace
(`enqueued` and `admitted` events of serving/obs.Trace)."""
from harness import stats


def reduce(run):
    waits = run.res["serve"]["queue_wait"]
    return stats.percentile(waits, 90) if waits else None
