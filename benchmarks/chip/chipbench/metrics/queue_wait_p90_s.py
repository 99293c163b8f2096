"""Scheduler and batcher: 90th percentile of the wait from when a request
was due to when the harness handed it to the engine (host clock), over the
requests due in the window."""
from chipbench.stats import percentile


def read(run):
    return percentile([r.dispatched - r.due
                       for r in run.record.due_in_window()], 90)
