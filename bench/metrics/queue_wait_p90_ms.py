"""Scheduler: from a request's due time to the start of its admission,
90th percentile over requests due in the window (one still waiting at
the end counts with its wait so far)."""

from harness.record import percentile


def read(run):
    end = run.window.elapsed
    waits = [(t.admit_start if t.admit_start is not None else end) - t.due
             for t in run.due]
    p = percentile(waits, 90)
    return None if p is None else 1e3 * p
