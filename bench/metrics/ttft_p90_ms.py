"""Time to first token, 90th percentile, over every request due in the
window: from its due time until its first token reached the host.  A
request with no token by the window's end counts with its wait so far,
so a stall cannot hide."""

from harness.record import percentile


def read(run):
    end = run.window.elapsed
    waits = [(t.token_times[0] if t.token_times else end) - t.due
             for t in run.due]
    p = percentile(waits, 90)
    return None if p is None else 1e3 * p
