"""Time per output token, 90th percentile, over requests that received
at least two tokens in the window: (last token time - first token
time) / (tokens - 1), over the tokens each received."""

from harness.loop import window_tokens
from harness.record import percentile


def read(run):
    times = [window_tokens(t) for t in run.window.tracked]
    per = [(x[-1] - x[0]) / (len(x) - 1) for x in times if len(x) >= 2]
    p = percentile(per, 90)
    return None if p is None else 1e3 * p
