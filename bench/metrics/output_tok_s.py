"""Output tokens delivered in the window, over the window's seconds
(until the last dispatch of the window returned)."""

from harness.loop import window_tokens


def read(run):
    n = sum(len(window_tokens(t)) for t in run.window.tracked)
    return n / run.window.elapsed if run.window.elapsed > 0 else None
