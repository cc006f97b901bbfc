"""Scheduler: live lanes over lanes, averaged over the window's decode
steps (a lane is live in a step while it still owes tokens)."""


def read(run):
    steps = live = 0
    for d in run.window.window_dispatches():
        steps += d.n_steps
        live += sum(min(d.n_steps, r) for r in d.remaining)
    return 100.0 * live / (steps * run.n_lanes) if steps else None
