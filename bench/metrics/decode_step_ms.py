"""Model step: device time of the decode program over the decode steps
it ran, in the traced slice."""


def read(run):
    if not run.traced:
        return None
    steps = sum(d.n_steps for d in run.traced_dispatches())
    t = run.decode_time()
    if not steps or t <= 0:
        return None
    return 1e3 * t / steps
