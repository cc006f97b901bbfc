"""Device: 1 - busy / time, over the stretches of the traced slice in
which at least one lane was live (waiting for arrivals is left out)."""


def read(run):
    if not run.traced or not run.reduced.modules:
        return None
    spans = run.live_spans()
    total = sum(e - s for s, e in spans)
    if total <= 0:
        return None
    return 100.0 * (1.0 - run.reduced.busy(spans) / total)
