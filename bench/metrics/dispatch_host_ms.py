"""Engine host: milliseconds the host spends in each decode dispatch's
own work (the engine's ``serve.decode.prepare``, ``serve.decode.launch``
and ``serve.decode.retire`` spans, summed inside the traced slice), over
the dispatches traced: a mean, so bursts count."""

SPANS = ("serve.decode.prepare", "serve.decode.launch",
         "serve.decode.retire")


def read(run):
    if not run.traced:
        return None
    n = len(run.traced_dispatches())
    a, b = run.trace_span()
    total = sum(max(0.0, min(e, b) - max(s, a))
                for name, s, e in run.reduced.host if name in SPANS)
    if not n or total <= 0:
        return None
    return 1e3 * total / n
