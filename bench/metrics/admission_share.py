"""Model step, admission side: device time outside the decode program
(prefill, page scatter, tail streaming, copy-on-write) over device busy
time, in the traced slice."""


def read(run):
    if not run.traced or not run.reduced.modules:
        return None
    busy = run.reduced.busy([run.trace_span()])
    if busy <= 0:
        return None
    return 100.0 * (busy - run.decode_time()) / busy
