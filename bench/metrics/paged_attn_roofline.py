"""Kernel: the paged decode-attention kernel's share of its roofline.

For every live lane-step in the traced slice, the least time the chip
could take (the larger of the step's attention FLOPs over peak and its
bytes over HBM bandwidth: the live K and V pages once, q and the
output), summed, over the device time of the Pallas kernel calls inside
the decode program."""

from harness import flops
from harness.record import lane_steps


def read(run):
    if not run.traced:
        return None
    kt = run.reduced.kernel_time_within(run.program_names("decode"),
                                        run.trace_span())
    if kt <= 0:
        return None
    bound = 0.0
    for n in lane_steps(run.traced_dispatches()):
        f, b = flops.paged_attention_cost(run.arch, n, run.page_size)
        bound += flops.roofline_seconds(f, b, run.peak)
    return 100.0 * bound / kt
