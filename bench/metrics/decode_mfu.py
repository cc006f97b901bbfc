"""Whole decode step: the FLOPs its live lane-steps need (2 per matmul
weight per token, plus attention over the live context) over the decode
program's device time at the chip's bf16 peak, in the traced slice."""

from harness.record import decode_flops


def read(run):
    if not run.traced:
        return None
    t = run.decode_time()
    if t <= 0:
        return None
    return 100.0 * decode_flops(run) / (t * run.peak["bf16_flops_per_s"])
