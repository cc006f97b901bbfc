"""Set-up: run every program shape the cell's traffic will use once.

The engine pads a prompt to a power-of-two bucket, one program per
bucket, for a batched prefill (a prompt-cache miss) and for the tail it
streams after a hit.  Warm-up admits one prompt per bucket the mix's
lengths reach; where the mix shares templates, it admits each template
once (so they are cached before the window, as the system prompts of a
running deployment are) and then a hit per tail bucket; it dispatches
``decode_n`` at 8, 4, 2 and 1 steps, and runs the lanes dry.  Warm-up
prompts open with tokens no request of the window opens with, so none of
them is ever matched.

The engine's eager admission steps (slicing the prefill's keys and
values, page scatter, block-table writes) compile one small program per
prompt length.  A mix that warms in meets every length the window will
meet in its warm-in; for any other mix, warm-up admits one prompt of each
length (template and unique part) among the requests the window can
send.  The window's counts of programs compiled and loaded show any
that were missed.
"""

from __future__ import annotations

import numpy as np

from harness import system
from harness.traffic import Traffic


def buckets(lo: int, hi: int, floor: int = 8):
    """Lengths in ``[lo, hi]`` that land in every power-of-two bucket
    the range reaches."""
    out, b = [], floor
    while b < lo:
        b <<= 1
    while True:
        out.append(min(max(b, lo), hi))
        if b >= hi:
            return out
        b <<= 1


def warm(engine, traffic: Traffic, max_len: int, uid0: int,
         seconds: float) -> int:
    """Run the warm-up requests to completion; returns the next uid."""
    rng = np.random.default_rng(0x3A12)
    firsts = iter(traffic.reserved_first)
    vocab = engine.cfg.vocab_size
    uid = uid0
    gen = 2 * engine.dispatch_n

    def admit(prompt):
        nonlocal uid
        req = system.Request(uid=uid, prompt=prompt, max_new_tokens=gen)
        uid += 1
        while not engine.admit(req):
            engine.decode_n()

    def fresh(n):
        p = rng.integers(0, vocab, n).astype(np.int32)
        p[0] = next(firsts)
        return p

    limit = max_len - gen - 1
    plens = traffic.prompt_lens()
    for n in buckets(min(plens), min(max(plens), limit)):
        admit(fresh(n))
    if traffic.templates:
        # the mix's system prompts are in the prompt cache before the
        # window, as in a deployment that has served them before; then
        # one hit per tail bucket
        for head in traffic.templates:
            admit(np.concatenate([head, fresh(1)]))
        head = traffic.templates[0]
        tails = [it.unique_len for it in traffic.items]
        for n in buckets(min(tails), max(tails)):
            admit(np.concatenate([head, fresh(min(n, limit - len(head)))]))
    if not traffic.lead(seconds):
        shapes = {(it.template, it.unique_len)
                  for it in traffic.window_items(seconds)}
        for tpl, n in sorted(shapes):
            uniq = fresh(n)
            admit(np.concatenate([traffic.templates[tpl], uniq])
                  if tpl >= 0 else uniq)
    for n in (8, 4, 2, 1):
        if engine.live_lanes():
            engine.decode_n(n)
    while engine.live_lanes():
        engine.decode_n()
    return uid
