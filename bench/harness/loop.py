"""The serving loop the window drives, and what it records.

Each turn admits every due request that fits, first come first served,
then runs one ``decode_n`` dispatch, and repeats.  Open loop: requests
fall due at the mix's arrival times whatever the engine does.  Closed
loop: each client sends its next request as soon as its last one
finishes.  The engine's ``run`` takes no arrival times, so this loop
stands in for an arrival-aware entry of the program.

Times are host-clock seconds from the window's start.  A token reaches
the host when the dispatch that made it returns.  Where the mix warms in
(``harness.traffic``), the loop starts serving before the window opens:
those times are negative, and what the window's metrics read starts at
0.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from harness import system
from harness.traffic import Traffic


@dataclasses.dataclass
class Tracked:
    uid: int
    prompt_len: int
    output_len: int
    due: float
    admit_start: Optional[float] = None
    admit_end: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    done_at: Optional[float] = None
    #: finished after the window closed, while the engine drained
    drained: bool = False
    lane: int = -1
    #: the engine's next-token array right after admission: the first
    #: token of the answer, sampled from the prefill logits, sits at
    #: ``lane`` (read after the window, so nothing syncs inside it)
    first_token_src: object = None
    req: object = None


@dataclasses.dataclass
class Dispatch:
    start: float
    end: float
    n_steps: int
    lanes: List[int]          # live lanes
    ctx: List[int]            # their context length at the start
    remaining: List[int]      # their budget at the start


@dataclasses.dataclass
class Window:
    seconds: float            # as asked
    elapsed: float            # until the last dispatch returned
    tracked: List[Tracked]
    dispatches: List[Dispatch]
    live_intervals: List[tuple]
    trace_span: Optional[tuple] = None   # (start, end) of the traced slice
    opened_at: float = 0.0    # the clock's reading when the window opened
    #: most pages the live lanes held or had reserved at a dispatch
    pages_live_peak: int = 0

    def due(self) -> List[Tracked]:
        """Requests due inside the window."""
        return [t for t in self.tracked if 0 <= t.due < self.seconds]

    def window_dispatches(self) -> List[Dispatch]:
        """Dispatches that started inside the window."""
        return [d for d in self.dispatches if d.start >= 0]

    def finished(self) -> List[Tracked]:
        """Requests that finished inside the window or while the engine
        drained after it."""
        return [t for t in self.tracked
                if (t.done_at is not None and t.done_at >= 0) or t.drained]


def window_tokens(t: Tracked) -> List[float]:
    """When each of the request's tokens inside the window reached the
    host."""
    return [x for x in t.token_times if x >= 0]


class Profiler:
    """Starts and stops the device trace at dispatch boundaries."""

    def __init__(self, logdir: str, start_s: float, stop_s: float):
        self.logdir, self.start_s, self.stop_s = logdir, start_s, stop_s
        self.on = False
        self.span = None

    def tick(self, now: float, clock) -> None:
        if not self.on and self.span is None and now >= self.start_s:
            jax.profiler.start_trace(self.logdir)
            self.on = True
            self.span = [clock(), None]
        elif self.on and now >= self.stop_s:
            self.close(clock)

    def close(self, clock) -> None:
        if self.on:
            self.span[1] = clock()
            jax.profiler.stop_trace()
            self.on = False


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def serve_window(engine, traffic: Traffic, seconds: float, *,
                 uid0: int = 0, profiler: Optional[Profiler] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 on_open: Callable[[], None] = lambda: None) -> Window:
    """Drive ``engine`` with ``traffic`` for ``seconds``, after the
    mix's warm-in if it has one.  ``on_open`` runs as the window opens
    (at the first turn of the loop at or after 0)."""
    items, due_times = traffic.schedule(seconds)
    tracked: List[Tracked] = []
    by_uid: Dict[int, Tracked] = {}
    pending: collections.deque = collections.deque()
    next_item = 0
    lead = traffic.lead(seconds)
    opened = lead == 0
    if opened:
        on_open()
    t0 = clock() + lead
    pages_peak = 0

    def now() -> float:
        return clock() - t0

    def issue(due: float) -> None:
        nonlocal next_item
        if next_item >= len(items):
            raise RuntimeError(
                f"traffic {traffic.name!r} ran out of requests "
                f"({len(items)}); raise its n_requests")
        it = items[next_item]
        next_item += 1
        uid = uid0 + len(tracked)
        t = Tracked(uid=uid, prompt_len=len(it.prompt),
                    output_len=it.output_len, due=due)
        t.req = system.Request(uid=uid, prompt=it.prompt,
                               max_new_tokens=it.output_len)
        tracked.append(t)
        by_uid[uid] = t
        pending.append(t)

    if traffic.closed:
        for _ in range(traffic.clients):
            issue(0.0)

    dispatches: List[Dispatch] = []
    live_intervals: List[list] = []
    live_since: Optional[float] = None
    while True:
        t = now()
        if t >= seconds:
            break
        if not opened and t >= 0:
            opened = True
            on_open()
        if profiler is not None:
            profiler.tick(t, now)
        if due_times is not None:
            while next_item < len(items) and due_times[next_item] <= t:
                issue(float(due_times[next_item]))
        while pending and engine.free_lanes() and now() < seconds:
            tr = pending[0]
            lane = engine.free_lanes()[0]
            start = now()
            if live_since is None:
                live_since = start
            with _annotate("bench.admit"):
                ok = engine.admit(tr.req)
            if not ok:
                if not engine.live_lanes():
                    live_since = None
                break
            pending.popleft()
            tr.admit_start, tr.admit_end = start, now()
            tr.lane = lane
            tr.first_token_src = system.next_token_array(engine)
        live = engine.live_lanes()
        if not live:
            if live_since is not None:
                live_intervals.append((live_since, now()))
                live_since = None
            if due_times is not None and next_item < len(items):
                wait = min(float(due_times[next_item]), seconds) - now()
                if wait > 0:
                    with _annotate("bench.wait"):
                        time.sleep(wait)
            continue
        pages_peak = max(pages_peak, system.pages_held(engine, live))
        n = engine.dispatch_n
        ctx = [engine.lane_context(i) for i in live]
        rem = [system.lane_remaining(engine, i) for i in live]
        steps0 = engine.stats["decode_steps"]
        start = now()
        with _annotate("bench.dispatch"):
            out = engine.decode_n(n)
        end = now()
        dispatches.append(Dispatch(start, end,
                                   engine.stats["decode_steps"] - steps0,
                                   live, ctx, rem))
        for uid, toks in out.items():
            tr = by_uid[uid]
            tr.token_times.extend([end] * len(toks))
            if tr.req.done:
                tr.done_at = end
                if traffic.closed:
                    issue(end)
        if not engine.live_lanes():
            live_intervals.append((live_since, end))
            live_since = None
    elapsed = now()
    if live_since is not None:
        live_intervals.append((live_since, elapsed))
    span = None
    if profiler is not None:
        profiler.close(now)
        span = tuple(profiler.span) if profiler.span else None
    return Window(seconds=float(seconds), elapsed=elapsed, tracked=tracked,
                  dispatches=dispatches,
                  live_intervals=[tuple(x) for x in live_intervals],
                  trace_span=span, opened_at=t0, pages_live_peak=pages_peak)


def drain(engine, window: Window, seconds: float,
          clock: Callable[[], float] = time.perf_counter) -> int:
    """After the window: admit nothing, and let the requests already in
    flight run on for at most ``seconds``, so the comparison has answers
    that began in the window to read.  Their late tokens are not the
    window's and no metric sees them.  Returns the requests finished."""
    by_uid = {t.uid: t for t in window.tracked}
    t0, n = clock(), 0
    while engine.live_lanes() and clock() - t0 < seconds:
        for uid in engine.decode_n():
            t = by_uid.get(uid)
            if t is not None and t.req.done and t.done_at is None \
                    and not t.drained:
                t.drained = True
                n += 1
    return n


def first_token(tr: Tracked) -> Optional[int]:
    """The answer's first token, sampled at admission."""
    if tr.first_token_src is None:
        return None
    return int(np.asarray(tr.first_token_src)[tr.lane])
