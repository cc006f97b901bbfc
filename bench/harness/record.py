"""What one run leaves for the metric readers (``bench/metrics/``).

A reader is a module with ``read(run) -> float | None``; ``run`` is a
:class:`Run`.  The helpers here are the arithmetic readers share, so
each reader stays a few lines.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from harness import flops, trace
from harness.loop import Dispatch, Tracked, Window


@dataclasses.dataclass
class Run:
    arch: object                     # harness.model_config.Arch
    window: Window
    counters: Dict[str, float]
    peak: dict                       # the chip's row of peaks.json
    n_lanes: int
    page_size: int
    reduced: Optional[trace.Reduced] = None
    offset: float = 0.0              # trace clock - harness clock
    roles: Optional[dict] = None

    # -- the window, on the harness's clock --------------------------------
    @property
    def due(self) -> List[Tracked]:
        return self.window.due()

    def admitted_prompt_tokens(self) -> int:
        return sum(t.prompt_len for t in self.window.tracked
                   if t.admit_start is not None and t.admit_start >= 0)

    # -- the traced slice, on the trace's clock ---------------------------
    @property
    def traced(self) -> bool:
        return self.reduced is not None and self.window.trace_span is not None

    def trace_span(self) -> Tuple[float, float]:
        a, b = self.window.trace_span
        return a + self.offset, b + self.offset

    def traced_dispatches(self) -> List[Dispatch]:
        a, b = self.window.trace_span
        return [d for d in self.window.dispatches
                if d.start >= a and d.end <= b]

    def live_spans(self) -> List[Tuple[float, float]]:
        """Where at least one lane was live, within the traced slice."""
        a, b = self.window.trace_span
        out = []
        for s, e in self.window.live_intervals:
            s, e = max(s, a), min(e, b)
            if e > s:
                out.append((s + self.offset, e + self.offset))
        return out

    def program_names(self, role: str):
        return set(self.roles.get(role, ()))

    def decode_time(self) -> float:
        return self.reduced.program_time(self.program_names("decode"),
                                         self.trace_span())


def lane_steps(dispatches: List[Dispatch]) -> Iterator[int]:
    """The key count of every live lane-step: a lane at context ``c``
    that owes ``r`` tokens decodes ``min(n, r)`` steps of the dispatch,
    the k-th over ``c + k + 1`` keys (its new token's key included)."""
    for d in dispatches:
        for c, r in zip(d.ctx, d.remaining):
            for k in range(min(d.n_steps, r)):
                yield c + k + 1


def percentile(values: List[float], q: float) -> Optional[float]:
    """The q-th percentile, linear between closest ranks (numpy's
    default); None for no values."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def decode_flops(run: Run) -> float:
    return float(sum(flops.decode_token_flops(run.arch, n)
                     for n in lane_steps(run.traced_dispatches())))
