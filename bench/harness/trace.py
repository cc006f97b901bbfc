"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

What a TPU trace holds (looked at by hand on a v5e): a plane
``/device:TPU:<n>`` per chip whose line ``XLA Modules`` has one event
per program execution (named ``jit_<function>(<fingerprint>)``) and
whose line ``XLA Ops`` has one per HLO operation, nested (a ``while``
covers its body); a Pallas kernel is an op whose text names
``custom_call_target="tpu_custom_call"``.  The plane ``/host:CPU`` holds
the host threads; the benchmark's own ``TraceAnnotation`` spans
(``bench.admit``, ``bench.dispatch``, ``bench.wait``) are on the Python
thread, and so are the engine's (``serve.decode.prepare`` and the
rest), nested inside them.  Device and host events share one clock:
nanoseconds from the start of the trace.

Which program plays which part is data: ``bench/programs.json`` maps a
role (``decode`` and so on) to the program names that fill it.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

PROGRAMS_FILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "programs.json")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
#: host annotations kept: the harness's own and the engine's spans
HOST_MARK = ("bench.", "serve.")
_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]          # seconds on the trace's clock


@dataclasses.dataclass
class Reduced:
    """One chip's device work and the host's annotations, in seconds."""
    modules: List[Tuple[str, float, float]]     # (program, start, end)
    kernels: List[Tuple[str, float, float]]     # Pallas kernel calls
    host: List[Tuple[str, float, float]]        # by start; HOST_MARK
    n_devices: int

    def program_time(self, names, span: Interval) -> float:
        return sum(_overlap((s, e), span) for n, s, e in self.modules
                   if n in names)

    def kernel_time_within(self, names, span: Interval) -> float:
        """Kernel time inside executions of the programs ``names``."""
        outer = merge([(s, e) for n, s, e in self.modules if n in names])
        return sum(_overlap((s, e), span) for _, s, e in self.kernels
                   if _inside(s, outer))

    def busy(self, spans: List[Interval]) -> float:
        """Seconds in which a program ran, within ``spans``."""
        ivs = merge([(s, e) for _, s, e in self.modules])
        spans = merge(spans)
        return sum(_overlap(iv, sp) for iv in ivs for sp in spans)

    def host_events(self, name: str) -> List[Interval]:
        return [(s, e) for n, s, e in self.host if n == name]


def program_roles() -> Dict[str, List[str]]:
    with open(PROGRAMS_FILE) as f:
        return json.load(f)


def short_name(module: str) -> str:
    return _FINGERPRINT.sub("", module)


def merge(ivs: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def _inside(t: float, merged: List[Interval]) -> bool:
    i = bisect.bisect_right([s for s, _ in merged], t) - 1
    return i >= 0 and t <= merged[i][1]


def find_xplane(logdir: str) -> Optional[str]:
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce(path: str, device: int = 0) -> Reduced:
    """Read the trace at ``path`` (chip ``device`` only)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules, kernels, host = [], [], []
    n_devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_devices += 1
            if plane.name != f"/device:TPU:{device}":
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        modules.append((short_name(ev.name),
                                        ev.start_ns * 1e-9, ev.end_ns * 1e-9))
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name
                        if KERNEL_MARK in name:
                            kernels.append((name.split(" = ")[0].lstrip("%"),
                                            ev.start_ns * 1e-9,
                                            ev.end_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_MARK):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     ev.end_ns * 1e-9))
    return Reduced(modules, kernels, sorted(host, key=lambda h: h[1]),
                   n_devices)


def host_offset(reduced: Reduced, host_starts: List[float]) -> float:
    """Trace clock minus the harness's clock, matched on the dispatch
    annotations (the k-th ``bench.dispatch`` span is the k-th dispatch
    the harness timed while the trace was on)."""
    spans = reduced.host_events("bench.dispatch")
    n = min(len(spans), len(host_starts))
    if n == 0:
        raise ValueError("no bench.dispatch span in the trace")
    diffs = sorted(spans[i][0] - host_starts[i] for i in range(n))
    return diffs[n // 2]


def top_programs(reduced: Reduced, span: Interval, roles, k: int = 10):
    """[[name, seconds], ...]: programs by device time, most first, each
    labelled with its role where ``programs.json`` names one."""
    role_of = {n: r for r, names in roles.items() for n in names}
    tot: Dict[str, float] = collections.Counter()
    for n, s, e in reduced.modules:
        label = f"{role_of[n]}:{n}" if n in role_of else n
        tot[label] += _overlap((s, e), span)
    kern: Dict[str, float] = collections.Counter()
    for n, s, e in reduced.kernels:
        kern[f"kernel:{n}"] += _overlap((s, e), span)
    rows = sorted(list(tot.items()) + list(kern.items()),
                  key=lambda kv: -kv[1])
    return [[n, t] for n, t in rows[:k] if t > 0]


def idle_gaps(reduced: Reduced, span: Interval, k: int = 10):
    """[[host activity, seconds], ...]: device idle time inside ``span``
    by what the host was doing (the innermost annotation around the
    middle of each gap: ``host`` is sorted by start, so the last that
    covers it), most first."""
    ivs = merge([(s, e) for _, s, e in reduced.modules])
    gaps, t = [], span[0]
    for s, e in ivs:
        if e <= span[0] or s >= span[1]:
            continue
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < span[1]:
        gaps.append((t, span[1]))
    tot: Dict[str, float] = collections.Counter()
    for a, b in gaps:
        mid = 0.5 * (a + b)
        what = "host:other"
        for n, s, e in reduced.host:
            if s <= mid <= e:
                what = f"host:{n}"
        tot[what] += b - a
    rows = sorted(tot.items(), key=lambda kv: -kv[1])
    return [[n, t] for n, t in rows[:k]]
