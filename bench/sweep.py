"""Find the knee of an open-loop cell: the highest arrival rate at which
the backlog does not grow across the window.

    python3 bench/sweep.py --workload <cell> --rates 0.1,0.2,0.4 --seconds 40

Runs the cell once per rate (the mix's own sizes and order, only the
rate changed; after the mix's warm-in, if it has one), in one process,
each rate on its own seed, and prints per rate what was due, admitted
and finished, the backlog (requests due and not finished: queued or in
a lane) as the window opened and as it closed, and the median time to
first token in the first and the last third of the window: a backlog
that grows shows as more at the close than at the open, and as a last
third far above the first.  A rate is sustained where the backlog at
the close exceeds that at the open by at most one request; the knee is
the highest sustained rate swept.  With ``--write`` the cell's traffic
file gets ``SHARE`` of the knee as its rate (exit 1, nothing written,
if no rate was sustained).  The benchmark's own runs never run this; its
result is the number in the traffic file.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import run  # noqa: E402

#: the share of the knee a cell offers: below capacity, so its tails
#: measure service and not a queue that grows all through the window
SHARE = 0.8


def thirds(window) -> tuple:
    due = window.due()
    end = window.elapsed
    ttft = [((t.token_times[0] if t.token_times else end) - t.due, t.due)
            for t in due]
    third = window.seconds / 3

    def med(lo, hi):
        xs = [a for a, d in ttft if lo <= d < hi]
        return float(np.median(xs)) if xs else None
    return med(0, third), med(2 * third, window.seconds)


def backlog(window, at: float) -> int:
    """Requests due by ``at`` and not finished by then."""
    return sum(t.due <= at and (t.done_at is None or t.done_at > at)
               for t in window.tracked)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)
    bench = run.load_benchmark(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = run.cell_of(bench, args.workload)
    tmp = tempfile.mkdtemp(prefix="bench-sweep-")
    try:
        for sub in ("configs", "limits", "metrics", "traffic"):
            shutil.copytree(os.path.join(run.BENCH, sub),
                            os.path.join(tmp, sub))
        path = os.path.join(tmp, "traffic", f"{cell['traffic']}.json")
        with open(path) as f:
            mix = json.load(f)
        sustained = []
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix["arrivals"]["rate_per_s"] = rate
            with open(path, "w") as f:
                json.dump(mix, f)
            keep: dict = {}
            ns = argparse.Namespace(workload=args.workload, seed=args.seed + i,
                                    seconds=args.seconds, trace=0)
            out = run.run_cell(ns, bench, bench_dir=tmp, keep=keep)
            w = keep["window"]
            due = w.due()
            first, last = thirds(w)
            print(json.dumps({
                "rate_per_s": rate, "due": len(due),
                "admitted": sum(t.admit_start is not None for t in due),
                "finished": sum(t.done_at is not None for t in due),
                "waiting_at_end": sum(t.admit_start is None for t in due),
                "backlog_open": backlog(w, 0.0),
                "backlog_close": backlog(w, w.elapsed),
                "ttft_median_first_third_s": first,
                "ttft_median_last_third_s": last,
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "correct": out["correct"],
                "max_logit_gap": out["checks"]["max_logit_gap"]["value"],
                "window": keep["info"]}), flush=True)
            if backlog(w, w.elapsed) <= backlog(w, 0.0) + 1:
                sustained.append(rate)
            keep.clear()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if not args.write:
        return 0
    if not sustained:
        print("sweep: no rate was sustained; nothing written", file=sys.stderr)
        return 1
    path = os.path.join(run.BENCH, "traffic", f"{cell['traffic']}.json")
    with open(path) as f:
        mix = json.load(f)
    mix["arrivals"]["rate_per_s"] = round(SHARE * max(sustained), 4)
    with open(path, "w") as f:
        json.dump(mix, f, indent=2)
        f.write("\n")
    print(f"sweep: knee {max(sustained)}; wrote rate "
          f"{mix['arrivals']['rate_per_s']} into {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
