"""Readings that set a cell's limit: the program's, and the control's.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed it makes one run of the cell (as ``run.py --trace 0``
does), then reads two numbers over the same served sequences: the
program's widest logit gap (what ``correct`` compares), and the
control's, the widest gap of the token the reference computed at
float8 (e4m3, per-tensor scaled) puts first.  The control's gaps go
through ``run.verdict`` with the cell's own limits, as the program's
do, and have to come out not correct.  One JSON line per seed; exits 1
if the control came out correct on any seed.  The benchmark's own runs
never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

CONTROL = "float8_e4m3fn"


def readings(args, bench, seed: int, *, require_tpu=True,
             bench_dir=run.BENCH) -> dict:
    from harness import check
    keep: dict = {}
    ns = argparse.Namespace(workload=args.workload, seed=seed,
                            seconds=args.seconds, trace=0)
    out = run.run_cell(ns, bench, require_tpu=require_tpu,
                       bench_dir=bench_dir, keep=keep)
    ctrl = check.widest_gaps(keep["params"], keep["arch"], keep["seqs"],
                             keep["arch"].serving["max_len"], keep["n_pos"],
                             control=CONTROL) if keep["seqs"] else []
    ctrl_correct, _, _ = run.verdict(ctrl, keep["short"], keep["seqs"],
                                     keep["limits"])
    row = {"seed": seed, "correct": out["correct"],
           "control_correct": ctrl_correct,
           "program_gap": max(keep["gaps"]) if keep["gaps"] else None,
           "control_gap": max(ctrl) if ctrl else None,
           "control_gap_min_request": min(ctrl) if ctrl else None,
           "tokens": sum(len(s[1]) for s in keep["seqs"]),
           "metrics": {k: v["value"] for k, v in out["metrics"].items()},
           "requests": len(keep["seqs"])}
    keep.clear()
    gc.collect()
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = run.load_benchmark(os.path.join(run.ROOT, "BENCHMARK.json"))
    passed = False
    for seed in (int(s) for s in args.seeds.split(",")):
        row = readings(args, bench, seed)
        passed |= row["control_correct"]
        print(json.dumps(row), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
