"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  It builds the cell's engine from its configuration file with
weights made from the seed, warms up every program shape the cell's
traffic uses, serves the traffic for ``--seconds`` (profiled in a slice
of the window with ``--trace 1``), lets the requests in flight run on
for a bounded time admitting nothing, compares a sample of the finished
answers with the plain reference, and prints one JSON line last on
standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` too when traced) and ``checks``, the numbers compared
with their limits, which also end standard error.

Everything about a cell is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/``, its traffic in
``bench/traffic/``, its limits in ``bench/limits/``, and each metric's
reader in ``bench/metrics/<metric>.py``.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

#: the traced slice: it opens this far into the window and lasts at
#: most TRACE_SECONDS (a trace of a whole window is large to write and
#: slow to read)
TRACE_START = 0.3
TRACE_SECONDS = 10.0
#: after the window the requests in flight run on (nothing is admitted)
#: for at most this long, so that the comparison has finished answers
DRAIN_SECONDS = 20.0


class NoChip(Exception):
    pass


def load_benchmark(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def reader(name: str, metrics_dir: str):
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCount:
    """Executables JAX builds while it is open, and how many of them it
    loaded from the persistent cache instead of compiling."""

    def __init__(self):
        self.built = self.loaded = 0
        self.on = False

    def _duration(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _event(self, event, **_):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    @property
    def compiled(self) -> int:
        return self.built - self.loaded


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d.platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def run_cell(args, bench: dict, *, require_tpu: bool = True,
             bench_dir: str = BENCH, patch=None, keep=None) -> dict:
    """One run of one cell; returns the result line as a dict.
    ``patch(engine)`` (tests) may break the engine before the window;
    ``keep`` (the control, tests) receives what the comparison used."""
    import jax

    from harness import (check, loop, model_config, peaks, record, system,
                         trace, traffic, warmup, weights)

    cell = cell_of(bench, args.workload)
    device = device_info(jax, cell["chips"], require_tpu)
    if require_tpu:
        system.setup_compilation_cache()
    counts = CompileCount().install()
    arch = model_config.load(os.path.join(bench_dir, "configs",
                                          f"{cell['config']}.json"))
    mix = traffic.generate(os.path.join(bench_dir, "traffic",
                                        f"{cell['traffic']}.json"),
                           args.seed, arch.vocab_size)
    peak = peaks.peaks(device["kind"]) if require_tpu else None
    with open(os.path.join(bench_dir, "limits", f"{cell['name']}.json")) as f:
        lim = json.load(f)

    params = weights.make_params(arch, args.seed)
    engine = system.build_engine(arch, params)
    warmup.warm(engine, mix, arch.serving["max_len"], uid0=1 << 30,
                seconds=args.seconds)
    if patch is not None:
        patch(engine)
    gc.collect()

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    prof = None
    if args.trace:
        start = TRACE_START * args.seconds
        prof = loop.Profiler(tdir, start,
                             min(start + TRACE_SECONDS, args.seconds))

    def on_open():
        engine.stats = {k: 0 for k in engine.stats}
        counts.on = True

    window = loop.serve_window(engine, mix, args.seconds, uid0=0,
                               profiler=prof, on_open=on_open)
    counts.on = False
    setup_s = window.opened_at - T_START
    counters = system.counters(engine)
    blocked = int(engine.stats["kv_admit_blocked"])
    loop.drain(engine, window, DRAIN_SECONDS)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    n_lanes, page_size = engine.n_lanes, engine.page_size
    del engine
    gc.collect()

    run = record.Run(arch=arch, window=window, counters=counters,
                     peak=peak, n_lanes=n_lanes, page_size=page_size)
    breakdown = None
    if args.trace:
        path = trace.find_xplane(tdir)
        if path is not None and window.trace_span is not None:
            run.reduced = trace.reduce(path)
            run.roles = trace.program_roles()
            run.offset = trace.host_offset(
                run.reduced, [d.start for d in run.traced_dispatches()])
            span = run.trace_span()
            device["busy_s"] = run.reduced.busy([span])
            device["window_s"] = span[1] - span[0]
            breakdown = {
                "device_ops": trace.top_programs(run.reduced, span,
                                                 run.roles),
                "idle_gaps": trace.idle_gaps(run.reduced, span)}
        shutil.rmtree(tdir, ignore_errors=True)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, cell["name"], kind):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = reader(m["name"], os.path.join(bench_dir, "metrics"))(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the comparison, once the window is closed and the engine freed
    done = window.finished()
    picked = check.sample(done, args.seed)
    seqs = [check.served(t, loop.first_token(t)) for t in picked]
    n_pos = max(it.output_len for it in mix.items) + 1
    gaps = check.widest_gaps(params, arch, seqs, arch.serving["max_len"],
                             n_pos) if seqs else []
    short = sum(len(t.req.generated) != t.output_len for t in done)
    correct, failed, checks = verdict(gaps, short, seqs, lim)
    info = {"compiled_in_window": counts.compiled,
            "loaded_in_window": counts.loaded,
            "sampled_requests": len(seqs),
            "finished": sum(t.done_at is not None and t.done_at >= 0
                            for t in window.tracked),
            "drained": sum(t.drained for t in window.tracked),
            "due": len(window.due()),
            "pages_live_peak": window.pages_live_peak,
            "admissions_blocked_on_pages": blocked}
    if keep is not None:
        keep.update(params=params, arch=arch, seqs=seqs, n_pos=n_pos,
                    gaps=gaps, window=window, short=short, limits=lim,
                    info=info)
    print("window: " + json.dumps(info), file=sys.stderr)
    out = {"correct": correct, "attempted": len(window.due()),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def verdict(gaps, short: int, seqs, lim: dict):
    """``correct``, the count of failed answers, and the numbers compared
    with their limits, from the widest logit gap of each compared
    sequence, the finished answers that came out short, and the cell's
    limits."""
    widest = max(gaps) if gaps else None       # nothing finished to compare
    checks = {
        "max_logit_gap": {"value": widest, "limit": lim["max_logit_gap"]},
        "short_answers": {"value": short, "limit": 0},
        "compared_tokens": {"value": sum(len(s[1]) for s in seqs),
                            "limit": lim["min_compared_tokens"]},
    }
    failed = sum(g > lim["max_logit_gap"] for g in gaps) + short
    correct = (widest is not None and widest <= lim["max_logit_gap"]
               and short == 0
               and checks["compared_tokens"]["value"]
               >= lim["min_compared_tokens"])
    return bool(correct), int(failed), checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark(os.path.join(ROOT, "BENCHMARK.json"))
    try:
        out = run_cell(args, bench)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
