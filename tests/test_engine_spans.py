"""The engine's spans at its host boundaries, on the profiler's clock.

* span tree -- a tiny paged engine over an admission, an admission the
  page pool refuses, and ``decode_n``: every boundary span is there,
  well nested, ``admit.reserve`` sits before ``admit`` and says ``ok``,
  and ``decode.retire`` covers the lane release;
* profiler round trip -- under ``jax.profiler`` on the CPU the engine's
  spans land in the trace as ``serve.*`` host events nested in the
  serving loop's own ``bench.dispatch``, with their ``uid`` / ``lane``
  / ``n_steps`` args, from a tracer that is not enabled; the sim-clock
  path writes none;
* counters -- on the benchmark's tiny closed-loop cell,
  ``decode.lane_steps`` gives the harness's ``batch_occupancy`` to the
  digit and ``kv.reserved_page_steps`` equals a sum by hand over the
  loop's dispatch records;
* neutrality -- token streams and compile counts are bit-identical
  under a profiler, with the tracer enabled or not, and the decode
  program keeps its name.
"""

import glob
import os
import statistics
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.obs import MetricsRegistry, SpanTracer
from repro.serving import Request, ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

pytestmark = pytest.mark.obs

DECODE_SPANS = ("decode.prepare", "decode.launch", "decode.wait")
# a request of 20 prompt + 20 answer tokens reserves 6 of the 8-token
# pages, so a second one does not fit in 10 until the first retires
ENGINE_KW = dict(n_lanes=2, max_len=64, dispatch_n=4, paged=True,
                 page_size=8, n_pages=10, prefix_sharing=True)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen2.5-1.5b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _reqs(cfg, lens, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, n,
                                               dtype=np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lens)]


def _inside(inner, outer) -> bool:
    return outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def test_span_tree_over_admit_refusal_and_decode(small_model):
    cfg, params = small_model
    tr = SpanTracer(enabled=True)
    eng = ServeEngine(cfg, params, tracer=tr, **ENGINE_KW)
    released = []
    release = eng._release_lane

    def logged_release(lane):
        released.append(tr.clock())
        release(lane)
    eng._release_lane = logged_release

    a, b = _reqs(cfg, [20, 20], max_new=20)
    assert eng.admit(a)
    assert not eng.admit(b)                     # pages, not lanes
    while eng.live_lanes():
        eng.decode_n()
    assert eng.admit(b)                         # a retired: b fits now
    while eng.live_lanes():
        eng.decode_n()

    assert tr.check_well_nested()
    names = {s.name for s in tr.spans}
    assert names >= {"admit.reserve", "admit", "prefill.bucket",
                     "prefill.launch", "prefill.scatter", "prefix.insert",
                     "decode.dispatch", "decode.retire", *DECODE_SPANS}

    # admission: reserve first, then admit; the refusal is a span too
    reserves = tr.spans_named("admit.reserve")
    assert [(s.args["uid"], s.args["ok"]) for s in reserves] \
        == [(0, True), (1, False), (1, True)]
    admits = tr.spans_named("admit")
    assert [s.args["uid"] for s in admits] == [0, 1]
    for adm, res in zip(admits, (reserves[0], reserves[2])):
        assert res.t1 <= adm.t0 and res.track == adm.track
        assert adm.args["lane"] == res.args["lane"]
    for bucket in tr.spans_named("prefill.bucket"):
        assert any(_inside(bucket, adm) for adm in admits)
    for name in ("prefill.launch", "prefill.scatter"):
        for sp in tr.spans_named(name):
            assert any(_inside(sp, bk)
                       for bk in tr.spans_named("prefill.bucket"))
    for sp in tr.spans_named("prefix.insert"):
        assert any(_inside(sp, adm) for adm in admits)

    # decode: prepare, launch and wait in order inside each dispatch,
    # retire right after it, on the engine's track
    dispatches = tr.spans_named("decode.dispatch")
    retires = tr.spans_named("decode.retire")
    assert len(dispatches) == len(retires) == eng.stats["decode_dispatches"]
    for name in DECODE_SPANS:
        assert len(tr.spans_named(name)) == len(dispatches)
    for i, disp in enumerate(dispatches):
        parts = [tr.spans_named(name)[i] for name in DECODE_SPANS]
        assert all(_inside(p, disp) and p.track == eng.name for p in parts)
        assert parts[0].t1 <= parts[1].t0 and parts[1].t1 <= parts[2].t0
        assert disp.t1 <= retires[i].t0
        assert retires[i].track == eng.name
    # the retire instants and the lane releases fall inside retire spans
    assert len(released) == 2
    for t in released + [e.t for e in tr.instants_named("retire")]:
        assert any(r.t0 <= t <= r.t1 for r in retires)


def _host_events(path):
    """(name, start s, end s, args) of every host-plane event."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns * 1e-9,
                                ev.end_ns * 1e-9, dict(ev.stats)))
    return out


def test_profiler_round_trip_nests_engine_spans(small_model, tmp_path,
                                                monkeypatch):
    from harness import trace

    cfg, params = small_model
    eng = ServeEngine(cfg, params, **ENGINE_KW)  # default: not enabled
    assert not eng.tracer.enabled
    reqs = _reqs(cfg, [12, 9], max_new=12, seed=1)
    for r in reqs:
        assert eng.admit(r)
    eng.decode_n()                               # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.live_lanes():
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                eng.decode_n()
        eng.tracer.add_span("sim.decode", 0.0, 1.0, track="node0", uid=7)
    finally:
        jax.profiler.stop_trace()
    assert eng.tracer.spans == []               # nothing kept in memory
    path = trace.find_xplane(str(tmp_path))
    assert path is not None

    # the harness's reduction keeps the engine's spans once its host
    # prefix names them
    monkeypatch.setattr(trace, "HOST_MARK", ("bench.", "serve."))
    red = trace.reduce(path)
    outer = red.host_events("bench.dispatch")
    assert outer
    by_name = {n: red.host_events(n) for n in
               ("serve.decode.dispatch", "serve.decode.retire",
                *(f"serve.{s}" for s in DECODE_SPANS))}
    for name, spans in by_name.items():
        assert len(spans) == len(outer), name
        for (s, e), (a, b) in zip(spans, outer):
            assert a <= s <= e <= b, name
    # the host time of a dispatch boundary: prepare + launch + retire
    host_ms = [1e3 * sum(e - s for s, e in (by_name[f"serve.{k}"][i]
                                            for k in ("decode.prepare",
                                                      "decode.launch",
                                                      "decode.retire")))
               for i in range(len(outer))]
    assert statistics.median(host_ms) > 0

    events = _host_events(path)
    disp = [ev for ev in events if ev[0] == "serve.decode.dispatch"]
    assert all(ev[3].get("n_steps") == ENGINE_KW["dispatch_n"]
               for ev in disp)
    assert not any(ev[0].endswith("sim.decode") for ev in events)


def test_counters_give_the_harness_readings_on_the_tiny_cell():
    from harness import loop, model_config, record, system, traffic, weights
    import run

    testdata = os.path.join(BENCH, "testdata")
    arch = model_config.load(os.path.join(testdata, "configs", "tiny.json"))
    mix = traffic.generate(os.path.join(testdata, "traffic",
                                        "tiny-closed.json"),
                           2 ** 31 + 29, arch.vocab_size)
    engine = system.build_engine(arch, weights.make_params(arch, 5))

    def on_open():
        engine.stats = {k: 0 for k in engine.stats}

    window = loop.serve_window(engine, mix, 3.0, on_open=on_open)
    stats = dict(engine.stats)
    assert stats["decode_dispatches"] == len(window.dispatches) > 0
    assert stats["prefix_hits"] == 0            # every prompt is distinct

    r = record.Run(arch=arch, window=window, counters={}, peak=None,
                   n_lanes=engine.n_lanes, page_size=engine.page_size)
    occupancy = run.reader("batch_occupancy",
                           os.path.join(BENCH, "metrics"))(r)
    engine_occupancy = (100.0 * stats["decode_lane_steps"]
                        / (stats["decode_steps"] * engine.n_lanes))
    assert engine_occupancy == pytest.approx(occupancy, abs=1e-9)
    assert 0 < engine_occupancy < 100

    # after a dispatch's pages are mapped, a lane at context c owing r
    # tokens holds P(c + min(n, r) + 1) of the P(c + r + 1) pages it
    # reserved at admission (its prompt plus its whole answer)
    ps, cap = engine.page_size, engine.max_len

    def pages(positions):
        return min(-(-positions // ps), cap // ps)

    by_hand = sum(
        d.n_steps * sum(pages(min(c + rem + 1, cap))
                        - pages(c + min(d.n_steps, rem) + 1)
                        for c, rem in zip(d.ctx, d.remaining))
        for d in window.dispatches)
    assert stats["kv_reserved_page_steps"] == by_hand > 0


@pytest.mark.parametrize("enabled", [False, True])
def test_streams_identical_under_a_profiler(small_model, enabled, tmp_path):
    """``tests/test_obs.py`` pins the tracer on against off; a running
    profiler, which records every annotation, changes nothing either."""
    cfg, params = small_model

    def serve(tracer):
        eng = ServeEngine(cfg, params, tracer=tracer, **ENGINE_KW)
        reqs = _reqs(cfg, [20, 7, 13, 20], max_new=14, seed=2)
        eng.run(reqs)
        return eng, [tuple(r.generated) for r in reqs]

    ref_eng, ref = serve(None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, out = serve(SpanTracer(enabled=enabled,
                                    registry=MetricsRegistry()))
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert out == ref
    for k in ("prefill_compiles", "ssm_prefill_compiles",
              "decode_compiles", "decode_lane_steps",
              "kv_reserved_page_steps"):
        assert eng.stats[k] == ref_eng.stats[k], k
    # the decode program keeps its name (the benchmark finds it by it)
    assert "jit__unknown" in eng.decode_program_text().split("\n", 1)[0]
