"""What the harness takes from the program: the configuration it builds
(with the norm epsilon where the program has a field for it), every
counter and gauge of the engine's registry, the engine's own spans in
the trace, and the two readers that use them."""

import dataclasses
import importlib.util
import os
import types

import pytest

from harness import model_config, record, system, trace
from harness.loop import Dispatch, Window
from repro.obs.metrics import MetricsRegistry

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name}", os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the configuration ----------------------------------------------------

@pytest.mark.parametrize("name", ["olmo-1b", "qwen2.5-1.5b"])
def test_program_config_is_the_one_built_before(name):
    """With a ``ModelConfig`` that has no ``norm_eps`` field, the program
    gets exactly the configuration it always got."""
    arch = model_config.load(name)
    old = system.ModelConfig(
        name=arch.name, family="dense", n_layers=arch.n_layers,
        d_model=arch.d_model, n_heads=arch.n_heads,
        n_kv_heads=arch.n_kv_heads, d_ff=arch.d_ff,
        vocab_size=arch.vocab_size, head_dim=arch.head_dim_override,
        qkv_bias=arch.qkv_bias, norm=arch.norm,
        tie_embeddings=arch.tie_embeddings, rope_theta=arch.rope_theta,
        max_seq_len=arch.max_position_embeddings,
        dtype=arch.compute_dtype)
    assert "norm_eps" not in {
        f.name for f in dataclasses.fields(system.ModelConfig)}
    assert system.program_config(arch) == old


def test_program_config_passes_the_epsilon_to_a_field_for_it(monkeypatch):
    @dataclasses.dataclass(frozen=True)
    class WithEps(system.ModelConfig):
        norm_eps: float = 1e-5

    monkeypatch.setattr(system, "ModelConfig", WithEps)
    qwen = system.program_config(model_config.load("qwen2.5-1.5b"))
    olmo = system.program_config(model_config.load("olmo-1b"))
    assert isinstance(qwen, WithEps)
    assert qwen.norm_eps == 1e-6 and olmo.norm_eps == 1e-5
    assert qwen.n_kv_heads == 2 and qwen.qkv_bias


# -- counters --------------------------------------------------------------

def test_counters_are_every_counter_and_gauge_of_the_engine():
    reg = MetricsRegistry()
    reg.counter("serve.decode.steps").set(40)
    reg.counter("serve.kv.reserved_page_steps").set(1200)
    reg.counter("serve.new.counter").set(3)
    reg.gauge("serve.pool.pages.free", fn=lambda: 17)
    reg.gauge("serve.set.gauge").set(2.5)
    reg.histogram("serve.span.seconds").observe(0.1)
    reg.counter("other.decode.steps").set(99)
    engine = types.SimpleNamespace(name="serve", registry=reg)
    assert system.counters(engine) == {
        "decode.steps": 40, "kv.reserved_page_steps": 1200,
        "new.counter": 3, "pool.pages.free": 17, "set.gauge": 2.5}


def test_counters_of_a_program_without_a_name():
    reg = MetricsRegistry()
    reg.counter("serve.decode.steps").set(40)
    got = system.counters(types.SimpleNamespace(name="serve", registry=reg))
    assert got == {"decode.steps": 40}
    assert reader("kv_reserved_idle_share")(run_with(got)) is None


# -- the engine's spans in the trace --------------------------------------

def nested_trace():
    """Two dispatches, the engine's spans nested in the harness's, and
    an admission between them; the device idles from 1.0 to 1.2 s (the
    host retiring the first dispatch) and from 1.3 to 1.6 s (preparing
    the second)."""
    host = [("bench.dispatch", 0.0, 1.18),
            ("serve.decode.dispatch", 0.0, 1.17),
            ("serve.decode.prepare", 0.0, 0.004),
            ("serve.decode.launch", 0.004, 0.005),
            ("serve.decode.wait", 0.01, 1.0),
            ("serve.decode.retire", 1.02, 1.16),
            ("bench.admit", 1.19, 1.35),
            ("serve.prefill.launch", 1.2, 1.21),
            ("bench.dispatch", 1.4, 2.1),
            ("serve.decode.dispatch", 1.4, 2.05),
            ("serve.decode.prepare", 1.41, 1.55),
            ("serve.decode.launch", 1.55, 1.56),
            ("serve.decode.wait", 1.56, 2.0),
            ("serve.decode.retire", 2.0, 2.03)]
    modules = [("jit__unknown", 0.0, 1.0), ("jit_prefill_fn", 1.2, 1.3),
               ("jit__unknown", 1.6, 2.0)]
    return trace.Reduced(modules, [], sorted(host, key=lambda h: h[1]), 1)


def test_idle_gaps_name_the_innermost_span():
    red = nested_trace()
    gaps = dict(trace.idle_gaps(red, (0.0, 2.0)))
    assert gaps == pytest.approx({"host:serve.decode.prepare": 0.3,
                                  "host:serve.decode.retire": 0.2})
    outer = dataclasses.replace(
        red, host=[h for h in red.host if h[0].startswith("bench.")])
    assert dict(trace.idle_gaps(outer, (0.0, 2.0))) == pytest.approx(
        {"host:bench.dispatch": 0.5})


def test_host_offset_reads_only_the_harness_dispatches():
    red = nested_trace()
    assert trace.HOST_MARK == ("bench.", "serve.")
    assert trace.host_offset(red, [-5.0, -3.6]) == pytest.approx(5.0)


# -- the two readers -------------------------------------------------------

def run_with(counters, reduced=None, offset=0.0):
    """A run of two dispatches inside the traced slice and one after it,
    on a pool of 100 pages; the harness's clock is ``offset`` behind the
    trace's."""
    disp = [Dispatch(s - offset, e - offset, 8, [0, 1], [10, 20], [8, 8])
            for s, e in ((0.0, 1.18), (1.4, 2.1), (2.2, 2.5))]
    span = (0.0 - offset, 2.15 - offset) if reduced is not None else None
    window = Window(seconds=3.0, elapsed=2.5, tracked=[], dispatches=disp,
                    live_intervals=[(0.0, 2.5)], trace_span=span)
    arch = types.SimpleNamespace(serving={"n_pages": 100})
    return record.Run(arch=arch, window=window, counters=counters,
                      peak=None, n_lanes=2, page_size=16, reduced=reduced,
                      offset=offset, roles={"decode": ["jit__unknown"]})


def test_dispatch_host_ms_is_the_mean_over_traced_dispatches():
    read = reader("dispatch_host_ms")
    # (4 + 1 + 140) ms of the first dispatch, (140 + 10 + 30) of the
    # second, over the two dispatches inside the slice; an engine span
    # outside the slice is not the slice's
    red = nested_trace()
    red.host.append(("serve.decode.prepare", 2.2, 2.3))
    assert read(run_with({}, red)) == pytest.approx((145 + 180) / 2)
    # the same on a harness clock 5 s behind the trace's
    assert read(run_with({}, red, offset=5.0)) == \
        pytest.approx((145 + 180) / 2)
    assert read(run_with({})) is None
    bare = dataclasses.replace(
        red, host=[h for h in red.host if h[0].startswith("bench.")])
    assert read(run_with({}, bare)) is None


def test_kv_reserved_idle_share_by_hand():
    read = reader("kv_reserved_idle_share")
    # 1,200 reserved page-steps over 40 steps of a 100-page pool
    assert read(run_with({"decode.steps": 40,
                          "kv.reserved_page_steps": 1200})) == \
        pytest.approx(30.0)
    assert read(run_with({"decode.steps": 0,
                          "kv.reserved_page_steps": 0})) is None
    assert read(run_with({"decode.steps": 40,
                          "kv.reserved_page_steps": 0})) == 0.0
