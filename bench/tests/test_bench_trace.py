"""The trace reduction on a small trace recorded on a TPU v5e: three
rounds of a program named as a prefill and one holding the paged decode
kernel (named as the decode program), each inside its ``bench.*``
annotation."""

import os

import pytest

from harness import trace

PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace.reduce(PATH)


def test_planes_and_events(red):
    assert red.n_devices == 1
    names = [m[0] for m in red.modules]
    assert names.count("jit__decode_n_fn") == 3
    assert names.count("jit_prefill_fn") == 3
    assert len(red.kernels) == 3                  # one kernel per decode
    assert [h[0] for h in red.host] == ["bench.admit", "bench.dispatch"] * 3


def test_kernels_inside_the_decode_program(red):
    roles = trace.program_roles()
    assert "jit__decode_n_fn" in roles["decode"]
    span = (0.0, 1.0)
    decode = set(roles["decode"])
    kt = red.kernel_time_within(decode, span)
    assert kt == pytest.approx(sum(e - s for _, s, e in red.kernels))
    assert 0 < kt <= red.program_time(decode, span)
    assert red.kernel_time_within({"jit_prefill_fn"}, span) == 0


def test_busy_and_idle_add_up(red):
    s0 = min(s for _, s, _ in red.modules)
    s1 = max(e for _, _, e in red.modules)
    span = (s0, s1)
    busy = red.busy([span])
    assert busy == pytest.approx(sum(e - s for _, s, e in red.modules))
    gaps = trace.idle_gaps(red, span)
    assert busy + sum(t for _, t in gaps) == pytest.approx(s1 - s0)
    assert {n for n, _ in gaps} <= {"host:bench.admit", "host:bench.dispatch",
                                    "host:other"}


def test_breakdown_names_roles(red):
    span = (0.0, 1.0)
    top = trace.top_programs(red, span, trace.program_roles())
    names = [n for n, _ in top]
    assert "decode:jit__decode_n_fn" in names
    assert "prefill:jit_prefill_fn" in names
    assert any(n.startswith("kernel:") for n in names)
    assert all(t > 0 for _, t in top) and len(top) <= 10


def test_host_offset_matches_dispatches(red):
    starts = [s for s, _ in red.host_events("bench.dispatch")]
    assert trace.host_offset(red, [s - 5.0 for s in starts]) == \
        pytest.approx(5.0)
    assert trace.short_name("jit_f(123456)") == "jit_f"
