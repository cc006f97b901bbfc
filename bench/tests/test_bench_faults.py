"""The comparison catches a broken timed path: each fault a serving cell
can have is planted under the engine, the rest of the run goes as
usual, and ``correct`` comes out false.  (The exchange between chips
does not exist in a one-chip cell.)"""

import argparse
import os
import shutil

import pytest

import run

TESTDATA = os.path.join(run.BENCH, "testdata")


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench")
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(os.path.join(TESTDATA, sub), d / sub)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), d / "metrics")
    return str(d)


def token_altered(engine):
    """Every dispatch's first served token of every lane is off by one."""
    orig = engine._decode_n
    vocab = engine.cfg.vocab_size

    def bad(*a, **k):
        out = list(orig(*a, **k))
        out[0] = out[0].at[0].set((out[0][0] + 1) % vocab)
        return tuple(out)
    engine._decode_n = bad


def state_unchanged(engine):
    """The decode step hands back the cache it was given: no key or
    value is written and no length advances."""
    orig = engine._decode_n

    def bad(params, cache, *a, **k):
        out = list(orig(params, cache, *a, **k))
        out[3] = cache
        return tuple(out)
    engine._decode_n = bad


def half_batch(engine):
    """The upper half of the lanes is left out of the step: their rows
    of logits stay zero, so each serves token 0."""
    orig = engine._decode_n
    half = engine.n_lanes // 2

    def bad(*a, **k):
        out = list(orig(*a, **k))
        out[0] = out[0].at[:, half:].set(0)
        return tuple(out)
    engine._decode_n = bad


def test_control_comes_out_not_correct(bench_dir):
    """The control (the reference at float8) in the program's place, judged
    by ``run.verdict`` with the cell's own limits, is not correct, where
    the program on the same sequences is."""
    import control
    b = run.load_benchmark(os.path.join(run.ROOT, "BENCHMARK.json"))
    b["workloads"] = [{"name": "tiny.closed", "config": "tiny",
                       "traffic": "tiny-closed", "chips": 1, "why": "t"}]
    ns = argparse.Namespace(workload="tiny.closed", seconds=3.0)
    row = control.readings(ns, b, 7, require_tpu=False, bench_dir=bench_dir)
    assert row["correct"] and not row["control_correct"], row


@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_batch])
def test_fault_fails_the_check(bench_dir, fault):
    b = run.load_benchmark(os.path.join(run.ROOT, "BENCHMARK.json"))
    # the closed mix keeps every lane busy, so the sample holds lanes of
    # both halves
    b["workloads"] = [{"name": "tiny.closed", "config": "tiny",
                       "traffic": "tiny-closed", "chips": 1, "why": "t"}]
    ns = argparse.Namespace(workload="tiny.closed", seed=5, seconds=3.0,
                            trace=0)
    out = run.run_cell(ns, b, require_tpu=False, bench_dir=bench_dir,
                       patch=fault)
    assert not out["correct"], out["checks"]
    c = out["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]
