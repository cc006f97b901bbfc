"""``run.py`` end to end at test size on the CPU: both kinds of cell,
the result line, the refusal without a chip, and a cell, a mix, a
configuration and a metric added by files alone."""

import argparse
import json
import os
import shutil

import pytest

import run

TESTDATA = os.path.join(run.BENCH, "testdata")
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def bench_for(cells, extra_metrics=()):
    b = run.load_benchmark(os.path.join(run.ROOT, "BENCHMARK.json"))
    b["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for n, c, t in cells]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    b["per_layer"] += list(extra_metrics)
    return b


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench")
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(os.path.join(TESTDATA, sub), d / sub)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), d / "metrics")
    return str(d)


def one(bench, bench_dir, cell, trace=0, seconds=3.0, **kw):
    ns = argparse.Namespace(workload=cell, seed=2 ** 31 + 17,
                            seconds=seconds, trace=trace)
    return run.run_cell(ns, bench, require_tpu=False, bench_dir=bench_dir,
                        **kw)


@pytest.mark.parametrize("cell,mix", [("tiny.shared", "tiny-shared"),
                                      ("tiny.closed", "tiny-closed")])
def test_cell_runs_and_is_correct(bench_dir, cell, mix):
    b = bench_for([(cell, "tiny", mix)])
    out = one(b, bench_dir, cell)
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    names = {m["name"] for m in b["end_to_end"]}
    assert set(out["metrics"]) <= names and "setup_s" in out["metrics"]
    assert out["metrics"]["tpot_p90_ms"]["value"] > 0
    assert out["checks"]["compared_tokens"]["value"] >= 16
    json.dumps(out)


def test_nothing_compiles_in_the_window(bench_dir):
    """The warm-in replays the window's requests, so it meets every
    program the window runs; its requests are set-up, and the window's
    metrics read none of them."""
    b = bench_for([("tiny.shared", "tiny", "tiny-shared")])
    keep = {}
    out = one(b, bench_dir, "tiny.shared", keep=keep)
    info, w = keep["info"], keep["window"]
    assert info["compiled_in_window"] == 0, info
    assert info["admissions_blocked_on_pages"] == 0, info
    assert all(0 <= t.due < w.seconds for t in w.due())
    assert any(t.due < 0 for t in w.tracked)
    assert out["attempted"] == len(w.due())
    assert out["metrics"]["setup_s"]["value"] > w.seconds


def test_warmup_admits_every_prompt_shape_the_window_can_send(bench_dir):
    """A mix with no warm-in: warm-up admits a prompt of each length the
    window can send (closed loop: two requests per client)."""
    from harness import model_config, system, traffic, warmup, weights
    arch = model_config.load(os.path.join(bench_dir, "configs", "tiny.json"))
    mix = traffic.generate(os.path.join(bench_dir, "traffic",
                                        "tiny-closed.json"), 3, arch.vocab_size)
    engine = system.build_engine(arch, weights.make_params(arch, 3))
    seen, admit = set(), engine.admit

    def logged(req):
        seen.add(len(req.prompt))
        return admit(req)
    engine.admit = logged
    warmup.warm(engine, mix, arch.serving["max_len"], uid0=1 << 30,
                seconds=3.0)
    want = {len(it.prompt) for it in mix.window_items(3.0)}
    assert len(mix.window_items(3.0)) == 2 * mix.clients
    assert want <= seen, want - seen
    assert not engine.live_lanes()


def test_traced_run_reports_per_layer(bench_dir):
    # the prefix cache's reader, kept for the qwen cells that wait on a
    # program fix (no cell in BENCHMARK.json reads it yet)
    share = {"name": "prefix_hit_token_share", "unit": "%",
             "better": "higher", "source": "program_counter",
             "layer": "prefix cache", "moves": "tpot_p90_ms"}
    b = bench_for([("tiny.shared", "tiny", "tiny-shared")], [share])
    out = one(b, bench_dir, "tiny.shared", trace=1)
    assert out["correct"]
    got = out["metrics"]
    assert got["prefix_hit_token_share"]["value"] > 50
    assert 0 < got["batch_occupancy"]["value"] <= 100
    # the CPU has no device plane: the trace readers find nothing and
    # say nothing, rather than 0
    assert "paged_attn_roofline" not in got and "decode_mfu" not in got
    assert "window_s" in out["device"] and "breakdown" in out


def test_refuses_without_a_chip(capsys):
    rc = run.main(["--workload", "olmo-1b.decode-long", "--seed",
                   "1", "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_a_new_cell_is_files_and_entries(bench_dir, tmp_path):
    """A configuration, a mix, a limit and a per-layer metric that the
    harness has never seen, added as files in a directory of their own
    plus entries in the benchmark's table: no file of the harness
    changes."""
    d = tmp_path / "bench"
    shutil.copytree(bench_dir, d)
    cfg = json.load(open(d / "configs" / "tiny.json"))
    cfg["name"] = "tiny-mha"
    cfg["num_key_value_heads"] = cfg["num_attention_heads"]
    json.dump(cfg, open(d / "configs" / "tiny-mha.json", "w"))
    mix = json.load(open(d / "traffic" / "tiny-closed.json"))
    mix["arrivals"] = {"process": "poisson", "rate_per_s": 6.0}
    json.dump(mix, open(d / "traffic" / "tiny-open.json", "w"))
    shutil.copy(d / "limits" / "tiny.closed.json",
                d / "limits" / "tiny-mha.open.json")
    (d / "metrics" / "finished_requests.py").write_text(
        "def read(run):\n"
        "    return float(sum(t.done_at is not None\n"
        "                     for t in run.window.tracked))\n")
    metric = {"name": "finished_requests", "unit": "requests",
              "better": "higher", "source": "host_clock",
              "layer": "scheduler", "moves": "tpot_p90_ms",
              "workloads": ["tiny-mha.open"]}
    b = bench_for([("tiny-mha.open", "tiny-mha", "tiny-open")], [metric])
    out = one(b, str(d), "tiny-mha.open", trace=1)
    assert out["correct"]
    assert out["metrics"]["finished_requests"]["value"] > 0
