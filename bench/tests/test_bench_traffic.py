"""The traffic generator: one seed, one set of requests; every seed the
same sizes; each mix's sizes follow the parameters its file states."""

import os

import numpy as np
import pytest

from harness import traffic

MIXES = sorted(f[:-5] for f in os.listdir(traffic.TRAFFIC_DIR)
               if f.endswith(".json"))
VOCAB = 50304


def test_mixes_exist():
    assert {"shared-prefix", "decode-long", "chat"} <= set(MIXES)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a = traffic.generate(mix, 3_000_000_017, VOCAB)
    b = traffic.generate(mix, 3_000_000_017, VOCAB)
    assert [list(i.prompt) for i in a.items] == [list(i.prompt)
                                                 for i in b.items]
    assert a.gaps == b.gaps


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_change_tokens_not_work(mix):
    a = traffic.generate(mix, 1, VOCAB)
    b = traffic.generate(mix, 2, VOCAB)
    assert a.prompt_lens() == b.prompt_lens()
    assert [i.output_len for i in a.items] == [i.output_len for i in b.items]
    assert a.gaps == b.gaps
    assert any((x.prompt != y.prompt).any() for x, y in zip(a.items, b.items))


@pytest.mark.parametrize("mix", MIXES)
def test_first_tokens_unique(mix):
    t = traffic.generate(mix, 5, VOCAB)
    firsts = [int(i.prompt[0 if i.template < 0
                           else t.template_lens[i.template]])
              for i in t.items]
    firsts += [int(x) for x in t.reserved_first]
    assert len(set(firsts)) == len(firsts)


def _check_dist(x, d):
    x = np.asarray(x)
    assert x.min() >= d["low"] and x.max() <= d["high"]
    if d["dist"] == "lognormal":
        med = np.median(x)
        assert abs(med - d["median"]) <= 0.2 * d["median"], (med, d)
        # spread of log-lengths away from the clipped ends
        inner = x[(x > d["low"]) & (x < d["high"])]
        sig = np.std(np.log(inner))
        assert 0.6 * d["sigma"] <= sig <= 1.2 * d["sigma"], (sig, d)
    else:
        mean = 0.5 * (d["low"] + d["high"])
        assert abs(x.mean() - mean) <= 0.1 * mean


@pytest.mark.parametrize("mix", MIXES)
def test_sizes_follow_parameters(mix):
    p = traffic.load_params(mix)
    t = traffic.generate(mix, 9, VOCAB)
    _check_dist([i.unique_len for i in t.items], p["prompt"])
    if "max_total" not in p:
        _check_dist([i.output_len for i in t.items], p["output"])
    else:
        tot = [len(i.prompt) + i.output_len for i in t.items]
        assert max(tot) <= p["max_total"]
    if p.get("templates"):
        tp = p["templates"]
        _check_dist(t.template_lens, tp["length"])
        counts = np.bincount([i.template for i in t.items],
                             minlength=tp["count"])
        assert counts[0] == counts.max()        # Zipf: rank 1 leads
    arr = p["arrivals"]
    if arr["process"] == "poisson":
        assert abs(np.mean(t.gaps) * arr["rate_per_s"] - 1) < 0.2
        assert t.due_times()[0] == 0
    else:
        assert t.closed and t.clients == arr["clients"]


def test_zipf_and_lognormal_by_hand():
    rng = np.random.default_rng(0)
    picks = traffic.zipf_choice(rng, 8, 1.1, 200_000)
    w = 1 / np.arange(1, 9) ** 1.1
    assert np.allclose(np.bincount(picks) / 200_000, w / w.sum(), atol=0.005)
    x = traffic.draw(rng, {"dist": "lognormal", "median": 128, "sigma": 0.8,
                           "low": 1, "high": 10 ** 9}, 200_000)
    assert abs(np.median(x) - 128) <= 2
