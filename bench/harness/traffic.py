"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its requests from a seed.

Every seed gets the same work.  The sizes (template lengths, which
template each request opens with, suffix and output lengths, the gaps
between arrivals) and their order are drawn once from the mix's own
``sizes_seed``; the run's seed draws only the token ids (and the
weights).  So two seeds differ in content, and not in what there is to
do or when.

An open-loop mix may ask for a warm-in (``"warm_in": true``): before
the window opens, the loop serves a replay of the window's own requests
(the same sizes and gaps, other tokens) for as long as the window lasts,
so that the window opens on an engine already at its working load, with
lanes live and a queue, and every prompt shape the window meets has
been met once.

Mix parameters::

  {"arrivals": {"process": "poisson", "rate_per_s": 0.4}
               | {"process": "closed", "clients": 48},
   "n_requests": 200, "sizes_seed": 1,
   "templates": {"count": 8, "length": <dist>, "zipf_s": 1.1},   # optional
   "prompt": <dist>,          # the unique part (after a template, if any)
   "output": <dist>,
   "max_total": 2032,         # optional cap on prompt + output
   "warm_in": true}           # optional, open loop only

  <dist> = {"dist": "uniform", "low": a, "high": b}
         | {"dist": "lognormal", "median": m, "sigma": s, "low": a, "high": b}

Lengths are whole tokens, clipped to ``[low, high]``.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional

import numpy as np

RESERVED_FIRST_TOKENS = 256

TRAFFIC_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "traffic")


@dataclasses.dataclass
class Item:
    """One request of the mix, before the run draws its tokens."""
    template: int          # -1: no shared template
    unique_len: int
    output_len: int
    prompt: Optional[np.ndarray] = None


@dataclasses.dataclass
class Traffic:
    name: str
    params: dict
    template_lens: List[int]
    items: List[Item]          # in the order the run sends them
    gaps: List[float]          # open loop: seconds between arrivals
    #: first tokens no request opens with, for warm-up prompts
    reserved_first: np.ndarray = None
    templates: List[np.ndarray] = dataclasses.field(default_factory=list)
    #: the warm-in's replay of ``items``: the same sizes, other tokens
    replay: List[Item] = dataclasses.field(default_factory=list)

    @property
    def closed(self) -> bool:
        return self.params["arrivals"]["process"] == "closed"

    @property
    def clients(self) -> int:
        return int(self.params["arrivals"]["clients"])

    def due_times(self) -> np.ndarray:
        """Open loop: when each item is due, from the window's start."""
        return np.cumsum(np.asarray(self.gaps)) - self.gaps[0]

    def prompt_lens(self) -> List[int]:
        return [len(i.prompt) for i in self.items]

    def lead(self, seconds: float) -> float:
        """Seconds the loop serves before the window opens."""
        return float(seconds) if self.replay else 0.0

    def schedule(self, seconds: float):
        """The items in the order the loop sends them and, open loop,
        when each falls due (seconds from the window's start: the
        warm-in's are negative); closed loop, None."""
        if self.closed:
            return list(self.items), None
        due = self.due_times()
        if not self.replay:
            return list(self.items), due
        n = int(np.searchsorted(due, seconds))
        return (self.replay[:n] + list(self.items),
                np.concatenate([due[:n] - seconds, due]))

    def window_items(self, seconds: float) -> List[Item]:
        """Items the window can send: open loop, those due in it; closed
        loop, one per client and one more per client."""
        if self.closed:
            return self.items[:2 * self.clients]
        return self.items[:int(np.searchsorted(self.due_times(), seconds))]


def load_params(name_or_path: str) -> dict:
    path = name_or_path if name_or_path.endswith(".json") else \
        os.path.join(TRAFFIC_DIR, f"{name_or_path}.json")
    with open(path) as f:
        return json.load(f)


def draw(rng: np.random.Generator, d: dict, n: int) -> np.ndarray:
    if d["dist"] == "uniform":
        x = rng.integers(d["low"], d["high"] + 1, n)
    elif d["dist"] == "lognormal":
        x = np.rint(d["median"] * np.exp(d["sigma"] * rng.standard_normal(n)))
    else:
        raise ValueError(f"unknown distribution {d['dist']!r}")
    return np.clip(x, d["low"], d["high"]).astype(np.int64)


def zipf_choice(rng: np.random.Generator, k: int, s: float, n: int):
    w = 1.0 / np.arange(1, k + 1) ** s
    return rng.choice(k, size=n, p=w / w.sum())


def sizes(params: dict):
    """The mix's fixed sizes: template lengths, items, arrival gaps."""
    rng = np.random.default_rng(params["sizes_seed"])
    n = int(params["n_requests"])
    tpl = params.get("templates")
    template_lens, which = [], np.full(n, -1)
    if tpl:
        template_lens = [int(x) for x in draw(rng, tpl["length"],
                                              tpl["count"])]
        which = zipf_choice(rng, tpl["count"], tpl["zipf_s"], n)
    uniq = draw(rng, params["prompt"], n)
    out = draw(rng, params["output"], n)
    cap = params.get("max_total")
    if cap:
        head = np.asarray([template_lens[w] if w >= 0 else 0
                           for w in which])
        out = np.minimum(out, cap - head - uniq)
        if (out < 1).any():
            raise ValueError("max_total leaves a request no output")
    arr = params["arrivals"]
    gaps = []
    if arr["process"] == "poisson":
        gaps = list(rng.exponential(1.0 / arr["rate_per_s"], n))
    elif arr["process"] != "closed":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    elif params.get("warm_in"):
        raise ValueError("a closed loop has no warm-in")
    items = [Item(int(w), int(u), int(o)) for w, u, o in zip(which, uniq, out)]
    return template_lens, items, gaps


def generate(name_or_path: str, seed: int, vocab_size: int) -> Traffic:
    params = load_params(name_or_path)
    template_lens, items, gaps = sizes(params)
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    replay = ([Item(it.template, it.unique_len, it.output_len)
               for it in items] if params.get("warm_in") else [])
    # every template and every unique part opens with a token no other
    # one opens with, so the prompt cache matches exactly the shared
    # templates and never a chance first token (``reserved`` more are
    # kept for warm-up prompts)
    firsts = iter(rng.choice(vocab_size, len(template_lens) + len(items)
                             + len(replay) + RESERVED_FIRST_TOKENS,
                             replace=False))
    templates = [_tokens(rng, n, next(firsts), vocab_size)
                 for n in template_lens]
    for it in items + replay:
        uniq = _tokens(rng, it.unique_len, next(firsts), vocab_size)
        it.prompt = (np.concatenate([templates[it.template], uniq])
                     if it.template >= 0 else uniq)
    reserved = np.asarray(list(firsts), np.int32)
    name = os.path.splitext(os.path.basename(name_or_path))[0]
    return Traffic(name, params, template_lens, items, gaps, reserved,
                   templates, replay)


def _tokens(rng, n: int, first: int, vocab_size: int) -> np.ndarray:
    out = rng.integers(0, vocab_size, n).astype(np.int32)
    out[0] = first
    return out
