"""Copy-on-write KV prefix sharing: radix prompt cache over the pool.

Four layers of invariants:

* allocator -- PagePool refcounts: ``share`` adds a holder, ``free``
  drops one (physical return only at the LAST drop), ``cow`` swaps a
  shared reference for a fresh exclusive page drawn from the caller's
  reservation, and every misuse (cow of an exclusive page, cow without
  a reservation, share of a free page) trips an assert;
* radix cache -- ``match`` returns the longest cached prefix in whole
  pages, always leaves >= 1 unmatched tail token, caps full pages at
  ``(plen - 1) // page_size``; eviction is LRU over LEAVES only (an
  interior page never outlives the prefixes extending it) and
  ``flush`` releases every cache reference;
* engine -- serving shared-prefix prompts with ``prefix_sharing=True``
  is bit-exact vs the non-shared paged engine for greedy AND seeded
  temperature, dense AND int8 KV; a consumer's divergent append onto a
  shared partial page copies-on-write without disturbing the donor;
  cache pages are trimmed (not leaked) under admission pressure; and a
  prefix-hit lane survives evict -> restore (same engine AND a fresh
  one) bit-identically -- shared pages are deep-copied at gather and
  re-anchored onto exclusive pages at restore;
* fleet -- the execution replay reproduces non-shared token counts over
  a ``shared_prefix_trace`` while reporting hits / pages saved, and
  preemption exactness holds with sharing enabled; the multi-model
  engine flushes a model's cache on weight unload (cold cache after
  reload, zero phantom page refs) without moving a token.
"""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving import PagePool, PrefixCache, Request, ServeEngine
from repro.serving.engine import prefix_sharing_supported

pytestmark = pytest.mark.prefix

PAGE = 8
ENGINE_KW = dict(n_lanes=2, max_len=32, dispatch_n=4, paged=True,
                 page_size=PAGE, rng_seed=7)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen2.5-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _head(cfg, n=2 * PAGE, seed=11):
    """A shared prompt head covering ``n // PAGE`` full pages."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, n, dtype=np.int32)


def _family(cfg, head, tail_lens, seed=12):
    """Prompts that OPEN with ``head`` and diverge into unique tails."""
    rng = np.random.default_rng(seed)
    return [np.concatenate(
                [head, rng.integers(0, cfg.vocab_size, t, dtype=np.int32)])
            for t in tail_lens]


def _reqs(prompts, max_new):
    return [Request(uid=i, prompt=p.copy(), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]


def _serve(cfg, params, prompts, max_new, **kw):
    reqs = _reqs(prompts, max_new)
    eng = ServeEngine(cfg, params, **kw)
    eng.run(reqs)
    return [tuple(r.generated) for r in reqs], eng


def _drain(*engines):
    for eng in engines:
        while eng.live_lanes():
            eng.decode_n()


def _flush_and_check_empty(*engines):
    """Release cache refs and pin the leak-free postcondition."""
    for eng in engines:
        if eng.prefix_cache is not None:
            eng.prefix_cache.flush()
        eng.pool.check()
        assert eng.pool.n_in_use == 0


# ----------------------------------------------------------------------
# allocator: refcounts, share / free / cow semantics
# ----------------------------------------------------------------------

def test_pagepool_refcount_share_free_cow():
    """A shared page returns to the free list only at the LAST drop,
    and cow exchanges a shared ref for a reserved exclusive page."""
    pool = PagePool(4, PAGE)
    assert pool.reserve(3)
    a, b = pool.alloc(2)
    assert pool.refcount(a) == pool.refcount(b) == 1
    assert not pool.is_shared(a)

    pool.share([a])                      # second holder (e.g. the cache)
    assert pool.refcount(a) == 2 and pool.is_shared(a)
    assert pool.n_refs == 3 and pool.n_shared == 1
    pool.free([a])                       # first drop: page stays in use
    assert pool.refcount(a) == 1 and a not in pool._free
    assert pool.n_in_use == 2

    pool.share([a])                      # re-share, then cow-split it
    new = pool.cow(a)                    # draws the remaining reservation
    assert new != a and pool.refcount(new) == 1
    assert pool.refcount(a) == 1         # caller's ref moved to `new`
    assert pool._reserved == 0
    pool.check()

    pool.free([a, b, new])               # last drops: physical returns
    assert pool.n_in_use == 0 and pool.n_free == pool.n_pages
    assert pool.cow_count == 1 and pool.share_count == 2


def test_pagepool_share_cow_guards():
    """Misuse trips asserts: share of a free page, cow of an exclusive
    page, cow without a reservation, and double physical free."""
    pool = PagePool(4, PAGE)
    assert pool.reserve(2)
    (p,) = pool.alloc(1)
    with pytest.raises(AssertionError):
        pool.share([p + 1])              # not allocated
    with pytest.raises(AssertionError):
        pool.cow(p)                      # refcount 1: nothing shared
    pool.share([p])
    pool.unreserve(1)
    with pytest.raises(AssertionError):
        pool.cow(p)                      # shared, but no reservation
    pool.free([p])
    pool.free([p])                       # drops the second holder
    with pytest.raises(AssertionError):
        pool.free([p])                   # page already free
    pool.check()


# ----------------------------------------------------------------------
# radix cache: match / insert / LRU-leaf eviction / flush
# ----------------------------------------------------------------------

def _cached_pages(pool, n):
    """Allocate ``n`` donor pages and hand their ONLY reference to the
    caller (mimics a prefilled lane about to be cached)."""
    assert pool.reserve(n)
    return pool.alloc(n)


def test_prefix_cache_match_caps_and_partial():
    """Full-page matches cap at ``(plen - 1) // PAGE`` (>= 1 tail token
    always re-runs), and a partial tail page only matches when it fits
    strictly inside the prompt."""
    pool = PagePool(8, PAGE)
    cache = PrefixCache(pool, PAGE)
    prompt = np.arange(20, dtype=np.int32)       # 2 full pages + 4 tail
    pages = _cached_pages(pool, 3)
    assert cache.insert(prompt, 20, pages) == 3  # 2 full + 1 partial
    pool.free(pages)                             # donor retires
    assert pool.n_in_use == 3                    # cache refs keep them

    # identical prompt: both full pages match, but its own partial tail
    # covers tokens [16, 20) and would leave NO tail token to re-run
    # (pos 16 + 4 > plen - 1 = 19), so it must NOT match
    got, matched, partial = cache.match(prompt)
    assert got == pages[:2] and partial is None
    assert matched == 16 <= len(prompt) - 1

    # exactly page-aligned prompt: the cap forfeits the last full page
    aligned = prompt[:16]
    got2, matched2, partial2 = cache.match(aligned)
    assert len(got2) == (16 - 1) // PAGE == 1
    assert matched2 <= 15 and partial2 is None

    # extension prompt: partial now fits inside plen - 1 and matches
    ext = np.concatenate([prompt, np.arange(100, 102, dtype=np.int32)])
    got3, matched3, partial3 = cache.match(ext)
    assert got3 == pages[:2] and partial3 == (pages[2], 4)
    assert matched3 == 20
    assert cache.hits >= 2 and cache.misses >= 0
    cache.flush()
    assert pool.n_in_use == 0


def test_prefix_cache_lru_leaf_eviction_and_flush():
    """Eviction drops the least-recently-matched LEAF: an interior page
    is never dropped while a cached prefix still extends it, and flush
    releases every reference the cache holds."""
    pool = PagePool(8, PAGE)
    cache = PrefixCache(pool, PAGE)
    chain = np.arange(17, dtype=np.int32)        # 2 full pages + 1 tail
    p_chain = _cached_pages(pool, 2)
    cache.insert(chain, 16, p_chain, allow_partial=False)
    other = np.arange(100, 109, dtype=np.int32)  # unrelated, 1 full page
    p_other = _cached_pages(pool, 1)
    cache.insert(other, 8, p_other, allow_partial=False)
    pool.free(p_chain + p_other)
    assert cache.n_pages == 3

    cache.match(chain)                           # chain is now MRU
    assert cache.evict_lru()                     # drops `other`'s leaf
    assert pool.n_in_use == 2
    assert cache.match(chain)[0] == p_chain      # chain intact
    assert cache.evict_lru()                     # leaf of the chain
    assert cache.match(chain)[0] == p_chain[:1]  # interior survives
    assert cache.evictions == 2
    assert cache.flush() == 1
    assert cache.n_pages == 0 and pool.n_in_use == 0
    assert not cache.evict_lru()                 # empty: nothing to drop


def test_prefix_cache_max_pages_budget():
    """A soft page budget evicts LRU leaves at insert time."""
    pool = PagePool(8, PAGE)
    cache = PrefixCache(pool, PAGE, max_pages=2)
    for fam in range(3):
        prompt = np.full(9, 100 * fam, dtype=np.int32)
        pages = _cached_pages(pool, 1)
        cache.insert(prompt, 8, pages, allow_partial=False)
        pool.free(pages)
        assert cache.n_pages <= 2
    assert cache.evictions >= 1
    cache.flush()
    assert pool.n_in_use == 0


# ----------------------------------------------------------------------
# engine: shared-prefix exactness, CoW, cache trim under pressure
# ----------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.9])
@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_engine_prefix_sharing_token_exact(small_model, temperature,
                                           kv_quant):
    """Serving a shared-prefix family with sharing on reproduces the
    non-shared engine bit for bit (greedy + temperature, dense + int8)
    while actually hitting the cache and saving pages."""
    cfg, params = small_model
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=kv_quant)
    head = _head(cfg)                            # 16 tokens, 2 pages
    prompts = _family(cfg, head, [4, 6, 8])      # plen 20 / 22 / 24
    prompts.append(_head(cfg, 12, seed=13))      # unrelated miss
    kw = dict(ENGINE_KW, temperature=temperature)

    base, beng = _serve(cfg, params, prompts, 6, **kw)
    shared, seng = _serve(cfg, params, prompts, 6, prefix_sharing=True,
                          **kw)
    assert shared == base
    assert seng.stats["prefix_hits"] >= 2        # two family followers
    assert seng.stats["prefix_pages_saved"] >= 2
    assert seng.stats["prefix_tokens_matched"] >= 2 * len(head)
    assert beng.stats["prefix_hits"] == 0        # sharing off: inert
    _flush_and_check_empty(beng, seng)


def test_engine_cow_on_divergent_append(small_model):
    """A consumer that maps the donor's partial tail page copies it on
    write: its stream AND the still-decoding donor's stream both match
    the non-shared run."""
    cfg, params = small_model
    head = _head(cfg)
    donor = _family(cfg, head, [4])[0]           # plen 20: 2 full + 4
    ext = np.concatenate(                        # donor prompt + 2 more
        [donor, np.array([3, 5], dtype=np.int32)])
    prompts = [donor, ext]

    base, _ = _serve(cfg, params, prompts, 8, **ENGINE_KW)
    shared, eng = _serve(cfg, params, prompts, 8, prefix_sharing=True,
                         **ENGINE_KW)
    assert shared == base
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_tokens_matched"] == 20   # 2 full + partial
    assert eng.stats["prefix_cow_copies"] >= 1        # divergent append
    _flush_and_check_empty(eng)


def _undonated(eng):
    """The engine's decode dispatch, jitted the same way but without
    donating the per-layer state."""
    eng._decode_n = jax.jit(
        functools.partial(eng._decode_n_fn, temperature=eng.temperature,
                          len_cap=eng.max_len - 1),
        static_argnames=("n_steps",))
    return eng


def _admit_cow_evict_retire(eng, donor, ext):
    """An admission, a copy-on-write admission over the donor's partial
    tail page, an evict/restore of that lane, then every lane retires."""
    dreq = Request(uid=0, prompt=donor.copy(), max_new_tokens=8)
    creq = Request(uid=1, prompt=ext.copy(), max_new_tokens=8)
    assert eng.admit(dreq)
    eng.decode_n()
    assert eng.admit(creq)
    eng.decode_n()
    lane = next(i for i, r in enumerate(eng.lane_req) if r is creq)
    ckpt = eng.evict(lane)
    eng.decode_n()
    assert eng.restore(ckpt)
    _drain(eng)
    assert eng.stats["prefix_cow_copies"] >= 1
    assert eng.stats["preemptions"] == eng.stats["restores"] == 1
    return [tuple(dreq.generated), tuple(creq.generated)]


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_decode_dispatch_donates_the_cache(small_model, temperature):
    """The decode dispatch updates the page pools in place: the arrays
    it was handed are deleted after it, and the engine's streams equal
    those of the same dispatch without donation, across an admission, a
    CoW, an evict/restore and the retirements."""
    cfg, params = small_model
    head = _head(cfg)
    donor = _family(cfg, head, [4])[0]           # plen 20: 2 full + 4
    ext = np.concatenate([donor, np.array([3, 5], dtype=np.int32)])
    kw = dict(ENGINE_KW, temperature=temperature, prefix_sharing=True)

    eng = ServeEngine(cfg, params, **kw)
    assert eng.admit(Request(uid=9, prompt=head.copy(), max_new_tokens=2))
    handed = {k: eng.cache[k] for k in ("k_pages", "v_pages")}
    eng.decode_n()
    assert all(x.is_deleted() for x in handed.values())
    assert not any(x.is_deleted() for x in eng.cache.values())
    _drain(eng)
    eng.prefix_cache.flush()

    donated = _admit_cow_evict_retire(ServeEngine(cfg, params, **kw),
                                      donor, ext)
    kept = _admit_cow_evict_retire(_undonated(ServeEngine(cfg, params,
                                                          **kw)),
                                   donor, ext)
    assert donated == kept
    base, _ = _serve(cfg, params, [donor, ext], 8, **dict(
        ENGINE_KW, temperature=temperature))
    assert donated == list(base)


def test_engine_trims_cache_under_admission_pressure(small_model):
    """When cached pages crowd the pool, admission trims the cache
    (LRU) instead of deadlocking -- every request completes and nothing
    leaks."""
    cfg, params = small_model
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, 9, dtype=np.int32)
               for _ in range(6)]                # 6 distinct families
    shared, eng = _serve(cfg, params, prompts, 4, prefix_sharing=True,
                         n_pages=8, **ENGINE_KW)
    assert all(len(s) == 4 for s in shared)
    assert eng.stats["prefix_evictions"] > 0
    assert eng.stats["kv_pages_hwm"] <= 8
    _flush_and_check_empty(eng)


def test_prefix_sharing_supported_predicate(small_model):
    """Sharing is attention-paged-only: sliding-window and recurrent
    families are refused by the predicate AND the constructor."""
    cfg, params = small_model
    assert prefix_sharing_supported(cfg)
    assert not prefix_sharing_supported(
        dataclasses.replace(cfg, sliding_window=16))
    assert not prefix_sharing_supported(
        dataclasses.replace(cfg, family="ssm"))
    assert not prefix_sharing_supported(
        dataclasses.replace(cfg, family="hybrid"))
    with pytest.raises(AssertionError):
        ServeEngine(dataclasses.replace(cfg, sliding_window=16), params,
                    prefix_sharing=True, **ENGINE_KW)


# ----------------------------------------------------------------------
# engine: evict / restore of a prefix-hit lane
# ----------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.9])
@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_evict_restore_prefix_hit_lane_cross_engine(small_model,
                                                    temperature,
                                                    kv_quant):
    """A lane admitted ON a cache hit (its head pages shared with the
    radix cache) is evicted mid-decode and restored on a FRESH engine:
    the stream must equal the unpreempted non-shared run -- the gather
    deep-copies shared pages, restore re-anchors them exclusively."""
    cfg, params = small_model
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=kv_quant)
    head = _head(cfg)
    donor, consumer = _family(cfg, head, [4, 6])
    kw = dict(ENGINE_KW, temperature=temperature)

    base, _ = _serve(cfg, params, [donor, consumer], 10, **kw)

    skw = dict(kw, prefix_sharing=True)
    src = ServeEngine(cfg, params, **skw)
    dreq = Request(uid=0, prompt=donor.copy(), max_new_tokens=10)
    src.run([dreq])                          # retire donor, warm cache
    creq = Request(uid=1, prompt=consumer.copy(), max_new_tokens=10)
    assert src.admit(creq)
    assert src.stats["prefix_hits"] == 1     # consumer rode the cache
    src.decode_n()                           # a few tokens in
    lane = next(i for i, r in enumerate(src.lane_req) if r is creq)
    ckpt = src.evict(lane)

    dst = ServeEngine(cfg, params, **skw)    # fresh board, cold cache
    assert dst.restore(ckpt)
    _drain(src, dst)

    assert [tuple(dreq.generated), tuple(creq.generated)] == list(base)
    assert dst.stats["pages_migrated"] == ckpt.n_pages > 0
    _flush_and_check_empty(src, dst)


def test_evict_restore_prefix_hit_lane_same_engine(small_model):
    """Same-engine evict -> restore of a prefix-hit lane while the
    donor pages stay pinned by the cache."""
    cfg, params = small_model
    head = _head(cfg)
    donor, consumer = _family(cfg, head, [4, 6])
    kw = dict(ENGINE_KW, temperature=0.9)

    base, _ = _serve(cfg, params, [donor, consumer], 10, **kw)

    eng = ServeEngine(cfg, params, prefix_sharing=True, **kw)
    dreq = Request(uid=0, prompt=donor.copy(), max_new_tokens=10)
    eng.run([dreq])
    creq = Request(uid=1, prompt=consumer.copy(), max_new_tokens=10)
    assert eng.admit(creq)
    eng.decode_n()
    lane = next(i for i, r in enumerate(eng.lane_req) if r is creq)
    ckpt = eng.evict(lane)
    eng.pool.check()                         # cache refs survive evict
    assert eng.restore(ckpt)
    _drain(eng)

    assert [tuple(dreq.generated), tuple(creq.generated)] == list(base)
    assert eng.stats["preemptions"] == eng.stats["restores"] == 1
    _flush_and_check_empty(eng)


# ----------------------------------------------------------------------
# fleet: replay + preemption exactness + multi-model cache invalidation
# ----------------------------------------------------------------------

def test_execution_replay_shared_prefix_trace(small_model):
    """The trace replay over a shared-prefix workload reproduces the
    non-shared token counts and surfaces hits / pages saved."""
    from repro.fleet.execution import run_trace_on_engine
    from repro.fleet.workload import FleetRequest, shared_prefix_trace

    cfg, params = small_model
    trace = shared_prefix_trace(
        [FleetRequest(uid=i, arrival_s=0.1 * i, prompt_len=18 + i % 3,
                      gen_len=4) for i in range(6)],
        prefix_len=2 * PAGE, n_prefixes=2, seed=1)
    kw = dict(n_lanes=2, max_len=32, dispatch_n=4, paged=True,
              page_size=PAGE)
    plain = run_trace_on_engine(trace, cfg, params, **kw)
    shared = run_trace_on_engine(trace, cfg, params,
                                 prefix_sharing=True, **kw)
    assert shared.gen_by_uid == plain.gen_by_uid
    assert shared.prefix_hits > 0 and shared.prefix_pages_saved > 0
    assert plain.prefix_hits == 0


def test_preemption_exactness_with_sharing(small_model):
    """Evict-and-replay churn over a shared-prefix trace must not move
    a token when both replays share cached prefixes."""
    from repro.fleet.execution import validate_preemption_exactness
    from repro.fleet.workload import FleetRequest, shared_prefix_trace

    cfg, params = small_model
    trace = shared_prefix_trace(
        [FleetRequest(uid=i, arrival_s=0.1 * i, prompt_len=18,
                      gen_len=6) for i in range(4)],
        prefix_len=2 * PAGE, n_prefixes=1, seed=2)
    result = validate_preemption_exactness(
        trace, cfg, params, preempt_every=1, prefix_sharing=True,
        n_lanes=2, max_len=32, dispatch_n=4, page_size=PAGE,
        temperature=0.8)
    assert result["resume_exact"], result["mismatches"]
    assert result["preemptions"] > 0


def test_modelpool_flushes_cache_on_unload(small_model):
    """Weight unload invalidates the model's radix cache (its pages
    index KV the outgoing weights computed): the page refs drop at
    unload, the reload starts cache-cold, and the full stream still
    equals one uninterrupted single-engine run."""
    from repro.serving import (ModelPool, MultiModelServeEngine,
                               kv_page_bytes, params_nbytes)

    cfg, params = small_model
    hbm = params_nbytes(params) + 12 * kv_page_bytes(cfg, PAGE)
    pool = ModelPool(hbm, page_size=PAGE)
    pool.register("a", cfg, params)
    mm_kw = dict(n_lanes=2, max_len=32, dispatch_n=4, rng_seed=7)
    mm = MultiModelServeEngine(pool, prefix_sharing=True, **mm_kw)

    head = _head(cfg)
    prompts = _family(cfg, head, [4, 6, 8])
    reqs = [Request(uid=i, prompt=p.copy(), max_new_tokens=5,
                    model_id="a") for i, p in enumerate(prompts)]
    mm.run(reqs[:2])
    eng = mm.engines["a"]
    assert eng.stats["prefix_hits"] >= 1
    assert eng.prefix_cache.n_pages > 0

    assert mm.unload("a")                    # flush + zero-ref assert
    assert "a" not in mm.engines

    mm.run([reqs[2]])                        # reload: cache starts cold
    eng2 = mm.engines["a"]
    assert eng2.stats["prefix_misses"] >= 1
    assert eng2.stats["prefix_hits"] == 0

    solo, _ = _serve(cfg, params, prompts, 5, **ENGINE_KW)
    assert [tuple(r.generated) for r in reqs] == list(solo)
    _flush_and_check_empty(*mm.engines.values())
