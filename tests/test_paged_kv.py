"""Paged KV cache: block-table kernels, page-pool engine, allocator.

Four layers of invariants:

* kernel -- the block-table paged kernels (dense + q8) match the
  gathered-page jnp oracle at ragged lengths and SHUFFLED page tables
  (physical page naming must be invisible to the math);
* model -- `lm_decode_step` over a paged cache is bitwise-equal to the
  dense fixed-lane cache, including sliding-window rotation past the
  window (the rotation lives in the block table now);
* engine -- the paged ServeEngine is token-exact vs the dense engine
  for greedy AND seeded temperature, dense AND int8 caches, and admits
  strictly more concurrent requests than ``n_lanes`` at short contexts;
* allocator -- admit/retire churn never leaks or double-frees pages,
  and over-commit rejects admission while a lane is still free.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention import (
    decode_attention_lengthaware_pallas, decode_attention_paged_pallas,
    decode_attention_paged_q8_pallas, decode_attention_paged_q8_ref,
    decode_attention_paged_ref, decode_attention_ref, gather_pages,
    kv_pages_fetched, quantize_kv_q8)
from repro.models import build_model
from repro.models.transformer import (init_cache, init_paged_cache,
                                      lm_decode_step)
from repro.serving import PagePool, Request, ServeEngine

pytestmark = pytest.mark.paged


# ----------------------------------------------------------------------
# kernel: block-table gather vs oracle
# ----------------------------------------------------------------------

def _shuffled_tables(b, t, n_pages, seed=0):
    """Disjoint, permuted page sets -- lanes never share physical pages
    and logical order is decoupled from physical order."""
    assert b * t <= n_pages
    perm = np.random.default_rng(seed).permutation(n_pages)[:b * t]
    return jnp.asarray(perm.reshape(b, t).astype(np.int32))


@pytest.mark.parametrize("h,hkv,d,ps,pool_dtype", [
    pytest.param(4, 4, 32, 32, jnp.float32, id="4-4"),
    pytest.param(8, 2, 32, 32, jnp.float32, id="8-2"),
    pytest.param(4, 1, 32, 32, jnp.float32, id="4-1"),
    # the served shapes: olmo-1b MHA 16:16, qwen2.5-1.5b GQA 12:2
    pytest.param(16, 16, 128, 64, jnp.bfloat16, id="olmo-ps64-bf16"),
    pytest.param(16, 16, 128, 64, jnp.float32, id="olmo-ps64-f32"),
    pytest.param(12, 2, 128, 64, jnp.bfloat16, id="qwen-ps64-bf16"),
    pytest.param(12, 2, 128, 64, jnp.float32, id="qwen-ps64-f32"),
    pytest.param(12, 2, 128, 16, jnp.bfloat16, id="qwen-ps16-bf16"),
    pytest.param(12, 2, 128, 16, jnp.float32, id="qwen-ps16-f32"),
])
def test_paged_kernel_matches_ref_ragged(h, hkv, d, ps, pool_dtype):
    b, t = 6, 8
    n_pages = b * t + 1                    # the last one is the scratch page
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, d))
    kp = jax.random.normal(jax.random.PRNGKey(1), (n_pages, hkv, ps, d),
                           pool_dtype)
    vp = jax.random.normal(jax.random.PRNGKey(2), (n_pages, hkv, ps, d),
                           pool_dtype)
    bt = _shuffled_tables(b, t, n_pages - 1)
    bt = bt.at[0].set(n_pages - 1)         # the dead lane maps the scratch page
    # ragged: dead lane, one key, sub-page, page-aligned, partial, full
    lens = jnp.array([0, 1, 7, 2 * ps, 4 * ps + 2, t * ps], jnp.int32)
    out = decode_attention_paged_pallas(q, kp, vp, bt, lens,
                                        interpret=True)
    ref = decode_attention_paged_ref(q, kp, vp, bt, lens)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5
    assert jnp.all(out[0] == 0.0)          # dead lane: no live keys
    # and against the pinned dense parity reference on the gathered view
    gk, gv = gather_pages(kp, bt), gather_pages(vp, bt)
    dense = decode_attention_lengthaware_pallas(q, gk, gv, lens, bk=ps,
                                                interpret=True)
    assert jnp.max(jnp.abs(out - dense)) < 2e-5


def test_paged_q8_kernel_matches_ref():
    b, h, hkv, d, ps, t, n_pages, qblock = 3, 4, 2, 32, 32, 4, 16, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (n_pages, hkv, ps, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (n_pages, hkv, ps, d))
    kq, ks = quantize_kv_q8(k, qblock=qblock)
    vq, vs = quantize_kv_q8(v, qblock=qblock)
    bt = _shuffled_tables(b, t, n_pages, seed=3)
    lens = jnp.array([0, 50, 128], jnp.int32)
    out = decode_attention_paged_q8_pallas(q, kq, ks, vq, vs, bt, lens,
                                           qblock=qblock, interpret=True)
    ref = decode_attention_paged_q8_ref(q, kq, ks, vq, vs, bt, lens,
                                        qblock=qblock)
    assert jnp.max(jnp.abs(out - ref)) < 2e-5


def test_kv_pages_fetched_contract():
    # the modeled fetch count BENCH_decode costs the paged section with
    pages = kv_pages_fetched(np.array([0, 1, 16, 17, 64, 200]), 4, 16)
    assert list(pages) == [1, 1, 1, 2, 4, 4]   # clamped at table width


# ----------------------------------------------------------------------
# model: paged cache == dense cache, bitwise
# ----------------------------------------------------------------------

@pytest.mark.parametrize("arch,kv_quant", [("qwen2.5-1.5b", None),
                                           ("qwen2.5-1.5b", "int8")])
def test_decode_step_paged_matches_dense(arch, kv_quant):
    cfg = get_config(arch, smoke=True)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=kv_quant)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, max_len, ps = 3, 32, 8
    dense = init_cache(cfg, B, max_len)
    paged = init_paged_cache(cfg, B, max_len, page_size=ps)
    t_w = paged["block_tables"].shape[1]
    paged["block_tables"] = _shuffled_tables(B, t_w, B * t_w, seed=1)
    toks = np.random.default_rng(2).integers(0, cfg.vocab_size, (B, 10),
                                             dtype=np.int32)
    for i in range(toks.shape[1]):
        ld, dense = lm_decode_step(params, cfg, dense,
                                   jnp.asarray(toks[:, i]))
        lp, paged = lm_decode_step(params, cfg, paged,
                                   jnp.asarray(toks[:, i]))
        assert jnp.array_equal(ld, lp), f"divergence at step {i}"


def test_window_rotation_in_block_table():
    """Sliding window as a FIXED page set rotated via the block table:
    decoding past the window stays bitwise-equal to the dense ring
    buffer (whose slot arithmetic is now the same ``pos % capacity``
    formula -- the rotation special case is gone)."""
    cfg = get_config("hymba-1.5b", smoke=True)     # window = 32
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, n = 2, cfg.sliding_window + 10              # exceed the window
    max_len = n + 6
    dense = init_cache(cfg, B, max_len)
    paged = init_paged_cache(cfg, B, max_len, page_size=8)
    t_w = paged["block_tables"].shape[1]
    assert t_w == cfg.sliding_window // 8          # fixed page set
    paged["block_tables"] = _shuffled_tables(B, t_w, B * t_w, seed=1)
    step_d = jax.jit(lambda c, t: lm_decode_step(params, cfg, c, t))
    step_p = jax.jit(lambda c, t: lm_decode_step(params, cfg, c, t))
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, (B, n),
                                             dtype=np.int32)
    for i in range(n):
        ld, dense = step_d(dense, jnp.asarray(toks[:, i]))
        lp, paged = step_p(paged, jnp.asarray(toks[:, i]))
    assert jnp.array_equal(ld, lp)


# ----------------------------------------------------------------------
# engine: token-exact parity + byte-proportional admission
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("qwen2.5-1.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
            for n in lens]


def _serve(cfg, params, prompts, max_new, **kw):
    reqs = [Request(uid=i, prompt=p.copy(), max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    eng = ServeEngine(cfg, params, **kw)
    eng.run(reqs)
    return [tuple(r.generated) for r in reqs], eng


@pytest.mark.parametrize("temperature", [0.0, 0.9])
@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_engine_paged_token_exact(small_model, temperature, kv_quant):
    cfg, params = small_model
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=kv_quant)
    prompts = _prompts(cfg, [5, 9, 6, 12, 7], seed=1)
    kw = dict(n_lanes=2, max_len=32, dispatch_n=4,
              temperature=temperature, rng_seed=7)
    dense, _ = _serve(cfg, params, prompts, 6, **kw)
    paged, eng = _serve(cfg, params, prompts, 6, paged=True, page_size=8,
                        **kw)
    assert dense == paged
    eng.pool.check()
    assert eng.pool.n_in_use == 0          # everything freed at the end


def test_admission_scales_with_bytes_not_lanes(small_model):
    """Pool sized to 2 dense lanes' KV memory; mean live context at a
    quarter of max_len -> strictly more than 2 concurrent requests."""
    cfg, params = small_model
    max_len, ps = 32, 8
    dense_lanes = 2
    pool = dense_lanes * (max_len // ps)           # 8 pages
    eng = ServeEngine(cfg, params, n_lanes=8, max_len=max_len,
                      dispatch_n=4, paged=True, page_size=ps,
                      n_pages=pool)
    admitted = 0
    for i, p in enumerate(_prompts(cfg, [4] * 12, seed=2)):
        if not eng.admit(Request(uid=i, prompt=p, max_new_tokens=3)):
            break
        admitted += 1                              # 4+3+1 = 1 page each
    assert admitted > dense_lanes
    assert admitted == min(8, pool)                # byte-bound, not lanes


def test_overcommit_rejected_then_recovers(small_model):
    """A free lane with an exhausted pool must NOT admit; pages freed at
    retirement make the same request admissible again."""
    cfg, params = small_model
    # pool = one full context: the second long request cannot fit
    eng = ServeEngine(cfg, params, n_lanes=2, max_len=32, dispatch_n=4,
                      paged=True, page_size=8, n_pages=4)
    p1, p2 = _prompts(cfg, [10, 10], seed=3)
    r1 = Request(uid=0, prompt=p1, max_new_tokens=12)   # 23 slots: 3+ pages
    r2 = Request(uid=1, prompt=p2, max_new_tokens=12)
    assert eng.admit(r1)
    assert eng.free_lanes()                    # a lane IS free...
    assert not eng.can_admit(r2)
    assert not eng.admit(r2)                   # ...but the bytes are not
    assert eng.stats["kv_admit_blocked"] == 1
    while not r1.done:
        eng.decode_n()
    assert eng.admit(r2)                       # retirement freed the pages
    eng.pool.check()


def test_allocator_churn_leak_free(small_model):
    """Admit/retire churn over many more requests than lanes: page
    conservation holds throughout, the pool drains to empty, and the
    high-water mark never exceeds the pool."""
    cfg, params = small_model
    pool = 6
    eng = ServeEngine(cfg, params, n_lanes=3, max_len=32, dispatch_n=4,
                      paged=True, page_size=8, n_pages=pool)
    rng = np.random.default_rng(4)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 3 + (i % 7),
                                        dtype=np.int32),
                    max_new_tokens=1 + (i % 5))
            for i in range(17)]
    pending = list(reqs)
    while pending or any(r is not None for r in eng.lane_req):
        while pending and eng.free_lanes():
            if not eng.admit(pending[0]):
                break
            pending.pop(0)
        eng.decode_n()
        eng.pool.check()                       # conservation every block
        assert eng.pool.hwm <= pool
    assert all(r.done for r in reqs)
    assert [len(r.generated) for r in reqs] == [1 + (i % 5)
                                               for i in range(17)]
    assert eng.pool.n_in_use == 0 and eng.pool.n_free == pool
    assert eng.pool.alloc_count == eng.pool.free_count > 0
    assert eng.stats["kv_pages_hwm"] <= pool


def test_pagepool_double_free_and_reservation_guards():
    pool = PagePool(4, 8)
    assert pool.reserve(3)
    pages = pool.alloc(2)
    assert not pool.reserve(2)                 # 2 free - 1 reserved < 2
    pool.free(pages)
    with pytest.raises(AssertionError):
        pool.free(pages)                       # double free
    with pytest.raises(AssertionError):
        pool.alloc(2)                          # exceeds reservation
    pool.unreserve(1)
    pool.check()


def test_engine_paged_hybrid_window(small_model):
    """Hybrid (attention + SSM) engine with a sliding window: paged run
    token-exact vs dense, exercising block-table rotation plus the
    scan-based SSM prompt prefill in one path."""
    cfg = get_config("hymba-1.5b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    prompts = _prompts(cfg, [6, 7, 5], seed=5)
    dense, _ = _serve(cfg, params, prompts, 4, n_lanes=2, max_len=32,
                      dispatch_n=4)
    paged, eng = _serve(cfg, params, prompts, 4, n_lanes=2, max_len=32,
                        dispatch_n=4, paged=True, page_size=8)
    assert dense == paged
    eng.pool.check()


def test_window_prompt_longer_than_window_scatter(small_model):
    """A prompt that WRAPS the sliding window must land at its ring
    slots (`slot = pos % window`) in the prefill scatter, so the decode
    step's ring write evicts the true oldest position -- regression
    test for the un-rotated scatter (dense and paged engines vs a pure
    ring decode-stream oracle)."""
    cfg, params = small_model
    cfg = dataclasses.replace(cfg, sliding_window=16)
    plen, max_new, max_len = 20, 4, 32          # prompt wraps the window
    prompt = _prompts(cfg, [plen], seed=8)[0]
    # oracle: stream everything through the ring decode step
    cache = init_cache(cfg, 1, max_len)
    step = jax.jit(lambda c, t: lm_decode_step(params, cfg, c, t))
    logits = None
    for t in prompt:
        logits, cache = step(cache, jnp.asarray([t], jnp.int32))
    tok, want = int(jnp.argmax(logits[0])), []
    for _ in range(max_new):
        logits, cache = step(cache, jnp.asarray([tok], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
        want.append(tok)
    for paged in (False, True):
        got, _ = _serve(cfg, params, [prompt], max_new, n_lanes=1,
                        max_len=max_len, dispatch_n=4, paged=paged,
                        page_size=8)
        assert list(got[0]) == want, f"paged={paged}"


def test_dead_lane_writes_cannot_corrupt_live_pages(small_model):
    """A lane that is idle (never admitted, or retired and not yet
    reused) still steps inside the jitted batch and writes its frozen
    slot THROUGH ITS BLOCK TABLE.  Those writes must land on the scratch
    page, never on a page the allocator re-issued to a live lane --
    regression test for the stale-table aliasing bug (3 lanes, 2
    requests: lane 2's zero-initialized table would alias page 0, which
    belongs to request 0)."""
    cfg, params = small_model
    prompts = _prompts(cfg, [9, 7], seed=7)
    kw = dict(n_lanes=3, max_len=32, dispatch_n=4)
    dense, _ = _serve(cfg, params, prompts, 10, **kw)
    paged, eng = _serve(cfg, params, prompts, 10, paged=True, page_size=8,
                        **kw)
    assert dense == paged
    eng.pool.check()


def test_execution_replay_reports_page_stats(small_model):
    """The trace replay surfaces page-pool pressure next to the token
    accounting: hwm > 0 for a paged replay, token counts identical to
    the fixed-lane replay (layout invariance)."""
    from repro.fleet.execution import run_trace_on_engine
    from repro.fleet.workload import FleetRequest

    cfg, params = small_model
    trace = [FleetRequest(uid=i, arrival_s=0.1 * i, prompt_len=5 + i,
                          gen_len=4) for i in range(5)]
    dense = run_trace_on_engine(trace, cfg, params, n_lanes=2, max_len=32,
                                dispatch_n=4)
    paged = run_trace_on_engine(trace, cfg, params, n_lanes=2, max_len=32,
                                dispatch_n=4, paged=True, page_size=8)
    assert paged.gen_by_uid == dense.gen_by_uid
    assert paged.kv_pages_hwm > 0
    assert dense.kv_pages_hwm == 0 and dense.kv_admit_blocked == 0


def test_ssm_prefill_scan_matches_eager(small_model):
    """The bucketed lax.scan prompt prefill (state-masked pads) must
    reproduce the eager one-dispatch-per-token stream: compare against
    a hand-rolled eager replay of the first request."""
    cfg = get_config("mamba2-780m", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = _prompts(cfg, [5, 6, 11], seed=6)
    served, eng = _serve(cfg, params, prompts, 5, n_lanes=3, max_len=32,
                         dispatch_n=4)
    # distinct buckets: 8 (len 5, 6) and 16 (len 11) -> two compiles
    assert eng.stats["ssm_prefill_compiles"] == 2
    # eager oracle for request 0: stream the prompt through decode_step
    cache = model.init_cache(params, 1, 32)
    step = jax.jit(lambda c, t: model.decode_step(params, c, t))
    logits = None
    for t in prompts[0]:
        logits, cache = step(cache, jnp.asarray([t], jnp.int32))
    tok = int(jnp.argmax(logits[0]))           # fed to decode, not emitted
    toks = []
    for _ in range(5):
        logits, cache = step(cache, jnp.asarray([tok], jnp.int32))
        tok = int(jnp.argmax(logits[0]))
        toks.append(tok)
    assert list(served[0]) == toks
