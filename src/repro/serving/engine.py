"""Serving engine: continuous batching over a fixed-lane or paged KV cache.

The paper's deployment target is single-board LLM inference; this engine
is the framework-scale version: a continuous batcher (vLLM-style) in
front of the model zoo's prefill/decode functions.

Two cache layouts:

* **fixed-lane** (default, the pinned parity reference): the cache is
  partitioned as ``n_lanes x max_len`` at construction -- admission
  capacity is lanes, independent of live context;
* **paged** (``paged=True``): KV lives in a global page pool governed by
  :class:`PagePool`; each lane holds a block table of page ids.  Pages
  are allocated at admission (prompt) and at dispatch boundaries
  (generation growth), freed at retirement, and admission is gated on
  free PAGES, not free lanes -- a board's concurrency becomes
  proportional to actual KV bytes, which is the §6.2 economics argument
  (1.5 TB/s HBM decode engine, capacity-constrained).  Lane reuse is
  copy-free: re-admission just rewrites the lane's block-table row.

The decode hot path is host-sync-free:

* ``prefill`` pads prompts to power-of-two buckets (one XLA compile per
  bucket, not per prompt length) and scatters the prompt KV into a free
  lane (or its pages);
* ``decode_n`` advances every lane ``dispatch_n`` tokens per Python
  dispatch via a jitted ``lax.scan``: sampling (greedy or temperature)
  happens on device, tokens and done-flags accumulate on device, and one
  host transfer drains the block;
* lane retirement/admission (and page mapping) happens only at dispatch
  boundaries;
* weights can be stored block-quantized (``quantize_params``): the
  bandwidth saving is what the paper's decode evaluation is about.

Sampling keys fold from (request admission index, per-request token
index), so a request's generated stream -- greedy or temperature -- is
invariant to dispatch granularity, admission timing, lane neighbors,
and cache layout (paged vs dense is token-exact).
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.invariants import invariant
from repro.analysis.sanitizer import PageSanitizer
from repro.models.common import ModelConfig
from repro.models.registry import Model, build_model
from repro.models.transformer import (CACHE_SHARED_KEYS, PAGED_POOL_KEYS,
                                      init_cache, init_paged_cache,
                                      lm_prefill_batched, paged_capacity,
                                      sample_tokens)
from repro.obs.flight import FlightRecorder, flight_guard
from repro.obs.metrics import MetricsRegistry, StatsView
from repro.obs.trace import SpanTracer
from repro.quant.quantize import QTensor, dequantize, quantize
from repro.serving.prefix_cache import PrefixCache
from repro.serving.resilience import AdmissionRejected, DegradationLadder


# ----------------------------------------------------------------------
# weight quantization store
# ----------------------------------------------------------------------

def quantize_params(params, fmt: str, min_size: int = 1 << 16):
    """Quantize every >=2-D weight whose k-dim divides the block size.

    Returns (q_params, stats).  Weights that cannot be block-quantized
    (small, odd shapes) stay dense -- same policy as llama.cpp, which
    keeps norms/embeddings in high precision for Q formats.
    """
    from repro.quant.formats import get_format
    blk = get_format(fmt).block
    n_q = n_dense = bytes_q = bytes_dense = 0

    def leaf(path, x):
        nonlocal n_q, n_dense, bytes_q, bytes_dense
        if (x.ndim == 2 and x.size >= min_size and x.shape[0] % blk == 0):
            qt = quantize(x, fmt)
            n_q += 1
            bytes_q += qt.nbytes()
            return qt
        n_dense += 1
        bytes_dense += x.size * x.dtype.itemsize
        return x

    qp = jax.tree_util.tree_map_with_path(leaf, params)
    stats = {"quantized": n_q, "dense": n_dense,
             "quantized_bytes": bytes_q, "dense_bytes": bytes_dense}
    return qp, stats


def dequantize_params(q_params):
    """Materialize dense weights (carrying the quantization error)."""
    return jax.tree_util.tree_map(
        lambda x: dequantize(x) if isinstance(x, QTensor) else x,
        q_params, is_leaf=lambda x: isinstance(x, QTensor))


# ----------------------------------------------------------------------
# page-pool allocator
# ----------------------------------------------------------------------

class PagePool:
    """Host-side free-list allocator over the global KV page pool.

    Invariants (pinned by the allocator-churn tests):

    * conservation -- ``n_free + n_in_use == n_pages`` at all times;
    * no double-free / no double-alloc -- page ids move between exactly
      two disjoint sets;
    * refcounting -- every in-use page carries a refcount >= 1 (one per
      holder: each mapping lane, plus the prefix cache when it caches
      the page).  ``share`` adds a holder, ``free`` drops one; the page
      returns to the free list only when the LAST holder lets go, so a
      retiring lane can never free a page another lane still maps;
    * reservation safety -- ``reserve(n)`` promises ``n`` future
      ``alloc`` pages; ``available()`` (what admission gates on) never
      counts pages already promised to admitted requests, so a lane's
      mid-generation growth (and its copy-on-write split of a shared
      page, which draws on the same reservation) cannot fail;
    * zero fragmentation by construction -- pages are an unordered pool
      (the block table supplies ordering), so any free page serves any
      request: the free list can never be "too fragmented to admit";
    * capacity elasticity -- ``shrink(n)`` retires up to ``n`` FREE
      (and unpromised) pages into a disabled set and ``grow(n)``
      returns them: the multi-model pool trades KV pages for weight
      residency without ever touching a page a lane holds or was
      promised.  A shared page is in-use like any other: sharing pins
      pages against shrink exactly as a live lane does.
    """

    def __init__(self, n_pages: int, page_size: int):
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._in_use: set = set()
        self._refcount: Dict[int, int] = {}
        self._disabled: List[int] = []
        self._reserved = 0
        self.hwm = 0                 # high-water mark: in-use + reserved
        self.alloc_count = 0
        self.free_count = 0
        self.share_count = 0
        self.cow_count = 0
        # optional lifecycle monitor (repro.analysis.sanitizer): every
        # mutator forwards its op through ONE attribute check -- the
        # entire cost of running unsanitized
        self.monitor = None

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_in_use(self) -> int:
        return len(self._in_use)

    @property
    def n_disabled(self) -> int:
        return len(self._disabled)

    @property
    def n_active(self) -> int:
        """Pages currently part of the pool (physical minus disabled)."""
        return self.n_pages - len(self._disabled)

    @property
    def n_reserved(self) -> int:
        """Pages promised to admitted requests and not yet allocated."""
        return self._reserved

    def available(self) -> int:
        """Pages admissible to NEW requests (free minus promised)."""
        return len(self._free) - self._reserved

    def reserve(self, n: int) -> bool:
        """Promise ``n`` pages to a request; False if over-committed."""
        ok = n <= self.available()
        if ok:
            self._reserved += n
            self.hwm = max(self.hwm, self.n_in_use + self._reserved)
        m = self.monitor
        if m is not None:
            m.record("reserve", n=n, ok=ok)
        return ok

    def unreserve(self, n: int) -> None:
        invariant(0 <= n <= self._reserved,
                  "unreserve exceeds reservation",
                  n=n, reserved=self._reserved)
        self._reserved -= n
        m = self.monitor
        if m is not None:
            m.record("unreserve", n=n)

    def alloc(self, n: int, holder: Any = None) -> List[int]:
        """Take ``n`` previously reserved pages off the free list.
        ``holder`` is an opaque owner tag (a lane index, the prefix
        cache ...) forwarded to the lifecycle monitor when one is
        attached."""
        invariant(n <= self._reserved, "alloc without reservation",
                  n=n, reserved=self._reserved)
        invariant(n <= len(self._free), "free list underflow",
                  n=n, n_free=len(self._free))
        self._reserved -= n
        pages = [self._free.pop() for _ in range(n)]
        self._in_use.update(pages)
        for p in pages:
            self._refcount[p] = 1
        self.alloc_count += n
        m = self.monitor
        if m is not None:
            m.record("alloc", pages=list(pages), holder=holder)
        return pages

    def free(self, pages: List[int], holder: Any = None) -> None:
        """Drop one reference per page; a page returns to the free list
        only when its LAST holder releases it (``free_count`` counts
        physical returns, not reference drops)."""
        for p in pages:
            invariant(p in self._in_use, f"double free of page {p}",
                      page=p)
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                del self._refcount[p]
                self._in_use.remove(p)
                self._free.append(p)
                self.free_count += 1
        m = self.monitor
        if m is not None and pages:
            m.record("free", pages=list(pages), holder=holder)

    def share(self, pages: List[int], holder: Any = None) -> None:
        """Add one reference per page: a second holder (another lane's
        block table, or the prefix cache) now maps the same bytes."""
        for p in pages:
            invariant(p in self._in_use,
                      f"share of unallocated page {p}", page=p)
            self._refcount[p] += 1
        self.share_count += len(pages)
        m = self.monitor
        if m is not None and pages:
            m.record("share", pages=list(pages), holder=holder)

    def cow(self, page: int, holder: Any = None) -> int:
        """Copy-on-write split: the caller gives up its reference on a
        SHARED ``page`` and receives a fresh exclusive page in exchange,
        drawn from its admission-time reservation (which is sized for
        the lane's full footprint, so the split cannot fail mid-flight).
        The caller copies the page contents and rewrites its block-table
        entry; the other holders keep the original."""
        invariant(page in self._in_use,
                  f"cow of unallocated page {page}", page=page)
        invariant(self._refcount[page] >= 2,
                  "cow of an exclusively owned page", page=page,
                  refcount=self._refcount[page])
        invariant(self._reserved >= 1, "cow without a reservation",
                  page=page)
        self._reserved -= 1
        new = self._free.pop()
        self._in_use.add(new)
        self._refcount[new] = 1
        self._refcount[page] -= 1
        self.alloc_count += 1
        self.cow_count += 1
        m = self.monitor
        if m is not None:
            m.record("cow", old=page, new=new, holder=holder)
        return new

    def refcount(self, page: int) -> int:
        """Holders of ``page`` (0 if free/disabled)."""
        return self._refcount.get(page, 0)

    def is_shared(self, page: int) -> bool:
        return self._refcount.get(page, 0) >= 2

    @property
    def n_shared(self) -> int:
        """In-use pages with more than one holder."""
        return sum(1 for c in self._refcount.values() if c >= 2)

    @property
    def n_refs(self) -> int:
        """Total references across all in-use pages."""
        return sum(self._refcount.values())

    def shrink(self, n: int) -> int:
        """Retire up to ``n`` free, unpromised pages from the pool (the
        weight-residency trade: HBM bytes leave the KV pool).  Returns
        the number actually retired -- never a page a lane holds or a
        reservation has promised."""
        take = max(min(int(n), self.available()), 0)
        pages = [self._free.pop() for _ in range(take)]
        self._disabled.extend(pages)
        m = self.monitor
        if m is not None and pages:
            m.record("shrink", pages=pages)
        return take

    def grow(self, n: int) -> int:
        """Return up to ``n`` previously retired pages to the free list
        (weights left the board; the KV pool gets its bytes back)."""
        back = max(min(int(n), len(self._disabled)), 0)
        pages = [self._disabled.pop() for _ in range(back)]
        self._free.extend(pages)
        m = self.monitor
        if m is not None and pages:
            m.record("grow", pages=pages)
        return back

    def bind_registry(self, registry: MetricsRegistry,
                      prefix: str = "pool") -> None:
        """Publish the pool's occupancy as live callback gauges.

        Callback gauges read through to the allocator's own state, so
        the alloc/free hot path pays nothing for being observable."""
        registry.gauge(f"{prefix}.pages.free", fn=lambda: self.n_free,
                       help="free pages (incl. reserved)")
        registry.gauge(f"{prefix}.pages.in_use", fn=lambda: self.n_in_use,
                       help="pages allocated to live lanes")
        registry.gauge(f"{prefix}.pages.reserved",
                       fn=lambda: self.n_reserved,
                       help="pages promised to admitted requests")
        registry.gauge(f"{prefix}.pages.disabled",
                       fn=lambda: self.n_disabled,
                       help="pages retired for weight residency")
        registry.gauge(f"{prefix}.pages.hwm", fn=lambda: self.hwm,
                       help="high-water mark of in-use + reserved pages")
        registry.gauge(f"{prefix}.pages.allocs",
                       fn=lambda: self.alloc_count,
                       help="cumulative page allocations")
        registry.gauge(f"{prefix}.pages.frees",
                       fn=lambda: self.free_count,
                       help="cumulative page frees")
        registry.gauge(f"{prefix}.pages.shared",
                       fn=lambda: self.n_shared,
                       help="in-use pages with more than one holder")
        registry.gauge(f"{prefix}.pages.cow_splits",
                       fn=lambda: self.cow_count,
                       help="cumulative copy-on-write page splits")

    def check(self) -> None:
        """Raise unless the conservation invariants hold (test hook)."""
        invariant(len(self._free) + len(self._in_use)
                  + len(self._disabled) == self.n_pages,
                  "page conservation broken", n_free=len(self._free),
                  n_in_use=len(self._in_use),
                  n_disabled=len(self._disabled), n_pages=self.n_pages)
        invariant(len(set(self._free)) == len(self._free),
                  "duplicate page on the free list")
        invariant(len(set(self._disabled)) == len(self._disabled),
                  "duplicate page on the disabled list")
        invariant(not self._in_use.intersection(self._free),
                  "page both in use and free")
        invariant(not self._in_use.intersection(self._disabled),
                  "page both in use and disabled")
        invariant(not set(self._free).intersection(self._disabled),
                  "page both free and disabled")
        invariant(0 <= self._reserved <= len(self._free),
                  "reservation exceeds the free list",
                  reserved=self._reserved, n_free=len(self._free))
        invariant(set(self._refcount) == self._in_use,
                  "refcounts out of sync with the in-use set")
        invariant(all(c >= 1 for c in self._refcount.values()),
                  "in-use page with zero refcount")


# ----------------------------------------------------------------------
# continuous-batching engine
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (len,) int32
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: which registered model serves this request (multi-model engines;
    #: a single-model ServeEngine ignores it)
    model_id: Optional[str] = None
    #: degradation ladder victim ordering: LOWER priority is evicted
    #: first when the engine sheds load (ties broken by highest lane
    #: context, i.e. the most page-hungry request goes first)
    priority: int = 0


@dataclasses.dataclass
class LaneCheckpoint:
    """Host-side snapshot of one live lane, taken at a dispatch boundary.

    Everything a request needs to resume BIT-IDENTICALLY -- on this
    engine or another one built with the same config, ``rng_seed`` and
    ``temperature``:

    * ``req`` -- the request itself (uid, prompt, tokens generated so
      far keep accumulating in place across engines);
    * ``lane_seed`` / ``tok_idx`` -- the sampling identity: the stream
      is a pure function of (key lineage, token index), so restoring
      both replays the exact RNG stream the request would have drawn;
    * ``next_token`` -- the already-sampled token the next decode step
      consumes (sampled before eviction, must not be re-drawn);
    * ``remaining`` / ``ctx_len`` -- generation budget left and live
      context length;
    * ``kv_pages`` -- the lane's live KV pages gathered from the pool
      through its block table, in logical order ``(L, n_pages, Hkv,
      ps[, D|1])`` per pool key (int8 caches carry their scale pages);
      the engine's scratch page is never captured;
    * ``ssm_state`` -- recurrent per-lane state for ssm/hybrid families.

    The payload is plain numpy: it is exactly what a fleet would ship
    over the host link, ``ceil(ctx/page_size)`` pages at a time.
    """

    req: Request
    lane_seed: int
    tok_idx: int
    remaining: int
    ctx_len: int
    next_token: int
    page_size: int
    kv_pages: Dict[str, np.ndarray]
    ssm_state: Dict[str, np.ndarray]

    @property
    def uid(self) -> int:
        return self.req.uid

    @property
    def n_pages(self) -> int:
        for v in self.kv_pages.values():
            return int(v.shape[1])
        return 0

    def nbytes(self) -> int:
        """Bytes a migration must move over the link (KV + state)."""
        return sum(int(v.nbytes) for v in self.kv_pages.values()) + sum(
            int(v.nbytes) for v in self.ssm_state.values())


def _bucket_len(n: int, floor: int = 8) -> int:
    """Smallest power-of-two >= n (>= floor) -- the prefill shape bucket."""
    b = floor
    while b < n:
        b <<= 1
    return b


def prefix_sharing_supported(cfg: ModelConfig) -> bool:
    """Whether ``cfg`` can serve with ``prefix_sharing=True``: the whole
    prompt context must be page-resident and append-only.  Sliding-
    window lanes rewrite their fixed page set in place (a shared page
    would corrupt under the donor); recurrent families (ssm/hybrid)
    keep prompt state outside the pool, so a mapped prefix would skip
    rebuilding it."""
    return (not cfg.is_encdec and not cfg.attn_free
            and cfg.family != "hybrid" and cfg.sliding_window is None)


@dataclasses.dataclass
class PrefixHit:
    """One admission's radix-cache match (see :class:`PrefixCache`)."""

    pages: List[int]                     # full shared pages, block order
    matched_len: int                     # prompt tokens covered in total
    partial: Optional[Tuple[int, int]]   # (page, n_tokens) tail page

    @property
    def n_full(self) -> int:
        """Full matched pages -- the lane's allocation discount (the
        partial page is NOT discounted: its copy-on-write split draws a
        fresh page from the reservation)."""
        return len(self.pages)


class ServeEngine:
    """Continuous batcher around the LM decode step (fixed-lane or paged).

    ``dispatch_n`` is the decode granularity: tokens generated per Python
    dispatch (per lane).  ``stats`` tracks dispatches, decode steps,
    generated tokens, and prefill compiles for the perf regression
    benches; a paged engine adds page-pool high-water mark and
    page-blocked admission counts.

    Paged mode: ``n_lanes`` bounds the decode batch width, ``n_pages``
    bounds KV bytes (default: dense-equivalent, ``n_lanes`` full
    contexts' worth).  Size ``n_lanes`` above ``n_pages / (max_len /
    page_size)`` and short-context admission exceeds the dense lane
    count -- the BENCH_decode paged section measures exactly this.
    """

    #: legacy stats key -> namespaced metric suffix (the authoritative
    #: telemetry schema; full names prepend the engine's ``name``)
    STATS_SCHEMA = {
        "decode_dispatches": "decode.dispatches",
        "decode_steps": "decode.steps",
        "decode_lane_steps": "decode.lane_steps",
        "decode_compiles": "decode.compiles",
        "generated_tokens": "tokens.generated",
        "prefill_compiles": "prefill.compiles",
        "ssm_prefill_compiles": "prefill.ssm_compiles",
        "kv_pages_hwm": "kv.pages_hwm",
        "kv_admit_blocked": "kv.admit_blocked",
        "kv_reserved_page_steps": "kv.reserved_page_steps",
        "preemptions": "preempt.evictions",
        "restores": "preempt.restores",
        "pages_migrated": "preempt.pages_migrated",
        "retry_attempts": "retry.attempts",
        "retry_hedges": "retry.hedges",
        "admit_rejected": "admit.rejected",
        "degrade_transitions": "degrade.transitions",
        "degrade_sheds": "degrade.sheds",
        "prefix_hits": "prefix.hits",
        "prefix_misses": "prefix.misses",
        "prefix_tokens_matched": "prefix.tokens_matched",
        "prefix_pages_shared": "prefix.pages_shared",
        "prefix_pages_saved": "prefix.pages_saved",
        "prefix_cow_copies": "prefix.cow_copies",
        "prefix_evictions": "prefix.evictions",
    }

    def __init__(self, cfg: ModelConfig, params, n_lanes: int = 4,
                 max_len: int = 512, temperature: float = 0.0,
                 rng_seed: int = 0, dispatch_n: int = 8,
                 prefill_bucketing: bool = True, paged: bool = False,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 prefix_sharing: bool = False, sanitize: bool = False,
                 tracer: Optional[SpanTracer] = None,
                 registry: Optional[MetricsRegistry] = None,
                 name: str = "serve",
                 ladder: Optional[DegradationLadder] = None,
                 clock: Optional[Callable[[], float]] = None,
                 flight: Optional[FlightRecorder] = None,
                 slo=None):
        self.cfg = cfg
        # graceful-degradation ladder (None = legacy behavior: run()
        # never sheds, and only raises in the never-admissible case)
        self.ladder = ladder
        if ladder is not None:
            ladder.name = name
        self.model = build_model(cfg)
        self.params = params
        self.n_lanes = n_lanes
        self.max_len = max_len
        # fixed at construction: the value is baked into the jitted
        # dispatch below, so post-hoc mutation would silently desync the
        # prefill-sampled first token from the decode stream
        self.temperature = float(temperature)
        self.dispatch_n = max(1, dispatch_n)
        self.prefill_bucketing = prefill_bucketing
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self._sanitizer: Optional[PageSanitizer] = None
        if self.paged:
            invariant(not cfg.is_encdec,
                      "paged cache: decoder-only families",
                      family=cfg.family)
            if cfg.attn_free:
                self._bt_width = 0      # O(1) recurrent state, no pages
            else:
                self._bt_width = paged_capacity(max_len, cfg) // page_size
            if n_pages is None:
                n_pages = n_lanes * self._bt_width
            invariant(n_pages >= self._bt_width, (
                "page pool smaller than one full context: no request "
                "could ever be admitted"), n_pages=n_pages,
                bt_width=self._bt_width)
            self.pool = PagePool(n_pages, page_size)
            # one extra physical page the allocator never hands out: a
            # DEAD lane still steps inside the jitted batch and writes
            # its (frozen) slot through its block table -- pointing dead
            # rows at the scratch page keeps that write off pages the
            # allocator may have re-issued to a live lane
            self._scratch_page = n_pages
            if sanitize:
                self._sanitizer = PageSanitizer(strict=True)
                self.pool.monitor = self._sanitizer
                self._sanitizer.record(
                    "init", n_pages=n_pages, page_size=page_size,
                    scratch=self._scratch_page)
            self.cache = init_paged_cache(cfg, n_lanes, max_len,
                                          page_size=page_size,
                                          n_pages=n_pages + 1)
            if "block_tables" in self.cache:
                self.cache["block_tables"] = jnp.full_like(
                    self.cache["block_tables"], self._scratch_page)
            self._lane_pages: List[List[int]] = [[] for _ in range(n_lanes)]
            self._lane_reserved = [0] * n_lanes
            self._blocked_uids: set = set()
            self.prefix_cache: Optional[PrefixCache] = None
            if prefix_sharing:
                invariant(prefix_sharing_supported(cfg), (
                    "prefix sharing needs the whole prompt context "
                    "page-resident and append-only (no sliding window, "
                    "no recurrent state)"), family=cfg.family)
                invariant("ssm_h" not in self.cache,
                          "prefix sharing: attention-backed paged "
                          "caches only")
                self.prefix_cache = PrefixCache(self.pool, page_size)
        else:
            self.pool = None
            self._bt_width = 0
            self.prefix_cache = None
            self.cache = init_cache(cfg, n_lanes, max_len)
        self._len_host = np.zeros((n_lanes,), np.int64)
        self.lane_req: List[Optional[Request]] = [None] * n_lanes
        base = jax.random.PRNGKey(rng_seed)
        self._rng_decode = jax.random.fold_in(base, 0)
        self._rng_prefill = jax.random.fold_in(base, 1)
        self._next_token = jnp.zeros((n_lanes,), jnp.int32)
        self._remaining = jnp.zeros((n_lanes,), jnp.int32)
        self._remaining_host = np.zeros((n_lanes,), np.int64)
        # per-lane sampling identity: the admission index seeds the
        # lane's key lineage, tok_idx is its generated-token counter --
        # streams depend only on (admission order, token index)
        self._lane_seed = jnp.zeros((n_lanes,), jnp.int32)
        self._tok_idx = jnp.zeros((n_lanes,), jnp.int32)
        self._admit_count = 0        # admission counter (key lineages)
        # telemetry: every counter lives in the registry under
        # "<name>.<suffix>"; self.stats is a MutableMapping view keyed
        # by the legacy flat names, so existing call sites (and the
        # bench's reset idiom) keep working against the shared registry
        self.name = name
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else SpanTracer(
            enabled=False, clock=clock, registry=self.registry)
        # one shared clock per engine: spans, SLO observations, and any
        # request timestamps all read THIS callable, so a timeline mixing
        # tracer spans with admit/first-token marks is skew-free (the
        # EventLog default matches; lint R003 patrols regressions)
        self.clock = clock if clock is not None else self.tracer.clock
        # flight recorder: taps the tracer's span/instant hooks; dumped
        # by flight_guard when a sanitizer/invariant error escapes an op
        self.flight = flight
        if flight is not None:
            flight.attach(tracer=self.tracer)
        # SLO burn-rate control loop (an SLOController); fed per-lane
        # TTFT/tpot at dispatch drain, stepped once per dispatch
        self.slo = slo
        self._admit_t: Dict[int, float] = {}
        keymap = {k: f"{name}.{suffix}"
                  for k, suffix in self.STATS_SCHEMA.items()}
        for metric_name in keymap.values():
            # a fresh engine starts its counters at zero even on a
            # shared registry (modelpool reloads accumulate history in
            # the entry, not in the live counters)
            self.registry.counter(metric_name).set(0)
        self._stats = StatsView(self.registry, keymap)
        if self.paged:
            self.pool.bind_registry(self.registry, prefix=f"{name}.pool")
        if self.prefix_cache is not None:
            self.registry.gauge(
                f"{name}.prefix.cached_pages",
                fn=lambda: self.prefix_cache.n_pages,
                help="pool pages the radix prompt cache holds a ref on")
        self._decode = jax.jit(
            lambda p, c, t: self.model.decode_step(p, c, t))
        self._temperature = self.temperature      # captured, see above
        # the per-layer state (page pools, dense KV, recurrent state) is
        # donated: the dispatch updates it in place, with no copy of the
        # pool on the way in or out, and the pre-dispatch arrays are
        # deleted (every path reads ``self.cache`` afresh).  The per-lane
        # vectors (``len``, ``block_tables``) travel as their own,
        # undonated argument: a wrapper of the dispatch may hand them
        # back as it was given them, as the benchmark's fault plants do
        self._decode_n = jax.jit(
            functools.partial(self._decode_n_fn,
                              temperature=self._temperature,
                              len_cap=self.max_len - 1),
            static_argnames=("n_steps",), donate_argnames=("state",))

        def prefill_fn(p, tokens, last_pos):
            # Python side effect fires once per trace == once per shape
            # bucket; the recompile regression test pins this counter.
            self.stats["prefill_compiles"] += 1
            return lm_prefill_batched(p, tokens, self.cfg,
                                      last_pos=last_pos)

        self._prefill = jax.jit(prefill_fn)

        def ssm_prefill_fn(p, lane_cache, tokens, plen):
            self.stats["ssm_prefill_compiles"] += 1
            return self._ssm_prefill_scan(p, lane_cache, tokens, plen)

        self._ssm_prefill = jax.jit(ssm_prefill_fn)

    def _decode_n_fn(self, params, cache, state, tokens, rng, remaining,
                     lane_seed, tok_idx, *, n_steps, temperature, len_cap):
        """The decode dispatch over the cache split in two: ``cache``
        the per-lane vectors (``len``, ``block_tables``), ``state`` the
        donated per-layer state.  Returns (tokens, valid, next tokens,
        cache, state, remaining, tok_idx)."""
        # Python side effect fires once per XLA trace == once per
        # distinct n_steps; the telemetry overhead-budget test pins this
        # counter traced-vs-untraced.
        self.stats["decode_compiles"] += 1
        toks, valid, nxt, full, remaining, tok_idx = \
            self.model.decode_n_steps(
                params, {**cache, **state}, tokens, rng, remaining,
                lane_seed, tok_idx, n_steps=n_steps,
                temperature=temperature, len_cap=len_cap)
        return (toks, valid, nxt, {k: full[k] for k in cache},
                {k: full[k] for k in state}, remaining, tok_idx)

    def _split_cache(self):
        """(per-lane vectors, per-layer state) of ``self.cache``: the
        dispatch's two cache arguments, the second one donated."""
        lanes = {k: x for k, x in self.cache.items()
                 if k in CACHE_SHARED_KEYS}
        state = {k: x for k, x in self.cache.items()
                 if k not in CACHE_SHARED_KEYS}
        return lanes, state

    def decode_program_text(self) -> str:
        """Compiled text of the ``dispatch_n``-step decode dispatch at
        the live arguments: which kernels the compiler placed in it (a
        Pallas kernel shows as ``tpu_custom_call``)."""
        return self._decode_n.lower(
            self.params, *self._split_cache(), self._next_token,
            self._rng_decode, self._remaining, self._lane_seed,
            self._tok_idx, n_steps=self.dispatch_n).compile().as_text()

    # -- telemetry --------------------------------------------------------
    @property
    def stats(self) -> StatsView:
        """Legacy stats mapping, backed by the metrics registry."""
        return self._stats

    @stats.setter
    def stats(self, values: Dict[str, Any]) -> None:
        # the bench reset idiom (`eng.stats = {k: 0 for k in eng.stats}`)
        # writes values through the view; the schema itself is fixed
        for k, v in values.items():
            self._stats[k] = v

    def lane_track(self, lane: int) -> str:
        """Trace track name for one lane of this engine."""
        return f"{self.name}/lane{lane}"

    # -- admission --------------------------------------------------------
    def free_lanes(self) -> List[int]:
        return [i for i, r in enumerate(self.lane_req) if r is None]

    def _pages_needed(self, positions: int) -> int:
        """Pages backing ``positions`` cache slots; a sliding-window lane
        rotates within its fixed ``bt_width`` page set, so the need is
        capped there."""
        if self._bt_width == 0:
            return 0
        ps = self.page_size
        return min(-(-int(positions) // ps), self._bt_width)

    def _trunc_plen(self, req: Request) -> int:
        return min(int(req.prompt.shape[0]), self.max_len - 1)

    def admission_pages(self, req: Request) -> int:
        """Worst-case page need of ``req`` (prompt + full budget + the
        trailing write slot) -- what admission gates on.  The worst case
        is CLAMPED to ``max_len`` positions: generation stops at the
        ``len_cap`` regardless of budget, so a request whose budget
        exceeds the cache must not demand more pages than the cache can
        ever back (it could otherwise never be admitted)."""
        worst = min(self._trunc_plen(req) + req.max_new_tokens + 1,
                    self.max_len)
        return self._pages_needed(worst)

    def can_admit(self, req: Request) -> bool:
        if not self.free_lanes():
            return False
        if not self.paged:
            return True
        return self.admission_pages(req) <= self.pool.available()

    def admit(self, req: Request) -> bool:
        lanes = self.free_lanes()
        if not lanes:
            return False
        lane = lanes[0]
        hit: Optional[PrefixHit] = None
        if self.paged:
            with self.tracer.span("admit.reserve",
                                  track=self.lane_track(lane),
                                  uid=req.uid, lane=lane) as sp:
                need = self.admission_pages(req)
                reserve = need
                if self.prefix_cache is not None:
                    hit = self._prefix_match(req)
                    # every FULL matched page is a page this request
                    # never allocates: the reservation (what admission
                    # gates on) shrinks by exactly that, which is the
                    # effective-admission gain the bench measures
                    reserve = need - hit.n_full
                ok = self.pool.reserve(reserve)
                if not ok:
                    if self.prefix_cache is not None \
                            and self.prefix_cache.n_pages:
                        # under pool pressure, cached-but-unmapped
                        # prefix pages are the first bytes to go;
                        # eviction may drop matched nodes (their pages
                        # can be reissued once the last holder lets
                        # go), so re-match after
                        self._trim_prefix_cache(reserve)
                        hit = self._prefix_match(req)
                        reserve = need - hit.n_full
                    ok = self.pool.reserve(reserve)
                if sp is not None:
                    sp.args["ok"] = ok
            if not ok:
                # a lane is free but the KV bytes are not: admission is
                # gated on pages, the caller retries after retirements.
                # Counted once per blocked EPISODE (not per retry), so
                # the stat is dispatch-granularity invariant.
                if req.uid not in self._blocked_uids:
                    self._blocked_uids.add(req.uid)
                    self.stats["kv_admit_blocked"] += 1
                    self.tracer.instant("admit.blocked",
                                        track=self.lane_track(lane),
                                        uid=req.uid, need_pages=need)
                return False
            self._blocked_uids.discard(req.uid)
        if self.slo is not None and req.uid not in self._admit_t:
            # TTFT starts at first successful admission (re-admission
            # after evict/restore keeps the original mark)
            self._admit_t[req.uid] = self.clock()
        with flight_guard(self.flight, op="admit",
                          registry=self.registry), \
                self.tracer.span("admit", track=self.lane_track(lane),
                                 uid=req.uid, lane=lane):
            if self.paged:
                self._lane_reserved[lane] = reserve
                self._lane_pages[lane] = []
                if hit is None or hit.matched_len == 0:
                    # map the prompt's pages (plus the first decode
                    # write slot); generation growth maps the rest at
                    # dispatch boundaries
                    self._map_pages(lane, self._pages_needed(
                        self._trunc_plen(req) + 1))
            self._lane_seed = self._lane_seed.at[lane].set(
                self._admit_count)
            self._tok_idx = self._tok_idx.at[lane].set(0)
            if hit is not None and hit.matched_len > 0:
                self._prefill_hit(req, lane, hit)
            else:
                if self.prefix_cache is not None:
                    self.stats["prefix_misses"] += 1
                self._prefill_into_lane(req, lane)
            if self.prefix_cache is not None:
                self._cache_lane_prefix(req, lane)
            self.lane_req[lane] = req
            self._remaining = self._remaining.at[lane].set(
                req.max_new_tokens)
            self._remaining_host[lane] = req.max_new_tokens
        return True

    def _map_pages(self, lane: int, target: int) -> None:
        """Grow ``lane``'s block table to ``target`` mapped pages, drawing
        on the reservation made at admission (which makes this infallible
        mid-flight).  Lane reuse is copy-free: the row is simply
        rewritten, pages of the previous occupant were freed at its
        retirement."""
        have = len(self._lane_pages[lane])
        if target <= have:
            return
        new = self.pool.alloc(target - have, holder=lane)
        self._lane_reserved[lane] -= len(new)
        self._lane_pages[lane].extend(new)
        self.cache["block_tables"] = (
            self.cache["block_tables"].at[lane, have:target]
            .set(jnp.asarray(new, jnp.int32)))
        s = self._sanitizer
        if s is not None:
            s.record("map", lane=lane, pages=list(new))
        self.stats["kv_pages_hwm"] = max(self.stats["kv_pages_hwm"],
                                         self.pool.hwm)

    # -- prefix sharing ----------------------------------------------------
    def _trunc_prompt(self, req: Request) -> np.ndarray:
        """The prompt as the lane will actually hold it: a fixed cache
        cannot back more than ``max_len - 1`` prompt positions and still
        decode, so over-long prompts keep their TAIL (coherent
        positions/KV, llama.cpp-style truncation)."""
        prompt = req.prompt
        limit = self.max_len - 1
        if prompt.shape[0] > limit:
            prompt = prompt[-limit:]
        return prompt

    def _prefix_match(self, req: Request) -> PrefixHit:
        """Match the (truncated) prompt against the radix cache.  int8
        caches match FULL pages only: the hit path replays the batched
        full-precision prefill for the logits (see ``_prefill_hit``),
        and a partial page would save nothing while still costing a
        copy-on-write split."""
        prompt = self._trunc_prompt(req)
        pages, matched, partial = self.prefix_cache.match(
            np.asarray(prompt),
            allow_partial=self.cfg.kv_quant != "int8")
        return PrefixHit(pages=pages, matched_len=matched, partial=partial)

    def _trim_prefix_cache(self, target_available: int) -> int:
        """Evict LRU cache entries until the pool can cover a
        ``target_available``-page reservation (or the cache is empty).
        A dropped page only refills the free list if no live lane still
        maps it, hence the loop on actual availability."""
        dropped = 0
        while (self.pool.available() < target_available
               and self.prefix_cache.n_pages):
            if not self.prefix_cache.evict_lru():
                break
            dropped += 1
            self.stats["prefix_evictions"] += 1
        return dropped

    def _prefill_hit(self, req: Request, lane: int, hit: PrefixHit) -> None:
        """Admit ``req`` over a radix-cache hit: map the matched pages
        into the lane's block table (refcount bump, zero copies), then
        produce the prompt's last-token logits.

        * full-precision KV: only the unmatched TAIL streams through
          the decode step (the masked-scan prefill path) -- zero new
          prefill work for the matched span.  A matched partial tail
          page is copy-on-written first: this lane's first append
          diverges from the donor's.
        * int8 KV: the decode step reads DEQUANTIZED pages, so a
          streamed tail would attend to lossy prefix KV while the
          non-shared engine's batched prefill attends at full
          precision -- the first token would drift.  The batched
          prefill replays for the logits (bit-exact by construction)
          and only the tail pages are scattered; the page/admission
          saving stands, the prefill-compute saving does not.
        """
        prompt = self._trunc_prompt(req)
        plen = int(prompt.shape[0])
        shared = list(hit.pages)
        if hit.partial is not None:
            shared.append(hit.partial[0])
        # the lane takes its own reference on every matched page; the
        # block-table row is written in logical order, so evict's
        # position-ordered gather needs no special case
        self.pool.share(shared, holder=lane)
        self._lane_pages[lane] = list(shared)
        self.cache["block_tables"] = (
            self.cache["block_tables"].at[lane, :len(shared)]
            .set(jnp.asarray(shared, jnp.int32)))
        s = self._sanitizer
        if s is not None:
            s.record("map", lane=lane, pages=list(shared))
        if hit.partial is not None:
            self._cow_lane_page(lane, len(hit.pages))
        self._map_pages(lane, self._pages_needed(plen + 1))
        self._len_host[lane] = plen
        self.stats["prefix_hits"] += 1
        self.stats["prefix_tokens_matched"] += hit.matched_len
        self.stats["prefix_pages_shared"] += len(shared)
        self.stats["prefix_pages_saved"] += hit.n_full
        self.tracer.instant("prefix.hit", track=self.lane_track(lane),
                            uid=req.uid, matched_tokens=hit.matched_len,
                            shared_pages=len(shared))
        if self.cfg.kv_quant == "int8":
            self._prefill_hit_quant(prompt, lane, plen, hit)
        else:
            self._prefill_hit_stream(prompt, lane, plen, hit.matched_len)

    def _cow_lane_page(self, lane: int, idx: int) -> None:
        """Copy-on-write split of the lane's shared block ``idx``: swap
        in a fresh page from the reservation, snapshot the shared
        page's contents into it (jax arrays are immutable, so the copy
        is a true point-in-time snapshot even while the donor keeps
        appending to the original), and retarget the block table."""
        old = self._lane_pages[lane][idx]
        with self.tracer.span("prefix.cow", track=self.lane_track(lane),
                              lane=lane, page=old):
            new = self.pool.cow(old, holder=lane)
            self._lane_reserved[lane] -= 1
            self._lane_pages[lane][idx] = new
            for key in PAGED_POOL_KEYS:
                if key in self.cache:
                    self.cache[key] = self.cache[key].at[:, new].set(
                        self.cache[key][:, old])
            self.cache["block_tables"] = (
                self.cache["block_tables"].at[lane, idx].set(new))
            s = self._sanitizer
            if s is not None:
                s.record("write", lane=lane, pages=[new],
                         kind="cow_copy")
        self.stats["prefix_cow_copies"] += 1
        self.stats["kv_pages_hwm"] = max(self.stats["kv_pages_hwm"],
                                         self.pool.hwm)

    def _prefill_hit_stream(self, prompt: np.ndarray, lane: int,
                            plen: int, matched_len: int) -> None:
        """Full-precision hit path: stream only the unmatched tail
        through the masked-scan decode path, attending over the shared
        span already page-resident.  Bit-exactness vs the batched
        prefill is pinned by the prefix exactness tests."""
        tail = np.asarray(prompt[matched_len:], np.int32)
        tlen = int(tail.shape[0])
        invariant(tlen >= 1, "prefix match must leave a tail token",
                  plen=plen, matched_len=matched_len)
        s = self._sanitizer
        if s is not None:
            # the streamed tail writes positions [matched_len, plen)
            # plus the frozen write slot at plen (pad steps)
            pages = self._lane_pages[lane][matched_len // self.page_size:
                                           self._pages_needed(plen + 1)]
            if pages:
                s.record("write", lane=lane, pages=list(pages),
                         kind="prefill")
        lane_cache = self._slice_lane_cache(lane)
        lane_cache["len"] = jnp.full((1,), matched_len, jnp.int32)
        bucket = _bucket_len(tlen) if self.prefill_bucketing else tlen
        padded = np.zeros((bucket,), np.int32)
        padded[:tlen] = tail
        with self.tracer.span("prefix.tail_prefill",
                              track=self.lane_track(lane), lane=lane,
                              bucket=bucket, tlen=tlen):
            logits, lane_cache = self._ssm_prefill(
                self.params, lane_cache, jnp.asarray(padded),
                jnp.asarray(tlen, jnp.int32))
        self._merge_lane_cache(lane_cache, lane)
        self._set_first_token(logits, lane)

    def _prefill_hit_quant(self, prompt: np.ndarray, lane: int,
                           plen: int, hit: PrefixHit) -> None:
        """int8 hit path: batched prefill for exact logits, scatter
        only the blocks the match did not cover."""
        bucket = _bucket_len(plen) if self.prefill_bucketing else plen
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = prompt
        track = self.lane_track(lane)
        with self.tracer.span("prefill.bucket", track=track, lane=lane,
                              bucket=bucket, plen=plen):
            with self.tracer.span("prefill.launch", track=track,
                                  lane=lane):
                logits, kv = self._prefill(
                    self.params, jnp.asarray(padded),
                    jnp.asarray([plen - 1], jnp.int32))
            with self.tracer.span("prefill.scatter", track=track,
                                  lane=lane):
                self._scatter_prompt_paged(kv, lane, plen,
                                           first_block=hit.n_full)
        self.cache["len"] = self.cache["len"].at[lane].set(plen)
        self._set_first_token(logits, lane)

    def _cache_lane_prefix(self, req: Request, lane: int) -> None:
        """Offer the freshly prefilled lane's prompt pages to the radix
        cache (the cache takes its own refs on pages it keeps)."""
        prompt = self._trunc_prompt(req)
        with self.tracer.span("prefix.insert", track=self.lane_track(lane),
                              lane=lane):
            self.prefix_cache.insert(
                np.asarray(prompt), int(prompt.shape[0]),
                self._lane_pages[lane],
                allow_partial=self.cfg.kv_quant != "int8")

    def _prefill_into_lane(self, req: Request, lane: int) -> None:
        prompt = req.prompt
        # a fixed-lane cache cannot hold more than max_len - 1 prompt
        # positions and still decode: keep the TAIL of over-long prompts
        # (coherent positions/KV, llama.cpp-style truncation) instead of
        # recording a length the cache cannot back.
        limit = self.max_len - 1
        if prompt.shape[0] > limit:
            prompt = prompt[-limit:]
        plen = int(prompt.shape[0])
        self._len_host[lane] = plen
        bucket = _bucket_len(plen) if self.prefill_bucketing else plen
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = prompt
        track = self.lane_track(lane)
        with self.tracer.span("prefill.bucket", track=track, lane=lane,
                              bucket=bucket, plen=plen):
            with self.tracer.span("prefill.launch", track=track,
                                  lane=lane):
                logits, kv = self._prefill(
                    self.params, jnp.asarray(padded),
                    jnp.asarray([plen - 1], jnp.int32))
            if kv is not None:
                with self.tracer.span("prefill.scatter", track=track,
                                      lane=lane):
                    if self.paged:
                        self._scatter_prompt_paged(kv, lane, plen)
                    else:
                        self._scatter_prompt_dense(kv, lane, plen)
        if "ssm_h" in self.cache:
            # SSM state is rebuilt by streaming the prompt through the
            # decode path (exactly once, O(len) state updates).
            self._stream_ssm_prompt(prompt, lane)
            return
        self.cache["len"] = self.cache["len"].at[lane].set(plen)
        self._set_first_token(logits, lane)

    def _prompt_kv_views(self, kv, plen: int, smax: int,
                         paged: bool = False):
        """Last ``min(plen, smax)`` prompt positions of the prefill KV,
        laid out at their ring slots (``slot = position mod smax``) and
        quantized when the cache is int8 (via ``quantize_kv_token``, the
        same per-(token, head) scales the decode write path uses).

        Returns (entries, take): ``entries`` maps cache key -> a
        (L, Hkv, take, ...) array in the cache's dtype: the dense keys,
        or with ``paged`` the page pools' keys in the pools' layout
        (the only place the two layouts meet).
        """
        from repro.models.attention import quantize_kv_token

        k, v = kv                       # (L, 1, Hkv, S_bucket, D)
        take = min(plen, smax)
        k = k[:, 0, :, plen - take:plen, :]
        v = v[:, 0, :, plen - take:plen, :]
        if take == smax:
            # window cache and the prompt filled (or wrapped) it: place
            # position p at slot p % smax, so the decode step's ring
            # write (same formula) evicts the true oldest position
            shift = plen % smax
            if shift:
                k = jnp.roll(k, shift, axis=2)
                v = jnp.roll(v, shift, axis=2)
        entries = {"k": k, "v": v}
        if self.cfg.kv_quant == "int8":
            kq, ks = quantize_kv_token(k)
            vq, vs = quantize_kv_token(v)
            entries = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        if paged:
            # the pools keep a scale per (token, head) with no trailing
            # unit axis
            entries = {f"{key}_pages": val[..., 0] if key.endswith("_scale")
                       else val for key, val in entries.items()}
        return entries, take

    def _scatter_prompt_dense(self, kv, lane: int, plen: int) -> None:
        smax = self.cache["k"].shape[3]
        entries, take = self._prompt_kv_views(kv, plen, smax)
        for key, val in entries.items():
            self.cache[key] = jax.lax.dynamic_update_slice(
                self.cache[key], val[:, None].astype(self.cache[key].dtype),
                (0, lane, 0, 0, 0))

    def _scatter_prompt_paged(self, kv, lane: int, plen: int,
                              first_block: int = 0) -> None:
        """Write the prompt KV into the lane's mapped pages (one
        dynamic_update_slice per page -- pages are not contiguous in the
        pool, that is the point).  ``first_block`` skips blocks already
        backed by shared prefix pages: their bytes are the donor's, and
        writing them would corrupt every other lane mapping them."""
        ps = self.page_size
        entries, take = self._prompt_kv_views(kv, plen, ps * self._bt_width,
                                              paged=True)
        n_pg = -(-take // ps)
        pad = n_pg * ps - take
        if pad:
            entries = {key: jnp.pad(val, [(0, pad) if ax == 2 else (0, 0)
                                          for ax in range(val.ndim)])
                       for key, val in entries.items()}
        for i, page in enumerate(self._lane_pages[lane][:n_pg]):
            if i < first_block:
                continue
            for key, val in entries.items():
                self._write_page(key, page,
                                 val[:, None, :, i * ps:(i + 1) * ps])
        s = self._sanitizer
        if s is not None:
            written = self._lane_pages[lane][first_block:n_pg]
            if written:
                s.record("write", lane=lane, pages=list(written),
                         kind="prefill")

    def _write_page(self, key: str, page: int, seg) -> None:
        """Write ``seg`` (L, 1, ...), one page of every layer in the
        pool's layout, into page ``page`` of pool ``key``."""
        pool = self.cache[key]
        self.cache[key] = jax.lax.dynamic_update_slice(
            pool, seg.astype(pool.dtype), (0, page) + (0,) * (pool.ndim - 2))

    def _set_first_token(self, logits: jnp.ndarray, lane: int) -> None:
        key = jax.random.fold_in(self._rng_prefill, self._admit_count)
        self._admit_count += 1
        tok = sample_tokens(logits, key, self._temperature)[0]
        self._next_token = self._next_token.at[lane].set(tok)

    def _slice_lane_cache(self, lane: int) -> Dict[str, jnp.ndarray]:
        """One lane's view of the cache: per-lane state is sliced to
        batch 1; the shared page pool passes through whole (the lane's
        block-table row names its pages)."""
        out = {}
        for key, x in self.cache.items():
            if key in PAGED_POOL_KEYS:
                out[key] = x
            elif key in CACHE_SHARED_KEYS:
                out[key] = x[lane:lane + 1]
            else:
                out[key] = x[:, lane:lane + 1]
        return out

    def _merge_lane_cache(self, lane_cache: Dict[str, jnp.ndarray],
                          lane: int) -> None:
        for key, x in lane_cache.items():
            if key in PAGED_POOL_KEYS:
                self.cache[key] = x
            elif key in CACHE_SHARED_KEYS:
                self.cache[key] = self.cache[key].at[lane].set(x[0])
            else:
                self.cache[key] = jax.lax.dynamic_update_slice(
                    self.cache[key], x, (0, lane) + (0,) * (x.ndim - 2))

    def _ssm_prefill_scan(self, params, lane_cache, tokens, plen):
        """Prompt streaming as ONE ``lax.scan`` over a shape bucket.

        The recurrent families have no batched cache-build path, so the
        prompt must flow through the decode step; doing it eagerly cost
        one host dispatch per prompt token.  Here the padded bucket is
        scanned on device with *state masking*: a pad position computes
        a decode step but its per-lane state update (length, recurrent
        state, lane KV) is discarded, so the carry after the scan equals
        the eager per-token stream exactly, and the logits captured at
        ``plen - 1`` are the real last-token logits.  Shared page pools
        are deliberately NOT masked (a pool-wide select per position
        would stream the whole pool ``bucket`` times): a pad step writes
        its garbage token at the frozen slot ``plen`` -- exactly where
        the first real decode token writes next, and nothing surviving
        the mask reads it first.  One compile per bucket, one dispatch
        per prompt.
        """
        logits0 = jnp.zeros((1, self.cfg.padded_vocab), jnp.float32)

        def body(carry, inp):
            cache, logits = carry
            tok, idx = inp
            live = idx < plen
            new_logits, new_cache = self.model.decode_step(
                params, cache, tok[None])
            cache = {
                key: (new_cache[key] if key in PAGED_POOL_KEYS
                      else jax.tree_util.tree_map(
                          lambda new, old: jnp.where(live, new, old),
                          new_cache[key], cache[key]))
                for key in cache}
            logits = jnp.where(idx == plen - 1, new_logits, logits)
            return (cache, logits), None

        (lane_cache, logits), _ = jax.lax.scan(
            body, (lane_cache, logits0),
            (tokens, jnp.arange(tokens.shape[0], dtype=jnp.int32)))
        return logits, lane_cache

    def _stream_ssm_prompt(self, prompt: np.ndarray, lane: int) -> None:
        lane_cache = self._slice_lane_cache(lane)
        lane_cache["len"] = jnp.zeros((1,), jnp.int32)
        # a re-admitted lane must NOT inherit the previous request's
        # recurrent state (init_mamba2_state is all-zeros)
        for key in ("ssm_h", "ssm_conv"):
            if key in lane_cache:
                lane_cache[key] = jnp.zeros_like(lane_cache[key])
        plen = int(prompt.shape[0])
        bucket = _bucket_len(plen) if self.prefill_bucketing else plen
        padded = np.zeros((bucket,), np.int32)
        padded[:plen] = prompt
        logits, lane_cache = self._ssm_prefill(
            self.params, lane_cache, jnp.asarray(padded),
            jnp.asarray(plen, jnp.int32))
        self._merge_lane_cache(lane_cache, lane)
        self._set_first_token(logits, lane)
        s = self._sanitizer
        if s is not None and self.paged and self._lane_pages[lane]:
            # hybrid lanes stream the whole prompt through the decode
            # path; every mapped page is exclusively this lane's
            s.record("write", lane=lane,
                     pages=list(self._lane_pages[lane]), kind="prefill")

    # -- stepping ----------------------------------------------------------
    def _dispatch_size(self, n: Optional[int]) -> int:
        """Tokens per dispatch: dispatch_n, shrunk (to a power of two, to
        bound recompiles) when every live lane owes fewer tokens."""
        n = n or self.dispatch_n
        live = [i for i, r in enumerate(self.lane_req) if r is not None]
        max_rem = int(self._remaining_host[live].max()) if live else 0
        return min(n, _bucket_len(max(max_rem, 1), floor=1))

    def decode_n(self, n: Optional[int] = None) -> Dict[int, List[int]]:
        """Advance all live lanes up to ``n`` tokens in ONE dispatch.

        Returns {uid: [tokens]} for this block; requests that exhaust
        their budget (or the cache) are retired at the boundary.
        """
        live = [i for i, r in enumerate(self.lane_req) if r is not None]
        if not live:
            return {}
        n = self._dispatch_size(n)
        t_disp0 = self.clock() if self.slo is not None else 0.0
        with flight_guard(self.flight, op="decode.dispatch",
                          registry=self.registry), \
                self.tracer.span(
                    "decode.dispatch", track=self.name, n_steps=n,
                    n_live=len(live),
                    uids=tuple(self.lane_req[i].uid for i in live)):
            with self.tracer.span("decode.prepare", track=self.name):
                steps = [min(n, int(self._remaining_host[lane]))
                         for lane in live]
                if self.paged:
                    # map the pages this block can write into BEFORE the
                    # jitted dispatch (the scan itself never touches the
                    # allocator); the admission-time reservation makes
                    # this infallible
                    for lane, k in zip(live, steps):
                        self._map_pages(lane, self._pages_needed(
                            int(self._len_host[lane]) + k + 1))
                    s = self._sanitizer
                    if s is not None:
                        for lane, k in zip(live, steps):
                            start = int(self._len_host[lane])
                            if self.cfg.sliding_window is not None:
                                # ring writes rotate within the fixed set
                                pages = list(self._lane_pages[lane])
                            else:
                                pages = self._lane_pages[lane][
                                    start // self.page_size:
                                    self._pages_needed(start + k + 1)]
                            if pages:
                                s.record("write", lane=lane, pages=pages,
                                         kind="decode")
                    # pages promised to live lanes and not yet mapped,
                    # idle for each of the dispatch's steps
                    self.stats["kv_reserved_page_steps"] += (
                        n * self.pool.n_reserved)
                self.stats["decode_lane_steps"] += sum(steps)
            with self.tracer.span("decode.launch", track=self.name):
                (toks, valid, self._next_token, lanes, state,
                 self._remaining, self._tok_idx) = self._decode_n(
                    self.params, *self._split_cache(), self._next_token,
                    self._rng_decode, self._remaining, self._lane_seed,
                    self._tok_idx, n_steps=n)
                self.cache = {**lanes, **state}
            self.stats["decode_dispatches"] += 1
            self.stats["decode_steps"] += n
            # one host transfer drains the whole block
            with self.tracer.span("decode.wait", track=self.name):
                toks_h, valid_h, rem_h = jax.device_get(
                    (toks, valid, self._remaining))
        with self.tracer.span("decode.retire", track=self.name):
            out = self._retire_block(live, toks_h, valid_h, rem_h,
                                     t_disp0)
        if self._sanitizer is not None:
            # dispatch boundary: shadow state must equal the real pool
            with flight_guard(self.flight, op="sanitizer.crosscheck",
                              registry=self.registry):
                self._sanitizer.crosscheck(self.pool)
        return out

    def _retire_block(self, live: List[int], toks_h, valid_h, rem_h,
                      t_disp0: float) -> Dict[int, List[int]]:
        """Host bookkeeping after a dispatch drains: hand each live
        lane its tokens, mark first tokens, feed the SLO monitor, and
        release the lanes whose budget ran out."""
        self._remaining_host = np.asarray(rem_h, np.int64)
        slo = self.slo
        if slo is not None:
            now = self.clock()
            disp_s = now - t_disp0
        out: Dict[int, List[int]] = {}
        for lane in live:
            req = self.lane_req[lane]
            seq = [int(t) for t in toks_h[valid_h[:, lane], lane]]
            first = not req.generated and bool(seq)
            req.generated.extend(seq)
            out[req.uid] = seq
            self.stats["generated_tokens"] += len(seq)
            # the lane's device-side length advanced once per valid
            # sample (exhausted lanes freeze it), so the host mirror
            # tracks it without an extra transfer
            self._len_host[lane] += len(seq)
            if first:
                self.tracer.instant("first_token",
                                    track=self.lane_track(lane),
                                    uid=req.uid)
                if slo is not None:
                    t_admit = self._admit_t.pop(req.uid, None)
                    if t_admit is not None:
                        slo.monitor.observe_ttft(now - t_admit, t=now)
            if slo is not None and seq:
                slo.monitor.observe_tpot(disp_s / len(seq), t=now)
            if self._remaining_host[lane] <= 0:
                req.done = True
                self.tracer.instant("retire",
                                    track=self.lane_track(lane),
                                    uid=req.uid,
                                    gen=len(req.generated))
                self._release_lane(lane)
        if slo is not None:
            slo.step(now)
        return out

    def _release_lane(self, lane: int) -> None:
        """Return a lane to the DEAD state (retirement and eviction both
        end here): zero its cache length so the length-aware kernel pins
        a single key block instead of streaming the stale context, drop
        the lane's reference on its pages (a page another lane or the
        prefix cache still maps survives; exclusively-owned pages return
        to the free list), and point the dead block-table row at the
        scratch page -- its page ids may be re-issued to another lane,
        but the dead lane keeps stepping (and writing its frozen slot)
        until re-admission."""
        self.lane_req[lane] = None
        self.cache["len"] = self.cache["len"].at[lane].set(0)
        self._len_host[lane] = 0
        if self.paged:
            self.pool.free(self._lane_pages[lane], holder=lane)
            self.pool.unreserve(self._lane_reserved[lane])
            self._lane_pages[lane] = []
            self._lane_reserved[lane] = 0
            if "block_tables" in self.cache:
                self.cache["block_tables"] = (
                    self.cache["block_tables"].at[lane]
                    .set(self._scratch_page))

    # -- preemption: evict-and-replay checkpointing ------------------------
    def live_lanes(self) -> List[int]:
        return [i for i, r in enumerate(self.lane_req) if r is not None]

    def lane_context(self, lane: int) -> int:
        """Live context length of ``lane`` (host mirror, no sync)."""
        return int(self._len_host[lane])

    def evict(self, lane: int) -> LaneCheckpoint:
        """Checkpoint and release a live lane at a dispatch boundary.

        The checkpoint captures the request, its sampling identity
        (``lane_seed``, ``tok_idx``), the pre-sampled next token, and
        the lane's live KV pages gathered from the pool through its
        block table -- everything :meth:`restore` needs to resume the
        exact token stream, here or on another engine built with the
        same config / ``rng_seed`` / ``temperature``.  The lane's pages
        return to the pool immediately (that is the point: a page-
        exhausted board sheds the decode without losing its tokens).

        The scratch page is dead-lane plumbing, not request state: it is
        never captured, never freed, never migrated.

        Prefix-shared pages: the gather is a DEEP COPY through the block
        table, so a page this lane maps but does not exclusively own
        (refcount > 1: the radix cache or a sibling lane also holds it)
        is captured by value and never stolen -- releasing the lane
        merely drops its reference, the other holders keep the bytes,
        and :meth:`restore` re-anchors the checkpoint onto fresh
        exclusively-owned pages.  Cross-engine restore of a prefix-hit
        lane is pinned bit-exact by the prefix test tier.
        """
        invariant(self.paged, "evict/restore: paged engines only")
        req = self.lane_req[lane]
        invariant(req is not None, f"evict of idle lane {lane}",
                  lane=lane)
        with flight_guard(self.flight, op="preempt.evict",
                          registry=self.registry), \
                self.tracer.span("preempt.evict",
                                 track=self.lane_track(lane), uid=req.uid,
                                 lane=lane,
                                 n_pages=len(self._lane_pages[lane])):
            pages = list(self._lane_pages[lane])
            invariant(self._scratch_page not in pages,
                      "scratch page leaked into a live block table",
                      lane=lane)
            s = self._sanitizer
            if s is not None:
                s.record("capture", lane=lane, pages=pages)
            idx = jnp.asarray(pages, jnp.int32)
            kv = {key: jnp.take(self.cache[key], idx, axis=1)
                  for key in PAGED_POOL_KEYS if key in self.cache}
            ssm = {key: self.cache[key][:, lane]
                   for key in ("ssm_h", "ssm_conv") if key in self.cache}
            kv, ssm, nxt, seed, idx_t = jax.device_get(
                (kv, ssm, self._next_token[lane], self._lane_seed[lane],
                 self._tok_idx[lane]))
            ckpt = LaneCheckpoint(
                req=req, lane_seed=int(seed), tok_idx=int(idx_t),
                remaining=int(self._remaining_host[lane]),
                ctx_len=int(self._len_host[lane]), next_token=int(nxt),
                page_size=self.page_size,
                kv_pages={k: np.asarray(v) for k, v in kv.items()},
                ssm_state={k: np.asarray(v) for k, v in ssm.items()})
            # the evicted lane is DEAD: freeze its budget so a dispatch
            # that runs before re-admission samples only invalid tokens
            # for it
            self._remaining = self._remaining.at[lane].set(0)
            self._remaining_host[lane] = 0
            self._release_lane(lane)
            self.stats["preemptions"] += 1
        return ckpt

    def restore_pages(self, ckpt: LaneCheckpoint) -> int:
        """Pages :meth:`restore` will reserve for ``ckpt`` -- the
        checkpointed pages plus headroom for the remaining budget,
        clamped (like admission) to what the cache can back."""
        worst = min(ckpt.ctx_len + ckpt.remaining + 1, self.max_len)
        return max(self._pages_needed(worst), ckpt.n_pages)

    def can_restore(self, ckpt: LaneCheckpoint) -> bool:
        if not self.free_lanes():
            return False
        return self.restore_pages(ckpt) <= self.pool.available()

    def restore(self, ckpt: LaneCheckpoint) -> bool:
        """Re-admit a checkpointed request through the normal
        reserve/alloc route and scatter its pages into a fresh block
        table.  Returns False when no lane or pages are available (the
        caller retries after retirements, exactly like admission).

        Restoration does NOT consume an admission index: the lane
        inherits the checkpoint's ``lane_seed``/``tok_idx``, so the
        resumed RNG stream continues bit-identically, and the first
        resumed step consumes the checkpoint's pre-sampled token
        instead of re-sampling from a prefill.
        """
        invariant(self.paged, "evict/restore: paged engines only")
        invariant(ckpt.page_size == self.page_size,
                  "checkpoint page size does not match this engine",
                  ckpt_page_size=ckpt.page_size,
                  page_size=self.page_size)
        lanes = self.free_lanes()
        if not lanes:
            return False
        lane = lanes[0]
        need = self.restore_pages(ckpt)
        if not self.pool.reserve(need):
            if ckpt.uid not in self._blocked_uids:
                self._blocked_uids.add(ckpt.uid)
                self.stats["kv_admit_blocked"] += 1
            return False
        self._blocked_uids.discard(ckpt.uid)
        self._lane_reserved[lane] = need
        self._lane_pages[lane] = []
        restore_span = self.tracer.span(
            "preempt.restore", track=self.lane_track(lane),
            uid=ckpt.uid, lane=lane, n_pages=ckpt.n_pages)
        try:
            with restore_span:
                self._map_pages(lane, ckpt.n_pages)
                for i, page in enumerate(self._lane_pages[lane]):
                    for key, val in ckpt.kv_pages.items():
                        self._write_page(key, page,
                                         jnp.asarray(val[:, i:i + 1]))
                for key, val in ckpt.ssm_state.items():
                    self.cache[key] = self.cache[key].at[:, lane].set(
                        jnp.asarray(val))
                s = self._sanitizer
                if s is not None and self._lane_pages[lane]:
                    s.record("write", lane=lane,
                             pages=list(self._lane_pages[lane]),
                             kind="restore")
        except Exception:
            # scatter failure (e.g. a checkpoint whose payload does not
            # match this engine's cache layout): the reservation and any
            # already-mapped pages MUST return to the pool, or they leak
            # -- the lane looks free but its pages stay in-use forever
            self.pool.free(self._lane_pages[lane], holder=lane)
            self.pool.unreserve(self._lane_reserved[lane])
            self._lane_pages[lane] = []
            self._lane_reserved[lane] = 0
            self.cache["len"] = self.cache["len"].at[lane].set(0)
            if "block_tables" in self.cache:
                self.cache["block_tables"] = (
                    self.cache["block_tables"].at[lane]
                    .set(self._scratch_page))
            raise
        self.cache["len"] = self.cache["len"].at[lane].set(ckpt.ctx_len)
        self._len_host[lane] = ckpt.ctx_len
        self._lane_seed = self._lane_seed.at[lane].set(ckpt.lane_seed)
        self._tok_idx = self._tok_idx.at[lane].set(ckpt.tok_idx)
        self._next_token = self._next_token.at[lane].set(ckpt.next_token)
        self._remaining = self._remaining.at[lane].set(ckpt.remaining)
        self._remaining_host[lane] = ckpt.remaining
        self.lane_req[lane] = ckpt.req
        self.stats["restores"] += 1
        self.stats["pages_migrated"] += ckpt.n_pages
        return True

    def decode_step(self) -> Dict[int, int]:
        """Single-token compatibility wrapper; returns {uid: token}."""
        return {uid: seq[0] for uid, seq in self.decode_n(1).items() if seq}

    def _never_admissible(self, head: Request) -> AdmissionRejected:
        """Structured terminal refusal: the head request was refused with
        NOTHING in flight, so no retirement can ever free a lane or a
        page.  ``retry_after_s`` is None -- retrying cannot help."""
        self.stats["admit_rejected"] += 1
        return AdmissionRejected(
            uid=head.uid, reason="never_admissible", retry_after_s=None,
            need_pages=(self.admission_pages(head) if self.paged else None),
            pool_pages=(self.pool.n_pages if self.paged else None),
            n_lanes=self.n_lanes)

    def _shed_victim(self) -> Optional[int]:
        """Lane the degradation ladder evicts next: lowest request
        priority first, then largest live context (most pages back)."""
        live = self.live_lanes()
        if len(live) < 2:
            return None          # never shed the last live lane
        return min(live, key=lambda i: (self.lane_req[i].priority,
                                        -self.lane_context(i), i))

    def run(self, requests: List[Request],
            dispatch_n: Optional[int] = None) -> List[Request]:
        """Serve a workload to completion with continuous admission.

        Retirement rides the done-flags returned by the batched dispatch
        (no per-step completion scan over the request list).

        With a :class:`DegradationLadder` attached, sustained page
        pressure or repeated page-blocked admissions escalate load
        shedding: the dispatch knob shrinks, new admissions are deferred
        (backpressure), and at the top rung the lowest-priority lane is
        evicted to a checkpoint and re-admitted once pressure clears.

        Raises :class:`AdmissionRejected` (a ``RuntimeError``) when the
        head request can never be admitted and nothing is in flight.
        """
        ladder = self.ladder
        pending = list(requests)
        shed: deque = deque()        # evicted-by-ladder checkpoints
        while pending or shed or any(r is not None for r in self.lane_req):
            # ladder-evicted checkpoints re-enter first (their tokens
            # are paid for; finishing them frees pages fastest) -- but
            # not while the ladder is still at the evict rung with live
            # work, or restore/evict would thrash
            while shed and self.free_lanes():
                if ladder is not None and ladder.should_evict \
                        and self.live_lanes():
                    break
                if not self.restore(shed[0]):
                    break
                shed.popleft()
            while pending and self.free_lanes():
                if ladder is not None and ladder.refusing_admissions \
                        and (self.live_lanes() or shed):
                    # backpressure rung: finish in-flight work before
                    # taking on new requests
                    break
                if not self.admit(pending[0]):
                    # paged: a lane is free but the pages are not --
                    # wait for retirements to refill the pool (a single
                    # request always fits an empty engine, see __init__)
                    if ladder is not None and self.paged:
                        ladder.note_admission_blocked(pending[0].uid)
                        self.stats["degrade_transitions"] = \
                            len(ladder.transitions)
                    break
                if ladder is not None:
                    ladder.note_ok()
                pending.pop(0)
            if not any(r is not None for r in self.lane_req):
                if shed:
                    # every live lane was shed and none can restore:
                    # force the head checkpoint back in (it fit before,
                    # so it fits an empty engine)
                    restored = self.restore(shed[0])
                    invariant(restored, "shed checkpoint no longer "
                              "fits an empty engine",
                              uid=shed[0].uid)
                    shed.popleft()
                    continue
                raise self._never_admissible(pending[0])
            n = dispatch_n if dispatch_n is not None else self.dispatch_n
            if ladder is not None:
                n = ladder.dispatch_n(n)
            self.decode_n(n)
            if ladder is not None:
                if self.paged:
                    pool = self.pool
                    ladder.note_pressure(
                        (pool.n_pages - pool.available()) / pool.n_pages)
                else:
                    ladder.note_ok()
                self.stats["degrade_transitions"] = len(ladder.transitions)
                if ladder.should_evict and self.paged:
                    victim = self._shed_victim()
                    if victim is not None:
                        uid = self.lane_req[victim].uid
                        shed.append(self.evict(victim))
                        self.stats["degrade_sheds"] += 1
                        self.tracer.instant(
                            "degrade.shed", track=self.lane_track(victim),
                            uid=uid, level=ladder.level_name)
        return requests
