"""The observability namespace, declared in one place.

Every span, instant, event, and metric name the serving stack emits is
cataloged here, plus the request-timeline schema keys
(:meth:`~repro.obs.requests.RequestTimeline.as_dict`).  Two consumers:

* ``docs/observability.md`` must mention every declared name -- the
  schema snapshot test fails ``make check`` when a new name ships
  undocumented (or a documented name disappears from this catalog);
* ``repro.obs.dump`` uses the catalogs to classify records when it
  summarizes a trace/flight/pages file.

Names with a ``<engine>`` / ``<model>`` placeholder are PREFIX
families: the live name substitutes the engine or model id (e.g.
``serve.decode_dispatches``, ``node0.pool.pages.free``).
"""

from __future__ import annotations

from typing import Dict, List

__all__ = [
    "SPAN_NAMES",
    "INSTANT_NAMES",
    "EVENT_NAMES",
    "METRIC_FAMILIES",
    "TIMELINE_KEYS",
    "FLIGHT_RECORD_KINDS",
    "all_names",
]

#: closed intervals on engine/lane/board tracks
SPAN_NAMES: Dict[str, str] = {
    "admit.reserve": "admission's prefix match, cache trim and page "
                     "reservation, before admit (uid, lane, ok)",
    "admit": "one admission: prefill + lane/page setup (uid, lane)",
    "prefill.bucket": "host enqueue of the batched prefill at a padded "
                      "bucket length plus the eager page scatter, not "
                      "device time (lane)",
    "prefill.launch": "enqueue of the jitted batched prefill (lane)",
    "prefill.scatter": "eager per-page scatter of the prompt KV (lane)",
    "prefix.insert": "radix prompt-cache insert of a lane's prompt "
                     "pages (lane)",
    "prefix.tail_prefill": "prefix-hit tail streamed through decode",
    "prefix.cow": "copy-on-write split of a shared page",
    "decode.dispatch": "one multi-step jitted decode dispatch "
                       "(n_steps, n_live, uids)",
    "decode.prepare": "page mapping, block-table writes and sanitizer "
                      "shadow writes before the launch",
    "decode.launch": "the jitted multi-step decode call until it "
                     "returns",
    "decode.wait": "the device_get drain of a dispatch's tokens",
    "decode.retire": "per-lane token bookkeeping, first_token/retire "
                     "instants, the SLO feed and lane release",
    "preempt.evict": "lane checkpointed off the board (uid, n_pages)",
    "preempt.restore": "checkpoint scattered back onto a lane "
                       "(uid, n_pages)",
    "weights.swap": "model-pool weight swap on the serving engine",
    "sim.prefill": "simulated prefill residency (uid, prompt_len)",
    "sim.decode": "simulated decode residency (uid, gen_len)",
    "sim.swap": "simulated model swap",
    "sim.migrate": "simulated checkpoint migration (uid, pages, dst)",
    "sim.recover": "simulated crash-recovery transfer",
    "sim.fault.derate": "injected thermal derate window",
    "sim.fault.link": "injected host-link degradation window",
    "sim.fault.transient": "injected dispatch stall window",
}

#: zero-duration markers
INSTANT_NAMES: Dict[str, str] = {
    "admit.blocked": "admission refused for pages (uid, need_pages)",
    "prefix.hit": "radix prompt-cache hit (uid, matched_tokens)",
    "first_token": "first generated token surfaced host-side (uid)",
    "retire": "request completed and lane released (uid, gen)",
    "degrade.shed": "ladder-driven eviction of a victim lane (uid)",
    "weights.swap.done": "model-pool swap completed",
    "sim.first_token": "simulated first token (uid)",
    "sim.request_lost": "retry budget exhausted, request dropped (uid)",
    "sim.straggler_detected": "derate flagged by the straggler monitor",
    "sim.fault.crash": "injected fail-stop board crash",
}

#: structured events on the shared EventLog
EVENT_NAMES: Dict[str, str] = {
    "degrade.transition": "degradation-ladder level change",
    "slo.alert": "multi-window burn-rate alert fired",
    "slo.clear": "burn-rate alert cleared (short window recovered)",
    "slo.escalate": "SLO controller escalated the ladder",
    "slo.deescalate": "SLO controller de-escalated the ladder",
    "validate.preemption_exactness": "preemption exactness verdict",
    "validate.recovery_exactness": "crash-recovery exactness verdict",
    "validate.multimodel_exactness": "multi-model exactness verdict",
}

#: metric-name families (prefixes substitute the engine/pool name)
METRIC_FAMILIES: Dict[str, str] = {
    "<engine>.*": "ServeEngine counters (STATS_SCHEMA legacy keys)",
    "<engine>.pool.pages.*": "page-pool gauges (free, in_use, reserved, "
                             "disabled, hwm, allocs, frees, shared, "
                             "cow_splits)",
    "<engine>.prefix.cached_pages": "pages the radix prompt cache holds",
    "<engine>.decode.lane_steps": "decode steps of live lanes: per "
                                  "dispatch, the sum over live lanes of "
                                  "min(n_steps, remaining)",
    "<engine>.kv.reserved_page_steps": "per dispatch, n_steps times the "
                                       "pool's pages reserved and not "
                                       "yet allocated",
    "modelpool.*": "weight-pool gauges (bytes.used, bytes.free, "
                   "residents)",
    "fleet.*": "fleet-sim gauges and fault counters (retry.attempts, "
               "retry.hedges, faults.requests_lost)",
    "slo.*": "burn-rate gauges (burn_rate.short, burn_rate.long) and "
             "counters (violations.ttft, violations.tpot, alerts)",
    "span.<name>.seconds": "per-span duration histograms",
}

#: keys of RequestTimeline.as_dict() -- the request-timeline schema
TIMELINE_KEYS: List[str] = [
    "request_id", "engines", "hops", "t_admit", "t_first_token",
    "t_retire", "ttft_s", "tpot_mean_s", "n_decode_dispatches",
    "pages_touched", "complete", "gaps",
]

#: record kinds inside a flight_<engine>.jsonl dump
FLIGHT_RECORD_KINDS: List[str] = ["span", "instant", "event", "metrics"]


def all_names() -> List[str]:
    """Every declared name, for the docs snapshot test."""
    return (sorted(SPAN_NAMES) + sorted(INSTANT_NAMES)
            + sorted(EVENT_NAMES) + sorted(METRIC_FAMILIES)
            + TIMELINE_KEYS + FLIGHT_RECORD_KINDS)
