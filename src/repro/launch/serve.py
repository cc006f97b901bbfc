"""Serving launcher: quantized continuous-batching inference.

``python -m repro.launch.serve --arch qwen2.5-1.5b --smoke --quant q8_0``
spins up the lane engine on synthetic prompts and reports the served
tokens, the device they ran on and the compile counters, plus the
capability-model prediction for a device profile (the paper's
llama-bench workflow, framework-side).  ``main(argv)`` returns the live
engine, so a caller can drive it further in the same process.
"""

from __future__ import annotations

import argparse
import time
from typing import List, NamedTuple

import jax
import numpy as np

from repro.configs import get_config
from repro.core.device_profile import get_profile
from repro.core.perf_model import InferencePerfModel, LLMSpec
from repro.launch.compile_cache import setup_compilation_cache
from repro.models import build_model
from repro.serving import Request, ServeEngine, dequantize_params, \
    quantize_params


class ServeRun(NamedTuple):
    """What :func:`main` served: its parsed arguments, the engine (still
    live, so a caller can serve more requests on it), and the requests
    with their generated tokens."""
    args: argparse.Namespace
    engine: ServeEngine
    requests: List[Request]


def synthetic_requests(args: argparse.Namespace, vocab_size: int, *,
                       seed: int = 0, uid0: int = 0) -> List[Request]:
    """``args.requests`` random prompts of ``args.prompt_len`` tokens.
    With ``--prefix-sharing`` their first tokens are one head shared by
    every call (``--shared-prefix-len``, default half the prompt); the
    rest are drawn from ``seed``."""
    head_len = 0
    if args.prefix_sharing:
        head_len = args.shared_prefix_len \
            if args.shared_prefix_len is not None else args.prompt_len // 2
        head_len = max(min(head_len, args.prompt_len - 1), 0)
    head = np.random.default_rng(0).integers(0, vocab_size, head_len)
    rng = np.random.default_rng((1, seed))
    return [Request(uid=uid0 + i,
                    prompt=np.concatenate(
                        [head, rng.integers(0, vocab_size,
                                            args.prompt_len - head_len)]
                    ).astype(np.int32),
                    max_new_tokens=args.gen)
            for i in range(args.requests)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default=None,
                    choices=[None, "q8_0", "q6_k", "q4_k", "q2_k"])
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--paged", action="store_true",
                    help="serve over the page-pool KV cache")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="radix prompt cache + copy-on-write page "
                         "sharing (implies --paged)")
    ap.add_argument("--shared-prefix-len", type=int, default=None,
                    help="tokens of common prompt head across the "
                         "synthetic requests (default: half the "
                         "prompt when --prefix-sharing is on)")
    ap.add_argument("--profile", default="tpu-v5e",
                    help="device profile for the analytic prediction")
    args = ap.parse_args(argv)

    print(f"compilation cache: {setup_compilation_cache()}")
    dev = jax.devices()[0]

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.quant:
        qp, stats = quantize_params(params, args.quant)
        print(f"quantized {stats['quantized']} weight matrices "
              f"({stats['quantized_bytes']/1e6:.1f} MB vs dense "
              f"{stats['dense_bytes']/1e6:.1f} MB kept dense)")
        # qmatmul is not on the served path: serve the round-tripped
        # dense weights
        params = dequantize_params(qp)

    reqs = synthetic_requests(args, cfg.vocab_size)

    paged = args.paged or args.prefix_sharing
    max_len = args.prompt_len + args.gen + 8
    if paged:                      # cache capacity is page granular
        max_len = -(-max_len // args.page_size) * args.page_size
    engine = ServeEngine(cfg, params, n_lanes=args.lanes,
                         max_len=max_len,
                         paged=paged, page_size=args.page_size,
                         prefix_sharing=args.prefix_sharing)
    t0 = time.time()
    engine.run(reqs)
    dt = time.time() - t0
    n_gen = sum(len(r.generated) for r in reqs)
    print(f"served {len(reqs)} requests, {n_gen} tokens in {dt:.2f}s "
          f"({n_gen/dt:.1f} tok/s on {dev.platform} {dev.device_kind}, "
          f"compiles included)")
    print(f"compiles: prefill {engine.stats['prefill_compiles']}, "
          f"decode {engine.stats['decode_compiles']} "
          f"(steady state re-serves from the jit cache)")
    if args.prefix_sharing:
        s = engine.stats
        print(f"prefix sharing: {s['prefix_hits']} hits, "
              f"{s['prefix_tokens_matched']} prompt tokens served from "
              f"cached pages, {s['prefix_pages_saved']} prefill pages "
              f"saved, {s['prefix_cow_copies']} copy-on-write splits")

    prof = get_profile(args.profile)
    spec = LLMSpec(name=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
                   n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                   d_ff=cfg.d_ff, vocab_size=cfg.vocab_size,
                   tied_embeddings=cfg.tie_embeddings)
    m = InferencePerfModel(prof, spec)
    fmt = args.quant or "f16"
    print(f"capability-model prediction (analytic) on {prof.name}: "
          f"prefill {m.prefill(fmt).tokens_per_s:,.0f} tok/s, "
          f"decode {m.decode(fmt).tokens_per_s:,.0f} tok/s ({fmt})")
    return ServeRun(args, engine, reqs)


if __name__ == "__main__":
    main()
