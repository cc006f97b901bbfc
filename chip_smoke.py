"""Chip smoke: drive the served path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: sharded training only

One chip runs three phases in one process:

1. device -- JAX must find a TPU;
2. kernel parity -- the paged decode op the model calls (the Pallas
   kernel on TPU) against the jnp reference, at the served widths of
   qwen2.5-1.5b, for a bf16 and an f32 page pool;
3. served path -- ``repro.launch.serve.main`` at the full published
   width of qwen2.5-1.5b (random weights from a seed), paged with prefix
   sharing: every request gets its tokens, prefixes hit, the pool drains
   back to what the prefix cache holds, the compiled decode program
   holds the Pallas kernel, and a second batch compiles nothing new.

``--chips 4`` runs sharded training of full-width olmo-1b on a
(data=2, model=2) mesh through ``repro.launch.train``'s mesh path, and
the same sharded step against a one-chip step at ``n_layers=2``.

Any failed check exits non-zero.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``.  Times and bytes printed are
smoke figures of one run, compiles included, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data.pipeline import DataConfig, synth_batch  # noqa: E402
from repro.kernels.decode_attention.ops import \
    decode_attention_paged  # noqa: E402
from repro.kernels.decode_attention.ref import \
    decode_attention_paged_ref  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import setup_compilation_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.parallel.sharding import use_mesh  # noqa: E402
from repro.train import TrainConfig, make_train_step  # noqa: E402

#: the paper's model and the served-path run the one-chip smoke drives
SERVE_ARGV = ["--arch", "qwen2.5-1.5b", "--paged", "--prefix-sharing",
              "--page-size", "16", "--lanes", "8", "--requests", "16",
              "--prompt-len", "512", "--shared-prefix-len", "256",
              "--gen", "64"]
#: bf16 output of an f32-accumulated attention, against an f32 reference
PARITY_ATOL = 2e-2
#: the four-chip run: full-width olmo-1b, a few steps on one batch
TRAIN_ARCH, TRAIN_MESH, TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = \
    "olmo-1b", "2,2", 5, 8, 256
#: sharded vs one-chip loss, as in tests/test_sharded.py
LOSS_RTOL = 2e-2


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}")


class CompileTimer:
    """Counts and times the backend compiles in a ``with`` block (every
    executable JAX builds, persistent-cache hits included)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def device_phase(chips: int = 1):
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{d.platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX found "
                         f"{len(devs)} devices")
    return devs


def kernel_parity_phase() -> float:
    """Paged decode op vs the f32 jnp reference at qwen2.5-1.5b widths,
    on one layer of a two-layer stacked pool: the new token's row is
    written in place and attended.  Returns the largest abs error over
    both pool dtypes."""
    cfg = get_config("qwen2.5-1.5b")
    h, hkv, d, ps = cfg.n_heads, cfg.n_kv_heads, cfg.hd, 16
    b, t, n_layers, layer = 8, 37, 2, 1  # served lanes, 592 positions
    n_pages = b * t + 23
    rng = np.random.default_rng(0)
    # shuffled, disjoint page sets: physical naming must not matter
    bt = jnp.asarray(rng.permutation(n_pages)[:b * t].reshape(b, t),
                     jnp.int32)
    # dead lane (its new key only), one key, sub-page, page edges, mid,
    # full table; the new token is each lane's last key
    lens = jnp.asarray([1, 1, 15, 16, 17, 255, 300, t * ps], jnp.int32)
    pos = lens - 1
    kq, kk, kv, kn = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (b, h, d), jnp.bfloat16)
    k_new, v_new = jax.random.normal(kn, (2, b, hkv, d), jnp.bfloat16)
    worst = 0.0
    for pool_dtype in (jnp.bfloat16, jnp.float32):
        shape = (n_layers, n_pages, hkv, ps, d)
        k = jax.random.normal(kk, shape, pool_dtype)
        v = jax.random.normal(kv, shape, pool_dtype)
        with jax.default_matmul_precision("highest"):
            ref, k_ref, v_ref = jax.jit(decode_attention_paged_ref)(
                q.astype(jnp.float32), k_new, v_new, k.astype(jnp.float32),
                v.astype(jnp.float32), bt, lens, pos, layer)
        out, k_out, v_out = decode_attention_paged(q, k_new, v_new, k, v,
                                                   bt, lens, pos, layer)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
        name = jnp.dtype(pool_dtype).name
        print(f"kernel parity ({name} pool): max abs error {err!r}")
        check(math.isfinite(err) and err <= PARITY_ATOL,
              f"paged decode matches the reference within {PARITY_ATOL} "
              f"({name} pool)")
        check(bool(jnp.array_equal(k_out.astype(jnp.float32), k_ref)
                   and jnp.array_equal(v_out.astype(jnp.float32), v_ref)),
              f"the new rows are written in place, nothing else "
              f"({name} pool)")
        dead = jnp.repeat(v_new[0].astype(jnp.float32), h // hkv, axis=0)
        check(float(jnp.max(jnp.abs(out[0].astype(jnp.float32) - dead)))
              <= PARITY_ATOL, f"dead lane reads its new value ({name} pool)")
        worst = max(worst, err)
    return worst


def served_phase(argv=SERVE_ARGV) -> dict:
    """Serve through the launcher in this process, then check the run and
    serve a second batch on the same engine."""
    t0 = time.perf_counter()
    with CompileTimer() as first:
        run = serve.main(list(argv))
    wall_first = time.perf_counter() - t0
    eng, reqs = run.engine, run.requests
    check(all(len(r.generated) == r.max_new_tokens for r in reqs),
          f"all {len(reqs)} requests got their tokens")
    hits = eng.stats["prefix_hits"]
    check(hits > 0, f"prefix hits above 0 ({hits})")
    pool, cached = eng.pool, eng.prefix_cache.n_pages
    pool.check()
    check(pool.n_in_use == cached and pool.available() == pool.n_free,
          f"pool drained: {pool.n_free} of {pool.n_pages} pages free, "
          f"{cached} held by the prefix cache")

    # a second batch with the same shapes (same shared head, new tails)
    # must re-serve from the jit cache
    keys = ("prefill_compiles", "ssm_prefill_compiles", "decode_compiles")
    before = {k: eng.stats[k] for k in keys}
    reqs2 = serve.synthetic_requests(run.args, eng.cfg.vocab_size, seed=1,
                                     uid0=len(reqs))
    t0 = time.perf_counter()
    with CompileTimer() as second:
        eng.run(reqs2)
    wall_second = time.perf_counter() - t0
    check(all(len(r.generated) == r.max_new_tokens for r in reqs2),
          f"second batch: all {len(reqs2)} requests got their tokens")
    after = {k: eng.stats[k] for k in keys}
    check(after == before, f"second batch compiled no prefill or decode "
          f"program ({before} -> {after})")
    check(second.count == 0, f"second batch built no executable at all "
          f"({second.count})")

    dev = jax.devices()[0]
    n_kernel = eng.decode_program_text().count("tpu_custom_call")
    want = dev.platform == "tpu"
    check((n_kernel > 0) == want,
          f"decode program holds {n_kernel} tpu_custom_call on "
          f"{dev.platform} (the Pallas kernel on TPU only)")

    stats = dev.memory_stats() or {}
    summary = {
        "first_batch_wall_s": wall_first,
        "first_batch_compiles": first.count,
        "first_batch_compile_s": first.seconds,
        "second_batch_wall_s": wall_second,
        "second_batch_compiles": second.count,
        "tokens": sum(len(r.generated) for r in reqs + reqs2),
        "prefix_hits": eng.stats["prefix_hits"],
        "decode_tpu_custom_calls": n_kernel,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    print("smoke figures (one run, compiles included; not benchmark "
          "numbers): "
          + json.dumps(summary))
    return summary


def sharded_train_phase() -> dict:
    """Full-width sharded training on a repeated batch, then the same
    sharded step against a one-device step at ``n_layers=2``."""
    cfg = get_config(TRAIN_ARCH)
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=3e-4, warmup_steps=1,
                                             total_steps=TRAIN_STEPS),
                       remat=True, microbatches=1)
    jb = {k: jnp.asarray(v) for k, v in synth_batch(
        DataConfig(cfg.vocab_size, TRAIN_SEQ, TRAIN_BATCH), 0).items()}
    mesh = train.parse_mesh(TRAIN_MESH)

    def losses_of(cfg, mesh, n):
        model = build_model(cfg)
        step, state = train.jit_train_step(
            make_train_step(cfg, tcfg),
            train.init_state(model, mesh), mesh)
        out = []
        with use_mesh(mesh):
            for _ in range(n):
                state, m = step(state, jb)
                out.append(float(m["loss"]))
        return out

    t0 = time.perf_counter()
    with CompileTimer() as ct:
        losses = losses_of(cfg, mesh, TRAIN_STEPS)
    print(f"sharded {cfg.name} on mesh {dict(mesh.shape)}: losses {losses}")
    check(all(math.isfinite(x) for x in losses), "losses are finite")
    check(losses[-1] < losses[0],
          f"loss falls on a repeated batch ({losses[0]!r} -> "
          f"{losses[-1]!r})")

    small = dataclasses.replace(cfg, n_layers=2)
    l_one = losses_of(small, None, 1)[0]
    l_mesh = losses_of(small, mesh, 1)[0]
    rel = abs(l_one - l_mesh) / abs(l_one)
    print(f"n_layers=2: one-device loss {l_one!r}, sharded {l_mesh!r}, "
          f"relative error {rel!r}")
    check(rel < LOSS_RTOL, f"sharded step matches one device "
          f"(rel err < {LOSS_RTOL})")
    summary = {"losses": losses, "loss_rel_err_n_layers_2": rel,
               "wall_s": time.perf_counter() - t0,
               "compiles": ct.count, "compile_s": ct.seconds,
               "peak_bytes_in_use": [
                   (d.memory_stats() or {}).get("peak_bytes_in_use")
                   for d in mesh.devices.flat]}
    print("smoke figures (one run, compiles included; not benchmark "
          "numbers): "
          + json.dumps(summary))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: sharded training only, on four chips")
    args = ap.parse_args(argv)
    print(f"compilation cache: {setup_compilation_cache()}")
    devs = device_phase(args.chips)
    if args.chips == 4:
        sharded_train_phase()
    else:
        kernel_parity_phase()
        served_phase()
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
