"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script

1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod)
   over host CPU devices,
2. constructs ShapeDtypeStruct stand-ins for params / optimizer state /
   batch / cache (zero allocation, ``jax.eval_shape``),
3. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
4. records ``memory_analysis()`` (fits-per-device proof),
   ``cost_analysis()`` (FLOPs / bytes) and HLO-parsed collective bytes,
5. appends a JSON line consumed by ``repro.core.roofline`` and
   EXPERIMENTS.md.

The dry-run is a compile proof on host devices and never takes an
accelerator: :func:`host_devices` pins the process to the CPU backend
with ``REPRO_DRYRUN_DEVICES`` (default 512) devices.  It must run before
anything else in the process initializes a JAX backend.
REPRO_SCAN_UNROLL is toggled per-compile inside run_cell: the scanned
build gives the production memory analysis (remat-aware liveness), the
unrolled build gives per-layer-accurate FLOPs / collective counts.
"""

import argparse
import functools
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, all_cells, get_config,
                           shape_applicable)
from repro.core.hlo_analysis import collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.models.common import ModelConfig
from repro.models.transformer import (init_cache, lm_decode_step,
                                      lm_prefill_batched)
from repro.models.whisper import (decode_forward, encode,
                                  init_whisper_cache, whisper_decode_step)
from repro.parallel.sharding import (batch_shardings, cache_shardings,
                                     param_shardings, use_mesh)
from repro.train import TrainConfig, init_train_state, make_train_step


def host_devices():
    """Pin this process to the CPU backend with ``REPRO_DRYRUN_DEVICES``
    host devices and return them."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices",
                      int(os.environ.get("REPRO_DRYRUN_DEVICES", "512")))
    return jax.devices("cpu")


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    sh = SHAPES[shape_name]
    b, s = sh["global_batch"], sh["seq_len"]
    kind = sh["kind"]
    if kind in ("train", "prefill"):
        specs = {"tokens": _sds((b, s), jnp.int32)}
        if kind == "train":
            specs["labels"] = _sds((b, s), jnp.int32)
        if cfg.is_encdec:
            specs["frames"] = _sds((b, s, cfg.d_model), jnp.float32)
        if cfg.n_vision_tokens:
            specs["vision_embeds"] = _sds(
                (b, cfg.n_vision_tokens, cfg.d_model), jnp.float32)
        return specs
    # decode: one new token against a seq_len-deep cache
    return {"tokens": _sds((b,), jnp.int32)}


def _abstract_params(model, serve_dtype=None):
    sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if serve_dtype is not None:
        # serving holds bf16 weights (no optimizer master copies)
        sds = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, serve_dtype
                if jnp.issubdtype(x.dtype, jnp.floating) else x.dtype), sds)
    return sds


def _attn_scan_correction(cfg: ModelConfig, shape_name: str) -> float:
    """Blockwise attention runs a lax.scan over KV blocks whose body XLA
    cost-analysis counts once; add the (nblk-1)/nblk remainder
    analytically.  4*B*H*hd*Sq*Sk flops per layer (QK^T + PV), x3 for
    train (fwd + bwd)."""
    if cfg.family == "ssm":
        return 0.0
    sh = SHAPES[shape_name]
    b, s = sh["global_batch"], sh["seq_len"]
    if sh["kind"] == "decode":
        return 0.0                      # decode attention has no scan
    block = 512
    sk_counted = min(block, s)
    per_layer = 4.0 * b * cfg.n_heads * cfg.hd * s * (s - sk_counted)
    mult = 3.0 if sh["kind"] == "train" else 1.0
    n_attn_layers = cfg.n_layers + cfg.n_encoder_layers
    if cfg.is_encdec:
        n_attn_layers += cfg.n_layers   # cross-attention
    return mult * per_layer * n_attn_layers


def _model_flops(cfg: ModelConfig, shape_name: str) -> float:
    sh = SHAPES[shape_name]
    n_active = cfg.active_params()
    tokens = sh["global_batch"] * (sh["seq_len"]
                                   if sh["kind"] != "decode" else 1)
    if sh["kind"] == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


# ----------------------------------------------------------------------
# per-kind lowering
# ----------------------------------------------------------------------

def lower_train(cfg: ModelConfig, mesh, shape_name: str,
                microbatches: int = None):
    model = build_model(cfg)
    if microbatches is None:
        microbatches = int(os.environ.get("REPRO_MICROBATCHES", "8"))
    # >=100B-param archs cannot hold f32 Adam moments at 16 GiB/chip
    # (arctic: 5.7 TB state vs 4 TB/pod); they train with 8-bit moments.
    moment_dtype = os.environ.get(
        "REPRO_OPT_QUANT",
        "int8" if cfg.total_params() > 1e11 else "f32")
    from repro.optim import AdamWConfig
    tcfg = TrainConfig(optimizer=AdamWConfig(moment_dtype=moment_dtype),
                       remat=True, microbatches=microbatches)
    step = make_train_step(cfg, tcfg)
    state_sds = jax.eval_shape(
        functools.partial(init_train_state, model,
                          moment_dtype=moment_dtype),
        jax.random.PRNGKey(0))
    batch_sds = input_specs(cfg, shape_name)
    state_sh = param_shardings(mesh, state_sds)
    batch_sh = batch_shardings(mesh, batch_sds)
    jit = jax.jit(step,
                  in_shardings=(state_sh, batch_sh),
                  out_shardings=(state_sh, None),
                  donate_argnums=(0,))
    with use_mesh(mesh):
        return jit.lower(state_sds, batch_sds)


def lower_prefill(cfg: ModelConfig, mesh, shape_name: str):
    model = build_model(cfg)
    params_sds = _abstract_params(model, serve_dtype=jnp.bfloat16)
    specs = input_specs(cfg, shape_name)
    p_sh = param_shardings(mesh, params_sds)
    b_sh = batch_shardings(mesh, specs)

    if cfg.is_encdec:
        def step(params, batch):
            enc = encode(params, batch["frames"], cfg)
            logits = decode_forward(params, batch["tokens"], enc, cfg)
            return logits[:, -1]
        jit = jax.jit(step, in_shardings=(p_sh, b_sh))
        with use_mesh(mesh):
            return jit.lower(params_sds, specs)

    def step(params, batch):
        return lm_prefill_batched(params, batch["tokens"], cfg,
                                  vision_embeds=batch.get("vision_embeds"))

    # out shardings: logits sharded (batch, vocab); kv cache like a cache
    out_sds = jax.eval_shape(step, params_sds, specs)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_ax = dp if len(dp) > 1 else (dp[0] if dp else None)
    sh_b = SHAPES[shape_name]["global_batch"]

    def kv_sharding(leaf):
        if leaf is None:
            return None
        spec = [None] * leaf.ndim
        if leaf.ndim == 5:  # (L, B, Hkv, S, D)
            if dp_ax and sh_b % _axsize(mesh, dp_ax) == 0:
                spec[1] = dp_ax
            if leaf.shape[3] % mesh.shape.get("model", 1) == 0:
                spec[3] = "model"
        return NamedSharding(mesh, P(*spec))

    logits_sh = NamedSharding(mesh, P(
        dp_ax if sh_b % _axsize(mesh, dp_ax) == 0 else None, "model"))
    kv_sh = jax.tree_util.tree_map(kv_sharding, out_sds[1])
    jit = jax.jit(step, in_shardings=(p_sh, b_sh),
                  out_shardings=(logits_sh, kv_sh))
    with use_mesh(mesh):
        return jit.lower(params_sds, specs)


def _axsize(mesh, axes):
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def lower_decode(cfg: ModelConfig, mesh, shape_name: str):
    # hillclimb knob: REPRO_KV_QUANT=int8 lowers the decode cell with the
    # quantized KV cache (SSPerf hillclimb 3)
    kvq = os.environ.get("REPRO_KV_QUANT")
    if kvq:
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_quant=kvq)
    model = build_model(cfg)
    sh = SHAPES[shape_name]
    b, s = sh["global_batch"], sh["seq_len"]
    params_sds = _abstract_params(model, serve_dtype=jnp.bfloat16)
    p_sh = param_shardings(mesh, params_sds, mode="serve")
    tok_sds = _sds((b,), jnp.int32)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_ax = dp if len(dp) > 1 else (dp[0] if dp else None)
    tok_sh = NamedSharding(mesh, P(
        dp_ax if (dp_ax and b % _axsize(mesh, dp_ax) == 0) else None))

    if cfg.is_encdec:
        enc_sds = _sds((b, s, cfg.d_model), cfg.compute_dtype)
        cache_sds = jax.eval_shape(
            lambda p, e: init_whisper_cache(p, e, cfg, b, s),
            params_sds, enc_sds)
        def step(params, cache, tokens):
            return whisper_decode_step(params, cfg, cache, tokens)
    else:
        cache_sds = jax.eval_shape(
            functools.partial(init_cache, cfg, b, s))

        def step(params, cache, tokens):
            return lm_decode_step(params, cfg, cache, tokens)

    c_sh = cache_shardings(mesh, cache_sds)
    logits_sh = NamedSharding(mesh, P(
        dp_ax if (dp_ax and b % _axsize(mesh, dp_ax) == 0) else None,
        "model"))
    jit = jax.jit(step, in_shardings=(p_sh, c_sh, tok_sh),
                  out_shardings=(logits_sh, c_sh), donate_argnums=(1,))
    with use_mesh(mesh, mode="serve"):
        return jit.lower(params_sds, cache_sds, tok_sds)


_LOWER = {"train": lower_train, "prefill": lower_prefill,
          "decode": lower_decode}


def _cost_dict(compiled) -> dict:
    """Normalize Compiled.cost_analysis() across jax versions: newer
    releases return a list with one dict per partition (all identical on
    an SPMD module); older ones return the dict directly."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        return dict(cost[0]) if cost else {}
    return dict(cost or {})


# ----------------------------------------------------------------------
# cell runner
# ----------------------------------------------------------------------

def _compile_once(cfg, mesh, shape_name, unroll: bool,
                  microbatches: int = None, moe_chunk: int = None):
    """One lower+compile. ``microbatches=1`` is used by the cost passes:
    the gradient-accumulation lax.scan body is counted once by XLA's
    cost analysis, so per-step FLOPs/bytes must be measured on the
    single-batch schedule (numerically the same totals)."""
    kind = SHAPES[shape_name]["kind"]
    prev = os.environ.get("REPRO_SCAN_UNROLL")
    prev_mb = os.environ.get("REPRO_MICROBATCHES")
    prev_mc = os.environ.get("REPRO_MOE_CHUNK")
    os.environ["REPRO_SCAN_UNROLL"] = "1" if unroll else "0"
    if microbatches is not None:
        os.environ["REPRO_MICROBATCHES"] = str(microbatches)
    if moe_chunk is not None:
        os.environ["REPRO_MOE_CHUNK"] = str(moe_chunk)
    try:
        t0 = time.time()
        lowered = _LOWER[kind](cfg, mesh, shape_name)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    finally:
        for k, v in (("REPRO_SCAN_UNROLL", prev),
                     ("REPRO_MICROBATCHES", prev_mb),
                     ("REPRO_MOE_CHUNK", prev_mc)):
            if k == "REPRO_SCAN_UNROLL" and v is None:
                os.environ.pop(k, None)
            elif v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return compiled, t_lower, t_compile


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, cost_pass: bool = True) -> dict:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod,
                                devices=host_devices())
    chips = mesh.size
    kind = SHAPES[shape_name]["kind"]

    # pass 1 -- production (scanned) build: memory analysis + fallback cost
    compiled, t_lower, t_compile = _compile_once(cfg, mesh, shape_name,
                                                 unroll=False)
    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled)
    coll = collective_bytes(compiled.as_text())

    # pass 2 -- unrolled build (single-pod only): per-layer-accurate
    # FLOPs / bytes / collective census for the roofline table.  Only the
    # dense-family train/prefill graphs unroll tractably on XLA-CPU (the
    # SSD chunk machinery, MoE dispatch, and the decode cache DUS chains
    # explode compiler time/RAM when multiplied by n_layers); all other
    # cells use the scanned build with an analytic xL correction,
    # validated against unrolled numbers on the dense archs (SSRoofline
    # notes in EXPERIMENTS.md).
    can_unroll = (cfg.family in ("dense", "vlm", "audio")
                  and kind in ("train", "prefill"))
    cost_method = "scanned"
    if cost_pass and can_unroll:
        compiled_u, _, t_u = _compile_once(cfg, mesh, shape_name,
                                           unroll=True, microbatches=1)
        cost = _cost_dict(compiled_u) or cost
        coll = collective_bytes(compiled_u.as_text())
        t_compile += t_u
        del compiled_u
        cost_method = "unrolled"
    elif cost_pass:
        # MoE/SSD cells: re-measure on the single-microbatch, un-chunked
        # scanned schedule (compile-only: memory does not matter here)
        # before the xL scaling below.
        compiled_1, _, t_1 = _compile_once(cfg, mesh, shape_name,
                                           unroll=False, microbatches=1,
                                           moe_chunk=0)
        cost = _cost_dict(compiled_1) or cost
        coll = collective_bytes(compiled_1.as_text())
        t_compile += t_1
        del compiled_1

    # cost_analysis is per-partition on the SPMD module -> whole-step
    flops_raw = float(cost.get("flops", 0.0)) * chips
    bytes_raw = float(cost.get("bytes accessed", 0.0)) * chips
    coll_total = float(coll.total_bytes) * chips
    if cost_pass and not can_unroll:
        # scanned build counts the while body once: scale by n_layers,
        # holding out the (one-shot) embedding/logits head terms.
        sh = SHAPES[shape_name]
        L = cfg.n_layers + cfg.n_encoder_layers
        if kind == "train":
            tokens = sh["global_batch"] * sh["seq_len"]
            head_f = 6.0 * cfg.d_model * cfg.padded_vocab * tokens
            head_b = 3.0 * cfg.d_model * cfg.padded_vocab * 2.0
        else:
            tokens = sh["global_batch"]
            head_f = 2.0 * cfg.d_model * cfg.padded_vocab * tokens
            head_b = 1.0 * cfg.d_model * cfg.padded_vocab * 2.0
        flops_raw = max(flops_raw - head_f, 0.0) * L + head_f
        bytes_raw = max(bytes_raw - head_b, 0.0) * L + head_b
        coll_total = coll_total * L
        cost_method = "scanned_xL"
    flops = flops_raw + _attn_scan_correction(cfg, shape_name)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": kind, "chips": chips,
        "hlo_flops": flops,
        "hlo_flops_raw": flops_raw,
        "hlo_bytes": bytes_raw,
        "collective_bytes": coll_total,
        "cost_method": cost_method,
        "collectives": coll.bytes_by_kind,
        "collective_counts": coll.count_by_kind,
        "model_flops": _model_flops(cfg, shape_name),
        "microbatches": int(os.environ.get("REPRO_MICROBATCHES", "8"))
        if kind == "train" else 1,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[attr] = int(v)
        # live set = args + temps + outputs - donated aliases
        total = (rec.get("argument_size_in_bytes", 0)
                 + rec.get("temp_size_in_bytes", 0)
                 + rec.get("output_size_in_bytes", 0)
                 - rec.get("alias_size_in_bytes", 0))
        rec["bytes_per_device"] = total
        rec["fits_16g"] = total < 16 * 1024**3
    if verbose:
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "hlo_flops",
                           "collective_bytes", "compile_s")}, default=str))
        print("  memory:", {k: rec.get(k) for k in
                            ("argument_size_in_bytes", "temp_size_in_bytes",
                             "bytes_per_device", "fits_16g")})
        print("  collectives:", coll.summary())
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default all)")
    ap.add_argument("--shape", default=None, help="one shape (default all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    ap.add_argument("--append", action="store_true")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already recorded (resume a run)")
    args = ap.parse_args()
    done = set()
    if args.skip_done and os.path.exists(args.out):
        for line in open(args.out):
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "error" not in r:
                done.add((r["arch"], r["shape"], r["mesh"]))
        args.append = True

    cells = all_cells()
    if args.arch:
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape:
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    mode = "a" if args.append else "w"
    failures = []
    with open(args.out, mode) as f:
        for multi_pod in meshes:
            for arch, shape in cells:
                mesh_name = "2x16x16" if multi_pod else "16x16"
                tag = f"{arch}/{shape}/{mesh_name}"
                if (arch, shape, mesh_name) in done:
                    continue
                print(f"=== {tag}")
                try:
                    rec = run_cell(arch, shape, multi_pod,
                                   cost_pass=not multi_pod)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    traceback.print_exc()
                    f.write(json.dumps({"arch": arch, "shape": shape,
                                        "mesh": tag.split("/")[-1],
                                        "error": repr(e)}) + "\n")
                    f.flush()
    print(f"\n{len(cells) * len(meshes) - len(failures)} cells OK, "
          f"{len(failures)} failed")
    for tag, err in failures:
        print("FAILED:", tag, err)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
