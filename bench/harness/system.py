"""The system under test: ``repro.serving.ServeEngine`` built from a
configuration file.  This is the one module of the benchmark that
imports the program; everything else takes what it measures from here.
"""

from __future__ import annotations

import dataclasses
import os
import sys

from harness.model_config import Arch

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if os.path.join(REPO, "src") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "src"))

from repro.launch.compile_cache import setup_compilation_cache  # noqa: E402,F401
from repro.models.common import ModelConfig, MoEConfig  # noqa: E402
from repro.serving import Request, ServeEngine  # noqa: E402,F401


class UnsupportedByProgram(Exception):
    """The program has no field for a part of the configuration's
    block: serving the model without it would serve another model."""


def program_config(arch: Arch) -> ModelConfig:
    """The program's ``ModelConfig`` for ``arch``.  The configuration's
    norm epsilon goes in as ``norm_eps`` where ``ModelConfig`` has a
    field of that name; a program without one gets the config it always
    got, and applies its own epsilon.

    A ``deepseek_v3`` configuration (``arch.mla`` set) builds
    ``family="moe"`` with these further fields, each of which the
    program has to have, or ``UnsupportedByProgram`` names the missing
    ones:

    - ``ModelConfig``: ``attention="mla"``, ``kv_lora_rank``,
      ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
      ``kv_norm_eps`` (the latent's RMSNorm), ``n_dense_layers`` (the
      leading dense layers, of width ``d_ff``) and
      ``rope_interleaved=True`` (DeepSeek's RoPE pairing);
    - ``MoEConfig``: ``n_experts`` (the published count, the router's
      width), ``top_k``, ``d_expert_ff``, ``n_shared_experts``,
      ``experts_held`` (how many experts this chip holds),
      ``first_expert`` (the id of the first of them),
      ``score_func="sigmoid"`` (top-k on score + the router's bias,
      weights from the unbiased scores), ``routed_scale`` and
      ``norm_topk_prob``.
    """
    have = {f.name for f in dataclasses.fields(ModelConfig)}
    extra = {}
    if "norm_eps" in have:
        extra["norm_eps"] = arch.norm_eps
    if arch.mla is not None:
        m, e = arch.mla, arch.moe
        latent = dict(
            attention="mla", kv_lora_rank=m.kv_lora_rank,
            qk_nope_head_dim=m.qk_nope_head_dim,
            qk_rope_head_dim=m.qk_rope_head_dim, v_head_dim=m.v_head_dim,
            kv_norm_eps=m.kv_norm_eps,
            n_dense_layers=e.first_k_dense_replace, rope_interleaved=True)
        routing = dict(
            n_experts=e.router_experts, top_k=e.num_experts_per_tok,
            d_expert_ff=e.moe_intermediate_size,
            n_shared_experts=e.n_shared_experts,
            experts_held=e.experts_held, first_expert=e.first_expert,
            score_func=e.scoring_func,
            routed_scale=e.routed_scaling_factor,
            norm_topk_prob=e.norm_topk_prob)
        have_moe = {f.name for f in dataclasses.fields(MoEConfig)}
        missing = ([f"ModelConfig.{k}" for k in latent if k not in have]
                   + [f"MoEConfig.{k}" for k in routing
                      if k not in have_moe])
        if missing:
            raise UnsupportedByProgram(
                f"{arch.name}: the program has no field for "
                + ", ".join(missing))
        extra.update(latent, moe=MoEConfig(**routing))
    return ModelConfig(
        name=arch.name, family="dense" if arch.moe is None else "moe",
        n_layers=arch.n_layers, d_model=arch.d_model, n_heads=arch.n_heads,
        n_kv_heads=arch.n_kv_heads, d_ff=arch.d_ff,
        vocab_size=arch.vocab_size, head_dim=arch.head_dim_override,
        qkv_bias=arch.qkv_bias, norm=arch.norm,
        tie_embeddings=arch.tie_embeddings, rope_theta=arch.rope_theta,
        max_seq_len=arch.max_position_embeddings,
        dtype=arch.compute_dtype, **extra)


def build_engine(arch: Arch, params) -> ServeEngine:
    s = arch.serving
    return ServeEngine(
        program_config(arch), params, n_lanes=s["n_lanes"],
        max_len=s["max_len"], temperature=s["temperature"],
        dispatch_n=s["dispatch_n"], paged=True, page_size=s["page_size"],
        n_pages=s["n_pages"], prefix_sharing=s["prefix_sharing"],
        name="serve")


def counters(engine: ServeEngine) -> dict:
    """Every counter and gauge in the engine's registry under its name
    (``<engine.name>.``), keyed without that prefix; histograms, which
    hold no single value, are left out.  A reader looks up the names it
    needs and finds nothing under a name the program does not keep."""
    prefix = f"{engine.name}."
    reg = engine.registry
    return {n[len(prefix):]: reg[n].value for n in reg.names()
            if n.startswith(prefix) and reg[n].kind != "histogram"}


def next_token_array(engine: ServeEngine):
    """The device array of each lane's next input token.  Right after
    ``admit`` the admitted lane holds the answer's first token, sampled
    from the prefill logits; the engine replaces the array (it never
    writes into it), so a reference taken then keeps that token."""
    return engine._next_token


def lane_remaining(engine: ServeEngine, lane: int) -> int:
    """Tokens the lane still owes (host mirror, no sync)."""
    return int(engine._remaining_host[lane])


def pages_held(engine: ServeEngine, lanes) -> int:
    """Pool pages the given lanes map (a page shared by several counted
    once) plus the pages they still have reserved: what admission had to
    find free for them."""
    mapped = set()
    for i in lanes:
        mapped.update(engine._lane_pages[i])
    return len(mapped) + sum(engine._lane_reserved[i] for i in lanes)
