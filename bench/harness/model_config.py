"""A configuration file under ``bench/configs/``, read into one record.

The file keeps the published ``config.json`` keys as they are, an
``architecture`` group for what the published keys imply without naming
(the norm, the q/k/v bias, the RoPE pairing), and a ``serving`` group
with the engine's settings for the cells that use it.  A file with no
``serving`` group (one read for its shapes alone) stores its weights in
the type it states.

A file whose ``model_type`` is ``deepseek_v3`` has latent attention
(MLA) and routed experts after ``first_k_dense_replace`` dense layers;
``Arch.mla`` and ``Arch.moe`` hold them (both ``None`` for a dense
file).  Its expert share is stated as the ``model-configs`` guide's
section 4 has it: ``n_routed_experts`` counts the experts held on this
chip (and is listed in ``reduced``), and a ``deployment`` group gives
the published count, the expert-parallel degree and the ids held here::

    "deployment": {"n_routed_experts": 64, "expert_parallel": 8,
                   "experts_here": [0, 8]}

A file with no ``deployment`` group holds every expert.  The router
keeps the published width.  Every published key is read,
checked against the one value the harness implements (a different value
raises ``ValueError`` naming the key: a configuration is never
referenced as a different model), or named in ``INERT_KEYS``.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import jax.numpy as jnp

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(BENCH_DIR, "configs")

ROPES = ("rotate_half", "interleaved")

#: deepseek_v3 keys the harness implements at one value only
DSV3_FIXED = {
    "q_lora_rank": None,          # q straight from the hidden state
    "rope_scaling": None,         # no YaRN, so no softmax mscale
    "n_group": 1,                 # no group-limited routing
    "topk_group": 1,
    "moe_layer_freq": 1,          # every layer after the dense ones
    "scoring_func": "sigmoid",
    "topk_method": "noaux_tc",    # top-k on score + selection bias
    "hidden_act": "silu",
    "attention_bias": False,
}
#: published keys that do not change the served logits
INERT_KEYS = {
    "seq_aux": "the training loss's sequence-wise balance term",
    "ep_size": "the expert parallelism of the publisher's own modelling "
               "code; the deployment group states this chip's share",
    "num_nextn_predict_layers": "multi-token-prediction modules, which "
                                "next-token decoding does not run",
}


@dataclasses.dataclass(frozen=True)
class MLA:
    """Latent attention: k and v decompressed per head from one latent
    of ``kv_lora_rank`` values, with one RoPE key of
    ``qk_rope_head_dim`` shared by all heads.  The latent's RMSNorm
    takes ``kv_norm_eps`` (the publisher's code builds it with its
    norm's default, which ``config.json`` does not name: the file's
    ``architecture`` group states it)."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_norm_eps: float


@dataclasses.dataclass(frozen=True)
class MoE:
    """Routed experts after ``first_k_dense_replace`` dense layers.
    ``router_experts`` is the published count (the router's width);
    this chip holds ``experts_held`` of them, ids ``first_expert`` on."""
    router_experts: int
    experts_held: int
    first_expert: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int
    first_k_dense_replace: int
    scoring_func: str
    routed_scaling_factor: float
    norm_topk_prob: bool


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    max_position_embeddings: int
    rope_theta: float
    norm: str                      # rmsnorm | nonparametric_ln
    norm_eps: float
    qkv_bias: bool
    tie_embeddings: bool
    initializer_range: float
    compute_dtype: str             # what the configuration states
    weights_dtype: str             # what the engine stores
    serving: dict
    head_dim_override: Optional[int] = None
    rope: str = "rotate_half"      # rotate_half | interleaved
    mla: Optional[MLA] = None
    moe: Optional[MoE] = None

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab_size // 256) * 256

    def kv_bytes_per_token(self) -> int:
        itemsize = jnp.dtype(self.compute_dtype).itemsize
        if self.mla is not None:
            return (self.n_layers * (self.mla.kv_lora_rank
                                     + self.mla.qk_rope_head_dim)
                    * itemsize)
        return (2 * self.n_layers * self.n_kv_heads * self.head_dim
                * itemsize)


def load(name_or_path: str) -> Arch:
    path = name_or_path if name_or_path.endswith(".json") else \
        os.path.join(CONFIG_DIR, f"{name_or_path}.json")
    with open(path) as f:
        c = json.load(f)
    a = c["architecture"]
    s = c.get("serving", {})
    rope = a.get("rope", "rotate_half")
    if rope not in ROPES:
        raise ValueError(f"{path}: architecture.rope {rope!r} is not one "
                         f"of {ROPES}")
    mla = moe = None
    if c.get("model_type") == "deepseek_v3":
        mla, moe = _deepseek_v3(c, path)
    return Arch(
        name=c["name"], n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        max_position_embeddings=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm=a["norm"],
        norm_eps=float(c.get("rms_norm_eps", a.get("norm_eps", 1e-5))),
        qkv_bias=bool(a["qkv_bias"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        initializer_range=float(c["initializer_range"]),
        compute_dtype=c["torch_dtype"],
        weights_dtype=s.get("weights_dtype", c["torch_dtype"]),
        serving=s, head_dim_override=c.get("head_dim"), rope=rope,
        mla=mla, moe=moe)


def _deepseek_v3(c: dict, path: str):
    """The latent attention and the expert layout of a ``deepseek_v3``
    file; raises on a key whose value the harness does not implement."""
    for key, want in DSV3_FIXED.items():
        got = c.get(key) if key == "rope_scaling" else _need(c, key, path)
        if got != want:
            raise ValueError(f"{path}: {key}={got!r} is not implemented "
                             f"(the harness implements {key}={want!r})")
    held = _need(c, "n_routed_experts", path)
    dep = c.get("deployment", {"n_routed_experts": held,
                               "expert_parallel": 1,
                               "experts_here": [0, held]})
    total, ep = dep["n_routed_experts"], dep["expert_parallel"]
    lo, hi = dep["experts_here"]
    if not (0 <= lo < hi <= total and hi - lo == held
            and total == ep * held):
        raise ValueError(
            f"{path}: n_routed_experts={held} held here does not match the "
            f"deployment {dep} ({total} experts over {ep} chips, ids "
            f"[{lo}, {hi}) here)")
    k = _need(c, "num_experts_per_tok", path)
    if not 0 < k <= total:
        raise ValueError(f"{path}: num_experts_per_tok={k} is not in "
                         f"1..{total}")
    n_dense = _need(c, "first_k_dense_replace", path)
    if not 0 <= n_dense < c["num_hidden_layers"]:
        raise ValueError(f"{path}: first_k_dense_replace={n_dense} leaves "
                         f"no expert layer of {c['num_hidden_layers']}")
    a = c["architecture"]
    if (a.get("rope"), a["norm"], a["qkv_bias"]) != ("interleaved",
                                                      "rmsnorm", False):
        raise ValueError(f"{path}: architecture has to state rope "
                         f"'interleaved' (DeepSeek's pairing), norm "
                         f"'rmsnorm' and no qkv_bias")
    mla = MLA(kv_lora_rank=_need(c, "kv_lora_rank", path),
              qk_nope_head_dim=_need(c, "qk_nope_head_dim", path),
              qk_rope_head_dim=_need(c, "qk_rope_head_dim", path),
              v_head_dim=_need(c, "v_head_dim", path),
              kv_norm_eps=float(_need(a, "kv_norm_eps", path)))
    moe = MoE(router_experts=total, experts_held=held, first_expert=lo,
              num_experts_per_tok=k,
              moe_intermediate_size=_need(c, "moe_intermediate_size", path),
              n_shared_experts=_need(c, "n_shared_experts", path),
              first_k_dense_replace=n_dense,
              scoring_func=c["scoring_func"],
              routed_scaling_factor=float(
                  _need(c, "routed_scaling_factor", path)),
              norm_topk_prob=bool(_need(c, "norm_topk_prob", path)))
    return mla, moe


def _need(c: dict, key: str, path: str):
    if key not in c:
        raise ValueError(f"{path}: {key} is missing (a deepseek_v3 "
                         f"file states it)")
    return c[key]
