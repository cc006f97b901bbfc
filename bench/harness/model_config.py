"""A configuration file under ``bench/configs/``, read into one record.

The file keeps the published ``config.json`` keys as they are, an
``architecture`` group for what the published keys imply without naming
(the norm, the q/k/v bias), and a ``serving`` group with the engine's
settings for the cells that use it.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import jax.numpy as jnp

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(BENCH_DIR, "configs")


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

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab_size // 256) * 256

    def kv_bytes_per_token(self) -> int:
        itemsize = jnp.dtype(self.compute_dtype).itemsize
        return (2 * self.n_layers * self.n_kv_heads * self.head_dim
                * itemsize)


def load(name_or_path: str) -> Arch:
    path = name_or_path if name_or_path.endswith(".json") else \
        os.path.join(CONFIG_DIR, f"{name_or_path}.json")
    with open(path) as f:
        c = json.load(f)
    a = c["architecture"]
    s = c["serving"]
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
        compute_dtype=c["torch_dtype"], weights_dtype=s["weights_dtype"],
        serving=s, head_dim_override=c.get("head_dim"))
