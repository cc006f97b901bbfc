"""Seeded random weights for a configuration, made on the device.

One jitted call builds every leaf from the seed, in the layout the
serving engine takes (``embed``/``blocks``/``final_norm``, layers
stacked on a leading axis) and in the type the engine serves them in.
The plain reference reads the same arrays; both sides get them from the
benchmark, neither makes its own.

A ``deepseek_v3`` configuration is laid out as the engine is to take
it: ``dense_blocks`` stacked over the ``first_k_dense_replace`` leading
dense layers, ``blocks`` over the expert layers that follow.  Each
layer's ``attn`` holds the latent attention, ``wq (d, H (dn+dr))``,
``wkv_a (d, r+dr)``, ``kv_norm.scale (r)``, ``wkv_b (r, H (dn+dv))`` and
``wo (H dv, d)``; an expert layer's ``moe`` is named as
``repro.models.moe.init_moe`` names it: ``router (d, E)`` over all
``E`` published experts, ``router_bias (E)`` (added to the scores for
the choice of experts only), ``w_gate``/``w_up (held, d, f)`` and
``w_down (held, f, d)`` for the experts held here, in the order of
their ids, and ``shared`` (``w_gate``/``w_up``/``w_down``) at width
``n_shared_experts f``.

Scales: matrices are normal with standard deviation ``fan_in ** -0.5``,
so every layer adds an O(1) update to the residual stream and attention
scores are O(1) (a model whose layers barely move the stream would
check little beyond the embedding); the embedding is normal at the
configuration's ``initializer_range``; norm scales are ``1 + 0.1 n``
and the q/k/v biases ``0.1 n``, so both paths are exercised.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.model_config import Arch


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, 32 bits wide or wider."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def param_shapes(arch: Arch) -> dict:
    """Leaf shapes of the served parameter tree, and each leaf's kind."""
    if arch.mla is not None:
        return _latent_expert_shapes(arch)
    d, L, hd = arch.d_model, arch.n_layers, arch.head_dim
    qd, kvd, ff = arch.n_heads * hd, arch.n_kv_heads * hd, arch.d_ff
    blocks = {
        "attn": {"wq": ((L, d, qd), "w"), "wk": ((L, d, kvd), "w"),
                 "wv": ((L, d, kvd), "w"), "wo": ((L, qd, d), "w")},
        "mlp": {"w_gate": ((L, d, ff), "w"), "w_up": ((L, d, ff), "w"),
                "w_down": ((L, ff, d), "w")},
        "norm1": {}, "norm2": {},
    }
    if arch.qkv_bias:
        blocks["attn"].update(bq=((L, qd), "bias"), bk=((L, kvd), "bias"),
                              bv=((L, kvd), "bias"))
    final_norm = {}
    if arch.norm == "rmsnorm":
        blocks["norm1"] = {"scale": ((L, d), "scale")}
        blocks["norm2"] = {"scale": ((L, d), "scale")}
        final_norm = {"scale": ((d,), "scale")}
    embed = {"tok": ((arch.padded_vocab, d), "embed")}
    if not arch.tie_embeddings:
        embed["head"] = ((d, arch.padded_vocab), "w")
    return {"embed": embed, "blocks": blocks, "final_norm": final_norm}


def _latent_expert_shapes(arch: Arch) -> dict:
    d, H, m, e = arch.d_model, arch.n_heads, arch.mla, arch.moe
    r, dn, dr, dv = (m.kv_lora_rank, m.qk_nope_head_dim,
                     m.qk_rope_head_dim, m.v_head_dim)
    f, held, E = e.moe_intermediate_size, e.experts_held, e.router_experts

    def swiglu(n, ff):
        return {"w_gate": ((n, d, ff), "w"), "w_up": ((n, d, ff), "w"),
                "w_down": ((n, ff, d), "w")}

    def layers(n, **ffn):
        return {"attn": {"wq": ((n, d, H * (dn + dr)), "w"),
                         "wkv_a": ((n, d, r + dr), "w"),
                         "kv_norm": {"scale": ((n, r), "scale")},
                         "wkv_b": ((n, r, H * (dn + dv)), "w"),
                         "wo": ((n, H * dv, d), "w")},
                "norm1": {"scale": ((n, d), "scale")},
                "norm2": {"scale": ((n, d), "scale")}, **ffn}

    n_dense = e.first_k_dense_replace
    n = arch.n_layers - n_dense
    moe = {"router": ((n, d, E), "w"), "router_bias": ((n, E), "bias"),
           "w_gate": ((n, held, d, f), "w"), "w_up": ((n, held, d, f), "w"),
           "w_down": ((n, held, f, d), "w"),
           "shared": swiglu(n, e.n_shared_experts * f)}
    embed = {"tok": ((arch.padded_vocab, d), "embed")}
    if not arch.tie_embeddings:
        embed["head"] = ((d, arch.padded_vocab), "w")
    out = {"embed": embed, "blocks": layers(n, moe=moe),
           "final_norm": {"scale": ((d,), "scale")}}
    if n_dense:
        out["dense_blocks"] = layers(n_dense,
                                     mlp=swiglu(n_dense, arch.d_ff))
    return out


def _leaf(key, shape, kind, init_range, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        x = x * shape[-2] ** -0.5
    elif kind == "embed":
        x = x * init_range
    elif kind == "scale":
        x = 1.0 + 0.1 * x
    else:
        x = 0.1 * x
    return x.astype(dtype)


def make_params(arch: Arch, seed: int):
    """Every weight of ``arch`` from ``seed``, on the default device."""
    spec = param_shapes(arch)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        spec, is_leaf=lambda x: isinstance(x, tuple))
    leaves = [s for _, s in paths]

    @jax.jit
    def build(key):
        return [_leaf(jax.random.fold_in(key, i), shape, kind,
                      arch.initializer_range, arch.weights_dtype)
                for i, (shape, kind) in enumerate(leaves)]

    return jax.tree_util.tree_unflatten(treedef, build(jax_key(seed)))
