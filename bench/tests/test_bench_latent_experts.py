"""The harness on a ``deepseek_v3`` configuration (latent attention, one
chip's share of routed experts), and what it reads for a dense one.

On the CPU at test size: the tiny three-layer file
(``testdata/configs/tiny-mla.json``: one dense layer, then expert
layers; 4 of 8 experts held) goes through ``load``, ``make_params``, the
reference and its control, and the counts; Moonlight-16B-A3B's
published widths (``moonlight-16b-a3b.widths.json``) are checked for
their shapes and counts alone, with no array made."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import flops, model_config, system, weights
from reference import model as ref

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
TINY_MLA = os.path.join(TESTDATA, "configs", "tiny-mla.json")
MOONLIGHT = os.path.join(TESTDATA, "configs",
                         "moonlight-16b-a3b.widths.json")
CONTROL = "float8_e4m3fn"

#: Moonlight-16B-A3B's published config.json, the keys the model-configs
#: catalog keeps of it
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}


def tiny_mla(**moe):
    """The tiny file with float32 weights (so the reference's two forms
    can be held to 1e-5), its expert record changed by ``moe``."""
    a = model_config.load(TINY_MLA)
    return dataclasses.replace(a, weights_dtype="float32",
                               moe=dataclasses.replace(a.moe, **moe))


def write(tmp_path, c):
    path = os.path.join(tmp_path, "c.json")
    with open(path, "w") as f:
        json.dump(c, f)
    return path


# -- the configuration ----------------------------------------------------

def test_tiny_file_loads_with_its_expert_share():
    a = model_config.load(TINY_MLA)
    assert a.mla == model_config.MLA(kv_lora_rank=32, qk_nope_head_dim=16,
                                     qk_rope_head_dim=8, v_head_dim=16,
                                     kv_norm_eps=1e-6)
    assert a.moe == model_config.MoE(
        router_experts=8, experts_held=4, first_expert=0,
        num_experts_per_tok=2, moe_intermediate_size=24,
        n_shared_experts=1, first_k_dense_replace=1, scoring_func="sigmoid",
        routed_scaling_factor=2.446, norm_topk_prob=True)
    assert a.rope == "interleaved" and a.n_layers == 3 and a.d_ff == 96
    assert a.weights_dtype == "bfloat16" and a.serving == {}
    # (32 latent + 8 rope) values a token a layer, in bf16
    assert a.kv_bytes_per_token() == 3 * 40 * 2


def mutated(value):
    if isinstance(value, bool):
        return not value
    if value is None:
        return 1
    if isinstance(value, (int, float)):
        return value + 1
    return value + "_other"


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_key_is_read_or_rejected(tmp_path, key):
    """Each key of the published config changes what the harness reads,
    or a value other than the one implemented is refused by name; only
    the keys of ``INERT_KEYS`` change nothing, as they say."""
    with open(TINY_MLA) as f:
        c = json.load(f)
    before = model_config.load(write(tmp_path, c))
    c[key] = mutated(c[key])
    try:
        after = model_config.load(write(tmp_path, c))
    except ValueError as e:
        assert key in str(e)
        assert key not in model_config.INERT_KEYS
        return
    if key in model_config.INERT_KEYS:
        assert after == before
    else:
        assert after != before


@pytest.mark.parametrize("deployment", [
    {"n_routed_experts": 8, "expert_parallel": 4, "experts_here": [0, 4]},
    {"n_routed_experts": 8, "expert_parallel": 2, "experts_here": [0, 3]},
    {"n_routed_experts": 8, "expert_parallel": 2, "experts_here": [6, 10]}])
def test_an_expert_share_off_its_deployment_is_refused(tmp_path, deployment):
    with open(TINY_MLA) as f:
        c = json.load(f)
    c["deployment"] = deployment
    with pytest.raises(ValueError, match="n_routed_experts"):
        model_config.load(write(tmp_path, c))


def test_a_missing_q_lora_rank_is_refused(tmp_path):
    """Left out, ``q_lora_rank`` would take the publisher's default of
    1536, a low-rank q the harness does not implement."""
    with open(TINY_MLA) as f:
        c = json.load(f)
    del c["q_lora_rank"]
    with pytest.raises(ValueError, match="q_lora_rank"):
        model_config.load(write(tmp_path, c))


# -- what a dense configuration reads, as before ----------------------------

def shapes_json(x):
    if isinstance(x, tuple):
        return [list(x[0]), x[1]]
    return {k: shapes_json(v) for k, v in x.items()}


@pytest.mark.parametrize("name", ["olmo-1b", "qwen2.5-1.5b", "tiny"])
def test_dense_readings_are_unchanged(name):
    """``testdata/dense-readings.json`` holds what the harness read for
    each dense configuration before latent attention and experts were
    added to it: its ``Arch``, parameter shapes, counts and the program's
    configuration.  The new fields read ``None`` and the RoPE pairing
    rotate-half."""
    with open(os.path.join(TESTDATA, "dense-readings.json")) as f:
        want = json.load(f)[name]
    a = model_config.load(os.path.join(TESTDATA, "configs", "tiny.json")
                          if name == "tiny" else name)
    got = dataclasses.asdict(a)
    assert {k: got.pop(k) for k in ("rope", "mla", "moe")} == {
        "rope": "rotate_half", "mla": None, "moe": None}
    assert got == want["arch"]
    assert a.kv_bytes_per_token() == want["kv_bytes_per_token"]
    assert shapes_json(weights.param_shapes(a)) == want["param_shapes"]
    assert flops.matmul_params_per_token(a) == \
        want["matmul_params_per_token"]
    for fn in ("attention_flops", "decode_token_flops", "prefill_flops"):
        assert {n: getattr(flops, fn)(a, int(n)) for n in want[fn]} == \
            want[fn], fn
    assert {n: list(flops.paged_attention_cost(a, int(n), 64))
            for n in want["paged_attention_cost"]} == \
        want["paged_attention_cost"]
    assert dataclasses.asdict(system.program_config(a)) == \
        want["program_config"]


@pytest.mark.parametrize("config", ["tiny.json", "tiny-mla.json"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_logits_are_those_of_a_tree_cast_up_front(config, dtype):
    """Casting each layer's weights inside the scan gives, bit for bit,
    the logits of the whole tree cast to float32 first (the cast is
    exact)."""
    a = model_config.load(os.path.join(TESTDATA, "configs", config))
    a = dataclasses.replace(a, weights_dtype=dtype)
    params = weights.make_params(a, 21)
    wide = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), params)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, a.vocab_size, 48), jnp.int32)
    pos = jnp.arange(40, 48, dtype=jnp.int32)
    spec = ref.spec_of(a)
    np.testing.assert_array_equal(
        np.asarray(ref.logits_at(params, toks, pos, spec=spec)),
        np.asarray(ref.logits_at(wide, toks, pos, spec=spec)))


# -- the hand-over to the program ------------------------------------------

def test_todays_program_refuses_latent_attention_by_name():
    with pytest.raises(system.UnsupportedByProgram) as e:
        system.program_config(model_config.load(TINY_MLA))
    for field in ("ModelConfig.attention", "ModelConfig.kv_lora_rank",
                  "ModelConfig.n_dense_layers", "MoEConfig.experts_held",
                  "MoEConfig.first_expert", "MoEConfig.score_func",
                  "MoEConfig.routed_scale"):
        assert field in str(e.value)
    for field in ("MoEConfig.n_experts", "MoEConfig.top_k"):
        assert field not in str(e.value)


def test_a_program_with_the_fields_gets_the_latent_and_expert_layout(
        monkeypatch):
    @dataclasses.dataclass(frozen=True)
    class MoEFields(system.MoEConfig):
        experts_held: int = 0
        first_expert: int = 0
        score_func: str = "softmax"
        routed_scale: float = 1.0
        norm_topk_prob: bool = True

    @dataclasses.dataclass(frozen=True)
    class Fields(system.ModelConfig):
        attention: str = "gqa"
        kv_lora_rank: int = 0
        qk_nope_head_dim: int = 0
        qk_rope_head_dim: int = 0
        v_head_dim: int = 0
        kv_norm_eps: float = 1e-5
        n_dense_layers: int = 0
        rope_interleaved: bool = False

    monkeypatch.setattr(system, "ModelConfig", Fields)
    monkeypatch.setattr(system, "MoEConfig", MoEFields)
    cfg = system.program_config(model_config.load(MOONLIGHT))
    assert (cfg.family, cfg.attention, cfg.n_layers, cfg.n_dense_layers,
            cfg.d_ff, cfg.rope_interleaved) == ("moe", "mla", 27, 1, 11264,
                                                True)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_norm_eps) == (512, 128, 64, 128, 1e-6)
    assert cfg.moe == MoEFields(
        n_experts=64, top_k=6, d_expert_ff=1408, n_shared_experts=2,
        experts_held=8, first_expert=0, score_func="sigmoid",
        routed_scale=2.446, norm_topk_prob=True)
    assert not cfg.tie_embeddings and cfg.vocab_size == 163840


# -- the reference ----------------------------------------------------------

def share_of(params, lo, hi):
    """The expert layers' weights as a chip holding experts lo..hi-1
    would have them."""
    moe = dict(params["blocks"]["moe"])
    for k in ("w_gate", "w_up", "w_down"):
        moe[k] = moe[k][:, lo:hi]
    return {**params["blocks"], "moe": moe}


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Guide section 4: what the two shares (experts 0-3 and 4-7) pass on
    from one expert layer, less what every chip computes alike (the
    residual stream, attention and the shared expert, as a chip holding
    no expert computes them) added once too many, is what the uncut
    layer passes on."""
    whole = tiny_mla(experts_held=8, first_expert=0)
    params = weights.make_params(whole, 31)
    x = jax.random.normal(jax.random.key(2), (24, whole.d_model))

    def layer(arch, stack):
        p = jax.tree_util.tree_map(lambda t: t[1], stack)
        return ref._block(x, p, ref.spec_of(arch), None)

    full = layer(whole, params["blocks"])
    lo = layer(tiny_mla(), share_of(params, 0, 4))
    hi = layer(tiny_mla(first_expert=4), share_of(params, 4, 8))
    common = layer(tiny_mla(experts_held=0), share_of(params, 0, 0))
    np.testing.assert_allclose(np.asarray(lo + hi - common),
                               np.asarray(full), rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(full))))
    # each share alone is not the layer: both halves carry routed work
    for part in (lo, hi):
        assert float(jnp.max(jnp.abs(part - common))) > 0.1


def hf_rope(x, pos, theta):
    """RoPE as the publisher's modelling code applies it: the rope dims
    de-interleaved (evens, then odds), then rotated by halves."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(
        x.shape)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.concatenate([pos[:, None] * inv] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def absorbed(h, a, arch):
    """Latent attention in the absorbed form a decode kernel takes:
    ``wkv_b``'s k part folded into q, scores and the weighted sum over
    the latent itself, its v part applied to the latent output."""
    hp = jax.lax.Precision.HIGHEST
    S, H, m = h.shape[0], arch.n_heads, arch.mla
    r, dn, dr, dv = (m.kv_lora_rank, m.qk_nope_head_dim,
                     m.qk_rope_head_dim, m.v_head_dim)
    pos = jnp.arange(S, dtype=jnp.float32)
    q = jnp.dot(h, a["wq"], precision=hp).reshape(S, H, dn + dr)
    ckv = jnp.dot(h, a["wkv_a"], precision=hp)
    lat = ckv[:, :r]
    lat = lat / jnp.sqrt(jnp.mean(lat ** 2, -1, keepdims=True)
                         + m.kv_norm_eps) * a["kv_norm"]["scale"]
    k_pe = hf_rope(ckv[:, None, r:], pos, arch.rope_theta)[:, 0]
    q_pe = hf_rope(q[..., dn:], pos, arch.rope_theta)
    wkv_b = a["wkv_b"].reshape(r, H, dn + dv)
    q_lat = jnp.einsum("shn,rhn->shr", q[..., :dn], wkv_b[..., :dn],
                       precision=hp)
    s = (jnp.einsum("qhr,kr->hqk", q_lat, lat, precision=hp)
         + jnp.einsum("qhe,ke->hqk", q_pe, k_pe, precision=hp)) \
        * (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o_lat = jnp.einsum("hqk,kr->qhr", jax.nn.softmax(s, -1), lat,
                       precision=hp)
    o = jnp.einsum("qhr,rhv->qhv", o_lat, wkv_b[..., dn:], precision=hp)
    return jnp.dot(o.reshape(S, H * dv), a["wo"], precision=hp)


def test_latent_attention_agrees_with_its_absorbed_form():
    """The reference's explicit per-head form and the absorbed form agree
    to 1e-5 of the output's size (float32 rounding, in another order);
    the float8 control does not."""
    arch = tiny_mla()
    params = weights.make_params(arch, 41)
    a = jax.tree_util.tree_map(lambda t: t[0],
                               params["dense_blocks"]["attn"])
    h = jax.random.normal(jax.random.key(3), (40, arch.d_model))
    want = absorbed(h, a, arch)
    scale = float(jnp.max(jnp.abs(want)))
    spec = ref.spec_of(arch)
    got = ref._mla(h, a, spec, None)
    ctrl = ref._mla(h, a, spec, CONTROL)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale
    assert float(jnp.max(jnp.abs(ctrl - want))) > 1e-3 * scale


def test_tiny_latent_experts_through_reference_control_and_counts():
    arch = model_config.load(TINY_MLA)          # bf16 weights, as stated
    params = weights.make_params(arch, 51)
    assert params["blocks"]["moe"]["w_gate"].shape == (2, 4, 64, 24)
    assert params["blocks"]["moe"]["w_gate"].dtype == jnp.bfloat16
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, arch.vocab_size, 64), jnp.int32)
    pos = jnp.arange(48, 64, dtype=jnp.int32)
    spec = ref.spec_of(arch)
    want = ref.logits_at(params, toks, pos, spec=spec)
    low = ref.logits_at(params, toks, pos, spec=spec, operand=CONTROL)
    assert want.shape == (16, arch.vocab_size)
    assert bool(jnp.all(jnp.isfinite(want)))
    # the control puts other tokens first at some positions
    best = jnp.argmax(want, -1)
    assert int(jnp.sum(jnp.argmax(low, -1) != best)) > 0
    assert flops.decode_token_flops(arch, 10) == \
        2 * flops.matmul_params_per_token(arch) + \
        flops.attention_flops(arch, 10)


# -- Moonlight-16B-A3B's widths, shapes only --------------------------------

def test_moonlight_file_holds_the_published_numbers():
    with open(MOONLIGHT) as f:
        c = json.load(f)
    assert "serving" not in c
    assert c["reduced"] == ["n_routed_experts"]
    assert c["deployment"] == {"n_routed_experts": 64, "expert_parallel": 8,
                               "experts_here": [0, 8]}
    assert {k: c[k] for k in PUBLISHED} == {**PUBLISHED,
                                            "n_routed_experts": 8}


def test_moonlight_parameters_held_here():
    """27 layers of MLA (13,763,072 with the latent's norm), one dense
    MLP of 11,264 (69,206,016), 26 expert layers (router 131,072 + bias
    64, 2 shared experts 17,301,504, 8 held experts 69,206,016), two
    norms a layer, the untied embedding and head (335,544,320 each) and
    the final norm."""
    a = model_config.load(MOONLIGHT)
    shapes = weights.param_shapes(a)
    n = sum(math.prod(s) for s, _ in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256 + 2048 * 2048
    assert mla == 13_763_072
    expert = 2048 * 64 + 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408
    want = (27 * (mla + 2 * 2048) + 3 * 2048 * 11264 + 26 * expert
            + 2 * 163840 * 2048 + 2048)
    assert n == want == 3_364_615_296
    assert shapes["blocks"]["moe"]["router"][0] == (26, 2048, 64)
    assert shapes["blocks"]["moe"]["w_down"][0] == (26, 8, 1408, 2048)
    assert shapes["dense_blocks"]["mlp"]["w_up"][0] == (1, 2048, 11264)
    assert a.weights_dtype == "bfloat16"
    # in bf16: 6.7 GB, the weights a Moonlight cell serves
    assert 2 * n == 6_729_230_592


def test_moonlight_counts_by_hand():
    a = model_config.load(MOONLIGHT)
    # per layer: wq 2048 x 3072, wkv_a 2048 x 576, wkv_b 512 x 4096,
    # wo 2048 x 2048 = 13,762,560; the dense MLP 3 x 2048 x 11264; an
    # expert layer's router 2048 x 64, 2 shared experts 3 x 2048 x 2816,
    # and 6 x 8 / 64 = 0.75 held expert MLPs of 3 x 2048 x 1408 a token
    attn = 13_762_560
    expert = 131_072 + 17_301_504 + 0.75 * 8_650_752
    mpt = 27 * attn + 69_206_016 + 26 * expert + 2048 * 163_840
    assert flops.matmul_params_per_token(a) == mpt == 1_398_276_096
    # absorbed decode over 100 latent positions: 2 x 16 x 576 + 2 x 16
    # x 512 a position and a layer
    assert flops.attention_flops(a, 100) == 27 * 100 * 34_816
    # 2 pages of 64 latent tokens of 576 bf16 values, q of 16 x 576 and
    # the latent output of 16 x 512
    f, b = flops.paged_attention_cost(a, 100, 64)
    assert (f, b) == (27 * 100 * 34_816,
                      27 * (128 * 576 * 2 + 16 * 1088 * 2))
    assert a.kv_bytes_per_token() == 31_104
    # prefill, per head: 2 x 16 x (128 + 64) + 2 x 16 x 128 a pair
    assert flops.prefill_flops(a, 100) == \
        2 * mpt * 100 + 27 * 10_240 * 5050
    # 32 tokens: 24 expected local rows of 2 x 8,650,752; bytes of the
    # held experts hit at least once, in bf16
    f, b = flops.moe_expert_cost(a, 32)
    assert f == 2 * 8_650_752 * 24
    assert b == pytest.approx(8 * (1 - (58 / 64) ** 32) * 8_650_752 * 2)
    # one token: 6/64 of the held experts' weights, one expert read in
    # expectation 0.75 times
    assert flops.moe_expert_cost(a, 1)[1] == pytest.approx(
        0.75 * 8_650_752 * 2)
    # a long batch reads every held expert once
    assert flops.moe_expert_cost(a, 4096)[1] == pytest.approx(
        8 * 8_650_752 * 2)


def test_moonlight_reference_would_run_in_its_served_type():
    """The reference's forward traces at Moonlight's widths over weights
    stored in bfloat16 (shapes only: nothing is computed), and keeps
    them in that type: each layer is widened inside the scan."""
    a = model_config.load(MOONLIGHT)
    spec = ref.spec_of(a)
    tree = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s[0], jnp.bfloat16),
        weights.param_shapes(a), is_leaf=lambda x: isinstance(x, tuple))
    toks = jax.ShapeDtypeStruct((2032,), jnp.int32)
    pos = jax.ShapeDtypeStruct((16,), jnp.int32)
    out = jax.eval_shape(
        lambda p, t, q: ref.logits_at(p, t, q, spec=spec), tree, toks, pos)
    assert out.shape == (16, 163_840) and out.dtype == jnp.float32
    jaxpr = jax.make_jaxpr(
        lambda p, t, q: ref.logits_at(p, t, q, spec=spec))(tree, toks, pos)
    text = str(jaxpr)
    # no float32 copy of a whole stacked expert weight outside the scan
    assert "f32[26,8,2048,1408]" not in text
