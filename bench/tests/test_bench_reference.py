"""The plain reference against the program at test size on the CPU:
the prefill's last logits, and prefill followed by paged decode through
``ServeEngine`` (prefix sharing on, so a hit streams its tail over
shared pages)."""

import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, model_config, system, weights
from reference import model as ref

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def tiny(dtype: str):
    a = model_config.load(os.path.join(TESTDATA, "configs", "tiny.json"))
    return dataclasses.replace(a, compute_dtype=dtype)


def serve(arch, params, prompts, gen):
    eng = system.build_engine(arch, params)
    seqs = []
    for uid, p in enumerate(prompts):
        req = system.Request(uid=uid, prompt=p, max_new_tokens=gen)
        lane = eng.free_lanes()[0]
        assert eng.admit(req)
        first = int(np.asarray(system.next_token_array(eng))[lane])
        while not req.done:
            eng.decode_n()
        seqs.append((p, np.asarray([first] + req.generated, np.int32)))
    return eng, seqs


def prompts(vocab):
    rng = np.random.default_rng(4)
    head = rng.integers(0, vocab, 70).astype(np.int32)
    return [rng.integers(0, vocab, 37).astype(np.int32),
            np.concatenate([head, rng.integers(0, vocab, 9)]).astype(np.int32),
            # shares the head: a prefix-cache hit, tail streamed, the
            # partial page copied on write
            np.concatenate([head, rng.integers(0, vocab, 21)]).astype(np.int32)]


def test_prefill_logits_match_in_float32():
    """Same weights, f32 on both sides: the only differences are the
    order of additions and the norm's epsilon (1e-5 in the program,
    1e-6 published), each far below 1e-4 of logits of size about 1."""
    from repro.models.transformer import lm_prefill_batched
    arch = tiny("float32")
    params = weights.make_params(arch, 11)
    toks = np.random.default_rng(0).integers(0, arch.vocab_size, 50)
    prog = lm_prefill_batched(params, jnp.asarray(toks[None], jnp.int32),
                              system.program_config(arch))[0][0]
    want = ref.logits_at(params, jnp.asarray(toks, jnp.int32),
                         jnp.asarray([49], jnp.int32),
                         spec=ref.spec_of(arch))[0]
    np.testing.assert_allclose(np.asarray(prog)[:arch.vocab_size],
                               np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("dtype,limit", [
    # f32 program: greedy tokens are the reference's best up to f32
    # rounding of logits of size about 1
    ("float32", 1e-4),
    # bf16 program (what the configurations state): rounding of the
    # activations and the cache to 8 bits of mantissa over two layers
    # stays under a tenth of a logit at this size
    ("bfloat16", 0.1)])
def test_served_tokens_are_the_references_best(dtype, limit):
    arch = tiny(dtype)
    params = weights.make_params(arch, 12)
    eng, seqs = serve(arch, params, prompts(arch.vocab_size), gen=12)
    assert eng.stats["prefix_hits"] == 1
    gaps = check.widest_gaps(params, arch, seqs,
                             arch.serving["max_len"], 13)
    assert max(gaps) <= limit, gaps


def test_control_is_further_from_the_reference():
    """The float8 control puts other tokens first than the reference:
    its widest gap is far above the bf16 program's."""
    arch = tiny("bfloat16")
    params = weights.make_params(arch, 13)
    _, seqs = serve(arch, params, prompts(arch.vocab_size), gen=12)
    prog = max(check.widest_gaps(params, arch, seqs,
                                 arch.serving["max_len"], 13))
    ctrl = max(check.widest_gaps(params, arch, seqs,
                                 arch.serving["max_len"], 13,
                                 control="float8_e4m3fn"))
    assert ctrl > 3 * prog, (prog, ctrl)
