"""Operations and bytes from shapes, checked by hand for one layer of
each configuration."""

import pytest

from harness import flops, model_config

PEAK = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def test_qwen_by_hand():
    a = model_config.load("qwen2.5-1.5b")
    # one layer: q,k,v (1536 x (12+2+2)*128) + o (1536 x 1536) + SwiGLU
    # 3 x 1536 x 8960 = 3,145,728 + 2,359,296 + 41,287,680
    layer = 46_792_704
    head = 1536 * 151_936
    assert flops.matmul_params_per_token(a) == 28 * layer + head
    # 100 keys: 2 pages of 64 for K and V, 2 kv heads of 128 dims in
    # bf16, plus q and the output (12 heads x 128 x 2 B each)
    f, b = flops.paged_attention_cost(a, 100, 64)
    assert f == 28 * 4 * 12 * 128 * 100
    assert b == 28 * (2 * 2 * 64 * 2 * 128 * 2 + 2 * 12 * 128 * 2)
    assert flops.decode_token_flops(a, 100) == 2 * (28 * layer + head) + f


def test_olmo_by_hand():
    a = model_config.load("olmo-1b")
    # q,k,v,o 2048 x 2048 each + SwiGLU 3 x 2048 x 8192
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert layer == 67_108_864
    assert flops.matmul_params_per_token(a) == 16 * layer + 2048 * 50_304
    f, b = flops.paged_attention_cost(a, 64, 64)      # exactly one page
    assert f == 16 * 4 * 16 * 128 * 64
    assert b == 16 * (2 * 64 * 16 * 128 * 2 + 2 * 16 * 128 * 2)


def test_prefill_counts_the_triangle():
    a = model_config.load("olmo-1b")
    one = flops.prefill_flops(a, 1)
    assert one == flops.decode_token_flops(a, 1)
    assert flops.prefill_flops(a, 3) == (
        3 * 2 * flops.matmul_params_per_token(a)
        + flops.attention_flops(a, 1 + 2 + 3))


@pytest.mark.parametrize("kv_len", [1, 500, 4000])
def test_roofline_takes_the_larger_bound(kv_len):
    a = model_config.load("qwen2.5-1.5b")
    f, b = flops.paged_attention_cost(a, kv_len, 64)
    t = flops.roofline_seconds(f, b, PEAK)
    assert t == max(f / PEAK["bf16_flops_per_s"], b / PEAK["hbm_bytes_per_s"])
    assert t == b / PEAK["hbm_bytes_per_s"]      # decode attention: bytes


def test_kv_bytes_per_token():
    assert model_config.load("qwen2.5-1.5b").kv_bytes_per_token() == 28_672
    assert model_config.load("olmo-1b").kv_bytes_per_token() == 131_072
