"""Operation and byte counts against hand counts at smoke shapes."""

import pytest

from flops import (decode_token_flops, layer_params, paged_attention_call,
                   prefill_flops, roofline_seconds)

# 2 layers, d 8, 4 query heads over 2 KV heads of 2, d_ff 16, vocab 10
M = dict(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
         d_ff=16, vocab=10)


def test_layer_params_by_hand():
    # wq 8x8 + wk 8x4 + wv 8x4 + wo 8x8 + wg, wu 8x16 + wd 16x8
    assert layer_params(M) == 64 + 32 + 32 + 64 + 3 * 128


def test_decode_token_flops_by_hand():
    # 2 x (2 layers x 576 + head 80) + 4 x 2 layers x 4 heads x 2 x 5
    assert decode_token_flops(M, 5) == 2 * (2 * 576 + 80) + 320


def test_prefill_flops_by_hand():
    # 3 tokens: 2 x 2 x 576 x 3 matmuls + one head row + causal 1+2+3
    assert prefill_flops(M, 3) == 2 * 2 * 576 * 3 + 2 * 80 + 4 * 2 * 4 * 2 * 6


def test_paged_attention_call_by_hand():
    ops, nbytes = paged_attention_call(M, [5, 16, 17], block_len=16)
    assert ops == 4 * 4 * 2 * (5 + 16 + 17)
    # K and V over 16 + 16 + 32 positions, 2 KV heads of 2, bf16; plus
    # 3 query rows read and 3 output rows written (4 heads of 2, bf16)
    assert nbytes == 2 * 64 * 2 * 2 * 2 + 2 * 3 * 4 * 2 * 2


@pytest.mark.parametrize("ops,nbytes,bound", [(1e12, 1e9, "ops"),
                                              (1e9, 1e10, "bytes")])
def test_roofline_bound(ops, nbytes, bound):
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, which = roofline_seconds(ops, nbytes, peaks)
    assert which == bound
    assert t == pytest.approx(max(ops / 197e12, nbytes / 819e9))
