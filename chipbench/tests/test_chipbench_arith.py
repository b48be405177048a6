"""The metric arithmetic: tails over every request, rates over the
whole window, and operation and byte counts against hand counts."""

import pytest

import _paths  # noqa: F401

from chipbench import stats
from chipbench.families import dense
from chipbench.families.dense import Dims
from chipbench.reference import least_time

INTERNLM2 = Dims(n_layers=24, d_model=2048, n_heads=16, n_kv_heads=8,
                 head_dim=128, d_ff=8192, vocab=92544)


def test_percentile_is_over_every_sample():
    assert stats.percentile(list(range(1, 11)), 90) == pytest.approx(9.1)
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([], 90) is None
    # a tail of all requests, not a median of per-chunk tails
    chunks = [[1, 1, 1, 1, 100], [1, 1, 1, 1, 1]]
    flat = [x for c in chunks for x in c]
    assert stats.percentile(flat, 90) == pytest.approx(1.0 + 0.1 * 99)


def test_rate_over_the_whole_window():
    stamps = [[0.5, 1.0, 9.9, 10.0], [], [-1.0, 3.0]]
    assert stats.tokens_in(stamps, 10.0) == 4
    assert stats.rate(4, 10.0) == 0.4
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_ttft_and_inter_token_gaps():
    tt = stats.ttfts([-1.0, 0.0, 2.0, 11.0], [0.5, 0.3, None, 11.5], 10.0)
    assert tt == [pytest.approx(0.3)]
    gaps = stats.inter_token_gaps([[-0.5, 0.1, 0.4], [9.0, 9.5, 10.5]], 10.0)
    assert gaps == [pytest.approx(0.3), pytest.approx(0.5)]


def test_kv_and_weight_bytes_by_hand():
    assert dense.kv_bytes_per_token(INTERNLM2) == 98304        # 24*2*8*128*2
    per_layer = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 8192
    assert dense.matmul_params(INTERNLM2) == 24 * per_layer
    assert dense.weight_bytes(INTERNLM2) == 2 * (24 * per_layer
                                                 + 2048 * 92544)


def test_attention_counts_by_hand():
    d = Dims(n_layers=1, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
             d_ff=8, vocab=16)
    # 3 queries at positions 5, 6, 7 see 6 + 7 + 8 = 21 keys
    assert dense.attn_flops(d, 5, 3) == 4 * 2 * 4 * 21
    # K/V of 8 positions (1 layer, 2 tensors, 1 head, 4 dims, 2 bytes)
    # plus q and o of 3 queries (2 heads x 4 dims x 2 bytes each)
    assert dense.attn_bytes(d, 5, 3) == 8 * 16 + 2 * 3 * 2 * 4 * 2
    fl, by = dense.decode_attn(d, [6, 8])
    assert fl == dense.attn_flops(d, 5, 1) + dense.attn_flops(d, 7, 1)
    assert by == dense.attn_bytes(d, 5, 1) + dense.attn_bytes(d, 7, 1)


def test_step_work_and_least_time():
    fl, by = dense.step_work(INTERNLM2, [100], [], 1)
    mm = 2.0 * dense.matmul_params(INTERNLM2) + 2.0 * 2048 * 92544
    assert fl == pytest.approx(mm + dense.attn_flops(INTERNLM2, 99, 1))
    assert by == pytest.approx(dense.weight_bytes(INTERNLM2)
                               + dense.attn_bytes(INTERNLM2, 99, 1)
                               + dense.kv_bytes_per_token(INTERNLM2))
    pk = {"flops_bf16": 197e12, "hbm_bytes_s": 819e9}
    assert least_time(fl, by, pk) == pytest.approx(by / 819e9)   # HBM-bound
    assert dense.step_work(INTERNLM2, [], [], 0) == (0.0, 0.0)
