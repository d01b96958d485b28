"""The functions that count operations and bytes, against hand counts for
one shape each."""

import pytest

from harness import cells


def test_flash_attention_counts_one_document():
    flash = cells.kernel("flash_attention")
    # one document of 4 tokens, hidden 8: 4*5/2 = 10 scores per head-dim
    c = flash.counts([4], hidden=8)
    assert c["forward"]["flops"] == 2 * 2 * 10 * 8          # 320
    assert c["dkv"]["flops"] == 4 * 2 * 10 * 8
    assert c["dq"]["flops"] == 3 * 2 * 10 * 8
    assert c["forward"]["bytes"] == 4 * 4 * 8 * 2           # q k v o, bf16
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e9}
    least = flash.least_seconds([4], 8, peaks)
    assert least["bound"] == {"forward": "compute", "dkv": "compute",
                              "dq": "compute"}
    assert least["seconds"] == pytest.approx((320 + 640 + 480) / 1e3)


def test_flash_attention_two_documents_do_not_see_each_other():
    flash = cells.kernel("flash_attention")
    both = flash.counts([3, 5], hidden=8)["forward"]["flops"]
    assert both == 2 * 2 * (6 + 15) * 8


def test_model_flops_per_step():
    model = cells.kernel("gpt2_model")
    # hidden 4, ffn 16, 2 layers, vocab 10: 2*(64+128) + 40 = 424 params
    assert model.matmul_params(4, 16, 2, 10) == 424
    # one document of 3 tokens: 6 pairs; 3 x 2 x 2 x 6 x 4 x 2 layers
    assert model.attention_flops([3], 4, 2) == 576
    assert model.train_step_flops([3], 4, 16, 2, 10) == 6 * 424 * 3 + 576


def test_ragged_counts_decode_is_memory_bound():
    ragged = cells.kernel("ragged_paged_attention")
    c = ragged.counts(live_kv_tokens=1000, prefill_rows=0, hidden=2048)
    assert c["bytes"] == 2 * 1000 * 2048 * 4
    assert c["flops"] == 4 * 1000 * 2048
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = ragged.least_seconds(1000, 0, 2048, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(c["bytes"] / 819e9)
    # split over 4 chips each reads a quarter
    assert ragged.counts(1000, 0, 2048, tp=4)["bytes"] == c["bytes"] / 4
