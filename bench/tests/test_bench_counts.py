"""``bench/counts.py`` against operations and bytes counted by hand."""

import pytest

from bench import counts

from bench.tests.tiny import TINY_CONF

# tiny: d=64, 4 query / 2 KV heads of 16, d_ff=128, vocab=256, window 16,
# 2 layers; one layer's matmul weights: 64*16*(4+2*2) + 4*16*64 + 3*64*128
LAYER = 8192 + 4096 + 24576


def test_layer_weights():
    assert counts.layer_matmul_params(TINY_CONF) == LAYER == 36864


@pytest.mark.parametrize("start,end,want", [
    (0, 8, 36),           # 1 + 2 + ... + 8
    (0, 16, 136),         # the window is not reached yet
    (0, 20, 120 + 5 * 16),  # positions 0..14 see pos+1, 15..19 see 16
    (20, 22, 32),         # past the window: 16 keys each
])
def test_visible_pairs(start, end, want):
    assert counts.visible_pairs(start, end, 16) == want


def test_one_kernel_call():
    # one prefill of 8 tokens: 36 visible pairs, 4 heads of 16, 2 layers
    c = counts.prefill_flash_counts(TINY_CONF, 8)
    assert c["flops"] == 4 * 4 * 16 * 36 * 2 == 18432
    # q, k and v in, o out, bf16, per layer
    q = 8 * 4 * 16 * 2
    kv = 2 * 8 * 2 * 16 * 2
    assert c["bytes"] == 2 * (q + kv + q) == 6144


def test_one_train_step():
    # seq 32: matmuls 2*(2*LAYER + 64*256) per token, three times (fwd+bwd)
    matmul = 3 * (2 * 2 * LAYER + 2 * 64 * 256) * 32
    pairs = 120 + 17 * 16
    attn_fwd = 4 * 4 * 16 * pairs * 2
    want = matmul + attn_fwd + 2.5 * attn_fwd
    assert counts.train_step_flops(TINY_CONF, 32, 1) == want == 18003968
    fl = counts.flash_train_counts(TINY_CONF, 32, 1)
    assert fl["fwd"]["flops"] == attn_fwd
    assert fl["bwd"]["flops"] == 2.5 * attn_fwd


def test_one_decode_step():
    # rows at positions 3 and 20 see 4 and 16 keys
    want = 2 * 2 * LAYER * 2 + 2 * 64 * 256 * 2 + 4 * 4 * 16 * 20 * 2
    assert counts.decode_step_flops(TINY_CONF, [3, 20]) == want == 370688
    pd = counts.paged_decode_counts(TINY_CONF, [3, 20])
    assert pd["flops"] == 4 * 4 * 16 * 20 * 2
    # 20 keys and values of 2 heads of 16, plus q in and o out, per layer
    assert pd["bytes"] == 2 * (2 * 20 * 2 * 16 * 2 + 2 * 2 * 4 * 16 * 2)


def test_roofline_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000.0, 50.0, peak) == 10.0
    assert counts.least_seconds(100.0, 50.0, peak) == 5.0


def test_danube_step_near_published_count():
    import json

    from bench.tests.tiny import ROOT

    conf = json.loads((ROOT / "bench/configs/h2o-danube-1.8b.json")
                      .read_text())
    f = counts.train_step_flops(conf, 16384, 1)
    assert 2.1e14 < f < 2.3e14
