"""The FLOP and byte counters against hand counts."""
import _paths  # noqa: F401

import pytest

from benchlib import counts

PEAK, BW = 197e12, 819e9


def test_gemm_hand_count():
    w = counts.gemm(2048, 11008, 4096)
    assert w.flops == 2 * 2048 * 11008 * 4096
    assert w.bytes == 2 * (2048 * 4096 + 4096 * 11008) + 2 * 2048 * 11008
    # compute-bound at this size: the roofline is FLOPs over the peak
    assert w.least_seconds(PEAK, BW) == pytest.approx(w.flops / PEAK)


def test_gemm_memory_bound_decode():
    w = counts.gemm(64, 3352, 768)           # a mamba2 in_proj at decode
    assert w.least_seconds(PEAK, BW) == pytest.approx(w.bytes / BW)


def test_causal_attention_hand_count():
    # 4 positions, 2 heads of 8, one KV head: 10 live pairs per head
    w = counts.causal_attention(1, 4, 2, 1, 8)
    assert w.flops == 4 * 2 * 10 * 8
    assert w.bytes == 2 * 4 * 8 * (2 * 2 + 2 * 1)


def _mamba(**kw):
    cfg = dict(family="ssm", num_layers=1, d_model=8, vocab_size=16,
               ssm_expand=2, ssm_headdim=4, ssm_state=2, ssm_chunk=4)
    cfg.update(kw)
    return cfg


def test_mamba2_decode_layer_hand_count():
    # d=8, d_in=16, 4 heads of 4, state 2; batch 3
    w = counts.mamba2_layer(_mamba(), 3, 1)
    in_proj = 2 * 3 * 8 * (2 * 16 + 2 * 2 + 4)
    out_proj = 2 * 3 * 16 * 8
    update = 2 * 3 * 2 * 16          # B x^T: (n=2) x (h*p=16), per row
    readout = 2 * 3 * 2 * 16         # C . state
    assert w.flops == in_proj + out_proj + update + readout


def test_mamba2_chunked_layer_hand_count():
    # 8 positions in 2 chunks of 4, batch 1
    w = counts.mamba2_layer(_mamba(), 1, 8)
    proj = 2 * 8 * 8 * 40 + 2 * 8 * 16 * 8
    cb = 2 * 2 * 4 * 4 * 2           # per chunk: (4 x 2) . (2 x 4)
    intra = 2 * 2 * 4 * 4 * 4 * 4    # per chunk and head: (4 x 4) . (4 x 4)
    states = 2 * 2 * 2 * 16 * 4      # per chunk: (2 x 4) . (4 x 16)
    out = 2 * 2 * 4 * 16 * 2         # per chunk: (4 x 2) . (2 x 16)
    assert w.flops == proj + cb + intra + states + out


def test_scoring_needs_the_head_at_the_last_position_only():
    cfg = dict(family="dense", num_layers=1, d_model=8, vocab_size=100,
               d_ff=16, num_heads=2, num_kv_heads=2, head_dim=4)
    contr, attn = counts.score_prompt(cfg, 32)
    layer = counts.dense_layer_gemms(cfg, 32)
    assert contr.flops == layer.flops + 2 * 8 * 100
    assert attn.flops == counts.causal_attention(1, 32, 2, 2, 4).flops


def test_roofline_sums_each_contraction_bound():
    small = counts.gemm(1, 1024, 1024)            # memory-bound
    big = counts.gemm(4096, 4096, 4096)           # compute-bound
    both = (small + big) * 3
    assert both.least_seconds(PEAK, BW) == pytest.approx(
        3 * (small.bytes / BW + big.flops / PEAK))
