"""Counts of bench/flops.py against counts worked by hand, and the peaks table."""

import pytest

from bench import flops
from bench.run import ROOT, Refused, load_peaks

MIXTRAL = dict(num_layers=2, d_model=4096, num_heads=32, num_kv_heads=8, head_dim=128,
               moe_d_ff=14336, num_experts=8, experts_per_token=2, vocab_size=32000,
               sliding_window=None)


def test_attention_pairs():
    assert flops.attention_pairs(4, 4, causal=True) == 10  # 1 + 2 + 3 + 4
    assert flops.attention_pairs(4, 4, causal=False) == 16
    assert flops.attention_pairs(4, 4, causal=True, window=2) == 7  # 1 + 2 + 2 + 2
    assert flops.attention_pairs(1, 6, causal=True, q_offset=5) == 6  # a decode step at pos 5


def test_mixtral_token_forward():
    # per layer: q, o: 2 * 4096 * 4096 * 2; k, v: 2 * 4096 * 1024 * 2 -> 83,886,080;
    # router 2 * 4096 * 8 = 65,536; two experts of three 4096 x 14336 matrices:
    # 2 * 3 * 2 * 4096 * 14336 = 704,643,072. Head: 2 * 4096 * 32000 = 262,144,000.
    per_layer = 83_886_080 + 65_536 + 704_643_072
    assert flops.token_forward_flops(MIXTRAL) == 2 * per_layer + 262_144_000 == 1_839_333_376


def test_mixtral_train_step():
    # 8 x 1024 tokens; causal pairs 8 * 1024 * 1025 / 2 = 4,198,400, each
    # 2 * 2 * 32 heads * 128 FLOPs in each of 2 layers; forward + backward = 3x.
    forward = 8192 * 1_839_333_376 + 2 * 4 * 32 * 128 * 4_198_400
    assert flops.train_step_flops(MIXTRAL, 8, 1024) == 3 * forward == 45_616_176_562_176


def test_decode_step():
    # 4 tokens at position 127: 128 cached keys each.
    scores = 2 * 4 * 32 * 128 * (4 * 128)
    assert flops.decode_step_flops(MIXTRAL, 4, 127) == 4 * 1_839_333_376 + scores
    tiny = dict(num_layers=1, d_model=4, num_heads=2, num_kv_heads=1, head_dim=2,
                moe_d_ff=3, num_experts=2, experts_per_token=1, vocab_size=5)
    # weights held: q, o 2 * 4 * 4, k, v 2 * 4 * 2, experts 3 * 2 * 4 * 3, norms 2 * 4
    # (bf16) -> 2 * (32 + 16 + 72 + 8) = 256; router 4 * 4 * 2 = 32; embed and head
    # 2 * (5 * 4 + 4) = 48 -> 336 held. The step also reads 3 embedding rows
    # (3 * 4 * 2 = 24) and the cache of positions 0..2: 1 layer * 3 * 3 * (k, v)
    # 2 * 1 head * 2 * 2 bytes = 72; its tokens were routed to both experts.
    assert flops.decode_step_bytes(tiny, 3, 2, experts_read=2) == 336 + 24 + 72


def test_decode_step_bytes_of_the_experts_read():
    # One expert of one layer at Mixtral widths: three 4096 x 14336 matrices
    # in bfloat16, 352,321,536 bytes; 4 layers hold 32 of them.
    m = dict(MIXTRAL, num_layers=4)
    assert flops.expert_bytes(m) == 2 * 3 * 4096 * 14336 == 352_321_536
    every = flops.decode_step_bytes(m, 4, 127, experts_read=32)
    assert every - flops.decode_step_bytes(m, 4, 127, experts_read=22) == 10 * 352_321_536
    # A step that read no expert still reads attention, router, norms, head,
    # 4 embedding rows and the cache.
    assert flops.decode_step_bytes(m, 4, 127, experts_read=0) == every - 32 * 352_321_536


def test_flash_attention_call():
    f, b = flops.flash_attention_call(1, 4, 4, 2, 1, 8)
    assert f == 2 * 2 * 1 * 2 * 8 * 10  # QK^T and PV over 10 causal pairs, 2 heads
    assert b == 2 * (2 * 1 * 4 * 2 * 8 + 2 * 1 * 4 * 1 * 8)  # q, o and k, v in bf16


def test_peaks_known_and_unknown_kind():
    assert load_peaks(ROOT, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(Refused):
        load_peaks(ROOT, "TPU v99")
