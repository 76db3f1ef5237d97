"""``counts_mla`` against a count by hand on a tiny DeepSeek-V2 shape:
one dense layer and one MoE layer, B = 1."""
import pytest

from chipbench import counts_mla

M = {"L": 2, "nd": 1, "d": 4, "H": 2, "R": 3, "dn": 2, "dr": 2, "dv": 2,
     "ff_dense": 6, "ff": 2, "E": 4, "k": 2, "shared": 1, "V": 10}


def test_decode_at_position_zero():
    # a layer's absorbed projections: q 4*2*4 = 32, kv_a 4*5 = 20, q_nope
    # into the latent 2*2*3 = 12, out of it 2*3*2 = 12, o 2*2*4 = 16: 92.
    # FFNs: dense 3*4*6 = 72; MoE router 16 + 2 experts 2*24 + shared 24
    # = 88.  Attention at pos 0: 2*L*H*(2R + dr) = 2*2*2*8 = 64.  Head
    # 2*4*10 = 80.  2*(2*92 + 72 + 88) + 64 + 80 = 832.
    # Bytes: each layer's bf16 q, kv_a, kv_b (3*2*4 = 24), o = 92 * 2 and
    # f32 norms (4 + 4 + 3) * 4 = 44, so 2 * 228 = 456; the dense MLP
    # 144; one token reaches 4*(1 - 1/2) = 2 experts: 2*24*2 = 96, shared
    # 48, router 64; head 80, final norm 16, its embedding row 8; the
    # cache at one position 2 layers * 5 * 2 = 20.  Total 932.
    assert counts_mla.decode(M, 1, 0) == (832.0, 932.0)


def test_prefill_of_two_tokens():
    # projections expanded (kv_b 24 for q_nope's 12 + 12): 92 a layer, as
    # in decode; 2 tokens: 2*2*(2*92 + 160) = 1376; causal pairs 3, each
    # 2*L*H*(dn + dr + dv) = 2*2*2*6 = 48: 144; head for the last 80.
    # Bytes: 2 tokens reach 4*(1 - 1/4) = 3 experts: 144 + 48 + 64 = 256;
    # attention 456, dense 144, head 80, norm 16, 2 embedding rows 16;
    # the cache of 2 positions 40.  Total 1008.
    assert counts_mla.prefill(M, 1, 2) == (1600.0, 1008.0)


def test_roofline_is_the_larger_bound_over_all_chips():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts_mla.roofline_s(800.0, 40.0, peak, 4) == pytest.approx(2.0)
    assert counts_mla.roofline_s(80.0, 400.0, peak, 4) == pytest.approx(10.0)
