"""Operation and byte counts against numbers worked out by hand from the
published sizes of granite-3.0-1b-a400m and Phi-3-mini."""
import json
import math

import pytest

from chipbench import counts
from chipbench.bench import ROOT
from chipbench.spec import dims


def m_of(name):
    return dims(json.loads((ROOT / "chipbench/configs" / f"{name}.json")
                           .read_text()))


GRANITE, PHI3 = m_of("granite_moe_1b_a400m"), m_of("phi3_mini_3_8b")
GRANITE8 = m_of("granite_moe_1b_a400m_8l")


def test_granite_active_params():
    # per layer: q,k,v,o 1024*(16+2*8)*64 + 16*64*1024 = 3,145,728;
    # router 1024*32 = 32,768; 8 experts of 3*1024*512 = 12,582,912
    per_layer = 3_145_728 + 32_768 + 12_582_912
    assert per_layer == 15_761_408
    # 24 layers and the tied head 1024*49155
    assert counts.active_params(GRANITE) == 24 * per_layer + 50_334_720
    assert counts.active_params(GRANITE) == 428_608_512


def test_phi3_active_params():
    # per layer: 4*3072^2 = 37,748,736 + 3*3072*8192 = 75,497,472;
    # 32 layers and the LM head 3072*32064 = 98,500,608
    assert counts.active_params(PHI3) == 32 * 113_246_208 + 98_500_608
    assert counts.active_params(PHI3) == 3_722_379_264


def test_experts_touched():
    # each of 32 tokens misses an expert with probability 3/4
    assert counts.experts_touched(GRANITE, 32) == pytest.approx(
        32 * (1 - 0.75 ** 32))
    assert counts.experts_touched(GRANITE, 1) == pytest.approx(8.0)


def test_phi3_decode_bytes():
    # weights: 32 layers of (37,748,736 + 75,497,472) bf16 and two f32
    # norm gains of 3072, the bf16 head, the final gain; cache 4 rows x
    # 1024 positions x 393,216 B; the 4 embedding rows read
    weights = 32 * (226_492_416 + 24_576) + 197_001_216 + 12_288
    cache = 4 * 1024 * 393_216
    flops, byts = counts.decode(PHI3, 4, 1023)
    assert byts == weights + cache + 4 * 3072 * 2 == 9_056_194_560
    # 2 per weight of 4 tokens, plus scores and values over 1024 keys
    assert flops == 2 * 4 * 3_722_379_264 + 4 * 32 * 32 * 96 * 4 * 1024


def test_granite_prefill_flops():
    B, S = 32, 1024
    matmul = 2 * B * S * (428_608_512 - 50_334_720)
    attn = 4 * 24 * 16 * 64 * B * S * (S + 1) // 2
    head = 2 * B * 1024 * 49155
    assert counts.prefill(GRANITE, B, S)[0] == matmul + attn + head


def test_granite_train_flops_per_token():
    # 6 x (8 layers x 15,761,408 + the head 50,334,720) = 1,058,555,904;
    # attention 3 x 4 x 8 x 16 x 64 x 2049/2 = 100,712,448
    assert counts.train_flops_per_token(GRANITE8, 2048) == pytest.approx(
        1_058_555_904 + 100_712_448)


def test_roofline_picks_the_larger_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.roofline_s(197e12, 1.0, peak) == pytest.approx(1.0)
    assert counts.roofline_s(1.0, 819e9, peak) == pytest.approx(1.0)
    # phi-3 decode at batch 4 is bound by bytes: about 11 ms
    f, b = counts.decode(PHI3, 4, 1023)
    assert counts.roofline_s(f, b, peak) == pytest.approx(b / 819e9)
    assert math.isclose(b / 819e9, 0.01106, rel_tol=1e-3)
