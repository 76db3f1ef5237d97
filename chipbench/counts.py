"""Operations and bytes that the model's work needs, computed from its
shapes: the yardstick for roofline shares and MFU.

Counted as the model requires, not as the program happens to run it:
mixture-of-experts work is the routed top-k, never the capacity-padded
slots; attention is causal (query i reads keys 0..i); a decode step reads
the parameters it touches (experts by their expected distinct count when
each of B tokens picks k of E uniformly) and the bfloat16 cache up to its
own position.  ``m`` is ``chipbench.spec.dims``.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _proj_params(m) -> int:
    """Attention projection weights of one layer."""
    return m["d"] * (m["H"] + 2 * m["Hkv"]) * m["hd"] + m["H"] * m["hd"] * m["d"]


def _ffn_params(m) -> int:
    """SwiGLU weights of one expert, or of the dense MLP."""
    return 3 * m["d"] * m["ff"]


def active_params(m) -> int:
    """Weights one token multiplies by: projections, the router, the k
    experts it reaches (or the MLP), and the output head.  The embedding
    lookup does no arithmetic."""
    per_layer = _proj_params(m) + (
        m["d"] * m["E"] + m["k"] * _ffn_params(m) if m["E"] else _ffn_params(m))
    return m["L"] * per_layer + m["d"] * m["V"]


def experts_touched(m, tokens: int) -> float:
    """Expected distinct experts that ``tokens`` tokens reach, each picking
    k of E uniformly."""
    return m["E"] * (1.0 - (1.0 - m["k"] / m["E"]) ** tokens)


def _weight_bytes(m, tokens: int) -> float:
    """Bytes of the parameters that ``tokens`` tokens touch, per layer,
    summed: bf16 matrices, f32 norm gains and router."""
    ffn = (experts_touched(m, tokens) * _ffn_params(m) * BF16
           + m["d"] * m["E"] * F32) if m["E"] else _ffn_params(m) * BF16
    per_layer = _proj_params(m) * BF16 + 2 * m["d"] * F32 + ffn
    return m["L"] * per_layer + m["d"] * m["V"] * BF16 + m["d"] * F32


def _kv_bytes(m, positions: int) -> int:
    """bf16 keys and values of ``positions`` positions over all layers."""
    return 2 * m["L"] * positions * m["Hkv"] * m["hd"] * BF16


def _attn_flops(m, queries_keys: int) -> int:
    """Scores and weighted sum over ``queries_keys`` (query, key) pairs,
    all layers."""
    return 4 * m["L"] * m["H"] * m["hd"] * queries_keys


def _matmul_flops(m, tokens: int) -> int:
    """Every weight product of ``tokens`` tokens, without the head."""
    return 2 * tokens * (active_params(m) - m["d"] * m["V"])


def prefill(m, B: int, S: int) -> tuple[float, float]:
    """(flops, bytes) of one prefill of B prompts of S tokens: logits for
    the last position only, the cache written for S positions."""
    flops = (_matmul_flops(m, B * S) + _attn_flops(m, B * S * (S + 1) // 2)
             + 2 * B * m["d"] * m["V"])
    byts = (_weight_bytes(m, B * S) + _kv_bytes(m, B * S)
            + B * S * m["d"] * BF16)
    return float(flops), float(byts)


def decode(m, B: int, pos: int) -> tuple[float, float]:
    """(flops, bytes) of one decode step of B tokens at position ``pos``:
    the cache is read for positions 0..pos and written at ``pos``."""
    flops = (_matmul_flops(m, B) + _attn_flops(m, B * (pos + 1))
             + 2 * B * m["d"] * m["V"])
    byts = (_weight_bytes(m, B) + _kv_bytes(m, B * (pos + 1))
            + B * m["d"] * BF16)
    return float(flops), float(byts)


def train_flops_per_token(m, S: int) -> float:
    """Forward and backward of one token in a causal sequence of S: six
    flops per active weight, three times the forward attention.
    Recomputation does not count."""
    return 6.0 * active_params(m) + 3.0 * _attn_flops(m, 1) * (S + 1) / 2


def roofline_s(flops: float, byts: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"], byts / peak["hbm_bytes_per_s"])
