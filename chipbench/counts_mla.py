"""Operations and bytes that DeepSeek-V2's serving work needs, computed
from its shapes (``chipbench.weights_mla.dims``): the yardstick for the
four-chip cell's roofline shares and MFU.

Counted as the model requires, not as the program runs it:
  * mixture-of-experts work is the routed top-k and the shared experts,
    never the capacity slots; the dense layer 0 is a SwiGLU of its width;
  * prefill runs latent attention expanded: ``kv_b`` projects the latent
    up to per-head keys and values, and attention is causal (query i reads
    keys 0..i) over ``qk_nope + qk_rope`` dims for scores and ``v_head``
    for the weighted sum;
  * decode runs it absorbed: ``q_nope`` is taken into the latent space
    (H x qk_nope x R) and the output out of it (H x R x v_head), and each
    of the pos + 1 cached positions costs 2 H (2 R + qk_rope) FLOPs a
    layer: scores against the latent and the rope key, and the weighted
    sum of the latent;
  * a decode step reads the parameters it touches, routed experts by their
    expected distinct count when each of B tokens picks k of E uniformly,
    and the bf16 latent cache up to its own position once; a prefill reads
    every parameter its tokens touch and writes the cache for S positions.
"""
from __future__ import annotations

from chipbench.counts import experts_touched

BF16, F32 = 2, 4


def _attn_params(m) -> int:
    """Projection weights of one layer's latent attention."""
    H, R = m["H"], m["R"]
    return (m["d"] * H * (m["dn"] + m["dr"]) + m["d"] * (R + m["dr"])
            + R * H * (m["dn"] + m["dv"]) + H * m["dv"] * m["d"])


def _swiglu(d: int, ff: int) -> int:
    return 3 * d * ff


def _per_token_layers(m) -> int:
    """Weights one token multiplies by in all layers, attention
    projections aside: the dense MLP, then the router, k routed and the
    shared experts."""
    d = m["d"]
    moe = (d * m["E"] + m["k"] * _swiglu(d, m["ff"])
           + _swiglu(d, m["shared"] * m["ff"]))
    return m["nd"] * _swiglu(d, m["ff_dense"]) + (m["L"] - m["nd"]) * moe


def _weight_bytes(m, tokens: int) -> float:
    """Bytes of the parameters that ``tokens`` tokens touch: bf16
    matrices, f32 norm gains and router, the head; the embedding rows."""
    d, L, nd = m["d"], m["L"], m["nd"]
    norms = (2 * d + m["R"]) * F32
    attn = L * (_attn_params(m) * BF16 + norms)
    dense = nd * _swiglu(d, m["ff_dense"]) * BF16
    moe = (L - nd) * (experts_touched(m, tokens) * _swiglu(d, m["ff"]) * BF16
                      + _swiglu(d, m["shared"] * m["ff"]) * BF16
                      + d * m["E"] * F32)
    return (attn + dense + moe + d * m["V"] * BF16 + d * F32
            + tokens * d * BF16)


def _cache_bytes(m, positions: int) -> int:
    """bf16 latent and rope key of ``positions`` positions, all layers."""
    return m["L"] * positions * (m["R"] + m["dr"]) * BF16


def prefill(m, B: int, S: int) -> tuple[float, float]:
    """(flops, bytes) of one prefill of B prompts of S tokens: logits for
    the last position only, the cache written for S positions."""
    T, H, L = B * S, m["H"], m["L"]
    pairs = B * S * (S + 1) // 2
    flops = (2 * T * (L * _attn_params(m) + _per_token_layers(m))
             + 2 * L * H * (m["dn"] + m["dr"] + m["dv"]) * pairs
             + 2 * B * m["d"] * m["V"])
    return float(flops), float(_weight_bytes(m, T) + _cache_bytes(m, T))


def decode(m, B: int, pos: int) -> tuple[float, float]:
    """(flops, bytes) of one decode step of B tokens at position ``pos``:
    the latent cache read for positions 0..pos and written at ``pos``."""
    H, R, L = m["H"], m["R"], m["L"]
    proj = (m["d"] * H * (m["dn"] + m["dr"]) + m["d"] * (R + m["dr"])
            + H * m["dn"] * R + H * R * m["dv"] + H * m["dv"] * m["d"])
    flops = (2 * B * (L * proj + _per_token_layers(m))
             + 2 * L * H * (2 * R + m["dr"]) * B * (pos + 1)
             + 2 * B * m["d"] * m["V"])
    byts = _weight_bytes(m, B) + _cache_bytes(m, B * (pos + 1))
    return float(flops), float(byts)


def roofline_s(flops: float, byts: float, peak: dict, chips: int) -> float:
    """Least time ``chips`` chips could take together: the larger of the
    two bounds over their summed peaks."""
    return max(flops / (chips * peak["bf16_flops_per_s"]),
               byts / (chips * peak["hbm_bytes_per_s"]))
