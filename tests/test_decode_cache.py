"""What one decode step does to the KV cache: it writes the new position's
K and V in every layer, what a prefill of one more token writes there, and
leaves every other position bit for bit as it was.  A second step then
reads the first step's write as a prefill of the longer prompt would.
Run at smoke size on the configurations the decode layer scans serve: a
sparse GQA model, a dense multi-head one, Gemma-3's local:global layers
(a sliding window of 8 that binds from position 8 on), an
encoder-decoder, whose cross-attention cache a decode step only reads,
DeepSeek-V2's latent cache (``ckv``, ``krope``: (L, B, S, ·)) across its
dense and MoE stacks, and Zamba2's hybrid of Mamba2 layers and one shared
attention block, whose K/V cache holds one entry a superblock.  A
recurrent state (``conv``, ``ssm``) is replaced whole each step, so it is
exempt from the bit-for-bit check.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T

B, S = 2, 8
MAX_SEQ = S + 4
TOL = dict(rtol=0.08, atol=0.15)   # test_decode_matches_forward's decode
# the caches a decode step writes at ``pos``, on their axis -2
WRITTEN = ("k", "v", "ckv", "krope")
# the recurrent states a decode step replaces whole
STATES = ("conv", "ssm")


def _batch(cfg, tokens):
    batch = {"tokens": tokens}
    if cfg.family == "encdec":
        batch["frames"] = jnp.full((B, cfg.encoder.n_ctx, cfg.d_model), .1,
                                   jnp.float32)
    return batch


@pytest.fixture(scope="module", params=["granite_moe_1b_a400m",
                                        "phi3_mini_3_8b", "gemma3_1b",
                                        "whisper_small",
                                        "deepseek_v2_lite_16b",
                                        "zamba2_2_7b"])
def stepped(request):
    """A prefill of S tokens, then one decode step at position S."""
    cfg = get_smoke_config(request.param)
    if cfg.moe is not None:  # no capacity drops between prompt lengths
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S + 2)), jnp.int32)

    def prefill(n):
        return T.prefill_forward(cfg, params, _batch(cfg, tokens[:, :n]),
                                 max_seq=MAX_SEQ)
    _, before = prefill(S)
    _, after = T.decode_forward(cfg, params, before, tokens[:, S:S + 1], S)
    return dict(cfg=cfg, params=params, tokens=tokens, prefill=prefill,
                before=before, after=after)


def _bits(a):
    return np.asarray(a).view(np.uint16)


def test_decode_leaves_every_other_position_bit_for_bit(stepped):
    before, after = stepped["before"], stepped["after"]
    assert set(after) == set(before)
    for name in set(before) - set(STATES):
        old, new = _bits(before[name]), _bits(after[name])
        assert new.shape == old.shape, name
        if name in WRITTEN:           # all but pos
            old = np.delete(old, S, axis=old.ndim - 2)
            new = np.delete(new, S, axis=new.ndim - 2)
        np.testing.assert_array_equal(new, old, err_msg=name)


def test_decode_writes_what_prefill_writes_at_pos(stepped):
    _, longer = stepped["prefill"](S + 1)
    for name in set(WRITTEN) & set(longer):
        got = np.take(np.asarray(stepped["after"][name], np.float32), S, -2)
        want = np.take(np.asarray(longer[name], np.float32), S, -2)
        assert np.abs(got).max() > 0, name
        np.testing.assert_allclose(got, want, err_msg=name, **TOL)


def test_second_step_matches_prefill(stepped):
    cfg, tokens = stepped["cfg"], stepped["tokens"]
    logits, _ = T.decode_forward(cfg, stepped["params"], stepped["after"],
                                 tokens[:, S + 1:S + 2], S + 1)
    want, _ = stepped["prefill"](S + 2)
    np.testing.assert_allclose(np.asarray(logits[:, 0], np.float32),
                               np.asarray(want[:, 0], np.float32), **TOL)
