"""Decode attention, ``models.attention.decode_attention``, which takes the G
query heads of a group together against their one K/V head, against a plain
float64 reference that repeats every K/V head for its query heads, at
granite's (G 2, hd 64) and Phi-3's (G 1, hd 96) head shapes: the new key
merged as the key at ``pos`` over a cache that does not hold it yet, a
window that binds, and the cache that already holds ``pos``.  Also: the products are asked for exact, and GQA decode
under a device mesh gives the one-device tokens."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import decode_attention

B, HKV, S = 2, 2, 300
TOL = dict(rtol=1e-5, atol=1e-5)


def _inputs(G, hd, seed):
    """q in float32; caches and the new key and value bf16, as stored."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, G * HKV, 1, hd), jnp.float32)
    kc, vc = (jax.random.normal(k, (B, HKV, S, hd)).astype(jnp.bfloat16)
              for k in ks[1:3])
    kn, vn = (jax.random.normal(k, (B, HKV, 1, hd)).astype(jnp.bfloat16)
              for k in ks[3:])
    return q, kc, vc, kn, vn


def _plain(q, kc, vc, pos, window=0):
    """Softmax attention of each query head over its group's K/V head
    repeated, float64, keys at positions <= pos and inside the window."""
    q, kc, vc = (np.asarray(t, np.float64) for t in (q, kc, vc))
    G = q.shape[1] // kc.shape[1]
    kc, vc = np.repeat(kc, G, axis=1), np.repeat(vc, G, axis=1)
    t = np.arange(kc.shape[2])
    valid = (t <= pos) & ((window <= 0) | (pos - t < window))
    s = np.einsum("bhqd,bhsd->bhqs", q, kc) / np.sqrt(q.shape[-1])
    s = np.where(valid, s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqs,bhsd->bhqd", w / w.sum(-1, keepdims=True), vc)


def _with(c, new, pos):
    """The cache with ``new`` written at ``pos``, float64."""
    out = np.asarray(c, np.float64).copy()
    out[:, :, pos] = np.asarray(new, np.float64)[:, :, 0]
    return out


@pytest.mark.parametrize("G,hd", [(2, 64), (1, 96)], ids=["granite", "phi3"])
@pytest.mark.parametrize("pos,window", [
    (0, 0),         # nothing cached yet: the new key alone
    (127, 0),
    (S - 1, 0),     # the whole cache below the new key
    (250, 100),     # a window that binds
    (S - 1, 1),     # a window of one: the new key alone again
])
def test_new_key_is_attended_as_the_key_at_pos(G, hd, pos, window):
    """The cache's row at ``pos`` is stale (the scan writes the new key
    only after every layer has run): the new key and value take its
    place, and a reference that reads the stale row is told apart."""
    q, kc, vc, kn, vn = _inputs(G, hd, seed=pos + window)
    out = decode_attention(q, kc, vc, pos=jnp.int32(pos),
                           window=jnp.int32(window), new=(kn, vn))
    assert out.shape == q.shape and out.dtype == jnp.float32
    want = _plain(q, _with(kc, kn, pos), _with(vc, vn, pos), pos, window)
    np.testing.assert_allclose(out, want, **TOL)
    stale = _plain(q, kc, vc, pos, window)
    assert not np.allclose(out, stale, **TOL)


@pytest.mark.parametrize("G,hd", [(2, 64), (1, 96)], ids=["granite", "phi3"])
def test_a_cache_that_already_holds_pos(G, hd):
    """Without ``new`` the cache holds ``pos`` already, as the encoder's
    K/V do for cross-attention: keys up to ``pos`` are read, the rest
    masked."""
    q, kc, vc, _, _ = _inputs(G, hd, seed=7)
    out = decode_attention(q, kc, vc, pos=jnp.int32(200))
    np.testing.assert_allclose(out, _plain(q, kc, vc, 200), **TOL)


def test_products_are_asked_for_exact():
    """Every product of the scores and of the weighted sum is a dot at
    HIGHEST: on the TPU a float32 dot at the default precision rounds the
    query and the weights to bf16."""
    q, kc, vc, kn, vn = _inputs(2, 64, seed=0)
    text = jax.jit(lambda *a: decode_attention(
        *a[:3], pos=jnp.int32(5), new=a[3:])).lower(
            q, kc, vc, kn, vn).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 4
    assert all("HIGHEST" in line for line in dots), dots


def test_gqa_serving_on_four_devices_matches_one():
    """serve()'s mesh path runs the same GQA decode attention as one
    device (granite's smoke config: G 2, experts and heads split over
    ``model``) and gives its tokens."""
    code = """
        import jax, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch import serve as SV
        from repro.launch.mesh import make_host_mesh
        from repro.models import transformer as T
        cfg = get_smoke_config("granite_moe_1b_a400m")
        params, _ = T.init_params(cfg, jax.random.PRNGKey(3))
        prompts = list(np.random.default_rng(3).integers(
            0, cfg.vocab, (4, 16)))
        r1 = SV.serve(cfg, params, prompts, batch=4, max_new=6,
                      emit=lambda _: None)
        r4 = SV.serve(cfg, params, prompts, batch=4, max_new=6,
                      emit=lambda _: None, mesh=make_host_mesh(1, 4))
        print("SAME", (r1.tokens == r4.tokens).mean())
        assert r4.tokens.shape == r1.tokens.shape
        assert (r1.tokens == r4.tokens).mean() >= 0.75
    """
    import os
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SAME" in r.stdout
