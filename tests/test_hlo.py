"""HLO analyzer: trip-count unsampling + collective accounting on real
compiled modules (single-device; the 512-device path is covered by the
dry-run artifact)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hlo import analyze_hlo


def test_scan_trip_count_unsampled():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), ()
        y, _ = jax.lax.scan(body, x, w)
        return y
    xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    res = analyze_hlo(jax.jit(f).lower(xs, ws).compile().as_text())
    per_iter = 2 * 64 * 128 * 128
    assert abs(res["dot_flops"] - 10 * per_iter) / (10 * per_iter) < 0.05
    assert res["n_while"] >= 1
    # XLA's own cost_analysis counts the body once — we must exceed it ~10x
    ca = jax.jit(f).lower(xs, ws).compile().cost_analysis()
    assert res["dot_flops"] > 5 * ca["flops"]


def test_nested_scan():
    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return jnp.tanh(c2 @ wi), ()
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, ()
        y, _ = jax.lax.scan(outer, x, w)
        return y
    xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    res = analyze_hlo(jax.jit(f).lower(xs, ws).compile().as_text())
    expect = 3 * 4 * 2 * 32 * 64 * 64
    assert abs(res["dot_flops"] - expect) / expect < 0.05


def test_elementwise_and_transcendentals():
    def f(x):
        return jnp.sum(jnp.exp(x) * x + jnp.tanh(x))
    xs = jax.ShapeDtypeStruct((1024,), jnp.float32)
    res = analyze_hlo(jax.jit(f).lower(xs).compile().as_text())
    assert res["transcendentals"] >= 2 * 1024
    assert res["flops"] >= 3 * 1024


def test_bytes_reasonable_for_copy():
    def f(x):
        return x * 2.0
    xs = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)
    res = analyze_hlo(jax.jit(f).lower(xs).compile().as_text())
    nbytes = 4 * (1 << 20)
    assert nbytes <= res["bytes"] <= 4 * nbytes


def test_dryrun_artifact_has_collectives():
    """The committed sweep results must show collectives on every multi-chip
    train cell (proves the pod axis actually shards)."""
    import json
    import pathlib
    p = pathlib.Path("experiments/dryrun/results.json")
    if not p.exists():
        import pytest
        pytest.skip("dry-run sweep not present")
    res = json.loads(p.read_text())
    ok = [r for r in res.values() if r["status"] == "ok"]
    assert len(ok) >= 60
    for r in ok:
        if r["kind"] == "train":
            assert r["hlo"]["collective_bytes"] > 0, r["arch"]
