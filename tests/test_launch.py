"""The serve launcher's loop and the helpers the chip entry points share
(``repro.launch.chip``), on the CPU at smoke size."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch import chip
from repro.launch.serve import model_inputs, serve
from repro.models import transformer as T
from repro.serve import make_prefill_step


def test_serve_pads_the_last_batch_and_keeps_its_cache():
    cfg = get_smoke_config("tinyllama_1_1b")
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = list(rng.integers(0, cfg.vocab, (5, 8), dtype=np.int32))
    r = serve(cfg, params, prompts, batch=2, max_new=4, emit=lambda _: None)
    assert r.tokens.shape == (5, 4)
    assert r.n_decode_steps == 3 * 2             # 3 batches x (4 - 2)
    assert r.next_pos == 8 + 4 - 1
    # the compiled steps' memory: decode donates its cache, so the cache
    # counts once although it is both an argument and an output
    assert set(r.memory) == {"prefill", "decode"}
    dec = r.memory["decode"]
    assert dec.alias_size_in_bytes > 0
    assert chip.program_bytes(dec) == (
        dec.argument_size_in_bytes + dec.output_size_in_bytes
        + dec.temp_size_in_bytes - dec.alias_size_in_bytes)
    # the cache is the last batch's: prompt 4 and its padding copy
    assert r.cache["k"].shape[1] == 2
    # one more step through the returned cache agrees with a prefill over
    # prompt + continuation (the check chip_smoke.py makes at full size)
    last = r.tokens[-1:, -1:]
    logits, _ = jax.jit(functools.partial(T.decode_forward, cfg))(
        params, r.cache, jnp.asarray(np.concatenate([last, last])),
        jnp.int32(r.next_pos))
    seq = np.concatenate([prompts[-1][None], r.tokens[-1:]], axis=1)
    want, _ = jax.jit(make_prefill_step(cfg, max_seq=seq.shape[1]))(
        params, model_inputs(cfg, jnp.asarray(seq)))
    np.testing.assert_allclose(np.asarray(logits[0, 0], np.float32),
                               np.asarray(want[0, -1], np.float32),
                               rtol=0.08, atol=0.15)
    with pytest.raises(ValueError, match="max_new"):
        serve(cfg, params, prompts, batch=2, max_new=2)


def test_serve_marks_each_batch_phase_with_a_span(tmp_path):
    """Four profiler spans a batch, in order and apart, on one host line,
    each carrying its batch's index: what the benchmark's per-layer serve
    metrics read off a chip trace."""
    import glob

    from jax.profiler import ProfileData
    cfg = get_smoke_config("granite_moe_1b_a400m")
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    prompts = list(np.random.default_rng(0).integers(0, cfg.vocab, (5, 8),
                                                     dtype=np.int32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve(cfg, params, prompts, batch=2, max_new=3, emit=lambda _: None)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [((plane.name, line.name), e.name, dict(e.stats)["batch"],
              e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("serve/")]
    assert len({where for where, *_ in spans}) == 1
    phases = ["serve/admit", "serve/prefill", "serve/decode", "serve/collect"]
    for b in range(3):
        mine = sorted((s for s in spans if s[2] == b), key=lambda s: s[3])
        assert [s[1] for s in mine] == phases
        assert all(a[4] <= z[3] for a, z in zip(mine, mine[1:]))
    assert len(spans) == 3 * len(phases)


def test_require_tpu_refuses_the_cpu():
    if jax.devices()[0].platform == "tpu":
        pytest.skip("checks the refusal on a host with no TPU")
    with pytest.raises(SystemExit, match="no TPU"):
        chip.require_tpu()


def test_compile_cache_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/the/env")
    assert chip.use_compile_cache() == "/from/the/env"
    assert jax.config.jax_compilation_cache_dir == before   # JAX reads env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = chip.use_compile_cache()
        assert path == str(chip.REPO / ".jax_cache")
        assert (chip.REPO / "src" / "repro" / "launch" / "chip.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_timer_counts_only_compiles():
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    with chip.CompileTimer() as cold:
        f(x).block_until_ready()
    with chip.CompileTimer() as warm:
        f(x).block_until_ready()
    assert cold.seconds > 0
    assert warm.seconds == 0
