"""Pluggable per-op cost backends (``repro.sim.backends``).

Contract layers:

* **roofline bit-identity** — ``cost_backend=None`` (the default),
  an explicit ``RooflineBackend()`` and the ``"roofline"`` name are all
  bit-identical on random DAGs and chains, across the event loop, the
  fused typed-array core and the chain fast path (plus a hypothesis
  property sweep), so the backend seam cannot perturb the pre-backend
  engine.
* **systolic** — utilization in (0, 1], exactly 1.0 on array-aligned
  tiles, fill/drain exposure without double buffering, im2col traffic
  for conv tiles; a degenerate 1x1 array with im2col off degenerates to
  roofline bit-exactly.
* **table** — reproduces its own measured samples exactly, log-log
  interpolates a power law exactly between them, clamps outside the
  range, and prices identically through every engine path.
* **calibration fit** — ``fit_linear_cost`` recovers known synthetic
  (peak, bandwidth, overhead) parameters; ``repro.kernels.calibrate``
  reports ~0 fitted MAPE on synthetic linear-law records.
* **restrictions** — the analytic DSE layer (``CostModel``,
  ``chain_params_for``, ``batched``/``optimize``) refuses non-roofline
  backends with ``Unsupported`` instead of mispricing them.
* **bugfix regressions** — ``costmodel._has_jax`` warns exactly once on
  a broken (not merely absent) jax; ``repro.kernels.ops`` interprets
  only when the caller passes ``interpret=True``, never on its own
  because of the backend; GQA attention passes KV to the
  kernel at its native ``(B, Hkv, S, D)`` instead of materializing the
  broadcast.
"""
import dataclasses
import math
import random
import sys
import warnings

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.apps.paper_graphs import build_paper_graph
from repro.configs.paper_nets import PAPER_NETS
from repro.sim import backends, costmodel, engine, hw, ir
from repro.sim.sweep import batched, optimize, sweep

CONFIGS = [
    engine.EngineConfig(),
    engine.EngineConfig(n_workers=4, interface="hbm", hbm_ports=2),
    engine.EngineConfig(n_workers=8, interface="dma", hbm_ports=1),
    engine.EngineConfig(n_workers=3, interface="acp", hbm_ports=0.5,
                        host_dispatch_s=1e-6, host_bw=20e9, host_threads=4),
    engine.EngineConfig(n_workers=2, interface="ideal",
                        overlap_transfers=True, host_floor_s=1e-4),
]

SYSTOLIC = backends.SystolicBackend()
TABLE = backends.TableBackend(samples=(("", 1e6, 1e-4), ("", 1e9, 1e-2)))


def assert_bit_identical(a, b):
    assert a.makespan == b.makespan
    assert a.breakdown == b.breakdown
    assert a.roofline == b.roofline
    assert a.energy == b.energy
    assert a.timeline.events == b.timeline.events


def random_program(rng: random.Random, n: int, chain: bool) -> ir.Program:
    """Random DAG/chain with tile/op_kind metadata on a subset of ops —
    the shapes every backend must price."""
    ops = []
    for i in range(n):
        if chain:
            deps = (f"op{i-1}",) if i else ()
        else:
            deps = tuple(f"op{j}" for j in range(max(0, i - 6), i)
                         if rng.random() < 0.35)
        kind = rng.choice(["", "", "matmul", "conv"])
        tile = ((rng.choice([32, 100, 128, 256]),
                 rng.choice([32, 100, 128, 256]),
                 rng.choice([9, 64, 576])) if kind else ())
        ops.append(ir.CostedOp(
            name=f"op{i}",
            flops=rng.choice([0.0, 1e6, 5e8, 2e9]),
            dot_flops=rng.choice([0.0, 1e6, 4e8]),
            bytes_in=rng.choice([0.0, 1e5, 3e7, 2e8]),
            bytes_out=rng.choice([0.0, 1e5, 2e6]),
            transcendentals=rng.choice([0.0, 1e5]),
            deps=deps,
            phase=f"ph{i % 3}",
            duration_s=rng.choice([None, None, None, 1e-4]),
            tile=tile, op_kind=kind))
    return ir.Program(ops, name="rand-backend")


def _with(cfg, backend):
    return dataclasses.replace(cfg, cost_backend=backend)


# ---------------------------------------------------------------------------
# roofline bit-identity: the tentpole's "don't move the needle" gate


@pytest.mark.parametrize("chain", [False, True])
@pytest.mark.parametrize("spec", [backends.RooflineBackend(), "roofline"])
def test_explicit_roofline_bit_identical_to_default(chain, spec):
    rng = random.Random(515 + chain)
    for _ in range(10):
        prog = random_program(rng, rng.randint(1, 60), chain)
        for cfg in CONFIGS:
            base = engine.run(prog, cfg)
            assert_bit_identical(engine.run(prog, _with(cfg, spec)), base)


@pytest.mark.parametrize("fast,fuse", [(True, None), (False, True),
                                       (False, False)])
def test_explicit_roofline_every_engine_path(fast, fuse):
    rng = random.Random(99)
    prog = random_program(rng, 40, chain=True)
    for cfg in CONFIGS:
        base = engine.run(prog, cfg, fast=fast, fuse=fuse)
        got = engine.run(prog, _with(cfg, backends.RooflineBackend()),
                         fast=fast, fuse=fuse)
        assert_bit_identical(got, base)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 40), st.booleans())
def test_roofline_identity_hypothesis(seed, n, chain):
    rng = random.Random(seed)
    prog = random_program(rng, n, chain)
    cfg = CONFIGS[seed % len(CONFIGS)]
    assert_bit_identical(
        engine.run(prog, _with(cfg, backends.RooflineBackend())),
        engine.run(prog, cfg))


# ---------------------------------------------------------------------------
# systolic


def test_systolic_utilization_bounds_and_alignment():
    rng = random.Random(3)
    for db in (True, False):
        bk = backends.SystolicBackend(double_buffered=db)
        for _ in range(200):
            tile = (rng.randint(1, 1000), rng.randint(1, 1000),
                    rng.randint(1, 4096))
            u = bk.utilization(tile)
            assert 0.0 < u <= 1.0, (tile, db)
    aligned = backends.SystolicBackend(rows=128, cols=128)
    for m, n in ((128, 128), (256, 128), (512, 384), (128, 1024)):
        assert aligned.utilization((m, n, 64)) == 1.0
    # partial folds idle PEs: exact closed form
    assert aligned.utilization((100, 100, 64)) == \
        (100 / 128) * (100 / 128)
    # no / short tile metadata -> full utilization (macro-op fallback)
    assert aligned.utilization(()) == 1.0
    assert aligned.utilization((5,)) == 1.0


def test_systolic_fill_drain_exposed_without_double_buffering():
    db = backends.SystolicBackend(double_buffered=True)
    nodb = backends.SystolicBackend(double_buffered=False)
    tile = (128, 128, 64)
    assert nodb.utilization(tile) == \
        db.utilization(tile) * 64 / (64 + 128 + 128 - 2)
    assert nodb.utilization(tile) < db.utilization(tile)


def test_systolic_op_time_contract():
    eff = engine.EngineConfig()
    bk = backends.SystolicBackend()
    op = ir.CostedOp("x", flops=1e9, tile=(100, 100, 64),
                     op_kind="matmul")
    assert bk.op_time(op, eff) == pytest.approx(
        1e9 / (eff.peak_flops * bk.utilization((100, 100, 64))))
    # duration_s always wins; zero flops is free
    assert bk.op_time(dataclasses.replace(op, duration_s=3e-5), eff) == 3e-5
    assert bk.op_time(ir.CostedOp("z", flops=0.0), eff) == 0.0


def test_systolic_im2col_charges_conv_patch_traffic():
    eff = engine.EngineConfig()
    tile = (256, 128, 576)                      # M x N x K patch matrix
    conv = ir.CostedOp("c", flops=1e9, bytes_in=1e5, tile=tile,
                       op_kind="conv")
    on = backends.SystolicBackend()
    off = backends.SystolicBackend(im2col=False)
    extra = (4.0 * tile[0] * tile[2] - 1e5) / eff.hbm_bw
    assert on.op_time(conv, eff) == pytest.approx(
        off.op_time(conv, eff) + extra)
    # matmul tiles never pay im2col
    mm = dataclasses.replace(conv, op_kind="matmul")
    assert on.op_time(mm, eff) == off.op_time(mm, eff)


def test_systolic_never_faster_than_roofline_on_real_graph():
    g = build_paper_graph(PAPER_NETS["lenet5"], batch=1)
    prog = ir.from_graph(g, batch=1, max_tile_elems=16384)
    cfg = engine.EngineConfig(n_workers=4)
    roof = engine.run(prog, cfg).makespan
    sys_ = engine.run(prog, _with(cfg, SYSTOLIC)).makespan
    assert sys_ >= roof
    # a 1x1 array is always perfectly utilized: with im2col off the
    # systolic model degenerates to the roofline bit-exactly
    degenerate = backends.SystolicBackend(rows=1, cols=1, im2col=False)
    assert_bit_identical(engine.run(prog, _with(cfg, degenerate)),
                         engine.run(prog, cfg))


def test_from_graph_attaches_tile_metadata():
    g = build_paper_graph(PAPER_NETS["lenet5"], batch=1)
    prog = ir.from_graph(g, batch=1, max_tile_elems=16384)
    kinds = {op.op_kind for op in prog.ops}
    assert "conv" in kinds and "matmul" in kinds
    for op in prog.ops:
        if op.op_kind:
            assert len(op.tile) == 3 and all(d > 0 for d in op.tile), op
        else:
            assert op.tile == ()


# ---------------------------------------------------------------------------
# table


def test_table_round_trips_its_samples():
    samples = (("matmul", 1e6, 3.1e-4), ("matmul", 1e8, 8.9e-3),
               ("conv", 2e6, 5.5e-4))
    bk = backends.TableBackend(samples=samples)
    eff = engine.EngineConfig()
    for kind, flops, secs in samples:
        assert bk.op_time(
            ir.CostedOp("o", flops=flops, op_kind=kind), eff) == secs
    # unknown kind falls back to the pooled table — still exact on a
    # sampled flop count that is unique across the pool
    assert bk.op_time(
        ir.CostedOp("o", flops=1e8, op_kind="mystery"), eff) == 8.9e-3


def test_table_interpolates_power_law_exactly():
    # t = c * f^0.8 sampled at two points: log-log interpolation is exact
    # at any flops between them
    c, a = 3e-10, 0.8
    f1, f2 = 1e6, 1e10
    bk = backends.TableBackend(samples=(("", f1, c * f1**a),
                                        ("", f2, c * f2**a)))
    eff = engine.EngineConfig()
    for f in (1e7, 1e8, 31e8):
        got = bk.op_time(ir.CostedOp("o", flops=f), eff)
        assert got == pytest.approx(c * f**a, rel=1e-12)
    # clamped outside the measured range
    assert bk.op_time(ir.CostedOp("o", flops=1e12), eff) == \
        pytest.approx(c * f2**a, rel=1e-12)
    assert bk.op_time(ir.CostedOp("o", flops=10.0), eff) == \
        pytest.approx(c * f1**a, rel=1e-12)


def test_table_rejects_empty():
    with pytest.raises(ValueError):
        backends.TableBackend(samples=())


@pytest.mark.parametrize("backend", [SYSTOLIC, TABLE])
def test_non_roofline_engine_paths_agree(backend):
    """fast chain path, dict event loop and fused typed-array core all
    price a non-roofline backend identically."""
    rng = random.Random(44)
    chain = random_program(rng, 30, chain=True)
    dag = random_program(rng, 40, chain=False)
    for cfg in CONFIGS[:3]:
        cfgb = _with(cfg, backend)
        fast = engine.run(chain, cfgb, fast=True)
        slow = engine.run(chain, cfgb, fast=False, fuse=False)
        fused = engine.run(chain, cfgb, fast=False, fuse=True)
        assert_bit_identical(fast, slow)
        assert_bit_identical(fast, fused)
        assert_bit_identical(engine.run(dag, cfgb, fuse=True),
                             engine.run(dag, cfgb, fuse=False))


def test_device_level_backend_override():
    """Device.cost_backend=None inherits the config; a per-device backend
    overrides it — priced like the flat config carrying that backend."""
    rng = random.Random(77)
    prog = random_program(rng, 25, chain=False)
    cfg = engine.EngineConfig(n_workers=2)
    topo = hw.SoCTopology(
        devices=(hw.Device("acc0", cost_backend=SYSTOLIC),
                 hw.Device("acc1", cost_backend=SYSTOLIC)),
        links=(hw.Link("hbm", bandwidth=cfg.hbm_bw,
                       ports=cfg.hbm_ports),),
        name="sys-devs")
    via_device = engine.run(prog, dataclasses.replace(cfg, topology=topo))
    via_config = engine.run(prog, _with(cfg, SYSTOLIC))
    assert_bit_identical(via_device, via_config)


# ---------------------------------------------------------------------------
# calibration fit


def test_fit_recovers_synthetic_parameters():
    rng = np.random.default_rng(5)
    f = rng.uniform(1e6, 1e10, 40)
    b = rng.uniform(1e4, 1e8, 40)
    peak, bw, c = 3.7e12, 6.1e10, 2.4e-5
    t = f / peak + b / bw + c
    fit = backends.fit_linear_cost(f, b, t)
    assert fit["peak_flops_eff"] == pytest.approx(peak, rel=1e-6)
    assert fit["bw_eff"] == pytest.approx(bw, rel=1e-6)
    assert fit["overhead_s"] == pytest.approx(c, rel=1e-6)
    assert fit["mape"] < 1e-9


def test_fit_drops_vanished_terms():
    # overhead-dominated samples whose time *decreases* with flops: the
    # unconstrained fit puts a negative coefficient on the flops column,
    # which the non-negativity projection must drop (rate -> inf)
    rng = np.random.default_rng(6)
    f = np.geomspace(1e6, 1e9, 12)
    b = rng.uniform(1e4, 1e6, 12)
    t = 4.2e-4 - 1e-16 * f
    fit = backends.fit_linear_cost(f, b, t)
    assert fit["peak_flops_eff"] == math.inf
    assert fit["overhead_s"] == pytest.approx(4.2e-4, rel=1e-3)
    assert fit["mape"] < 1e-3


def test_calibrate_fit_on_synthetic_records():
    from repro.kernels import calibrate
    rng = np.random.default_rng(11)
    peak, bw, c = 8e11, 3e10, 1e-5
    records = []
    for kernel in ("matmul", "attention", "mamba"):
        for _ in range(6):
            f = float(rng.uniform(1e7, 1e10))
            b = float(rng.uniform(1e5, 1e8))
            records.append({"kernel": kernel, "kind": kernel,
                            "shape": [1], "flops": f, "bytes": b,
                            "measured_s": f / peak + b / bw + c})
    fits = calibrate.calibrate(records)
    for kernel, fit in fits.items():
        assert fit["fitted_mape"] < 1e-9, kernel
        assert fit["fitted"]["peak_flops_eff"] == pytest.approx(
            peak, rel=1e-5)
        assert fit["fitted_mape"] < fit["roofline_mape"]
        assert fit["table_max_rel_err"] == 0.0
    report = calibrate.build_report(
        records, {"backend": "synthetic", "interpret": False,
                  "grid": "synthetic", "repeat": 1}, fits)
    assert report["n_improved"] == 3


def test_mape_and_table_from_samples():
    assert backends.mape([2.0, 2.0], [1.0, 4.0]) == pytest.approx(0.75)
    bk = backends.table_from_samples(
        [{"kind": "matmul", "flops": 1e6, "measured_s": 2e-4}])
    assert bk.op_time(
        ir.CostedOp("o", flops=1e6, op_kind="matmul"),
        engine.EngineConfig()) == 2e-4


# ---------------------------------------------------------------------------
# registry / config plumbing


def test_get_backend_resolution_and_errors():
    assert backends.get_backend(None) is backends.ROOFLINE
    assert backends.get_backend("roofline") is backends.ROOFLINE
    assert isinstance(backends.get_backend("systolic"),
                      backends.SystolicBackend)
    assert backends.get_backend(SYSTOLIC) is SYSTOLIC
    with pytest.raises(ValueError, match="unknown cost backend"):
        backends.get_backend("scale-sim")
    with pytest.raises(TypeError):
        backends.get_backend(42)
    assert isinstance(SYSTOLIC, backends.CostBackend)


def test_configs_with_backends_stay_hashable():
    for bk in (SYSTOLIC, TABLE, backends.RooflineBackend(), "systolic"):
        cfg = engine.EngineConfig(cost_backend=bk)
        assert hash(cfg) == hash(dataclasses.replace(cfg))


def test_analytic_layer_refuses_non_roofline():
    rng = random.Random(8)
    chain = random_program(rng, 12, chain=True)
    for bk in (SYSTOLIC, TABLE, "systolic"):
        cfg = _with(engine.EngineConfig(), bk)
        with pytest.raises(costmodel.Unsupported, match="backend"):
            costmodel.CostModel(chain, cfg)
        with pytest.raises(costmodel.Unsupported, match="backend"):
            costmodel.chain_params_for(cfg)
        with pytest.raises(costmodel.Unsupported):
            batched(chain, [cfg])
        with pytest.raises(costmodel.Unsupported):
            optimize(chain, {"peak_flops": (1e13, 1e14)},
                     base_config=cfg)
    # the explicit roofline instance is fully supported and exact
    cfgs = [_with(c, backends.RooflineBackend()) for c in CONFIGS[:2]]
    bs = batched(chain, cfgs, top_k=0)
    exact = [r.makespan for r in sweep(chain, cfgs)]
    np.testing.assert_allclose(bs.lower, exact, rtol=1e-12)


def test_sweep_batched_rejects_mixed_backends():
    rng = random.Random(9)
    chain = random_program(rng, 8, chain=True)
    cfgs = [engine.EngineConfig(), _with(engine.EngineConfig(), SYSTOLIC)]
    with pytest.raises(costmodel.Unsupported, match="backend"):
        batched(chain, cfgs)


def test_serving_step_table_degrades_gracefully():
    """StepCostTable falls back from the analytic chain params to
    backend-aware per-op pricing for non-roofline configs."""
    from repro.serve.policy import ContinuousBatching
    from repro.sim import serving
    from repro.configs.gemma_2b import FULL as GEMMA_2B
    trace = serving.poisson_trace(40, 80.0, prompt_len=64, output_len=8,
                                  seed=3)
    cfg = _with(engine.EngineConfig(), SYSTOLIC)
    res = serving.simulate_serving(GEMMA_2B, trace, ContinuousBatching(),
                                   config=cfg)
    assert res.makespan_s > 0.0


# ---------------------------------------------------------------------------
# bugfix regressions (the three satellites)


def test_has_jax_warns_once_on_broken_install(monkeypatch):
    import builtins
    monkeypatch.setattr(costmodel, "_JAX_PROBE_WARNED", False)
    real_import = builtins.__import__

    def broken(name, *a, **k):
        # a jax whose import *crashes* (broken jaxlib, bad wheel) — the
        # case the old blanket `except Exception: return False`
        # swallowed silently
        if name == "jax":
            raise RuntimeError("mock: jaxlib ABI mismatch")
        return real_import(name, *a, **k)
    monkeypatch.setattr(builtins, "__import__", broken)
    with pytest.warns(RuntimeWarning, match="jax import failed with "
                                            "RuntimeError"):
        assert costmodel._has_jax() is False
    with warnings.catch_warnings():
        warnings.simplefilter("error")              # second probe: silent
        assert costmodel._has_jax() is False


def test_has_jax_quiet_when_absent_or_present(monkeypatch):
    jax = pytest.importorskip("jax")
    monkeypatch.setattr(costmodel, "_JAX_PROBE_WARNED", False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert costmodel._has_jax() is True         # healthy install
        # merely *absent* (ModuleNotFoundError for jax itself) stays
        # silent: None in sys.modules raises exactly that
        monkeypatch.setitem(sys.modules, "jax", None)
        assert costmodel._has_jax() is False
    assert costmodel._JAX_PROBE_WARNED is False
    del jax


def test_interpret_only_when_asked():
    """Kernels interpret only when the caller passes ``interpret=True``.
    Off the TPU a call without it fails rather than falling back to the
    interpreter, so no timing of the interpreter passes for a chip's."""
    jax = pytest.importorskip("jax")
    if jax.default_backend() == "tpu":
        pytest.skip("checks the refusal on a host with no TPU")
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    a = jax.random.normal(jax.random.PRNGKey(0), (128, 256), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 128), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        ops.matmul(a, b)
    np.testing.assert_allclose(np.asarray(ops.matmul(a, b, interpret=True)),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-3)


def test_gqa_kv_reaches_kernel_unmaterialized(monkeypatch):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.kernels import flash_attention as fa
    from repro.kernels import ops
    B, H, Hkv, S, D = 1, 4, 2, 64, 16
    seen = {}
    real = fa.pl.pallas_call

    def spy(*a, **kw):
        call = real(*a, **kw)

        def run(q, k, v):
            seen["k"], seen["v"] = k.shape, v.shape
            return call(q, k, v)
        return run
    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    fa.flash_attention.clear_cache()        # trace again, through the spy
    q = jnp.zeros((B, H, S, D))
    kv = jnp.zeros((B, Hkv, S, D))
    ops.flash_attention(q, kv, kv, interpret=True)
    # the regression: a wrapper used to jnp.broadcast_to KV to the full
    # (B, H, S, D) before the kernel ever saw it; the kernel gets the
    # (B*Hkv, S, D) rows and resolves the group in its index maps
    assert seen["k"] == (B * Hkv, S, D)
    assert seen["v"] == (B * Hkv, S, D)


def test_gqa_native_kernel_matches_repeated_kv_reference():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.kernels import ops
    B, H, Hkv, S, D = 1, 4, 2, 128, 16
    key = jax.random.PRNGKey(0)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, S, D), jnp.float32)
    k = jax.random.normal(kk, (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(kv_, (B, Hkv, S, D), jnp.float32)
    native = ops.flash_attention(q, k, v, bq=64, bk=64, interpret=True)
    repeated = ops.flash_attention(q, jnp.repeat(k, H // Hkv, axis=1),
                                   jnp.repeat(v, H // Hkv, axis=1),
                                   bq=64, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(native), np.asarray(repeated),
                               rtol=1e-5, atol=1e-5)
