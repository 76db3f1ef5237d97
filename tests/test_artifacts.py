"""Guards over the committed experiment artifacts: the dry-run table is
complete, and the BENCH_*.json benchmark grids at the repo root keep their
golden schema — required keys, finite positive timings, and the derived
claims (speedups >= 1, continuous >= static at saturation, 1F1B-vs-GPipe
and bubble-vs-bound relations) — so a benchmark refactor cannot silently
ship a malformed artifact."""
import json
import math
from pathlib import Path

import pytest

DRYRUN = Path("experiments/dryrun/results.json")
ROOFLINE = Path("experiments/roofline_single_pod.json")
BENCH_ENGINE = Path("BENCH_engine.json")
BENCH_SERVING = Path("BENCH_serving.json")
BENCH_SOC = Path("BENCH_soc.json")
BENCH_TRAINING = Path("BENCH_training.json")
BENCH_DSE = Path("BENCH_dse.json")
BENCH_FLEET = Path("BENCH_fleet.json")
BENCH_CLUSTER = Path("BENCH_cluster.json")
BENCH_CALIBRATION = Path("BENCH_calibration.json")


def _finite_pos(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x) and x > 0.0


@pytest.mark.skipif(not DRYRUN.exists(), reason="sweep not present")
def test_dryrun_sweep_complete():
    res = json.loads(DRYRUN.read_text())
    ok = [r for r in res.values() if r["status"] == "ok"]
    skip = [r for r in res.values() if r["status"] == "skip"]
    err = [r for r in res.values() if r["status"] == "error"]
    assert len(err) == 0, err
    assert len(ok) == 66   # 33 runnable cells x 2 meshes
    assert len(skip) == 14  # 7 full-attention long_500k x 2 meshes
    # every ok cell has the full record
    for r in ok:
        assert r["memory"]["temp_bytes"] >= 0
        assert r["hlo"]["flops"] > 0
        assert r["hlo"]["bytes"] > 0


@pytest.mark.skipif(not ROOFLINE.exists(), reason="table not present")
def test_roofline_table_covers_40_cells():
    table = json.loads(ROOFLINE.read_text())
    assert len(table) == 40  # 33 ok + 7 documented skips
    ok = [r for r in table.values() if r["status"] == "ok"]
    assert len(ok) == 33
    for r in ok:
        assert r["bound"] in ("compute", "memory", "collective")
        assert r["compute_s"] > 0 and r["memory_s"] > 0


# ---------------------------------------------------------------------------
# golden schemas of the BENCH_*.json grids (repo root)


@pytest.mark.skipif(not BENCH_ENGINE.exists(), reason="bench not present")
def test_bench_engine_schema():
    b = json.loads(BENCH_ENGINE.read_text())
    assert set(b) >= {"cases", "budget_s", "sweep_8cfg_decode_5k",
                      "recorded", "note"}
    assert b["cases"], "no recorded cases"
    for name, case in b["cases"].items():
        assert case["n_ops"] > 0, name
        assert _finite_pos(case["engine_s"]), name
        assert _finite_pos(case["reference_s"]), name
        # the engine must never have regressed below the frozen PR base
        assert case["speedup"] >= 1.0, name
        # the recorded column is derived from the (rounded) timings
        assert case["speedup"] == pytest.approx(
            case["reference_s"] / case["engine_s"], rel=0.05), name
    assert all(_finite_pos(v) for v in b["budget_s"].values())
    # the sweep case scales at least as well as serial execution
    sw = b["sweep_8cfg_decode_5k"]
    assert _finite_pos(sw["sweep_s"]) and sw["speedup"] >= 1.0


@pytest.mark.skipif(not BENCH_SERVING.exists(), reason="bench not present")
def test_bench_serving_schema():
    b = json.loads(BENCH_SERVING.read_text())
    assert set(b) >= {"model", "n_requests", "config", "grid", "recorded"}
    grid = b["grid"]
    assert grid, "empty serving grid"
    required = {"policy", "rate_rps", "makespan_s", "busy_s",
                "engine_makespan_s", "throughput_tok_s", "occupancy",
                "ttft_p50", "ttft_p99", "tpot_p50", "latency_p99",
                "total_j"}
    by_cell = {}
    for rec in grid:
        assert required <= set(rec), rec.get("policy")
        assert _finite_pos(rec["makespan_s"])
        assert _finite_pos(rec["throughput_tok_s"])
        assert all(math.isfinite(rec[k]) and rec[k] >= 0.0
                   for k in required - {"policy"})
        # the co-simulation invariant survives serialization
        assert rec["busy_s"] == rec["engine_makespan_s"]
        assert rec["makespan_s"] >= rec["busy_s"]
        assert 0.0 <= rec["occupancy"] <= 1.0
        by_cell[(rec["policy"], rec["rate_rps"])] = rec
    # the recorded headline claim: continuous beats static at the
    # saturating (highest) arrival rate
    rates = sorted({r["rate_rps"] for r in grid})
    top = rates[-1]
    assert by_cell[("continuous", top)]["throughput_tok_s"] > \
        by_cell[("static", top)]["throughput_tok_s"]
    # the monotone speedup column: continuous batching's gain over static
    # grows with offered load (that is WHY it exists; dynamic is allowed
    # to sag at saturation — max-wait queueing is a real effect)
    gains = [by_cell[("continuous", rate)]["throughput_tok_s"]
             / by_cell[("static", rate)]["throughput_tok_s"]
             for rate in rates]
    assert gains == sorted(gains), gains


@pytest.mark.skipif(not BENCH_SOC.exists(), reason="bench not present")
def test_bench_soc_schema():
    b = json.loads(BENCH_SOC.read_text())
    assert set(b) >= {"records", "budget_s", "grid", "recorded"}
    g = b["grid"]
    want = len(g["frontends"]) * len(g["n_accels"]) * len(g["link_ports"])
    assert len(b["records"]) == want, "incomplete SoC grid"
    for rec in b["records"]:
        assert _finite_pos(rec["makespan_s"]), rec["topology"]
        assert _finite_pos(rec["total_j"]), rec["topology"]
        assert 0.0 <= rec["frontend_util"] <= 1.0
        assert 0.0 <= rec["accel_util_mean"] <= 1.0
        assert rec["bound"] in ("compute", "memory", "collective")
        assert rec["n_accels"] in g["n_accels"]
    assert all(_finite_pos(v) for v in b["budget_s"].values())


@pytest.mark.skipif(not BENCH_TRAINING.exists(), reason="bench not present")
def test_bench_training_schema():
    b = json.loads(BENCH_TRAINING.read_text())
    assert set(b) >= {"records", "budget_s", "grid", "recorded"}
    g = b["grid"]
    want = (len(g["models"]) * len(g["schedules"]) * len(g["n_stages"])
            * len(g["n_microbatches"]))
    assert len(b["records"]) == want, "incomplete training grid"
    by_cell = {}
    for rec in b["records"]:
        key = (rec["model"], rec["schedule"], rec["n_stages"],
               rec["n_microbatches"])
        by_cell[key] = rec
        assert _finite_pos(rec["step_time_s"]), key
        assert _finite_pos(rec["tokens_per_s"]), key
        assert 0.0 <= rec["bubble_fraction"] < 1.0, key
        assert rec["bubble_bound"] == pytest.approx(
            (rec["n_stages"] - 1)
            / (rec["n_microbatches"] + rec["n_stages"] - 1)), key
        assert 0.0 < rec["stage_util_mean"] <= 1.0, key
    for (model, schedule, p, m), rec in by_cell.items():
        # a single stage has no pipeline bubble, deeper pipes have more
        if p == 1:
            assert rec["bubble_fraction"] < 0.05, (model, schedule)
        # the analytic bound is monotone in m at fixed p — and the
        # recorded bound column must follow it
        if m > min(g["n_microbatches"]):
            prev = by_cell[(model, schedule, p, min(g["n_microbatches"]))]
            assert rec["bubble_bound"] <= prev["bubble_bound"]
    assert all(_finite_pos(v) for v in b["budget_s"].values())


@pytest.mark.skipif(not BENCH_DSE.exists(), reason="bench not present")
def test_bench_dse_schema():
    b = json.loads(BENCH_DSE.read_text())
    assert set(b) >= {"speedup", "dag_fidelity", "port_study", "budget_s",
                      "recorded", "note"}
    sp = b["speedup"]
    assert sp["n_configs"] >= 1024 and sp["n_ops"] >= 5000
    assert _finite_pos(sp["batched_s"]) and _finite_pos(sp["process_s"])
    # the recorded headline claim: the analytic batch beats the
    # process-pool engine sweep by the acceptance floor
    assert sp["speedup_vs_process"] >= 50.0
    assert sp["speedup_vs_process"] == pytest.approx(
        sp["process_s"] / sp["batched_s"], rel=0.05)
    # on chains the model is the engine: zero relaxation error, and the
    # analytic winner is the true winner
    assert sp["max_verified_relaxation_err"] == 0.0
    assert sp["best_matches_exact"] is True
    fid = b["dag_fidelity"]
    assert fid["bracket_holds"] is True
    assert math.isfinite(fid["lb_err_mean"]) and fid["lb_err_mean"] >= 0.0
    assert fid["lb_err_max"] < 1.0          # lower bound stays positive
    assert fid["ub_over_exact_mean"] >= 1.0
    ps = b["port_study"]
    assert len(ps["grid_exact_s"]) == len(ps["grid_ports"])
    assert all(_finite_pos(e) for e in ps["grid_exact_s"])
    assert _finite_pos(ps["opt_exact_s"]) and _finite_pos(ps["grid_best_s"])
    # optimize lands within 2% of the exact grid best (acceptance gate)
    assert abs(ps["within_frac"]) <= 0.02
    assert ps["knee_ports"] in ps["grid_ports"]
    assert all(_finite_pos(v) for v in b["budget_s"].values())


@pytest.mark.skipif(not BENCH_FLEET.exists(), reason="bench not present")
def test_bench_fleet_schema():
    b = json.loads(BENCH_FLEET.read_text())
    assert set(b) >= {"headline", "headline_quick", "speedup",
                      "bit_identity", "conservation", "fleet_grid",
                      "autoscale", "budget_s", "recorded", "note"}
    hl = b["headline"]
    assert hl["n_requests"] >= 1_000_000
    assert _finite_pos(hl["wall_s"]) and hl["wall_s"] <= 20.0
    # the recorded headline claim: >= 50k simulated requests/s
    assert hl["replay_rate_rps"] >= 50_000.0
    assert hl["replay_rate_rps"] == pytest.approx(
        hl["n_requests"] / hl["wall_s"], rel=0.05)
    assert 0.0 < hl["memo_hit_rate"] <= 1.0
    assert 0.0 <= hl["occupancy"] <= 1.0
    assert 0.0 <= hl["slo_attainment"] <= 1.0
    assert hl["n_steps"] > 0 and hl["n_replicas"] >= 1
    sp = b["speedup"]
    # memoization must actually pay, and must not change the arithmetic
    assert sp["speedup"] >= 10.0
    assert sp["speedup"] == pytest.approx(
        sp["unmemoized_s"] / sp["replay_s"], rel=0.05)
    assert sp["bit_identical"] is True
    assert b["bit_identity"]["bit_identical"] is True
    assert b["conservation"]["all_served_once"] is True
    for rec in b["fleet_grid"]:
        assert rec["router"] in ("round_robin", "least_outstanding",
                                 "session_affinity")
        assert rec["n_replicas"] >= 1
        assert 0.0 <= rec["slo_attainment"] <= 1.0
        assert _finite_pos(rec["throughput_req_s"])
        assert _finite_pos(rec["cost_per_token_j"])
    # more replicas never hurt SLO attainment on the shared trace
    by_router = {}
    for rec in b["fleet_grid"]:
        by_router.setdefault(rec["router"], []).append(
            (rec["n_replicas"], rec["slo_attainment"]))
    for router, cells in by_router.items():
        cells.sort()
        slos = [s for _, s in cells]
        assert slos == sorted(slos), (router, slos)
    asc = b["autoscale"]
    assert asc["n_scale_events"] >= 1
    assert asc["peak_replicas"] >= 2          # the burst forced a scale-up
    assert all(_finite_pos(v) for v in b["budget_s"].values())


@pytest.mark.skipif(not BENCH_CLUSTER.exists(), reason="bench not present")
def test_bench_cluster_schema():
    b = json.loads(BENCH_CLUSTER.read_text())
    assert set(b) >= {"cluster_grid", "cheapest_under_target", "bounds",
                      "hier_vs_ring", "single_tier_identity", "budget_s",
                      "recorded", "note"}
    # the recorded correctness probes must all hold
    assert b["bounds"]["exact"] is True
    assert b["bounds"]["worst_rel_err"] <= 1e-12
    assert b["hier_vs_ring"]["all_hold"] is True
    sid = b["single_tier_identity"]
    assert sid["no_dp_bit_identical"] is True
    assert sid["dp_ring_matches"] is True

    # the grid is committed columnar (compact artifact format): a column
    # list plus one row array per cell, floats rounded to 6 significant
    # digits — decode it back to records before the content checks
    g = b["cluster_grid"]
    assert set(g) == {"columns", "rows"}
    assert g["rows"] and all(len(r) == len(g["columns"])
                             for r in g["rows"])
    grid = [dict(zip(g["columns"], r)) for r in g["rows"]]
    need = {"model", "n_accel", "dp_degree", "pp_degree", "tp_degree",
            "collective_algo", "step_time_s", "cluster_tokens_per_s",
            "cluster_j", "tco_usd_per_step", "tco_usd_per_mtok",
            "speedup", "collective_s", "fabric"}
    assert grid
    for rec in grid:
        assert need <= set(rec), rec.get("program")
        assert _finite_pos(rec["step_time_s"])
        assert _finite_pos(rec["cluster_tokens_per_s"])
        assert _finite_pos(rec["tco_usd_per_step"])
        assert _finite_pos(rec["speedup"])
        assert rec["collective_algo"] in ("ring", "tree", "hierarchical")
        assert (rec["dp_degree"] * rec["pp_degree"] * rec["tp_degree"]
                == rec["n_accel"])
    # the acceptance sweep: >= 512 accelerators with all three degrees on
    assert any(rec["n_accel"] >= 512 and rec["dp_degree"] > 1
               and rec["pp_degree"] > 1 and rec["tp_degree"] > 1
               for rec in grid)
    # hierarchical <= ring cell-by-cell on node/inter-spanning dp groups
    cells = {}
    for rec in grid:
        key = (rec["model"], rec["n_accel"], rec["dp_degree"],
               rec["pp_degree"], rec["tp_degree"])
        cells.setdefault(key, {})[rec["collective_algo"]] = \
            rec["step_time_s"]
    assert cells
    for key, cell in cells.items():
        if "ring" in cell and "hierarchical" in cell:
            # 2e-6 headroom: the committed grid rounds to 6 significant
            # digits, so equal-to-rounding cells may differ by ~5e-7 rel
            assert cell["hierarchical"] <= cell["ring"] * (1 + 2e-6), key
    # the headline question has an answer for both models
    tgt = b["cheapest_under_target"]
    assert _finite_pos(tgt["target_step_s"])
    for model, best in tgt.items():
        if model == "target_step_s" or best is None:
            continue
        assert best["step_time_s"] <= tgt["target_step_s"]
        assert _finite_pos(best["tco_usd_per_step"])
    assert all(_finite_pos(v) for v in b["budget_s"].values())


@pytest.mark.skipif(not BENCH_CALIBRATION.exists(),
                    reason="bench not present")
def test_bench_calibration_schema():
    b = json.loads(BENCH_CALIBRATION.read_text())
    assert set(b) >= {"backend", "interpret", "samples", "kernels",
                      "improved", "n_improved", "budget_s", "recorded",
                      "note"}
    assert set(b["kernels"]) == {"matmul", "attention", "mamba"}
    for name, k in b["kernels"].items():
        assert k["n_samples"] >= 2, name
        assert _finite_pos(k["roofline_mape"]), name
        assert _finite_pos(k["fitted_mape"]), name
        # the measured table reproduces its own samples bit-exactly
        assert k["table_max_rel_err"] == 0.0, name
        for key, v in k["fitted"].items():
            assert v is None or (_finite_pos(v) or v == 0.0), (name, key)
    for s in b["samples"]:
        assert {"kernel", "kind", "shape", "flops", "bytes",
                "measured_s"} <= set(s)
        assert _finite_pos(s["flops"]) and _finite_pos(s["measured_s"])
        assert s["kernel"] in b["kernels"]
    # the acceptance claim: fitted error beats the uncalibrated roofline
    # on >= 2 of the 3 kernels (recorded, and re-gated by
    # benchmarks/bench_calibration.py --quick in CI)
    assert b["n_improved"] >= 2
    assert set(b["improved"]) == {
        name for name, k in b["kernels"].items()
        if k["fitted_mape"] < k["roofline_mape"]}
    assert all(_finite_pos(v) for v in b["budget_s"].values())
