"""A run's path on the CPU at a small size, without the harness's look for
a chip: the drivers serve and train through the program, compare with the
reference, and must say ``correct`` false when the timed path is broken
underneath.  Also the refusal without a chip, and ``BENCHMARK.json``
against the files it names."""
import json
import re
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from chipbench.bench import ROOT, Cell
from chipbench.drivers import serve as serve_driver
from chipbench.drivers import train as train_driver

MOE = {"registry": "granite_moe_1b_a400m", "num_hidden_layers": 2,
       "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 32, "vocab_size": 256, "num_local_experts": 4,
       "num_experts_per_tok": 2, "tie_word_embeddings": True,
       "rope_theta": 10000.0, "rms_norm_eps": 1e-6}
SERVE = {"kind": "serve", "batch": 4, "prompt_len": 32, "max_new": 8,
         "check_requests": 8}
TRAIN = {"kind": "train", "batch": 2, "seq_len": 16, "trace_steps": 2,
         "optimizer": {"lr": 3e-4, "warmup": 0, "total_steps": 10000,
                       "grad_clip": 1.0, "weight_decay": 0.1, "b1": 0.9,
                       "b2": 0.95, "eps": 1e-8},
         "loss": {"z_loss": 1e-4, "moe_aux": 0.01, "moe_router_z": 0.001}}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# Limits at this small size, above what sound runs read on the CPU
# (mean_gap 0.0015-0.0022; loss 3e-4, grad_norm 0.01, grad1_leaf 0.05,
# change_leaf 0.16) and below what the faults and the control read.
SERVE_LIMITS = {"mean_gap": 0.02}
TRAIN_LIMITS = {"loss": 0.003, "grad_norm": 0.1, "grad1_leaf": 0.3,
                "change_leaf": 0.5}


def cell(tr, limits, seed=4_000_000_007):
    # seconds this short give the fewest batches or steps a run makes
    return Cell("test", MOE, tr, seed, 1e-3, False, limits, PEAK,
                time.perf_counter())


def test_sound_serve_is_correct():
    res = serve_driver.run(cell(SERVE, SERVE_LIMITS))
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["e2e"]["serve_tok_s"] > 0 and res["e2e"]["req_p95_s"] > 0


def _decode_fault(kind):
    from repro.serve import step as program_step
    make = program_step.make_decode_step

    def broken(cfg, greedy=True):
        inner = make(cfg, greedy=greedy)

        def decode_step(params, cache, tokens, pos):
            tok, new_cache = inner(params, cache, tokens, pos)
            if kind == "state_unchanged":
                return tok, cache
            # a token altered where it is produced, at one position
            bumped = (tok + 1) % cfg.vocab
            return jnp.where(pos == 35, bumped, tok), new_cache
        return decode_step
    return broken


@pytest.mark.parametrize("kind", ["state_unchanged", "token_altered"])
def test_broken_decode_is_not_correct(monkeypatch, kind):
    import repro.launch.serve as launch_serve
    monkeypatch.setattr(launch_serve, "make_decode_step", _decode_fault(kind))
    res = serve_driver.run(cell(SERVE, SERVE_LIMITS))
    assert not res["correct"], res["checks"]


def test_sound_train_is_correct():
    res = train_driver.run(cell(TRAIN, TRAIN_LIMITS))
    assert res["correct"], res["checks"]
    assert res["e2e"]["train_tok_s"] > 0


def _train_fault(kind):
    import repro.train as program_train
    make = program_train.make_train_step

    def broken(cfg, tc):
        inner = make(cfg, tc)

        def train_step(params, opt, batch, step):
            if kind == "half_batch":
                half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
                return inner(params, opt, half, step)
            _, _, metrics = inner(params, opt, batch, step)
            return params, opt, metrics     # the state returned unchanged
        return train_step
    return broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(monkeypatch, kind):
    import repro.train as program_train
    monkeypatch.setattr(program_train, "make_train_step", _train_fault(kind))
    res = train_driver.run(cell(TRAIN, TRAIN_LIMITS))
    assert not res["correct"], res["checks"]


def test_control_fails_the_training_limits():
    """The reference computed in float8 in the program's place."""
    from chipbench.drivers.train import CHECKED_STEPS, compare
    from chipbench.reference import train as ref_train
    c = cell(TRAIN, TRAIN_LIMITS)
    ref = ref_train.first_steps(MOE, c.seed, TRAIN, CHECKED_STEPS)
    ctl = ref_train.first_steps(MOE, c.seed, TRAIN, CHECKED_STEPS, fp8=True)
    numbers = compare(ctl, ref)
    assert any(numbers[k] > TRAIN_LIMITS[k] for k in numbers), numbers


def test_control_fails_the_serving_limit():
    """At each position of served requests, the token that the float8
    reference puts first lies below the float32 reference's best by more
    than the limit somewhere."""
    import numpy as np
    from chipbench import traffic
    from chipbench.reference.serve import gaps, served_logits
    dense = dict(MOE, registry="phi3_mini_3_8b", num_local_experts=0,
                 num_key_value_heads=4, intermediate_size=96,
                 tie_word_embeddings=False)
    seed = 11
    prompts = np.stack(traffic.prompts(SERVE, 256, seed, traffic.WINDOW, 1))
    served = traffic.tokens(seed, 9, 0, (4, 8), 256)
    ref = served_logits(dense, seed, prompts, served)
    ctl = served_logits(dense, seed, prompts, served, fp8=True)
    assert gaps(ref, np.asarray(jnp.argmax(ctl, -1))).mean() > \
        SERVE_LIMITS["mean_gap"]


def test_refuses_without_a_chip():
    r = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "granite_moe.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0 and r.stdout == ""
    assert "no chip" in r.stderr


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_names_only_what_exists():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        spec = json.loads((ROOT / c["file"]).read_text())
        assert spec["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        tr = json.loads((ROOT / "chipbench/traffic" / f"{w['traffic']}.json")
                        .read_text())
        assert (ROOT / "chipbench/drivers" / f"{tr['kind']}.py").exists()
        limits = json.loads((ROOT / "chipbench/limits" / f"{w['name']}.json")
                            .read_text())["limits"]
        assert limits and all(v > 0 for v in limits.values())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").exists()
    # a full check of 24 cells fits its 43200 seconds
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
