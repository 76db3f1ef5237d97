"""The four-chip cell's readers (``ep.*``) on a traced batch of four
chips built by hand: each step counts once, not once a chip, every share
stays at or under 100, and collectives are found by name or opcode inside
the decode steps alone."""
import json
import pathlib

import pytest

from chipbench import counts_mla
from chipbench.bench import reader
from chipbench.trace import Trace
from chipbench.weights_mla import dims

SPEC = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                   / "deepseek_v2_lite_16b.json").read_text())
M = dims(SPEC)
TR = {"batch": 16, "prompt_len": 1024, "max_new": 256}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LO, HI = 10.0, 20.0
PREFILL = [1.0, 1.1, 1.2, 1.3]          # s, on chips 0..3
DECODE = [0.020, 0.022, 0.024, 0.026]   # s a step, on chips 0..3
STEPS = 3


def chip(c):
    """Chip c: the previous batch's decode step, a prefill, STEPS decode
    steps; inside each step an all-reduce pair, a sync all-reduce and a
    fusion, and an all-gather inside the prefill."""
    mods = [("jit_decode_step(9)", 5.0, 0.02),
            ("jit_prefill_step(1)", 10.0, PREFILL[c])]
    ops = [("%all-gather.1 = bf16[4,16]{1,0} all-gather(%p)", 10.1, 0.5)]
    t = 12.0
    for _ in range(STEPS):
        d = DECODE[c]
        mods.append(("jit_decode_step(9)", t, d))
        ops += [("%all-reduce-start.3 = (bf16[16,2048]) "
                 "all-reduce-start(%fusion.2)", t, 0.001 * (c + 1)),
                ("%all-reduce-done.3 = bf16[16,2048] "
                 "all-reduce-done(%all-reduce-start.3)", t + 0.001, 0.001),
                ("%psum.11 = bf16[16,2048]{1,0} all-reduce(%fusion.4)",
                 t + 0.003, 0.002),
                ("%fusion.5 = bf16[16,2048]{1,0} fusion(%psum.11)",
                 t + 0.006, 0.010)]
        t += 0.5
    return ops, mods


def ctx(chips=4):
    ops, mods = zip(*[chip(c) for c in range(chips)])
    t = Trace(ops=list(ops), modules=list(mods),
              spans=[("chipbench/batch", LO, HI - LO)], host=[])
    return {"kind": "serve", "mla": True, "m": M, "traffic": TR,
            "peak": PEAK, "chips": chips, "trace": t, "span": (LO, HI)}


def test_decode_roofline_counts_each_step_once_over_the_chips_mean():
    least = sum(counts_mla.roofline_s(*counts_mla.decode(M, 16, 1024 + j),
                                      PEAK, 4) for j in range(STEPS))
    mean_step = sum(DECODE) / 4
    got = reader("ep.decode_roofline")(ctx())
    assert got == pytest.approx(100 * least / (STEPS * mean_step))
    assert 0 < got <= 100


def test_prefill_roofline_counts_the_one_prefill_once():
    least = counts_mla.roofline_s(*counts_mla.prefill(M, 16, 1024), PEAK, 4)
    got = reader("ep.prefill_roofline")(ctx())
    assert got == pytest.approx(100 * least / (sum(PREFILL) / 4))
    assert 0 < got <= 100


def test_mfu_counts_each_step_once_over_four_chips_peaks():
    flops = counts_mla.prefill(M, 16, 1024)[0] + sum(
        counts_mla.decode(M, 16, 1024 + j)[0] for j in range(STEPS))
    got = reader("ep.serve.mfu")(ctx())
    assert got == pytest.approx(100 * flops / ((HI - LO) * 4 * 197e12))
    assert 0 < got <= 100


def test_collective_share_is_inside_decode_steps_averaged_over_chips():
    # chip c: (0.001 (c + 1) + 0.001 + 0.002) / DECODE[c] a step; the
    # prefill's all-gather and the fusion do not count
    want = sum((0.001 * (c + 1) + 0.003) / DECODE[c] for c in range(4)) / 4
    got = reader("ep.collective_share")(ctx())
    assert got == pytest.approx(100 * want)
    assert 0 < got <= 100


@pytest.mark.parametrize("name", ["ep.decode_roofline", "ep.prefill_roofline",
                                  "ep.serve.mfu", "ep.collective_share"])
def test_other_cells_and_untraced_runs_read_nothing(name):
    one_chip = dict(ctx(), mla=False)
    assert reader(name)(one_chip) is None
    assert reader(name)({"kind": "serve", "compile_s": 1.0}) is None
