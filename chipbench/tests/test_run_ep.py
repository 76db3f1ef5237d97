"""The four-chip serving driver's path on four virtual CPU devices at a
small size (a subprocess, so the device count never leaks into this
process): a sound run is ``correct``; one chip's share of the routed
experts left out of the program, or the float8 reference in its place,
is not."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from chipbench.bench import ROOT

SMALL = {"num_hidden_layers": 3, "hidden_size": 64, "intermediate_size": 96,
         "moe_intermediate_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "n_routed_experts": 8, "num_experts_per_tok": 2,
         "n_shared_experts": 2, "vocab_size": 256}
TRAFFIC = {"kind": "serve_ep", "batch": 4, "prompt_len": 32, "max_new": 8,
           "check_requests": 4}
# above what sound runs read on the CPU (mean_gap under 1e-4) and below
# the fault (0.0076 in the reference, more in the program) and the control
# (0.084); routed experts weigh little beside the shared ones here, since
# the top-2 of 8 probabilities are used unnormalised
LIMIT = 0.004


def _run(body: str) -> dict:
    code = textwrap.dedent(f"""
        import json, time
        from chipbench.bench import Cell
        from chipbench.drivers import serve_ep
        spec = json.load(open("chipbench/configs/deepseek_v2_lite_16b.json"))
        spec.update({SMALL!r})
        cell = Cell("test", spec, {TRAFFIC!r}, 4_000_000_007, 1e-3, False,
                    {{"mean_gap": {LIMIT}}},
                    {{"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}},
                    time.perf_counter())
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=f"{ROOT}:{ROOT / 'src'}",
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    res = _run("""
        res = serve_ep.run(cell)
        print(json.dumps({k: res[k] for k in
                          ("correct", "checks", "attempted", "failed")}))
    """)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 8 and res["failed"] == 0


def test_one_chips_experts_left_out_is_not_correct():
    res = _run("""
        from repro.models import moe
        shard = moe._moe_local_shard

        def broken(p, x, cfg, rank, size, axis):
            out, aux = shard(p, x, cfg, rank, size, axis)
            return out * (rank != size - 1).astype(out.dtype), aux
        moe._moe_local_shard = broken
        res = serve_ep.run(cell)
        print(json.dumps({k: res[k] for k in ("correct", "checks")}))
    """)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("kind", ["control_fp8", "fault_one_chip_experts"])
def test_calibration_controls_fail_the_limit(kind):
    res = _run(f"""
        import jax
        from chipbench import calibrate_ep
        rows = calibrate_ep.readings(cell.spec, cell.traffic, cell.seed, True)
        print(json.dumps({{r["who"]: r["mean_gap"] for r in rows}}))
    """)
    assert res["program"] <= LIMIT < res[kind], res
