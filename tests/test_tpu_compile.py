"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at the widths ``chip_smoke.py`` runs, the full-width decode
steps of granite and Phi-3, and DeepSeek-V2-Lite's steps on four chips.  The TPU compiler refuses here what interpret
mode accepts (a slice the tiling cannot prove aligned, more VMEM than a
kernel may use) and a program that does not fit the chip's 16 GB of HBM.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU library, so only the
worker that runs this file does.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.launch.chip import program_bytes
from repro.models import transformer as T
from repro.serve import make_decode_step

GRANITE = get_config("granite_moe_1b_a400m")
BATCH, PROMPT_LEN, MAX_NEW = 8, 2048, 64      # chip_smoke.py's serve phase
HBM_BYTES = 16 * 10**9                        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure: no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name):
    """(kernel, argument shapes) at chip_smoke.py's widths."""
    g, bf, f32 = GRANITE, jnp.bfloat16, jnp.float32
    if name == "matmul":
        M, K, N = BATCH * PROMPT_LEN, g.d_model, g.moe.d_ff_expert
        return ops.matmul, [((M, K), bf), ((K, N), bf)]
    if name == "matmul_f32_4096":     # default tiling at the VMEM limit
        return ops.matmul, [((4096, 4096), f32), ((4096, 4096), f32)]
    if name == "flash_attention":
        H, Hkv, D = g.n_heads, g.n_kv_heads, g.resolved_head_dim
        return ops.flash_attention, [((2, H, PROMPT_LEN, D), bf),
                                     ((2, Hkv, PROMPT_LEN, D), bf),
                                     ((2, Hkv, PROMPT_LEN, D), bf)]
    d, n = 8192, 16                   # mamba_scan at d_inner 8192
    return ops.mamba_scan, [((1, PROMPT_LEN, d), bf), ((1, PROMPT_LEN, d), bf),
                            ((1, PROMPT_LEN, n), bf), ((1, PROMPT_LEN, n), bf),
                            ((d, n), f32), ((d,), f32)]


@pytest.mark.parametrize("name", ["matmul", "matmul_f32_4096",
                                  "flash_attention", "mamba_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_decode(one_chip, cfg, batch, max_seq):
    """The donated decode step at full width, compiled for one v5e."""
    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda k: T.init_params(cfg, k)[0], jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: T.init_cache(cfg, batch, max_seq)[0]))
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return jax.jit(make_decode_step(cfg), donate_argnums=(1,)).lower(
        params, cache, tokens, pos).compile()


def test_granite_decode_compiles_for_v5e(one_chip):
    compiled = _compile_decode(one_chip, GRANITE, BATCH,
                               PROMPT_LEN + MAX_NEW)
    total = program_bytes(compiled.memory_analysis())
    assert total < HBM_BYTES, total


@pytest.mark.parametrize("arch,batch,max_seq", [
    pytest.param("granite_moe_1b_a400m", 32, 1280,
                 id="granite_moe_1b_a400m-32"),
    pytest.param("phi3_mini_3_8b", 4, 1280, id="phi3_mini_3_8b-4"),
    pytest.param("granite_moe_1b_a400m", 8, 3872,
                 id="granite_moe_1b_a400m-8-longdoc")])
def test_decode_writes_the_cache_in_place_on_v5e(one_chip, arch, batch,
                                                 max_seq):
    """At the chat and longdoc cells' shapes the layer scan neither stacks
    the cache as its output nor copies it back: the step's temporaries
    stay under one K cache, and no copy has the whole cache's shape.  The
    attention reads each layer of the cache where it lies: no array of one
    layer's shape (the cache's order or the position-minor one, any dtype,
    a float32 upcast included) or of its broadcast to every query head of
    a group is written to memory; such shapes live inside fusions only."""
    cfg = get_config(arch)
    compiled = _compile_decode(one_chip, cfg, batch, max_seq)
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // Hkv
    shape = (cfg.n_layers, batch, Hkv, max_seq, hd)
    one_cache = 2 * int(np.prod(shape))                   # bf16
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < one_cache, (temp, one_cache)
    text = compiled.as_text()

    def dims(*d):
        return ",".join(map(str, d))
    whole = re.compile(r"= bf16\[%s\]\S* copy\(" % dims(*shape))
    copies = [line.strip() for line in text.splitlines()
              if whole.search(line)]
    assert not copies, copies
    one_layer = [dims(batch, Hkv, max_seq, hd), dims(batch, Hkv, hd, max_seq),
                 dims(batch, Hkv, G, max_seq, hd), dims(batch, Hkv * G,
                                                         max_seq, hd)]
    layer = re.compile(r"= \w+\[(?:1,)*(?:%s)\]" % "|".join(one_layer))
    found = [line[:200] for _, line in _materialized(text)
             if layer.search(line)]
    assert not found, found


def _materialized(text):
    """(computation, instruction) of every instruction outside the fused
    computations of an optimized HLO module's text: the arrays that are
    written to memory."""
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head:
            comp = head.group(1)
        elif comp and not comp.startswith("fused") and re.match(
                r"^\s+(?:ROOT )?%\S+ = ", line):
            yield comp, line.strip()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_deepseek_steps_fit_four_v5e_chips(topo, step):
    """DeepSeek-V2-Lite whole, at the four-chip benchmark cell's 16 slots
    of 1024 + 256 positions, jitted as ``serve(mesh=...)`` jits it on a
    data=1 x model=4 mesh: each chip's bytes fit its HBM, and the
    expert-parallel combine and tensor-parallel all-reduces are in."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.launch import serve as SV
    from repro.serve import make_prefill_step
    cfg, B, P, N = get_config("deepseek_v2_lite_16b"), 16, 1024, 256
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("data", "model"))
    rules, params_sh, cache_sh, rows = SV._on_mesh(cfg, mesh, B, P + N)
    one = NamedSharding(mesh, PartitionSpec())

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings)
    params = placed(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.key(0))[0]), params_sh)
    with SV._installed(mesh, rules):
        if step == "prefill":
            f = jax.jit(make_prefill_step(cfg, max_seq=P + N),
                        in_shardings=(params_sh, rows),
                        out_shardings=(one, cache_sh))
            args = (params, {"tokens": jax.ShapeDtypeStruct(
                (B, P), jnp.int32, sharding=rows)})
        else:
            cache = placed(jax.eval_shape(
                lambda: T.init_cache(cfg, B, P + N)[0]), cache_sh)
            f = jax.jit(make_decode_step(cfg), donate_argnums=(1,),
                        in_shardings=(params_sh, cache_sh, rows, one),
                        out_shardings=(rows, cache_sh))
            args = (params, cache,
                    jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=rows),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=one))
        compiled = f.lower(*args).compile()
    total = program_bytes(compiled.memory_analysis())
    assert total < HBM_BYTES, total
    assert re.search(r" all-reduce(?:-start)?\(", compiled.as_text())
