"""Every operation of the jitted prefill, decode and train steps sits under
a documented layer scope (``repro.models.SCOPES``).

A scope reaches each HLO instruction's ``op_name``, which the profiler
reports as the device operation's ``tf_op``: it is how a chip trace's time
is put on attention, the cache, the experts or the optimizer.  The steps
are compiled on the CPU at smoke size and their optimized HLO, fused
computations included, is read instruction by instruction.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import SCOPES
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.serve import make_decode_step, make_prefill_step
from repro.train import TrainConfig, make_train_step

B, S, NEW = 2, 8, 4
# where the work that streams weights or the cache has to land
MATMUL_SCOPES = {"attention", "kv_cache", "moe", "mlp", "logits"}
# op_names that XLA, not the program, gives an instruction of its own
# making, and why no scope reaches them
XLA_NAMES = {
    "reduce_window_sum": "XLA expands a cumulative sum's reduce-window into "
                         "adds that carry the reducer's name",
}

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(\S+) = (.+?) ([a-z][\w\-]*)\((.*)$")


def _compile(arch, step):
    cfg = get_smoke_config(arch)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((B, S), jnp.int32)
    prefill = make_prefill_step(cfg, max_seq=S + NEW)
    if step == "prefill":
        lowered = jax.jit(prefill).lower(params, {"tokens": tokens})
    elif step == "decode":
        _, cache = jax.eval_shape(prefill, params, {"tokens": tokens})
        lowered = jax.jit(make_decode_step(cfg), donate_argnums=(1,)).lower(
            params, cache, tokens[:, :1], jnp.int32(S))
    else:
        lowered = jax.jit(make_train_step(cfg, TrainConfig())).lower(
            params, adamw_init(params),
            {"tokens": tokens, "labels": tokens}, jnp.int32(0))
    return cfg, lowered.compile().as_text()


def _instructions(text):
    """[(computation, opcode, result dims, op_name)] of every instruction
    with an ``op_name``, and the computations that reduce, scatter, sort
    and all-reduce instructions apply (``to_apply=``)."""
    out, applied, comp = [], set(), None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        ins = _INSTRUCTION.match(line)
        if not ins:
            continue
        _, shape, opcode, rest = ins.groups()
        applied.update(re.findall(r"to_apply=%(\S+?)[,\s)]", rest + " "))
        name = re.search(r'op_name="([^"]*)"', rest)
        if name:
            dims = re.match(r"\w+\[([\d,]*)\]", shape)
            dims = (tuple(int(d) for d in dims.group(1).split(",") if d)
                    if dims else None)
            out.append((comp, opcode, dims, name.group(1)))
    return out, applied


def scopes_of(op_name):
    """The documented scopes in an ``op_name`` path, outermost first, with
    ``jvp(...)`` and ``transpose(...)`` stripped so that a layer's backward
    counts as that layer."""
    path = re.sub(r"(?:jvp|transpose)\(", "", op_name).replace(")", "")
    return [p for p in path.split("/") if p in SCOPES]


@pytest.mark.parametrize("step", ["prefill", "decode", "train"])
@pytest.mark.parametrize("arch", ["granite_moe_1b_a400m", "phi3_mini_3_8b"])
def test_every_operation_sits_under_a_documented_scope(arch, step):
    cfg, text = _compile(arch, step)
    instructions, applied = _instructions(text)
    assert instructions
    unscoped, dots, cache_writes, seen = [], 0, 0, set()
    one_layer_cache = (B, cfg.n_kv_heads, S + NEW, cfg.resolved_head_dim)
    whole_cache = (cfg.n_layers,) + one_layer_cache
    whole_cache_writes = 0
    # decode's scores of the cache's positions joined with the new key's,
    # which takes the place of the cache's row at pos
    merged_scores = (B, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
                     S + NEW + 1)
    merges = 0
    arguments = {name for _, opcode, _, name in instructions
                 if opcode == "parameter"}
    for comp, opcode, dims, op_name in instructions:
        # Not work of their own, so no scope is asked of them: an entry
        # parameter's op_name is the argument's name, as is a bitcast's of
        # it (a bitcast moves no data), and the scalar body of a reduce,
        # scatter or sort runs as part of the instruction that applies it.
        if (opcode == "parameter" or comp in applied
                or (opcode == "bitcast" and op_name in arguments)
                or op_name in XLA_NAMES):
            continue
        found = scopes_of(op_name)
        seen.update(found)
        if not found:
            unscoped.append(f"{opcode} {op_name}")
        if opcode == "dot":
            dots += 1
            assert MATMUL_SCOPES & set(found), (opcode, op_name)
        if opcode == "dynamic-update-slice" and dims == one_layer_cache:
            cache_writes += 1
            assert "kv_cache" in found, op_name
        if opcode == "dynamic-update-slice" and dims == whole_cache:
            whole_cache_writes += 1
            assert "kv_cache" in found, op_name
        if opcode == "concatenate" and dims == merged_scores:
            merges += 1
            assert "attention" in found, op_name
    assert not unscoped, unscoped
    assert dots > 0
    if step == "decode":
        # the new token's K and V are merged into the attention, and all
        # layers' go into the cache once, after the scan: no layer writes
        # a slice of its own
        assert cache_writes == 0
        assert merges > 0
        assert whole_cache_writes > 0
        assert {"kv_cache", "sample"} <= seen
    if step == "train":
        assert {"loss", "optimizer"} <= seen
    assert {"embed", "layers", "attention", "logits"} <= seen
    moe = {"moe", "route", "dispatch", "experts", "combine"}
    assert (moe <= seen) == (cfg.moe is not None)
    assert ("mlp" in seen) == (cfg.moe is None)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_every_deepseek_operation_sits_under_a_documented_scope(step):
    """DeepSeek-V2's latent attention, its dense layer 0 and its MoE with
    a shared expert: every instruction under a documented scope, and in
    decode each layer's latent-cache write and the write of all layers'
    after the scan under ``kv_cache``."""
    cfg, text = _compile("deepseek_v2_lite_16b", step)
    instructions, applied = _instructions(text)
    arguments = {name for _, opcode, _, name in instructions
                 if opcode == "parameter"}
    m = cfg.mla
    one_layer = {(B, S + NEW, m.kv_lora_rank), (B, S + NEW, m.qk_rope_dim)}
    whole = {(cfg.n_layers,) + d for d in one_layer}
    unscoped, seen, writes = [], set(), {"layer": 0, "whole": 0}
    for comp, opcode, dims, op_name in instructions:
        if (opcode == "parameter" or comp in applied
                or (opcode == "bitcast" and op_name in arguments)
                or op_name in XLA_NAMES):
            continue
        found = scopes_of(op_name)
        seen.update(found)
        if not found:
            unscoped.append(f"{opcode} {op_name}")
        if opcode == "dot":
            assert MATMUL_SCOPES & set(found), (opcode, op_name)
        if opcode == "dynamic-update-slice" and dims in one_layer | whole:
            writes["layer" if dims in one_layer else "whole"] += 1
            assert "kv_cache" in found, op_name
    assert not unscoped, unscoped
    assert {"embed", "layers", "attention", "logits", "moe", "route",
            "dispatch", "experts", "combine", "mlp"} <= seen
    if step == "decode":
        assert writes["layer"] > 0 and writes["whole"] > 0, writes
        assert {"kv_cache", "sample"} <= seen
