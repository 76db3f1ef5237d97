"""Model FLOPs of the prefill and decode steps completed in the traced
batch (``counts``), over the traced window times the chip's peak."""
from chipbench import counts, trace


def read(ctx):
    if ctx["kind"] != "serve" or "trace" not in ctx:
        return None
    lo, hi = ctx["span"]
    tr, m = ctx["traffic"], ctx["m"]
    n_pre = sum(len(trace.calls(mods, "jit_prefill_step", lo, hi))
                for mods in ctx["trace"].modules)
    n_dec = sum(len(trace.calls(mods, "jit_decode_step", lo, hi))
                for mods in ctx["trace"].modules)
    if not n_pre and not n_dec:
        return None
    flops = n_pre * counts.prefill(m, tr["batch"], tr["prompt_len"])[0]
    flops += sum(counts.decode(m, tr["batch"], tr["prompt_len"] + j)[0]
                 for j in range(n_dec))
    return 100.0 * flops / ((hi - lo) * ctx["peak"]["bf16_flops_per_s"])
