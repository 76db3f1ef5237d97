"""Model FLOPs of the prefill and decode steps completed in the four-chip
cell's traced batch (``counts_mla``), each step counted once and not once
a chip, over the traced window times the chips' summed peak."""
from chipbench import counts_mla, trace


def read(ctx):
    if not ctx.get("mla") or "trace" not in ctx:
        return None
    lo, hi = ctx["span"]
    tr, m = ctx["traffic"], ctx["m"]
    mods = ctx["trace"].modules
    n_pre = min((len(trace.calls(x, "jit_prefill_step", lo, hi))
                 for x in mods), default=0)
    n_dec = min((len(trace.calls(x, "jit_decode_step", lo, hi))
                 for x in mods), default=0)
    if not n_pre and not n_dec:
        return None
    flops = n_pre * counts_mla.prefill(m, tr["batch"], tr["prompt_len"])[0]
    flops += sum(counts_mla.decode(m, tr["batch"], tr["prompt_len"] + j)[0]
                 for j in range(n_dec))
    return 100.0 * flops / ((hi - lo) * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])
