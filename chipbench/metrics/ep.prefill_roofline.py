"""Share of its roofline that ``jit_prefill_step`` reaches on the
four-chip cell's chips in the traced batch: the least time its work needs
(``counts_mla.prefill``, the routed top-k and never the capacity slots)
over all the chips' summed peaks, over its device time averaged over the
chips.  Each call counts once, not once a chip."""
from chipbench import counts_mla, trace


def read(ctx):
    if not ctx.get("mla") or "trace" not in ctx:
        return None
    lo, hi = ctx["span"]
    tr = ctx["traffic"]
    per_chip = [trace.calls(mods, "jit_prefill_step", lo, hi)
                for mods in ctx["trace"].modules]
    n = min((len(runs) for runs in per_chip), default=0)
    if not n:
        return None
    least = counts_mla.roofline_s(*counts_mla.prefill(
        ctx["m"], tr["batch"], tr["prompt_len"]), ctx["peak"], ctx["chips"])
    device_s = sum(sum(d for _, d in sorted(runs)[:n])
                   for runs in per_chip) / len(per_chip)
    return 100.0 * least * n / device_s
