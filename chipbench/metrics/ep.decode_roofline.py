"""Share of its roofline that ``jit_decode_step`` reaches on the four-chip
cell's chips in the traced batch: for each step, the least time its work
needs at its own cache position (``counts_mla.decode``) over all the
chips' summed peaks, summed over the steps, over the steps' device time
averaged over the chips.  Each step counts once, not once a chip."""
from chipbench import counts_mla, trace


def read(ctx):
    if not ctx.get("mla") or "trace" not in ctx:
        return None
    lo, hi = ctx["span"]
    tr = ctx["traffic"]
    per_chip = [trace.calls(mods, "jit_decode_step", lo, hi)
                for mods in ctx["trace"].modules]
    n = min((len(runs) for runs in per_chip), default=0)
    if not n or n > tr["max_new"] - 1:   # within one batch
        return None
    least = sum(counts_mla.roofline_s(*counts_mla.decode(
        ctx["m"], tr["batch"], tr["prompt_len"] + j), ctx["peak"],
        ctx["chips"]) for j in range(n))
    device_s = sum(sum(d for _, d in sorted(runs)[:n])
                   for runs in per_chip) / len(per_chip)
    return 100.0 * least / device_s
