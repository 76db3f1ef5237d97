"""Share of its roofline that ``jit_decode_step`` reaches in the traced
batch: the least time each step's work needs at its own cache position
(``counts.decode``), summed, over the steps' device time."""
from chipbench import counts, trace


def read(ctx):
    if ctx["kind"] != "serve" or "trace" not in ctx:
        return None
    lo, hi = ctx["span"]
    tr = ctx["traffic"]
    runs = [c for mods in ctx["trace"].modules
            for c in trace.calls(mods, "jit_decode_step", lo, hi)]
    if not runs or len(runs) > tr["max_new"] - 1:   # within one batch
        return None
    least = sum(counts.roofline_s(*counts.decode(
        ctx["m"], tr["batch"], tr["prompt_len"] + j), ctx["peak"])
        for j in range(len(runs)))
    return 100.0 * least / sum(d for _, d in runs)
