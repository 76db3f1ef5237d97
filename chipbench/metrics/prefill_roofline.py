"""Share of its roofline that ``jit_prefill_step`` reaches in the traced
batch: the least time its work needs (``counts.prefill``), over its device
time per call."""
from chipbench import counts, trace


def read(ctx):
    if ctx["kind"] != "serve" or "trace" not in ctx:
        return None
    lo, hi = ctx["span"]
    tr = ctx["traffic"]
    runs = [c for mods in ctx["trace"].modules
            for c in trace.calls(mods, "jit_prefill_step", lo, hi)]
    if not runs:
        return None
    least = counts.roofline_s(*counts.prefill(ctx["m"], tr["batch"],
                                              tr["prompt_len"]), ctx["peak"])
    return 100.0 * least * len(runs) / sum(d for _, d in runs)
