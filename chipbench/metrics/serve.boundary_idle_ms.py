"""Milliseconds of the traced batch in which no operation ran on the
device (one minus the union of ``XLA Ops``) while the host was admitting
the batch (``serve/admit``) or collecting its tokens (``serve/collect``):
the device time lost at the batch's two boundaries, averaged over the
chips."""
from chipbench import phases, trace


def read(ctx):
    host = phases.spans(ctx, "serve/admit") + phases.spans(ctx,
                                                           "serve/collect")
    if not host:
        return None
    lo, hi = ctx["span"]
    lost = []
    for ops in ctx["trace"].ops:
        idle = trace.gaps(trace.busy(trace.clip(ops, lo, hi)), lo, hi)
        lost.append(sum(max(0.0, min(e, he) - max(s, hs))
                        for s, e in idle for hs, he in host))
    return 1e3 * sum(lost) / len(lost) if lost else None
