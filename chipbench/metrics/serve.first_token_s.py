"""Seconds from the traced batch's admission (the start of its
``serve/admit`` span) to the device start of its first ``jit_decode_step``,
the step that takes the batch's first token as its input: admission,
prefill and the first token's argmax, as the batch's requests wait for
them."""
from chipbench import phases, trace


def read(ctx):
    admit = phases.only(ctx, "serve/admit")
    if admit is None:
        return None
    t0, hi = admit[0], ctx["span"][1]
    starts = [min(t for t, _ in runs) for runs in (
        trace.calls(mods, "jit_decode_step", t0, hi)
        for mods in ctx["trace"].modules) if runs]
    return max(starts) - t0 if starts else None
