"""Median milliseconds between the device ends of consecutive
``jit_decode_step`` runs inside the traced batch's ``serve/decode`` span:
the gap between one token of every request and the next."""
import statistics

from chipbench import phases, trace
from chipbench.bench import log


def read(ctx):
    decode = phases.only(ctx, "serve/decode")
    if decode is None:
        return None
    gaps = []
    for mods in ctx["trace"].modules:
        ends = sorted(t + d for t, d in
                      trace.calls(mods, "jit_decode_step", *decode))
        gaps += [b - a for a, b in zip(ends, ends[1:])]
    if not gaps:
        return None
    log(f"[serve.token_gap_ms] median of {len(gaps)} gaps; "
        f"{1e3 * min(gaps)!r} - {1e3 * max(gaps)!r} ms")
    return 1e3 * statistics.median(gaps)
