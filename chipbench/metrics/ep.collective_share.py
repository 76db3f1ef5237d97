"""Share of ``jit_decode_step``'s device time that its collective
operations take in the four-chip cell's traced batch, averaged over the
chips: the summed durations of the ops that are an ``all-reduce``,
``all-gather``, ``reduce-scatter``, ``all-to-all`` or
``collective-permute`` (or the ``-start`` / ``-done`` of one), found by
the op's name or its HLO opcode, inside the chip's decode steps, over
those steps' device time."""
import bisect
import re

from chipbench import trace

COLLECTIVES = {f"{op}{part}" for op in (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute") for part in ("", "-start", "-done")}
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def is_collective(name: str) -> bool:
    """'%all-reduce-start.3 = ...' or 'all-reduce.1' or an instruction
    whose opcode is one of them."""
    short = trace.short_op(name)
    if short.rsplit(".", 1)[0] in COLLECTIVES:
        return True
    _, _, text = name.partition(" = ")
    found = _OPCODE.search(" " + text)
    return bool(found) and found.group(1) in COLLECTIVES


def read(ctx):
    if not ctx.get("mla") or "trace" not in ctx:
        return None
    lo, hi = ctx["span"]
    shares = []
    for ops, mods in zip(ctx["trace"].ops, ctx["trace"].modules):
        steps = sorted(trace.calls(mods, "jit_decode_step", lo, hi))
        if not steps:
            continue
        starts = [t for t, _ in steps]
        inside = 0.0
        for name, t, d in ops:
            i = bisect.bisect_right(starts, t) - 1
            if (i >= 0 and t + d <= steps[i][0] + steps[i][1] + 1e-9
                    and is_collective(name)):
                inside += d
        shares.append(inside / sum(d for _, d in steps))
    return 100.0 * sum(shares) / len(shares) if shares else None
