"""The serve loop's phases in the traced batch.

``repro.launch.serve.serve`` marks each batch's ``serve/admit``,
``serve/prefill``, ``serve/decode`` and ``serve/collect`` with a profiler
span on the thread that runs the benchmark's own spans, so ``trace.load``
keeps them among ``Trace.host``, on the device trace's clock.  A program
without these spans gives none here, and the readers built on them then
read nothing.
"""
from __future__ import annotations


def spans(ctx: dict, name: str) -> list:
    """(start, end) of each ``name`` span that starts inside the traced
    batch; [] where the run was not traced."""
    if "trace" not in ctx:
        return []
    lo, hi = ctx["span"]
    return [(t, t + d) for n, t, d in ctx["trace"].host
            if n == name and lo <= t <= hi]


def only(ctx: dict, name: str):
    """The traced batch's one ``name`` span, or None."""
    found = spans(ctx, name)
    return found[0] if len(found) == 1 else None
