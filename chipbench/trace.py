"""Reduction of the JAX profiler's trace to what the per-layer metrics
read: device programs ("XLA Modules"), device operations ("XLA Ops"), the
benchmark's own host spans (``TraceAnnotation`` names starting with
``chipbench/``) and the host events around them.

``load`` is the only function that touches the ``.xplane.pb`` format; the
rest work on plain ``(name, start_s, duration_s)`` tuples, so the tests
check them on a small recorded trace.
"""
from __future__ import annotations

import collections
import glob
import os
from dataclasses import dataclass, field

SPAN_PREFIX = "chipbench/"


@dataclass
class Trace:
    ops: list = field(default_factory=list)       # per device: [(name, t, dur)]
    modules: list = field(default_factory=list)   # per device: [(name, t, dur)]
    spans: list = field(default_factory=list)     # [(name, t, dur)]
    host: list = field(default_factory=list)      # [(name, t, dur)]


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, got {paths}")
    out = Trace()
    host_lines = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            out.ops.append(_events(lines.get("XLA Ops")))
            out.modules.append(_events(lines.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            host_lines += [_events(ln) for ln in plane.lines]
    for evs in host_lines:
        mine = [e for e in evs if e[0].startswith(SPAN_PREFIX)]
        if mine:
            out.spans += mine
            out.host += [e for e in evs if not e[0].startswith(SPAN_PREFIX)]
    return out


def _events(line) -> list:
    if line is None:
        return []
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def short_op(name: str) -> str:
    """'%fusion.198 = f32[8]{0} fusion(...)' -> 'fusion.198'."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_base(name: str) -> str:
    """'jit_decode_step(9365443)' -> 'jit_decode_step'."""
    return name.split("(", 1)[0]


def clip(events, lo: float, hi: float) -> list:
    """Events that overlap [lo, hi], cut to it."""
    out = []
    for name, t, d in events:
        s, e = max(t, lo), min(t + d, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def busy(events) -> list:
    """Union of the events' intervals, as sorted disjoint (start, end)."""
    merged = []
    for _, t, d in sorted(events, key=lambda e: e[1]):
        if merged and t <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t + d)
        else:
            merged.append([t, t + d])
    return [tuple(iv) for iv in merged]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(intervals, lo: float, hi: float) -> list:
    """Idle (start, end) between busy intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in intervals:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events) -> collections.Counter:
    """Seconds of each event name not covered by events nested inside it
    on the same line."""
    out = collections.Counter()
    stack = []  # [name, end, child seconds]
    for name, t, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= t:
            n, _, kids, dur = stack.pop()
            out[n] += dur - kids
        if stack:
            stack[-1][2] += d
        stack.append([name, t + d, 0.0, d])
    for n, _, kids, dur in stack:
        out[n] += dur - kids
    return out


def label_ops(ops, modules) -> list:
    """Ops renamed '<program>/<op>' by the program whose interval holds
    their start."""
    mods = sorted(modules, key=lambda e: e[1])
    out, j = [], 0
    for name, t, d in sorted(ops, key=lambda e: e[1]):
        while j < len(mods) and mods[j][1] + mods[j][2] < t:
            j += 1
        prog = (module_base(mods[j][0])
                if j < len(mods) and mods[j][1] <= t else "?")
        out.append((f"{prog}/{short_op(name)}", t, d))
    return out


def top(counter, n: int = 10) -> list:
    return [[k, v] for k, v in counter.most_common(n)]


def gap_causes(idle, host, spans, n: int = 10) -> list:
    """The ``n`` longest idle gaps, each named by the benchmark span it
    falls in and the host event that overlaps it most."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        def overlap(ev):
            return min(e, ev[1] + ev[2]) - max(s, ev[1])
        span = max(spans, key=overlap, default=None)
        ev = max(host, key=overlap, default=None)
        where = span[0][len(SPAN_PREFIX):] if span and overlap(span) > 0 else "-"
        what = ev[0] if ev and overlap(ev) > 0 else "no host event"
        out.append([f"{where}: {what}", e - s])
    return out


def calls(modules, program: str, lo: float, hi: float) -> list:
    """(start, duration) of each run of ``program`` whose midpoint lies in
    [lo, hi]: a run the window's edge cuts, by the host's and the device's
    clocks disagreeing by microseconds, still counts once."""
    return [(t, d) for name, t, d in modules
            if module_base(name) == program and lo <= t + d / 2 <= hi]


def window(t: Trace, lo: float, hi: float) -> tuple[float, dict]:
    """(device busy seconds averaged over the chips, breakdown) of the
    window [lo, hi]: the operations with the most self time, and the
    longest idle gaps with what the host was doing in each."""
    busy_s, ops, idle = [], [], []
    for dev_ops, dev_mods in zip(t.ops, t.modules):
        inside = clip(dev_ops, lo, hi)
        iv = busy(inside)
        busy_s.append(total(iv))
        idle += gaps(iv, lo, hi)
        ops += label_ops(inside, dev_mods)
    return (sum(busy_s) / max(len(busy_s), 1),
            {"device_ops": top(self_times(ops)),
             "idle_gaps": gap_causes(idle, t.host, t.spans)})
