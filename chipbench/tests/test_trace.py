"""The reduction from trace to metrics, on a decode step recorded on one
v5e chip (``data/trace_decode_step.json``: granite at full width, batch 8)
and on small cases worked out by hand."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import trace as T

FIXTURE = json.loads((pathlib.Path(__file__).parent / "data"
                      / "trace_decode_step.json").read_text())
LO, HI = FIXTURE["window"]


def as_events(rows):
    return [tuple(r) for r in rows]


OPS, MODS = as_events(FIXTURE["ops"]), as_events(FIXTURE["modules"])


def brute_busy(events, lo, hi, res=1e-8):
    """Busy seconds by painting a timeline at 10 ns resolution."""
    n = int(round((hi - lo) / res))
    line = np.zeros(n, bool)
    for _, t, d in events:
        a = max(int(np.floor((t - lo) / res)), 0)
        b = min(int(np.ceil((t + d - lo) / res)), n)
        line[a:b] = True
    return line.sum() * res


def test_busy_matches_a_painted_timeline():
    inside = T.clip(OPS, LO, HI)
    exact = T.total(T.busy(inside))
    assert exact == pytest.approx(brute_busy(inside, LO, HI), abs=2e-8 * len(inside))
    # the recorded step is one 8.72 ms decode with a host gap before it
    assert 0.0080 < exact < HI - LO


def test_busy_and_gaps_cover_the_window():
    iv = T.busy(T.clip(OPS, LO, HI))
    assert T.total(iv) + T.total(T.gaps(iv, LO, HI)) == pytest.approx(HI - LO)
    assert all(s < e for s, e in T.gaps(iv, LO, HI))


def test_calls_finds_the_decode_step():
    runs = T.calls(MODS, "jit_decode_step", LO, HI)
    assert runs == [(0.0002, 0.00872346)]
    assert T.calls(MODS, "jit_prefill_step", LO, HI) == []


def test_ops_are_labelled_by_their_program():
    labels = {n.split("/")[0] for n, t, _ in T.label_ops(T.clip(OPS, 0.0002, HI),
                                                        MODS)}
    assert labels <= {"jit_decode_step", "jit_convert_element_type"}
    assert "jit_decode_step" in labels


def test_self_times_of_nested_events():
    ev = [("loop", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 4.0, 2.0),
          ("a", 7.0, 1.0), ("c", 11.0, 1.0)]
    st = T.self_times(ev)
    assert st == {"loop": 5.0, "a": 3.0, "b": 2.0, "c": 1.0}
    # on the recorded step, self time never exceeds the busy time
    inside = T.clip(OPS, LO, HI)
    assert sum(T.self_times(inside).values()) <= sum(d for *_, d in inside) + 1e-12
    assert sum(T.self_times(inside).values()) == pytest.approx(
        T.total(T.busy(inside)), rel=1e-6)


def test_gap_causes_name_the_span_and_host_event():
    idle = [(1.0, 3.0), (5.0, 5.5)]
    host = [("PjitFunction(decode_step)", 0.5, 2.0), ("np.asarray", 5.0, 0.4)]
    spans = [("chipbench/batch", 0.0, 10.0)]
    assert T.gap_causes(idle, host, spans) == [
        ["batch: PjitFunction(decode_step)", 2.0], ["batch: np.asarray", 0.5]]
    assert T.gap_causes([(20.0, 21.0)], host, spans) == [
        ["-: no host event", 1.0]]


def test_window_reduction_of_the_recorded_step():
    t = T.Trace(ops=[OPS], modules=[MODS], spans=as_events(FIXTURE["spans"]),
                host=as_events(FIXTURE["host"]))
    busy_s, br = T.window(t, LO, HI)
    assert busy_s == pytest.approx(T.total(T.busy(T.clip(OPS, LO, HI))))
    assert len(br["device_ops"]) == 10
    assert br["device_ops"][0][1] >= br["device_ops"][-1][1]
    assert br["device_ops"][0][0].startswith("jit_decode_step/")
    # the longest idle gap is the one before the step, which starts at
    # 0.2 ms, 11.5 us after the small program before it ends; its
    # operations leave the device idle a little longer
    (name, secs), = br["idle_gaps"][:1]
    assert name.startswith("batch: ")
    assert 0.0002 - 0.000187891 - 5.93e-07 <= secs < 2e-5


def test_names():
    assert T.short_op("%fusion.198 = f32[8]{0} fusion(x)") == "fusion.198"
    assert T.module_base("jit_decode_step(936544314628915541)") == "jit_decode_step"
