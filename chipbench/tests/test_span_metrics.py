"""The readers of the serve loop's phase spans, on a traced batch built by
hand whose answers are worked out below."""
import pytest

from chipbench.bench import reader
from chipbench.trace import Trace

LO, HI = 10.0, 20.0
METRICS = ["serve.first_token_s", "serve.token_gap_ms",
           "serve.boundary_idle_ms"]

# the traced batch's four phases, and the previous batch's admission,
# which starts before the window and is not this batch's
PHASES = [("serve/admit", 0.1, 0.2),
          ("serve/admit", 10.1, 0.2),          # 10.10 - 10.30
          ("serve/prefill", 10.3, 0.05),       # 10.30 - 10.35
          ("PjitFunction(decode_step)", 10.36, 0.001),
          ("serve/decode", 10.35, 9.0),        # 10.35 - 19.35
          ("serve/collect", 19.35, 0.4)]       # 19.35 - 19.75
MODULES = [("jit_decode_step(3)", 5.0, 0.5),   # the previous batch's
           ("jit_prefill_step(1)", 10.25, 2.0),
           ("jit_argmax(2)", 12.25, 0.01),
           ("jit_decode_step(3)", 12.30, 0.50),  # ends 12.80
           ("jit_decode_step(3)", 12.90, 0.50),  # ends 13.40: gap 0.60
           ("jit_decode_step(3)", 13.40, 0.50),  # ends 13.90: gap 0.50
           ("jit_decode_step(3)", 13.95, 0.45)]  # ends 14.40: gap 0.50
# busy 10.00-10.12, 10.25-14.40, 19.40-19.45, 19.90-20.10; so idle in the
# window 10.12-10.25, 14.40-19.40 and 19.45-19.90
OPS = [("fusion.1", 10.0, 0.12), ("copy.1", 10.25, 4.15),
       ("concatenate.1", 19.40, 0.05), ("fusion.2", 19.90, 0.2)]


def ctx(host=PHASES, ops=(OPS,), modules=(MODULES,)):
    t = Trace(ops=list(ops), modules=list(modules),
              spans=[("chipbench/batch", LO, HI - LO)], host=list(host))
    return {"kind": "serve", "trace": t, "span": (LO, HI)}


def test_first_token_is_admission_to_the_first_decode_step():
    # 12.30 (first decode step starts on the device) - 10.10 (admit)
    assert reader("serve.first_token_s")(ctx()) == pytest.approx(2.2)


def test_token_gap_is_the_median_gap_between_decode_step_ends():
    # gaps 0.60, 0.50, 0.50 s; the previous batch's step is outside
    assert reader("serve.token_gap_ms")(ctx()) == pytest.approx(500.0)


def test_boundary_idle_is_device_idle_under_admit_and_collect():
    # admit 10.10-10.30 meets idle 10.12-10.25: 0.13 s; collect
    # 19.35-19.75 meets 14.40-19.40 (0.05 s) and 19.45-19.90 (0.30 s)
    assert reader("serve.boundary_idle_ms")(ctx()) == pytest.approx(480.0)
    # averaged over the chips: a second one idle all through the window
    # loses the whole admit (0.2 s) and collect (0.4 s)
    assert reader("serve.boundary_idle_ms")(ctx(ops=(OPS, []))) == \
        pytest.approx((480.0 + 600.0) / 2)


@pytest.mark.parametrize("name", METRICS)
def test_readers_read_nothing_without_the_serve_spans(name):
    no_phases = [e for e in PHASES if not e[0].startswith("serve/")]
    assert reader(name)(ctx(host=no_phases)) is None
    assert reader(name)({"kind": "serve", "compile_s": 0.5}) is None
    assert reader(name)({"kind": "train"}) is None


def test_token_gap_needs_two_decode_steps():
    one = [m for m in MODULES if m[1] not in (12.90, 13.40, 13.95)]
    assert reader("serve.token_gap_ms")(ctx(modules=(one,))) is None
    assert reader("serve.first_token_s")(ctx(modules=(one,))) == \
        pytest.approx(2.2)

