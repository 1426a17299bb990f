"""reduce.py: busy union, idle share, per-program device time and the
attribution of idle gaps to host spans, on hand-made events and on a small
profiler trace recorded on a TPU v5e (fixtures/tpu_trace.xplane.pb: three
``harness.step`` spans, each running a 2048x2048 bf16 program named
``_decode_entry``, the second also a ``_prefill_entry`` inside
``engine.admit``, with 3 ms ``harness.wait`` sleeps between steps)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import reduce  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "fixtures" / \
    "tpu_trace.xplane.pb"
SPANS = {"harness.step", "harness.wait", "engine.decode", "engine.admit"}


def _ev():
    ms = 1_000_000
    return {
        "host": [("harness.window", 0, 100 * ms),
                 ("harness.step", 0, 40 * ms),
                 ("engine.decode", 5 * ms, 30 * ms),
                 ("harness.wait", 40 * ms, 100 * ms)],
        "devices": {"/device:TPU:0": {
            # overlapping ops merge; the one past the window is clipped
            "ops": [("fusion.1", 10 * ms, 20 * ms),
                    ("fusion.2", 15 * ms, 25 * ms),
                    ("copy.3", 60 * ms, 70 * ms),
                    ("late", 95 * ms, 120 * ms)],
            "modules": [("jit__decode_entry(7)", 10 * ms, 25 * ms),
                        ("jit__moves_entry(2)", 60 * ms, 70 * ms)]}}}


def test_busy_union_and_idle_share():
    t = reduce.reduce(_ev())
    assert t.window_s == pytest.approx(0.1)
    assert t.busy == [(10e6, 25e6), (60e6, 70e6), (95e6, 100e6)]
    assert t.busy_s == pytest.approx(0.030)
    assert 1 - t.busy_s / t.window_s == pytest.approx(0.7)


def test_program_time():
    t = reduce.reduce(_ev())
    assert t.program({"_decode_entry"}) == (pytest.approx(0.015), 1)
    assert t.program({"_moves_entry", "_decode_entry"})[1] == 2
    assert t.program({"_prefill_entry"}) == (0, 0)
    assert t.ops["late"] == pytest.approx(0.005)


def test_idle_attribution():
    t = reduce.reduce(_ev())
    # idle [0,10): step until 5, then decode; [25,40): decode until 30,
    # then step; [40,60) [70,95): wait
    assert t.idle == {"harness.step": pytest.approx(0.015),
                      "engine.decode": pytest.approx(0.010),
                      "harness.wait": pytest.approx(0.045)}
    assert sum(t.idle.values()) == pytest.approx(t.window_s - t.busy_s)
    bd = t.breakdown()
    assert bd["idle_gaps"][0] == ["harness.wait", pytest.approx(0.045)]
    assert {k for k, _ in bd["device_ops"][:3]} == \
        {"fusion.1", "fusion.2", "copy.3"}


def test_no_device_no_trace():
    ev = _ev()
    ev["devices"] = {}
    assert reduce.reduce(ev) is None


def test_recorded_tpu_trace():
    t = reduce.load(FIXTURE, SPANS)
    assert t is not None
    secs, n = t.program({"_decode_entry"})
    assert n == 3 and secs > 0
    assert t.program({"_prefill_entry"})[1] == 1
    assert 0 < t.busy_s < t.window_s
    assert t.span({"harness.step"})[1] == 3
    assert t.span({"harness.wait"})[1] == 3
    # the sleeps are idle time, charged to the spans that cover them
    assert t.idle.get("harness.wait", 0) > 0.008
    assert sum(t.idle.values()) == pytest.approx(t.window_s - t.busy_s,
                                                 rel=1e-6)
