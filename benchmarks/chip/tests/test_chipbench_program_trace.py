"""program_trace.py and the metrics that read the program's own spans and
counters, on a small traced window recorded on a TPU v5e
(fixtures/program_trace.xplane.pb, made by record_program_trace.py: a
smoke-size engine serves three requests inside ``harness.window``; the
third has a prompt length of its own, so its prefill variant compiles
inside the window; the JSON beside it holds the engine's stats at the
window's ends and its request times)."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402
import program_trace  # noqa: E402
import reduce  # noqa: E402
from run import Run, load_module  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
TRACE = FIXTURES / "program_trace.xplane.pb"
FACTS = json.loads((FIXTURES / "program_trace.json").read_text())
METRICS = ("admit_wait_p50_ms", "engine_compiles", "compile_idle_share",
           "host_idle_ms_per_step", "mm_fault_ms_per_step",
           "policy_exec_us_per_call")


def _run(path=TRACE) -> Run:
    times = [SimpleNamespace(**t) for t in FACTS["request_times"]]
    live = [t for t in times if t.rid >= 2]         # served in the window
    start = min(t.submitted for t in live)
    end = max(t.first_token for t in live) + 1.0
    recs = {t.rid: harness.Record(t.rid, due=t.submitted, prompt_len=0,
                                  max_new=4, submitted=t.submitted)
            for t in live}
    win = harness.Window(start, end, recs, [], FACTS["stats0"],
                         FACTS["stats1"])
    run = Run(cell="fixture", seconds=end - start, setup_s=0.0, win=win,
              engine=SimpleNamespace(request_times=lambda: times),
              cfg=None, max_batch=4, peaks=None, memory=None,
              trace=reduce.load(path, {"harness.step", "harness.wait"}))
    program_trace.of(run, path)
    return run


def _read(run, name):
    return load_module(CHIP / "metrics" / f"{name}.py", name).read(run)


def test_recorded_on_a_tpu():
    assert FACTS["device_kind"].startswith("TPU")
    assert TRACE.stat().st_size <= 200_000


def test_spans_counted_and_self_time():
    run = _run()
    p = program_trace.of(run)
    assert set(p.count) == set(program_trace.SPANS)
    assert p.steps == run.trace.span({"harness.step"})[1]
    for name in program_trace.SPANS:
        assert 0 <= p.self_s[name] <= p.total_s[name] + 1e-12, name
    # a step is its own time plus its direct children's
    kids = ("serve/admit", "serve/decode", "serve/tick")
    assert p.total_s["serve/step"] == pytest.approx(
        p.self_s["serve/step"] + sum(p.total_s[k] for k in kids))


def test_idle_charged_to_the_compile_and_the_host_spans():
    run = _run()
    p = program_trace.of(run)
    idle = run.trace.window_s - run.trace.busy_s
    assert sum(p.idle_s.values()) == pytest.approx(idle, rel=1e-6)
    # the window compile stalls the device
    assert p.idle_s["serve/compile"] > 0.05 * idle
    host = sum(v for k, v in p.idle_s.items()
               if k in program_trace.SPANS and k != "serve/compile")
    assert host > 0
    # what no program span covers, besides the harness's sleep, is small
    assert p.idle_s.get("host idle", 0.0) <= 0.10 * (
        idle - p.idle_s.get("harness.wait", 0.0))
    assert p.idle_s.get("harness.wait", 0.0) > 0.002


def test_the_six_readers():
    run = _run()
    got = {m: _read(run, m) for m in METRICS}
    assert all(v is not None for v in got.values()), got
    p = program_trace.of(run)
    compiles = FACTS["stats1"]["compiles"] - FACTS["stats0"]["compiles"]
    assert got["engine_compiles"] == compiles > 0
    assert p.count["serve/compile"] == compiles
    assert got["compile_idle_share"] == pytest.approx(
        100 * p.idle_s["serve/compile"] / run.trace.window_s)
    assert 0 < got["compile_idle_share"] < 100
    assert got["host_idle_ms_per_step"] > 0
    assert got["mm_fault_ms_per_step"] == pytest.approx(
        1e3 * p.total_s["mm/fault"] / p.steps)
    assert got["policy_exec_us_per_call"] == pytest.approx(
        1e6 * p.total_s["policy/run"] / p.count["policy/run"])
    waits = sorted(t["admitted"] - t["submitted"]
                   for t in FACTS["request_times"] if t["rid"] >= 2)
    assert got["admit_wait_p50_ms"] == pytest.approx(
        1e3 * waits[(len(waits) + 1) // 2 - 1])


def test_a_trace_without_program_spans_reads_nothing():
    # the benchmark's older fixture: harness spans only, as a program
    # without these spans writes
    old = FIXTURES / "tpu_trace.xplane.pb"
    run = _run(old)
    assert program_trace.of(run) is None
    for m in METRICS[2:]:
        assert _read(run, m) is None, m


def test_another_runs_trace_reads_nothing():
    run = _run()
    other = _run(FIXTURES / "tpu_trace.xplane.pb")
    del other.program_spans
    # this run's window, another run's file
    other.trace = run.trace
    assert program_trace.of(other, FIXTURES / "tpu_trace.xplane.pb") is None


def test_launch_lag_says_the_clocks_are_aligned():
    """Each engine program starts about a ms after its dispatch span
    starts on the host, once device times are on the host's clock."""
    ev = reduce.load_events(TRACE, set(program_trace.SPANS))
    assert 0 < program_trace.launch_lag_ms(ev) < 5
