"""The hand-made runs of ``test_chipbench_metrics`` hold what a traced run
of the serving program holds.

That module builds a run of a cell by hand (``_run``) and asks every metric
the cell lists to read from it.  The metrics that read the program's own
records (``program_trace.py``, the engine's counters and request times)
find them in a real run; here each hand-made run gets them too: inside
every step an event of each program span name (``program_trace.SPANS``;
the first step's dispatch a compile), every field of the engine's
``EngineStats().snapshot()`` in the window's stats (as they are at the
start, and a window's worth more at the end, so that a counter the
program adds reads here with no edit) and the engine's request times,
admitted 2 ms after the harness's admitting step began.
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

MS = 1_000_000


def program_spans(t0: float, t1: float, first: bool) -> list:
    """A step's spans (ns) over its device work at +2..+33 ms: one of each
    name in ``program_trace.SPANS`` (the dispatch or, in the first step,
    the compile)."""
    import program_trace
    a, b = t0 * 1e9, t1 * 1e9
    out = [("serve/step", a, b),
           ("serve/admit", a, a + 0.4 * MS),
           ("serve/prefix", a + 0.05 * MS, a + 0.1 * MS),
           ("serve/prefill", a + 0.1 * MS, a + 0.35 * MS),
           ("serve/decode", a + 0.5 * MS, b - MS),
           ("serve/fault", a + 0.5 * MS, a + 1.5 * MS),
           ("mm/fault", a + 0.6 * MS, a + 1.4 * MS),
           ("policy/run", a + 0.7 * MS, a + 1.2 * MS),
           ("serve/tables", a + 1.42 * MS, a + 1.48 * MS),
           ("serve/compile" if first else "serve/dispatch",
            a + 1.5 * MS, a + 2 * MS),
           ("serve/sync", a + 2 * MS, a + 33.5 * MS),
           ("serve/sample", a + 33.5 * MS, b - MS),
           ("serve/tick", b - MS, b),
           ("serve/moves", b - 0.8 * MS, b - 0.6 * MS)]
    placed = {name for name, _, _ in out} | {"serve/compile",
                                              "serve/dispatch"}
    # a name the list gains later: a short span at the step's start
    out += [(name, a + 0.01 * MS, a + 0.02 * MS)
            for name in program_trace.SPANS if name not in placed]
    return out


def _grown(value, n: int):
    """A counter's value a window of ``n`` steps later."""
    if isinstance(value, bool) or not isinstance(value,
                                                 (int, float, list)):
        return value
    if isinstance(value, list):
        return [_grown(v, n * (i + 1)) for i, v in enumerate(value)]
    return value + n


def window_stats(n: int) -> tuple[dict, dict]:
    """Every field of the engine's stats at a window's start (a fresh
    engine's) and ``n`` steps later; the first step compiled one
    variant."""
    from repro.serving.engine import EngineStats
    start = EngineStats().snapshot()
    end = {k: _grown(v, n) for k, v in start.items()}
    end.update(compiles=start["compiles"] + 1,
               compiles_by_entry={"_decode_entry": 1})
    return start, end


def with_program(run):
    import program_trace
    host = []
    for i, s in enumerate(run.win.steps):
        host += program_spans(s.t0, s.t1, i == 0)
    program_trace.from_events(run, host)
    start, end = window_stats(len(run.win.steps))
    # what the hand-made run states itself stands
    run.win.stats0 = {**start, **run.win.stats0}
    run.win.stats1 = {**end, **run.win.stats1}
    times = [SimpleNamespace(rid=r.rid, submitted=r.submitted,
                             admitted=r.admitted_step + 0.002,
                             first_token=r.deliveries[0][0])
             for r in run.win.records.values()]
    run.engine.request_times = lambda: times
    return run


@pytest.fixture(autouse=True)
def _hand_made_runs_hold_the_program(request, monkeypatch):
    mod = request.module
    if mod.__name__ != "test_chipbench_metrics":
        return
    make = mod._run
    monkeypatch.setattr(mod, "_run",
                        lambda cell, **kw: with_program(make(cell, **kw)))
