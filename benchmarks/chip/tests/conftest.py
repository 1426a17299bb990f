"""The hand-made runs of ``test_chipbench_metrics`` hold what a traced run
of the serving program holds.

That module builds a run of a cell by hand (``_run``) and asks every metric
the cell lists to read from it.  The metrics that read the program's own
records (``program_trace.py``, the engine's compile counter and request
times) find them in a real run; here each hand-made run gets them too:
inside every step the program's spans (the first step's dispatch a
compile), the compile counter in the window's stats and the engine's
request times, admitted 2 ms after the harness's admitting step began.
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

MS = 1_000_000


def program_spans(t0: float, t1: float, first: bool) -> list:
    """A step's spans (ns) over its device work at +2..+33 ms."""
    a, b = t0 * 1e9, t1 * 1e9
    return [("serve/step", a, b),
            ("serve/admit", a, a + 0.4 * MS),
            ("serve/decode", a + 0.5 * MS, b - MS),
            ("serve/fault", a + 0.5 * MS, a + 1.5 * MS),
            ("mm/fault", a + 0.6 * MS, a + 1.4 * MS),
            ("policy/run", a + 0.7 * MS, a + 1.2 * MS),
            ("serve/compile" if first else "serve/dispatch",
             a + 1.5 * MS, a + 2 * MS),
            ("serve/sync", a + 2 * MS, a + 33.5 * MS),
            ("serve/sample", a + 33.5 * MS, b - MS),
            ("serve/tick", b - MS, b)]


def with_program(run):
    import program_trace
    host = []
    for i, s in enumerate(run.win.steps):
        host += program_spans(s.t0, s.t1, i == 0)
    program_trace.from_events(run, host)
    run.win.stats0["compiles"] = 0
    run.win.stats1["compiles"] = 1
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
    monkeypatch.setattr(mod, "_run", lambda cell: with_program(make(cell)))
