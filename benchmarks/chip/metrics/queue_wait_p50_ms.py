"""Median over the requests due in the window of due time to the start of
the step that admitted it; one not admitted by the window's end counts as
(end - due) (ms)."""

import harness
from readers import ms


def read(run):
    w = run.win
    waits = [(r.admitted_step if r.admitted_step is not None
              and r.admitted_step <= w.end else w.end) - r.due
             for r in harness.due_in_window(w)]
    return ms(harness.pct(waits, 50))
