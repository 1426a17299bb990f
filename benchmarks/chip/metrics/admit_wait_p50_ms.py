"""Median over the requests due in the window of the engine's own admit
wait, ``admitted - submitted`` from ``ServingEngine.request_times()``; one
not admitted by the window's end counts as waiting from its submission to
the end (ms)."""

import harness
from readers import ms


def read(run):
    times = getattr(run.engine, "request_times", None)
    if times is None:
        return None
    by_rid = {t.rid: t for t in times()}
    w = run.win
    waits = []
    for r in harness.due_in_window(w):
        t = by_rid.get(r.rid)
        if t is None or r.submitted is None:
            continue
        left = w.end - r.submitted
        waits.append(left if t.admitted is None
                     else min(t.admitted - t.submitted, left))
    return ms(harness.pct(waits, 50))
