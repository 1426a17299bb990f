"""Host time in the memory manager's fault path (``mm/fault`` spans: ctx
build, policy, install) per ``serve/step`` of the traced window (ms)."""

import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or not p.steps:
        return None
    return p.total_s.get("mm/fault", 0.0) * 1000.0 / p.steps
