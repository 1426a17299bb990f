"""Host time in the memory manager's fault entries (``mm.fault_batch``,
``mm.fault_range`` spans) per engine step of the traced window (ms)."""


def read(run):
    if run.trace is None:
        return None
    secs, _ = run.trace.span({"mm.fault_batch", "mm.fault_range"})
    _, n = run.trace.span({"harness.step"})
    return secs * 1000.0 / n if n else None
