"""Host time per policy-executor batch call (``policy.run_batch`` spans of
the traced window) (us)."""


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.span({"policy.run_batch"})
    return secs * 1e6 / n if n else None
