"""Device time per call of the decode program in the traced window (ms)."""

from readers import DECODE


def read(run):
    if run.trace is None:
        return None
    secs, n = run.trace.program(DECODE)
    return secs * 1000.0 / n if n else None
