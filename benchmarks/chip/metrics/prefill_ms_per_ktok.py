"""Device time of the prefill programs per 1000 prompt tokens computed in
the traced window (ms)."""

from readers import PREFILL, traced_steps


def read(run):
    if run.trace is None:
        return None
    secs, calls = run.trace.program(PREFILL)
    ktok = sum(s.prefill_tokens for s in traced_steps(run)) / 1000.0
    return secs * 1000.0 / ktok if calls and ktok > 0 else None
