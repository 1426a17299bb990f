"""Share of the traced window in which the device idled under the program's
``serve/compile`` span: a device program's variant traced, lowered and
compiled (or loaded from the persistent cache) on its first call (%)."""

import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or p.window_s <= 0:
        return None
    return 100.0 * p.idle_s.get("serve/compile", 0.0) / p.window_s
