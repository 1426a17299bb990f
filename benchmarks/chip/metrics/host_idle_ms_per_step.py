"""Device idle time charged to the program's host spans (``serve/*``,
``mm/*``, ``policy/*``) other than ``serve/compile``, per ``serve/step`` of
the traced window (ms)."""

import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or not p.steps:
        return None
    idle = sum(v for k, v in p.idle_s.items()
               if k in program_trace.SPANS and k != "serve/compile")
    return idle * 1000.0 / p.steps
