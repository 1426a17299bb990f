"""Host time per policy-executor invocation (``policy/run`` spans of the
traced window) (us)."""

import program_trace


def read(run):
    p = program_trace.of(run)
    if p is None or not p.count.get("policy/run"):
        return None
    return p.total_s["policy/run"] * 1e6 / p.count["policy/run"]
