"""Arithmetic the metric readers (``metrics/<name>.py``) share.

Every reader is ``read(run) -> float | None``; ``run`` is ``run.Run``.  A
reader that finds nothing to read returns None and the metric is left out
of the result.
"""

from __future__ import annotations

import harness
import workcount

DECODE = ("_decode_entry",)
PREFILL = ("_prefill_entry", "_prefill_sfx_entry")


def ms(x):
    return None if x is None else x * 1000.0


def steps(run):
    return harness.window_steps(run.win)


def decode_steps(run):
    return [s for s in steps(run) if s.decode_tokens > 0]


def traced_steps(run):
    """The window's steps that fall in the traced part of it."""
    t = run.trace_from if run.trace_from is not None else run.win.start
    return [s for s in steps(run) if s.t0 >= t]


def window_flops(run) -> float | None:
    """Operations of every prefill and decode token of the window's steps;
    None where the configuration's layers cannot be counted."""
    cfg = run.cfg
    total = 0.0
    try:
        for s in steps(run):
            total += workcount.decode_flops(cfg, s.kv_rows)
            for L, computed in s.admitted:
                if computed > 0:
                    total += workcount.prefill_flops(cfg, L, computed)
    except workcount.Uncounted:
        return None
    return total
