"""Bytes the traced decode steps need (weights once, each row's K/V at its
real length, the new K/V) over (decode device time x peak HBM bytes/s) (%).

The decode step is bound by memory at every batch and context the cells
use, so the HBM bound is its roofline."""

import workcount
from readers import DECODE, traced_steps


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    secs, n = run.trace.program(DECODE)
    st = [s for s in traced_steps(run) if s.decode_tokens > 0]
    if not n or not st:
        return None
    need = sum(workcount.decode_bytes(run.cfg, s.kv_rows) for s in st)
    # scale to the calls the trace saw, should a step straddle its edges
    need *= n / len(st)
    return 100.0 * need / (secs * run.peaks["hbm_bytes_per_s"])
