"""Rows decoded over (decode steps x max_batch) in the window (%)."""

from readers import decode_steps


def read(run):
    st = decode_steps(run)
    if not st:
        return None
    return 100.0 * sum(s.decode_tokens for s in st) / (len(st) * run.max_batch)
