"""Process start to window start: weights, engine, warm-up, lead-in (s)."""


def read(run):
    return run.setup_s
