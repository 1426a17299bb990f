"""95th percentile of the gaps between consecutive deliveries of output
tokens to a request, over every delivery inside the window (ms)."""

import harness
from readers import ms


def read(run):
    return ms(harness.pct(harness.gaps_s(run.win), 95))
