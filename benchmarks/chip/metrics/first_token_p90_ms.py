"""90th percentile over the requests due in the window of first token minus
due time; a request with no first token by the window's end counts as
(end - due) (ms).  Read per layer: at four fifths of the knee a burst that
fills every slot decides it, so it swings from seed to seed."""

import harness
from readers import ms


def read(run):
    return ms(harness.pct(harness.ttft_s(run.win), 90))
