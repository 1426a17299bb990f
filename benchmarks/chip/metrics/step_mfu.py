"""Operations of every prefill and decode token of the window over
(window seconds x peak bf16 operations/s) (%)."""

from readers import window_flops


def read(run):
    flops = window_flops(run)
    if run.peaks is None or flops is None:
        return None
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])
