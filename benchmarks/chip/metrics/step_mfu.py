"""Operations of every prefill and decode token of the window over
(window seconds x peak bf16 operations/s) (%)."""

from readers import window_flops


def read(run):
    if run.peaks is None:
        return None
    return 100.0 * window_flops(run) / (
        run.seconds * run.peaks["bf16_flops_per_s"])
