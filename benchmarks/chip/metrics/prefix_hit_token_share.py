"""Share of the prompt tokens admitted in the window that the prefix cache
served, i.e. that prefill did not compute (%)."""

from readers import steps


def read(run):
    st = steps(run)
    prompt = sum(L for s in st for L, _ in s.admitted)
    if prompt <= 0:
        return None
    return 100.0 * (1.0 - sum(s.prefill_tokens for s in st) / prompt)
