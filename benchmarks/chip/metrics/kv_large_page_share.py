"""Share of the K/V blocks the decode steps of the window read that lay in
pages of order 1 or more (4+ blocks): the engine's
``kv_blocks_read_by_order`` counter (``EngineStats``, the paged decode
kernel's own count) at the window's end less at its start (%)."""


def read(run):
    a = run.win.stats0.get("kv_blocks_read_by_order")
    b = run.win.stats1.get("kv_blocks_read_by_order")
    if a is None or b is None:
        return None
    blocks = [y - x for x, y in zip(a, b)]
    if sum(blocks) <= 0:
        return None
    return 100.0 * sum(blocks[1:]) / sum(blocks)
