"""Share of mapped KV blocks backed by pages of order > 0 at the window's
end (``mm.hugepage_block_fraction()``) (%)."""


def read(run):
    return 100.0 * run.engine.mm.hugepage_block_fraction()
