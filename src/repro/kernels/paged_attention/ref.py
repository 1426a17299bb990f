"""Host oracle for the page-class derivation in ``ops.page_classes``.

The attention itself has its reference in the gather path
(``repro.models.decode.paged_decode_attention_gather``), which the kernel's
tests compare against.
"""

from __future__ import annotations

import numpy as np

from ...core.context import NUM_ORDERS


def page_classes_ref(block_table, first, end) -> np.ndarray:
    """Order of the largest page that holds each live block (-1 where the
    block is not read), by a walk over each row: a page of order c is an
    aligned run of 4^c live logical blocks whose physical blocks are
    consecutive and start on a multiple of 4^c."""
    tbl = np.asarray(block_table)
    B, MB = tbl.shape
    out = np.full((B, MB), -1, np.int64)
    for b in range(B):
        live = [(j >= first[b]) and (j < end[b]) and tbl[b, j] >= 0
                for j in range(MB)]
        for j in range(MB):
            if not live[j]:
                continue
            out[b, j] = 0
            for c in range(1, NUM_ORDERS):
                n = 4 ** c
                s = j - j % n
                run = range(s, s + n)
                if (s + n <= MB and all(live[i] for i in run)
                        and tbl[b, s] % n == 0
                        and all(tbl[b, i] == tbl[b, s] + i - s for i in run)):
                    out[b, j] = c
    return out
