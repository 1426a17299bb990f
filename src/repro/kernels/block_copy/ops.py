"""jit'd wrapper: expand MM move plans into per-block row copies and run the
migration kernel over every pool in a serving cache."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import block_copy


def expand_moves(plan, pad_to: int | None = None):
    """[(src_start, dst_start, order)] -> (src[NM], dst[NM]) per-block rows."""
    src, dst = [], []
    for s, d, o in plan:
        n = 4 ** o
        src.extend(range(s, s + n))
        dst.extend(range(d, d + n))
    if pad_to is not None:
        while len(src) < pad_to:
            src.append(0)
            dst.append(0)      # self-copy padding
    return (np.asarray(src, np.int32), np.asarray(dst, np.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def apply_moves(pool, src, dst, *, interpret: bool = False):
    """pool: [NB, ...] (any trailing dims); src/dst: [NM]."""
    return block_copy(pool, src, dst, interpret=interpret)
