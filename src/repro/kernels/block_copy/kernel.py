"""Pallas TPU kernel: pool block migration (compaction / collapse copies).

Executes a host-planned move list (src row -> dst row) over the KV pool with
one grid step per move; the move list rides in scalar-prefetch memory and
steers both BlockSpec index maps.  The pool aliases in-place
(input_output_aliases), so on TPU this is NB-row HBM->HBM DMA traffic — the
device half of the paper's "compaction cost" term, and what khugepaged-style
collapse executes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(src_ref, dst_ref, in_ref, out_ref):
    out_ref[...] = in_ref[...]


def block_copy(pool, src, dst, *, interpret: bool = False):
    """pool: [NB, ...]; src/dst: [NM] int32. Returns the updated pool.

    Real plans always move into free rows; padding entries must be
    self-copies (src[i] == dst[i]), which are harmless.  One grid step
    copies one whole row: the block is (1, *pool.shape[1:]), so its two
    minor dims equal the array's, as the TPU lowering requires of a block
    not tiled by (8, 128); a 2-D pool gets a unit axis for that reason.
    """
    if pool.ndim == 2:
        return block_copy(pool[:, None], src, dst, interpret=interpret)[:, 0]
    NB, *rest = pool.shape
    NM = src.shape[0]
    tail = (0,) * len(rest)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NM,),
        in_specs=[
            pl.BlockSpec((1, *rest), lambda i, src_r, dst_r: (src_r[i], *tail)),
        ],
        out_specs=pl.BlockSpec((1, *rest),
                               lambda i, src_r, dst_r: (dst_r[i], *tail)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},    # pool (after the 2 scalar args) -> out
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(src, dst, pool)
