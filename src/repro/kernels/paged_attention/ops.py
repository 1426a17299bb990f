"""The kernel's work lists, derived on the device from a batch's block table.

An aligned run of 4^c logical blocks whose physical blocks are consecutive
and start on a multiple of 4^c is a page of order c, whatever mapping made
it: the memory manager's huge pages, order-0 blocks that happen to lie in
order, shared prefix-cache blocks.  ``page_work`` finds each live block's
largest such page, inside the jit that runs the decode step, from the
``[B, MB]`` table the step already holds.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...core.context import NUM_ORDERS
from .kernel import SLICE_KINDS, paged_decode_kernel

PAGE = 4 ** (NUM_ORDERS - 1)     # blocks in the largest page


class PageWork(NamedTuple):
    """Row-major work items of one decode call: item i reads 4**kind pool
    blocks as logical blocks [lblk, ...) of row ``row``; ``count[0]`` items
    are real, the rest are padding.  ``at[c]`` is, per item, the pool block
    (in units of 4**c blocks) that the kernel's kind-c buffer holds: the
    item's own where it is of kind c, else the last kind-c item's before it
    (the first one's before any), so that no block is copied in twice in a
    row.  ``reads`` counts the blocks read by the order of the page that
    holds them."""
    row: jax.Array
    lblk: jax.Array
    kind: jax.Array
    count: jax.Array
    at: jax.Array
    reads: jax.Array


def page_classes(block_table, first, end):
    """Order of the largest live page that holds each block, -1 where the
    block is not read.  Live blocks: mapped (>= 0) and in [first, end) of
    their row.  block_table: [B, MB]; first, end: [B]."""
    B, MB = block_table.shape
    MBp = -(-MB // PAGE) * PAGE
    tbl = jnp.pad(block_table, ((0, 0), (0, MBp - MB)), constant_values=-1)
    j = jnp.arange(MBp)[None, :]
    live = (tbl >= 0) & (j >= first[:, None]) & (j < end[:, None])
    tbl = jnp.where(live, tbl, -1)
    cls = jnp.where(live, 0, -1)
    for c in range(1, NUM_ORDERS):
        n = 4 ** c
        g = tbl.reshape(B, MBp // n, n)
        ok = (jnp.all(g >= 0, -1)
              & jnp.all(g == g[..., :1] + jnp.arange(n), -1)
              & (g[..., 0] % n == 0))
        ok = jnp.broadcast_to(ok[:, :, None], g.shape).reshape(B, MBp)
        cls = jnp.where(ok, c, cls)
    return cls[:, :MB]


def page_work(block_table, lengths, *, block_tokens: int,
              window: int | None = None) -> PageWork:
    """The pages a decode call reads.  lengths: [B] tokens valid, the current
    one included (0 for an inactive row); a windowed layer skips the blocks
    wholly before its window."""
    B, MB = block_table.shape
    bt = block_tokens
    end = (lengths + bt - 1) // bt
    first = jnp.zeros_like(lengths)
    if window is not None:
        first = jnp.maximum(lengths - window, 0) // bt
    cls = page_classes(block_table, first, end)
    # a page's items: itself, or its slices where it is larger than a slice
    kind = jnp.minimum(cls, SLICE_KINDS - 1)
    j = jnp.arange(MB)[None, :]
    start = ((cls >= 0) & (j % (4 ** jnp.maximum(kind, 0)) == 0)).reshape(-1)
    kind = kind.reshape(-1)
    # compaction by a sort, not a cumsum: the TPU compiles a sort of a few
    # hundred keys faster, and every table-install variant of the decode
    # step compiles this again
    W = B * MB
    pos = jnp.arange(W, dtype=jnp.int32)
    idx = jnp.sort(jnp.where(start, pos, W + pos)) % W
    count = jnp.sum(start, dtype=jnp.int32)
    phys, kind = block_table.reshape(-1)[idx], kind[idx]
    at = []
    for c in range(SLICE_KINDS):
        mine = (kind == c) & (pos < count)
        last = jnp.max(jnp.where(mine[None, :] & (pos[None, :] <= pos[:, None]),
                                 pos[None, :], -1), axis=1)
        src = jnp.where(last >= 0, last, jnp.argmax(mine))
        at.append(jnp.where(mine[src], phys[src] // 4 ** c, 0))
    reads = jnp.stack([jnp.sum(cls == c) for c in range(NUM_ORDERS)])
    return PageWork(row=idx // MB, lblk=idx % MB, kind=kind,
                    count=count[None], at=jnp.stack(at),
                    reads=reads.astype(jnp.int32))


def paged_decode_attention(q, pool_k, pool_v, block_table, lengths, *,
                           block_tokens: int, window: int | None = None,
                           work: PageWork | None = None,
                           interpret: bool = False):
    """Decode attention over the paged pool through the kernel: the gather
    path's contract (``models.decode.paged_decode_attention_gather``).
    q: [B,H,hd]; pools: [NB,bt,KVH,hd]; block_table: [B,MB] (-1 unmapped);
    lengths: [B] tokens INCLUDING the current one.  ``work`` may carry the
    lists ``page_work`` made for these arguments.
    Returns (out [B,H,hd] in q.dtype, heat [B,MB] f32)."""
    if work is None:
        work = page_work(block_table, lengths, block_tokens=block_tokens,
                         window=window)
    out, heat = paged_decode_kernel(
        q, pool_k, pool_v, work, lengths, block_tokens=block_tokens,
        max_blocks=block_table.shape[1], window=window, interpret=interpret)
    return out.astype(q.dtype), heat

