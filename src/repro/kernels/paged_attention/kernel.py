"""Pallas TPU kernel: decode attention that reads only a batch's live KV pages.

One call serves every row of the batch for one layer.  The caller
(``ops.page_work``) lists, row by row, the pages that hold the rows' valid
tokens.  A page of order c is an aligned run of 4^c logical blocks whose
physical blocks are consecutive and start on a multiple of 4^c (the buddy
allocator's page), so its K and V each arrive in ONE contiguous DMA; a
64-block page streams as four contiguous 16-block slices, which bounds the
VMEM a block buffer takes.  The grid has one step per item of the list (its
length is a scalar the call reads), so unmapped blocks, blocks past a row's
length and inactive rows cost nothing, and the kernel's cost grows with the
pages it reads, not with ``max_blocks``.  Each item size (1, 4, 16 blocks)
has its own BlockSpec, steered by the scalar-prefetched list; the pipeline
copies the next item's K and V while this one computes.

Per row the flash state (m, l, acc) lives in VMEM scratch.  K and V come in
the pool's dtype and are computed with in f32 on the VPU, in the layout the
pool holds them, and the softmax is exact.  The heat (softmax mass per
logical block, summed over heads: the DAMON signal) is kept per (head,
block) in the frame of the row's running max, rescaled with ``acc``, and
divided by the final sum at the end, so it equals the mass a full softmax
gives.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
SLICE_KINDS = 3              # a work item moves 1, 4 or 16 blocks


def _kernel(row_ref, lblk_ref, kind_ref, n_ref, len_ref, *refs,
            block_tokens: int, window: int | None):
    q_ref, *kv_refs, o_ref, heat_ref, m_sc, l_sc, acc_sc, hp_sc = \
        refs[SLICE_KINDS:]        # the first refs feed the index maps alone
    bt = block_tokens
    G, KVH, hd = q_ref.shape[1:]
    MBp = hp_sc.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)
        hp_sc[...] = jnp.zeros_like(hp_sc)

    def block(k_ref, v_ref, r, first, j):
        # one block of the item: [bt, KVH, hd] as the pool holds it.  A
        # score is a product summed over the lanes, and P.V a
        # lane-broadcast weight summed over the tokens, so neither needs a
        # transpose
        k = k_ref[j].astype(F32)
        v = v_ref[j].astype(F32)
        length = len_ref[r]
        pos = (first + j) * bt + jax.lax.broadcasted_iota(
            jnp.int32, (bt, 1, 1), 0)
        valid = pos < length
        if window is not None:
            valid &= pos > (length - 1 - window)
        here = jax.lax.broadcasted_iota(jnp.int32, (1, MBp), 1) == first + j
        for g in range(G):
            q = q_ref[r, g].astype(F32)                       # [KVH, hd]
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            s = jnp.where(valid, s, NEG_INF)                  # [bt, KVH, 1]
            m_prev = m_sc[r, g]                               # [KVH, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.where(valid, jnp.exp(s - m_new[None]), 0.0)
            corr = jnp.exp(m_prev - m_new)
            mass = jnp.sum(p, axis=0)                         # [KVH, 1]
            m_sc[r, g] = m_new
            l_sc[r, g] = l_sc[r, g] * corr + mass
            acc_sc[r, g] = acc_sc[r, g] * corr + jnp.sum(p * v, axis=0)
            # the block's mass per head, in its column of the row's heat
            hp_sc[r, g] = hp_sc[r, g] * corr + jnp.where(here, mass, 0.0)

    def compute(k_ref, v_ref):
        r, first = row_ref[i], lblk_ref[i]

        def body(j, carry):
            block(k_ref, v_ref, r, first, j)
            return carry

        jax.lax.fori_loop(0, k_ref.shape[0], body, 0)

    live = i < n_ref[0]
    kind = kind_ref[i]
    for c in range(SLICE_KINDS):
        pl.when(live & (kind == c))(
            functools.partial(compute, *kv_refs[2 * c:2 * c + 2]))

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        l = l_sc[...]
        inv = jnp.where(l > 0, 1.0 / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[...] = acc_sc[...] * inv
        heat_ref[...] = jnp.sum(jnp.sum(hp_sc[...] * inv, axis=1), axis=1)


def _padded_bytes(shape, dtype) -> int:
    """VMEM bytes of an array whose two minor dims are tiled."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    *major, a, b = shape
    return (math.prod(major) * (-(-a // sub) * sub) * (-(-b // 128) * 128)
            * item)


def paged_decode_kernel(q, pool_k, pool_v, work, lengths, *,
                        block_tokens: int, max_blocks: int,
                        window: int | None = None, interpret: bool = False):
    """q: [B,H,hd]; pools: [NB,bt,KVH,hd]; ``work``: the ``ops.PageWork``
    lists (row, lblk, kind [W] int32; count [1]; at [SLICE_KINDS, W]);
    lengths: [B] tokens valid, the current one included.

    One grid step per work item (at least one): the step's K and V blocks
    of 4**kind pool blocks come in through the BlockSpec of its kind, and
    the pipeline copies the next item's while this one computes.

    Returns (out [B,H,hd] f32, heat [B,max_blocks] f32): rows with nothing
    to read get zeros."""
    B, H, hd = q.shape
    NB, bt, KVH, _ = pool_k.shape
    assert bt == block_tokens
    G = H // KVH
    MBp = -(-max_blocks // 128) * 128
    kern = functools.partial(_kernel, block_tokens=bt, window=window)
    state = (B, G, KVH)
    whole = lambda i, *refs: (0,) * 4                      # noqa: E731
    kv_specs, vmem = [], 0
    for c in range(SLICE_KINDS):
        blk = (4 ** c, bt, KVH, hd)
        at = lambda i, *refs, c=c: (refs[5 + c][i], 0, 0, 0)   # noqa: E731
        kv_specs += [pl.BlockSpec(blk, at), pl.BlockSpec(blk, at)]
        vmem += 4 * _padded_bytes(blk, pool_k.dtype)      # K, V; two buffers
    # a block's K and V in f32 and their products with q and p
    vmem += (4 * _padded_bytes((bt, KVH, hd), F32)
             + 2 * _padded_bytes((B, G, KVH, hd), q.dtype)
             + 4 * _padded_bytes(state + (hd,), F32)
             + 2 * _padded_bytes(state + (MBp,), F32)
             + 8 * _padded_bytes((bt, KVH, 1), F32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + SLICE_KINDS,
        grid=(jnp.maximum(work.count[0], 1),),
        in_specs=[pl.BlockSpec((B, G, KVH, hd), whole)] + kv_specs,
        out_specs=[
            pl.BlockSpec((B, G, KVH, hd), whole),
            pl.BlockSpec((B, MBp), lambda i, *refs: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM(state + (1,), F32),
                        pltpu.VMEM(state + (1,), F32),
                        pltpu.VMEM(state + (hd,), F32),
                        pltpu.VMEM(state + (MBp,), F32)],
    )
    out, heat = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, G, KVH, hd), F32),
                   jax.ShapeDtypeStruct((B, MBp), F32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (8 << 20)),
    )(work.row, work.lblk, work.kind, work.count, lengths,
      *work.at,
      q.reshape(B, KVH, G, hd).transpose(0, 2, 1, 3),
      *([pool_k, pool_v] * SLICE_KINDS))
    out = out.transpose(0, 2, 1, 3)
    return out.reshape(B, H, hd), heat[:, :max_blocks]
