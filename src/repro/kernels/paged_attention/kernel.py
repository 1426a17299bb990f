"""Pallas TPU kernel: multi-size paged flash-decoding, one size class per call.

Grid: (B, MP) — one program per (sequence, class-page).  The page table and
logical indices ride in scalar-prefetch memory so the BlockSpec index map can
steer the K/V DMA straight at the page's pool rows: a class-c page is
4^c buddy-ALIGNED consecutive pool blocks, so its whole K/V arrives in ONE
contiguous VMEM copy of (4^c * block_tokens) tokens.  This is the TPU-native
payoff of the paper's huge pages: one descriptor + one large contiguous DMA
per page instead of 4^c small ones (cf. TLB reach), and per-page transfer
size is what drives effective HBM bandwidth.

Flash state (m, l, acc) lives in revisited output blocks (index maps constant
in j, the innermost grid dim), initialized at j == 0 — the standard Pallas
reduction pattern.  The kernel also emits per-page attention mass ("heat"),
the DAMON signal; heat is normalized against the RUNNING max at visit time
(exact mass needs a second pass; DAMON only consumes relative heat — see
ref.paged_class_heat_running_ref which mirrors this semantics exactly).
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


def _kernel(table_ref, logical_ref, len_ref,      # scalar prefetch
            q_ref, k_ref, v_ref,                  # VMEM inputs
            acc_ref, m_ref, l_ref, heat_ref,      # VMEM outputs (revisited)
            *, page_blocks: int, block_tokens: int, kv_heads: int,
            head_dim: int, window: int | None):
    b = pl.program_id(0)
    j = pl.program_id(1)
    pt = page_blocks * block_tokens
    scale = 1.0 / math.sqrt(head_dim)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    page_ok = table_ref[b, j] >= 0

    @pl.when(page_ok)
    def _compute():
        # the dots take K/V in the pool's dtype with f32 accumulation: an
        # f32 copy of a large page's K/V would not fit the scoped VMEM
        k = k_ref[...].reshape(pt, kv_heads, head_dim)
        v = v_ref[...].reshape(pt, kv_heads, head_dim)
        q = (q_ref[0].astype(F32) * scale).astype(k.dtype)   # [KVH, G, hd]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=F32)                      # [KVH, G, pt]

        length = len_ref[b]
        pos = logical_ref[b, j] * pt + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, pt), 2)
        valid = pos < length
        if window is not None:
            valid &= pos > (length - 1 - window)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[0]                                    # [KVH, G]
        l_prev = l_ref[0]
        acc_prev = acc_ref[0]                                # [KVH, G, hd]

        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                         jnp.exp(m_prev - m_new))
        l_new = l_prev * corr + p.sum(-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=F32)                      # [KVH, G, hd]

        acc_ref[0] = acc_prev * corr[..., None] + pv
        m_ref[0] = m_new
        l_ref[0] = l_new
        heat_ref[...] = jnp.full(heat_ref.shape, p.sum(), F32)

    @pl.when(jnp.logical_not(page_ok))
    def _skip():
        heat_ref[...] = jnp.zeros(heat_ref.shape, F32)


def paged_class_partials(q, pool_k, pool_v, page_table, logical_idx, lengths,
                         *, page_blocks: int, block_tokens: int,
                         window: int | None = None, interpret: bool = False,
                         active=None):
    """One size class. q: [B,H,hd]; pools: [NB,bt,KVH,hd];
    page_table/logical_idx: [B,MP] int32 (phys start block / logical page,
    -1 = pad); lengths: [B] int32.

    ``active`` ([B] bool, optional) masks out whole lanes — an inactive lane
    is exactly "every page invalid", so it folds into the existing per-page
    ``page_ok`` gate by blanking the lane's table row before prefetch; the
    kernel body and its scalar-prefetch arity are unchanged (no recompile
    churn against cached executables).

    Returns (acc [B,H,hd] f32, m [B,H] f32, l [B,H] f32, heat [B,MP] f32).
    """
    B, H, hd = q.shape
    NB, bt, KVH, _ = pool_k.shape
    MP = page_table.shape[1]
    assert bt == block_tokens
    if active is not None:
        page_table = jnp.where(active[:, None], page_table,
                               jnp.asarray(-1, page_table.dtype))

    kern = functools.partial(
        _kernel, page_blocks=page_blocks, block_tokens=block_tokens,
        kv_heads=KVH, head_dim=hd, window=window)

    def pool_index(b, j, tbl, logical, lens):
        return (jnp.maximum(tbl[b, j], 0) // page_blocks, 0, 0, 0)

    # Every block's two minor dims equal the array's (the TPU lowering's
    # rule for blocks not tiled by (8, 128)): the flash state is laid out
    # [B, KVH, G(, hd)] so that one lane's block is the whole minor slab,
    # and heat is [B, MP, 1, 1] so that each page owns a (1, 1) block.
    G = H // KVH
    state = lambda b, j, *refs: (b, 0, 0, 0)                   # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, MP),
        in_specs=[
            pl.BlockSpec((1, KVH, G, hd), state),
            pl.BlockSpec((page_blocks, bt, KVH, hd), pool_index),
            pl.BlockSpec((page_blocks, bt, KVH, hd), pool_index),
        ],
        out_specs=[
            pl.BlockSpec((1, KVH, G, hd), state),
            pl.BlockSpec((1, KVH, G), lambda b, j, *refs: (b, 0, 0)),
            pl.BlockSpec((1, KVH, G), lambda b, j, *refs: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, j, *refs: (b, j, 0, 0)),
        ],
    )
    out_shapes = [
        jax.ShapeDtypeStruct((B, KVH, G, hd), F32),
        jax.ShapeDtypeStruct((B, KVH, G), F32),
        jax.ShapeDtypeStruct((B, KVH, G), F32),
        jax.ShapeDtypeStruct((B, MP, 1, 1), F32),
    ]
    acc, m, l, heat = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(page_table, logical_idx, lengths, q.reshape(B, KVH, G, hd), pool_k,
      pool_v)
    return (acc.reshape(B, H, hd), m.reshape(B, H), l.reshape(B, H),
            heat.reshape(B, MP))
