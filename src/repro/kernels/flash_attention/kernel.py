"""Pallas TPU kernel: causal/windowed GQA flash attention (forward).

Grid (B, H, nQ, nK), nK innermost; flash state (m, l, unnormalized acc) lives
in revisited output blocks; the final nK step normalizes.  Causal and
out-of-window K blocks are skipped entirely (the flash block-skip), so local
attention layers (gemma-3's 5:1 pattern) only pay for the window.

Block sizes default to (bq, bk) = (256, 256) with hd padded by Pallas to lane
width; MXU work per step is [bq, hd] x [hd, bk] -> [bq, bk].
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
            bq: int, bk: int, nk: int, head_dim: int, causal: bool,
            window: int | None, soft_cap: float | None, seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    scale = 1.0 / math.sqrt(head_dim)

    @pl.when(ik == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk
    # static-shape block skip predicates (traced on grid indices)
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window is not None:
        relevant &= k_start + bk - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(F32) * scale                   # [bq, hd]
        k = k_ref[0, 0].astype(F32)                            # [bk, hd]
        v = v_ref[0, 0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)    # [bq, bk]
        if soft_cap is not None:
            s = soft_cap * jnp.tanh(s / soft_cap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_k
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[0, 0]                                   # [bq]
        l_prev = l_ref[0, 0]
        acc_prev = o_ref[0, 0].astype(F32)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
        l_new = l_prev * corr + p.sum(-1)
        acc_new = acc_prev * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        m_ref[0, 0] = m_new
        l_ref[0, 0] = l_new
        o_ref[0, 0] = acc_new.astype(o_ref.dtype)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[0, 0]
        o_ref[0, 0] = (o_ref[0, 0].astype(F32)
                       / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: int | None = None,
                        soft_cap: float | None = None,
                        bq: int = 256, bk: int = 256,
                        interpret: bool = False):
    """q: [B,Sq,H,hd]; k,v: [B,Sk,KVH,hd] -> [B,Sq,H,hd]."""
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    # layout: head-major for clean 2D blocks
    qt = q.transpose(0, 2, 1, 3)                  # [B,H,Sq,hd]
    kt = k.transpose(0, 2, 1, 3)                  # [B,KVH,Sk,hd]
    vt = v.transpose(0, 2, 1, 3)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = qt.shape[2] // bq
    nk = kt.shape[2] // bk

    kern = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, head_dim=hd,
                             causal=causal, window=window, soft_cap=soft_cap,
                             seq_k=Sk)
    out, m, l = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nq * bq, hd), F32),
            jax.ShapeDtypeStruct((B, H, nq * bq), F32),
            jax.ShapeDtypeStruct((B, H, nq * bq), F32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qt, kt, vt)
    out = out[:, :, :Sq].transpose(0, 2, 1, 3)
    return out.astype(q.dtype)
