"""Distributed paged flash-decoding (the optimized serve path).

The gather baseline reads the whole KV through one global gather — on a
sharded pool GSPMD turns that into pool-sized collectives.  This module is
the beyond-paper fix: the pool's block dim shards over the whole mesh,
the engine round-robins each sequence's blocks across the owning shards,
and shard_map runs flash partials over SHARD-LOCAL blocks only; partials
combine with one tiny pmax/psum (flash-decoding algebra).  Per-chip HBM
traffic drops to KV_bytes / num_chips and the only cross-chip payload is
[B, H, hd]-sized — see EXPERIMENTS.md §Perf.

Two layouts:
  * batch_sharded=True  — B divides the data axis: batch over ("pod","data"),
    blocks over "model"; combine = psum over "model".  (decode_32k)
  * batch_sharded=False — small B (long-context): batch replicated, blocks
    over the WHOLE mesh; combine = psum over every axis.   (long_500k)

The per-shard inner loop is exactly the computation of the Pallas
paged-attention kernel; on TPU the jnp body below is swapped for the kernel
(same signature), which additionally coalesces multi-size pages into single
DMAs.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

F32 = jnp.float32
NEG_INF = -1e30

_DECODE_MESH: Mesh | None = None


def set_decode_mesh(mesh: Mesh | None) -> None:
    global _DECODE_MESH
    _DECODE_MESH = mesh


def get_decode_mesh() -> Mesh | None:
    return _DECODE_MESH


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _axis_index(names) -> jax.Array:
    if isinstance(names, str):
        return jax.lax.axis_index(names)
    idx = jax.lax.axis_index(names[0])
    for n in names[1:]:
        idx = idx * jax.lax.psum(1, n) + jax.lax.axis_index(n)
    return idx


def _partials(q_l, k, v, logical, ok, len_l, *, bt, window, soft_cap, KVH, G):
    """Shared inner flash-partial computation over local blocks."""
    Bl, MBl = logical.shape
    hd = q_l.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    k = k.reshape(Bl, MBl * bt, KVH, hd)
    v = v.reshape(Bl, MBl * bt, KVH, hd)
    qg = q_l.reshape(Bl, KVH, G, hd).astype(F32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(F32)) * scale
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    pos = (jnp.maximum(logical, 0)[:, :, None] * bt
           + jnp.arange(bt)[None, None, :]).reshape(Bl, MBl * bt)
    valid = jnp.repeat(ok, bt, axis=1) & (pos < len_l[:, None])
    if window is not None:
        valid &= pos > (len_l[:, None] - 1 - window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    m_loc = jnp.max(s, axis=-1)
    p = jnp.where(valid[:, None, None], jnp.exp(s - m_loc[..., None]), 0.0)
    l_loc = p.sum(-1)
    acc = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(F32))
    heat = p.sum(axis=(1, 2)).reshape(Bl, MBl, bt).sum(-1)
    return m_loc, l_loc, acc, heat


def _combine(m_loc, l_loc, acc, axes):
    m_g = m_loc
    for ax in axes:
        m_g = jax.lax.pmax(m_g, ax)
    corr = jnp.where(m_loc <= NEG_INF / 2, 0.0, jnp.exp(m_loc - m_g))
    l_g = jax.lax.psum(l_loc * corr, axes)
    acc_g = jax.lax.psum(acc * corr[..., None], axes)
    return acc_g / jnp.maximum(l_g, 1e-30)[..., None]


def paged_decode_attention_sharded(q, pool_k, pool_v, sharded_table,
                                   sharded_logical, lengths, *,
                                   block_tokens: int, window=None,
                                   soft_cap=None, batch_sharded: bool = True):
    """q: [B,H,hd]; pools: [NB,bt,KVH,hd] (NB mesh-sharded);
    sharded_table/logical: [B, NS, MBl] int32 — entry (b,s,:) lists the
    GLOBAL phys blocks of sequence b owned by shard s (-1 pads; the engine/
    placement policy guarantees locality); lengths: [B] incl. current token.

    batch_sharded: NS = model axis size, B sharded over data (+pod).
    else:          NS = total shards, B replicated, blocks over whole mesh.

    Returns (out [B,H,hd], heat [B, NS*MBl] f32)."""
    mesh = _DECODE_MESH
    if mesh is None:
        raise RuntimeError("set_decode_mesh() first (launch/serve does this)")
    names = mesh.axis_names
    data_axes = tuple(n for n in names if n != "model")       # ("pod","data")
    model_ax = "model"
    D = int(np.prod([mesh.shape[n] for n in data_axes]))
    M = mesh.shape[model_ax]
    NB = pool_k.shape[0]
    B, H, hd = q.shape
    KVH = pool_k.shape[2]
    G = H // KVH
    bt = block_tokens
    pool_spec = P((*data_axes, model_ax))
    NB_loc = NB // (D * M)

    if batch_sharded:
        def body(q_l, pk_l, pv_l, tbl_l, log_l, len_l):
            d = _axis_index(data_axes)
            m = jax.lax.axis_index(model_ax)
            offset = (d * M + m) * NB_loc
            tbl = tbl_l[:, 0, :]
            logical = log_l[:, 0, :]
            local = tbl - offset
            ok = (tbl >= 0) & (local >= 0) & (local < NB_loc)
            safe = jnp.clip(local, 0, NB_loc - 1)
            m_loc, l_loc, acc, heat = _partials(
                q_l, pk_l[safe], pv_l[safe], logical, ok, len_l,
                bt=bt, window=window, soft_cap=soft_cap, KVH=KVH, G=G)
            out = _combine(m_loc, l_loc, acc, (model_ax,))
            Bl = tbl.shape[0]
            return (out.reshape(Bl, H, hd).astype(q_l.dtype),
                    heat[:, None, :])

        fn = _shard_map(
            body, mesh,
            in_specs=(P(data_axes, None, None), pool_spec, pool_spec,
                      P(data_axes, model_ax, None),
                      P(data_axes, model_ax, None), P(data_axes)),
            out_specs=(P(data_axes, None, None),
                       P(data_axes, model_ax, None)))
    else:
        all_axes = tuple(names)

        def body(q_l, pk_l, pv_l, tbl_l, log_l, len_l):
            shard = _axis_index(all_axes)
            offset = shard * NB_loc
            tbl = tbl_l[:, 0, :]
            logical = log_l[:, 0, :]
            local = tbl - offset
            ok = (tbl >= 0) & (local >= 0) & (local < NB_loc)
            safe = jnp.clip(local, 0, NB_loc - 1)
            m_loc, l_loc, acc, heat = _partials(
                q_l, pk_l[safe], pv_l[safe], logical, ok, len_l,
                bt=bt, window=window, soft_cap=soft_cap, KVH=KVH, G=G)
            out = _combine(m_loc, l_loc, acc, all_axes)
            return (out.reshape(B, H, hd).astype(q_l.dtype),
                    heat[:, None, :])

        fn = _shard_map(
            body, mesh,
            in_specs=(P(None, None, None), pool_spec, pool_spec,
                      P(None, all_axes, None), P(None, all_axes, None),
                      P(None)),
            out_specs=(P(None, None, None), P(None, all_axes, None)))

    out, heat = fn(q, pool_k, pool_v, sharded_table, sharded_logical, lengths)
    return out, heat.reshape(B, -1)


def paged_mla_decode_sharded(q_eff, q_rope, pool_ckv, sharded_table,
                             sharded_logical, lengths, *, block_tokens: int,
                             kv_lora: int, qk_nope: int = 128,
                             batch_sharded: bool = True):
    """MLA absorbed decode over the mesh-sharded latent pool (flash-decoding
    over latent blocks; §Perf hillclimb #1).

    q_eff: [B,H,L] (q_nope @ w_uk); q_rope: [B,H,Dr];
    pool_ckv: [NB, bt, L+Dr] with NB sharded over the whole mesh;
    sharded_table/logical: [B, NS, MBl] as in the GQA path.
    Returns (o_lat [B,H,L] f32, heat [B, NS*MBl])."""
    mesh = _DECODE_MESH
    if mesh is None:
        raise RuntimeError("set_decode_mesh() first (launch/serve does this)")
    names = mesh.axis_names
    data_axes = tuple(n for n in names if n != "model")
    D = int(np.prod([mesh.shape[n] for n in data_axes]))
    M = mesh.shape["model"]
    NB = pool_ckv.shape[0]
    NB_loc = NB // (D * M)
    B, H, L = q_eff.shape
    bt = block_tokens
    scale = 1.0 / math.sqrt(qk_nope + q_rope.shape[-1])
    pool_spec = P((*data_axes, "model"))
    comb_axes = ("model",) if batch_sharded else tuple(names)
    if batch_sharded:
        q_spec = P(data_axes, None, None)
        tbl_spec = P(data_axes, "model", None)
        len_spec = P(data_axes)
    else:
        q_spec = P(None, None, None)
        tbl_spec = P(None, tuple(names), None)
        len_spec = P(None)

    def body(qe_l, qr_l, pool_l, tbl_l, log_l, len_l):
        if batch_sharded:
            d = _axis_index(data_axes)
            m = jax.lax.axis_index("model")
            shard = d * M + m
        else:
            shard = _axis_index(tuple(names))
        offset = shard * NB_loc
        tbl = tbl_l[:, 0, :]
        logical = log_l[:, 0, :]
        local = tbl - offset
        ok = (tbl >= 0) & (local >= 0) & (local < NB_loc)
        safe = jnp.clip(local, 0, NB_loc - 1)
        lat = pool_l[safe]                           # [Bl, MBl, bt, L+Dr]
        Bl, MBl = tbl.shape
        lat = lat.reshape(Bl, MBl * bt, -1)
        ckv, kr = lat[..., :kv_lora], lat[..., kv_lora:]
        s = (jnp.einsum("bhl,bsl->bhs", qe_l.astype(F32), ckv.astype(F32))
             + jnp.einsum("bhr,bsr->bhs", qr_l.astype(F32), kr.astype(F32)))
        s = s * scale
        pos = (jnp.maximum(logical, 0)[:, :, None] * bt
               + jnp.arange(bt)[None, None, :]).reshape(Bl, MBl * bt)
        valid = jnp.repeat(ok, bt, axis=1) & (pos < len_l[:, None])
        s = jnp.where(valid[:, None], s, NEG_INF)
        m_loc = jnp.max(s, axis=-1)                  # [Bl,H]
        p = jnp.where(valid[:, None], jnp.exp(s - m_loc[..., None]), 0.0)
        l_loc = p.sum(-1)
        acc = jnp.einsum("bhs,bsl->bhl", p, ckv.astype(F32))
        heat = p.sum(axis=1).reshape(Bl, MBl, bt).sum(-1)
        m_g = m_loc
        for ax in comb_axes:
            m_g = jax.lax.pmax(m_g, ax)
        corr = jnp.where(m_loc <= NEG_INF / 2, 0.0, jnp.exp(m_loc - m_g))
        l_g = jax.lax.psum(l_loc * corr, comb_axes)
        acc_g = jax.lax.psum(acc * corr[..., None], comb_axes)
        o_lat = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        return o_lat, heat[:, None, :]

    fn = _shard_map(
        body, mesh,
        in_specs=(q_spec, q_spec, pool_spec, tbl_spec, tbl_spec, len_spec),
        out_specs=(q_spec, tbl_spec))
    o_lat, heat = fn(q_eff, q_rope, pool_ckv, sharded_table, sharded_logical,
                     lengths)
    return o_lat, heat.reshape(B, -1)
