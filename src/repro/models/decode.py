"""Serving-side model execution: paged-KV prefill and single-token decode.

This is where the paper's technique meets the device: the KV cache lives in a
multi-size-paged HBM pool owned by repro.core.MemoryManager.  Every attention
layer reads KV through the block table (the page-table analogue) and emits
per-block attention mass — the DAMON heat signal that drives promotion
decisions.

Three attention backends:
  * "gather"      — reference/jnp path: gather every table block then dense
                    attention.  Used by the CPU engine, by configs the kernel
                    does not cover, and as the dry-run BASELINE (its lowering
                    shows the collective cost of naive paged reads on a
                    sharded pool — see EXPERIMENTS.md §Perf).
  * "paged"       — the Pallas kernel (kernels/paged_attention): reads only
                    the live pages, one contiguous DMA per buddy-aligned page
                    (a 64-block page in four 16-block slices), K/V read in
                    the pool's dtype.
                    The engine's decode step on a TPU.
  * "flashdecode" — shard_map flash-decoding over the ("data","model")-sharded
                    pool with shard-local block lists.

Cache layout (pytree mirroring the block segmentation of transformer.py):
  attn (GQA) : {"pool_k","pool_v"}: [NB, bt, KVH, hd]  (stacked [reps,...] in scans)
  MLA        : {"pool_ckv"}: [NB, bt, kv_lora + qk_rope]
  mamba      : {"ssm","conv"} per mamba_state_init
  whisper dec: adds {"xk","xv"}: [B, F, KVH, hd] static cross-attn cache
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.context import NUM_ORDERS
from ..kernels.paged_attention.ops import page_work, paged_decode_attention
from .attention import flash_attention
from .common import BF16, F32, apply_mrope, apply_rope, pad_vocab, rms_norm, \
    sinusoidal_position_at, sinusoidal_positions
from .mamba2 import mamba_apply, mamba_decode_step, mamba_state_init
from .transformer import (LayerPlan, _apply_norm, _attn_forward, _mlp_forward,
                          _project_qkv, _xattn_forward, build_layer_plans,
                          build_segments, encoder_forward, layer_forward)
from .moe import moe_apply

Pytree = Any
NEG_INF = -1e30


@dataclass(frozen=True)
class PagedLayout:
    """Static paged-KV geometry for one served batch."""
    num_blocks: int          # NB: physical pool blocks (global)
    block_tokens: int        # bt
    max_blocks: int          # MB: per-sequence block-table length

    @property
    def max_seq(self) -> int:
        return self.max_blocks * self.block_tokens


# ---------------------------------------------------------------------------
# Cache construction (abstract for the dry-run, concrete for the engine)
# ---------------------------------------------------------------------------

def _attn_cache_shape(cfg: ModelConfig, layout: PagedLayout) -> dict:
    if cfg.mla is not None:
        m = cfg.mla
        return {"pool_ckv": (layout.num_blocks, layout.block_tokens,
                             m.kv_lora + m.qk_rope)}
    return {
        "pool_k": (layout.num_blocks, layout.block_tokens, cfg.kv_heads, cfg.head_dim),
        "pool_v": (layout.num_blocks, layout.block_tokens, cfg.kv_heads, cfg.head_dim),
    }


def cache_spec(cfg: ModelConfig, layout: PagedLayout, batch: int,
               dtype=BF16) -> Pytree:
    """ShapeDtypeStruct pytree of the serving cache, segment-structured."""
    def leaf(shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    def layer_cache(plan: LayerPlan) -> dict:
        if plan.kind == "mamba":
            di = cfg.mamba.expand * cfg.d_model
            H = di // cfg.mamba.head_dim
            c = {
                "ssm": leaf((batch, H, cfg.mamba.d_state, cfg.mamba.head_dim)),
                "conv": leaf((batch, cfg.mamba.conv_dim - 1,
                              di + 2 * cfg.mamba.d_state)),
            }
        else:
            c = {k: leaf(s) for k, s in _attn_cache_shape(cfg, layout).items()}
        if plan.xattn:
            c["xk"] = leaf((batch, cfg.enc_frames, cfg.kv_heads, cfg.head_dim))
            c["xv"] = leaf((batch, cfg.enc_frames, cfg.kv_heads, cfg.head_dim))
        return c

    segs = build_segments(build_layer_plans(cfg, decoder=True))
    out: dict = {}
    for si, seg in enumerate(segs):
        if seg[0] == "plain":
            out[f"p{si}"] = layer_cache(seg[1])
        else:
            _, cycle, reps = seg
            member = {f"m{j}": layer_cache(pl) for j, pl in enumerate(cycle)}
            out[f"s{si}"] = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct((reps,) + l.shape, l.dtype),
                member)
    return out


def cache_init(cfg: ModelConfig, layout: PagedLayout, batch: int,
               dtype=BF16) -> Pytree:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, layout, batch, dtype))


# ---------------------------------------------------------------------------
# Paged attention backends (decode)
# ---------------------------------------------------------------------------

def paged_decode_attention_gather(q, pool_k, pool_v, block_table, lengths, *,
                                  block_tokens: int, window=None,
                                  soft_cap=None):
    """Reference paged decode. q: [B,H,hd]; pools: [NB,bt,KVH,hd];
    block_table: [B,MB] (-1 = unmapped); lengths: [B] (tokens INCLUDING the
    current one).  Returns (out [B,H,hd], heat [B,MB])."""
    B, H, hd = q.shape
    MB = block_table.shape[1]
    KVH = pool_k.shape[2]
    G = H // KVH
    bt = block_tokens
    scale = 1.0 / math.sqrt(hd)
    safe_bt = jnp.maximum(block_table, 0)
    k = pool_k[safe_bt].reshape(B, MB * bt, KVH, hd)
    v = pool_v[safe_bt].reshape(B, MB * bt, KVH, hd)
    qg = q.reshape(B, KVH, G, hd).astype(F32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(F32)) * scale
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    pos = jnp.arange(MB * bt)[None, :]
    valid = (pos < lengths[:, None]) & jnp.repeat(block_table >= 0, bt, axis=1)
    if window is not None:
        valid &= pos > (lengths[:, None] - 1 - window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, None], p, 0.0)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(F32))
    heat = p.sum(axis=(1, 2)).reshape(B, MB, bt).sum(-1)      # attention mass/block
    return out.reshape(B, H, hd).astype(q.dtype), heat


def paged_decode_attention_mla_gather(q_eff, q_rope, pool_ckv, block_table,
                                      lengths, *, block_tokens: int,
                                      kv_lora: int, qk_nope: int = 128):
    """MLA absorbed decode over the paged latent cache.
    q_eff: [B,H,L] (q_nope @ w_uk); q_rope: [B,H,Dr];
    pool_ckv: [NB,bt,L+Dr]. Returns (o_lat [B,H,L], heat [B,MB])."""
    B, H, L = q_eff.shape
    MB = block_table.shape[1]
    bt = block_tokens
    safe_bt = jnp.maximum(block_table, 0)
    lat = pool_ckv[safe_bt].reshape(B, MB * bt, -1)
    ckv, kr = lat[..., :kv_lora], lat[..., kv_lora:]
    scale = 1.0 / math.sqrt(qk_nope + q_rope.shape[-1])
    s = (jnp.einsum("bhl,bsl->bhs", q_eff.astype(F32), ckv.astype(F32))
         + jnp.einsum("bhr,bsr->bhs", q_rope.astype(F32), kr.astype(F32))) * scale
    pos = jnp.arange(MB * bt)[None, :]
    valid = (pos < lengths[:, None]) & jnp.repeat(block_table >= 0, bt, axis=1)
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None], p, 0.0)
    o_lat = jnp.einsum("bhs,bsl->bhl", p, ckv.astype(F32))
    heat = p.sum(axis=1).reshape(B, MB, bt).sum(-1)
    return o_lat, heat


def paged_kernel_supported(cfg: ModelConfig) -> bool:
    """Whether every attention layer of ``cfg`` is one the paged kernel
    computes: plain GQA/MHA, windowed or not, with no logit soft cap (and
    the config has an attention layer)."""
    return (cfg.mla is None and cfg.attn.logit_soft_cap is None
            and "a" in cfg.layer_kinds())


def _attn_windows(cfg: ModelConfig) -> dict:
    """window (None = global) -> number of decoder layers attending with it."""
    out: dict = {}
    for plan in build_layer_plans(cfg, decoder=True):
        if plan.kind != "mamba":
            w = cfg.attn.window if plan.local else None
            out[w] = out.get(w, 0) + 1
    return out


def _mask_inactive(block_table, lengths, active):
    """Inactive rows see an all ``-1`` table and length 0."""
    if active is None:
        return block_table, lengths
    return (jnp.where(active[:, None], block_table,
                      jnp.asarray(-1, block_table.dtype)),
            jnp.where(active, lengths, jnp.asarray(0, lengths.dtype)))


def decode_page_work(cfg: ModelConfig, block_table, lengths,
                     layout: PagedLayout, active=None) -> dict:
    """The paged kernel's work lists for one decode step, per attention
    window (``{window: PageWork}``); arguments as ``decode_step``'s."""
    block_table, lengths = _mask_inactive(block_table, lengths, active)
    return {w: page_work(block_table, lengths + 1,
                         block_tokens=layout.block_tokens, window=w)
            for w in _attn_windows(cfg)}


def page_reads(cfg: ModelConfig, works: dict) -> jax.Array:
    """[NUM_ORDERS] int32: K/V blocks the step's kernel calls read, by the
    order of the page that holds them, over every attention layer."""
    total = jnp.zeros((NUM_ORDERS,), jnp.int32)
    for w, n in _attn_windows(cfg).items():
        total = total + n * works[w].reads
    return total


# ---------------------------------------------------------------------------
# KV pool writes
# ---------------------------------------------------------------------------

def write_token_kv(pool, new_kv, block_table, lengths, *, block_tokens: int):
    """Scatter one token's KV into the pool.
    pool: [NB,bt,...]; new_kv: [B,...]; lengths: position of the new token.
    Rows whose target block is unmapped (table = -1: empty batch slots,
    sequences skipped this step) are dropped, like write_prefill_kv."""
    B = new_kv.shape[0]
    blk = lengths // block_tokens
    off = lengths % block_tokens
    raw = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
    # route unmapped rows out of bounds and drop them: a write-back of the
    # stale value would race a live row scattering to the same (block, off)
    safe = jnp.where(raw >= 0, raw, pool.shape[0])
    return pool.at[safe, off].set(new_kv.astype(pool.dtype), mode="drop")


def write_prefill_kv(pool, kv_seq, block_table, *, block_tokens: int):
    """Scatter a full prefill's KV. kv_seq: [B,S,...]; S % bt == 0 assumed
    (engine pads).  Blocks with table = -1 are dropped to a scratch row."""
    B, S = kv_seq.shape[:2]
    bt = block_tokens
    nb = S // bt
    kvb = kv_seq.reshape((B * nb, bt) + kv_seq.shape[2:])
    tbl = block_table[:, :nb].reshape(-1)
    # out-of-bounds + mode='drop' instead of a masked write-back, which
    # would race a live row scattering to the same block
    safe = jnp.where(tbl >= 0, tbl, pool.shape[0])
    return pool.at[safe].set(kvb.astype(pool.dtype), mode="drop")


# ---------------------------------------------------------------------------
# Decode step (single token for the whole batch)
# ---------------------------------------------------------------------------

def _decode_attn_layer(cfg: ModelConfig, plan: LayerPlan, p: Pytree,
                       cache: Pytree, x: jax.Array, lengths: jax.Array,
                       block_table: jax.Array, layout: PagedLayout,
                       pos3d=None, attn_impl: str = "gather",
                       sharded_table=None, sharded_logical=None,
                       works=None):
    """x: [B,d] -> (out [B,d], new cache, heat [B,MB])."""
    B, d = x.shape
    H, KVH, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    bt = layout.block_tokens
    window = cfg.attn.window if plan.local else None
    positions = lengths.astype(F32)[:, None]                  # [B,1]

    if cfg.mla is not None:
        m = cfg.mla
        q = (x @ p["wq"].astype(x.dtype)).reshape(B, H, m.qk_nope + m.qk_rope)
        q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
        dkv = x @ p["w_dkv"].astype(x.dtype)
        c_kv = rms_norm(dkv[..., :m.kv_lora], p["kv_norm"])
        k_rope = dkv[..., m.kv_lora:]
        q_rope = apply_rope(q_rope[:, None], positions,
                            theta=cfg.attn.rope_theta)[:, 0]
        k_rope = apply_rope(k_rope[:, None, None], positions,
                            theta=cfg.attn.rope_theta)[:, 0, 0]
        new_lat = jnp.concatenate([c_kv, k_rope], axis=-1)
        pool = write_token_kv(cache["pool_ckv"], new_lat, block_table, lengths,
                              block_tokens=bt)
        q_eff = jnp.einsum("bhd,hld->bhl", q_nope.astype(F32),
                           p["w_uk"].astype(F32))
        if attn_impl.startswith("flashdecode"):
            from ..distributed.flashdecode import paged_mla_decode_sharded
            o_lat, heat = paged_mla_decode_sharded(
                q_eff, q_rope, pool, sharded_table, sharded_logical,
                lengths + 1, block_tokens=bt, kv_lora=m.kv_lora,
                qk_nope=m.qk_nope,
                batch_sharded=not attn_impl.endswith("blocksharded"))
        else:
            o_lat, heat = paged_decode_attention_mla_gather(
                q_eff, q_rope, pool, block_table, lengths + 1,
                block_tokens=bt, kv_lora=m.kv_lora, qk_nope=m.qk_nope)
        out = jnp.einsum("bhl,hld->bhd", o_lat, p["w_uv"].astype(F32))
        out = out.reshape(B, H * m.v_head).astype(x.dtype)
        return out @ p["wo"].astype(x.dtype), {"pool_ckv": pool}, heat

    q = (x @ p["wq"].astype(x.dtype)).reshape(B, H, hd)
    k_new = (x @ p["wk"].astype(x.dtype)).reshape(B, KVH, hd)
    v_new = (x @ p["wv"].astype(x.dtype)).reshape(B, KVH, hd)
    if cfg.attn.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k_new = rms_norm(k_new, p["k_norm"])
    if cfg.attn.mrope_sections is not None:
        q = apply_mrope(q[:, None], pos3d, cfg.attn.mrope_sections,
                        theta=cfg.attn.rope_theta)[:, 0]
        k_new = apply_mrope(k_new[:, None], pos3d, cfg.attn.mrope_sections,
                            theta=cfg.attn.rope_theta)[:, 0]
    elif cfg.attn.use_rope:
        q = apply_rope(q[:, None], positions, theta=cfg.attn.rope_theta)[:, 0]
        k_new = apply_rope(k_new[:, None], positions,
                           theta=cfg.attn.rope_theta)[:, 0]
    pool_k = write_token_kv(cache["pool_k"], k_new, block_table, lengths,
                            block_tokens=bt)
    pool_v = write_token_kv(cache["pool_v"], v_new, block_table, lengths,
                            block_tokens=bt)
    if attn_impl.startswith("flashdecode"):
        from ..distributed.flashdecode import paged_decode_attention_sharded
        out, heat = paged_decode_attention_sharded(
            q, pool_k, pool_v, sharded_table, sharded_logical, lengths + 1,
            block_tokens=bt, window=window, soft_cap=cfg.attn.logit_soft_cap,
            batch_sharded=not attn_impl.endswith("blocksharded"))
    elif attn_impl == "paged":
        out, heat = paged_decode_attention(
            q, pool_k, pool_v, block_table, lengths + 1, block_tokens=bt,
            window=window, work=works[window])
    else:
        out, heat = paged_decode_attention_gather(
            q, pool_k, pool_v, block_table, lengths + 1,
            block_tokens=bt, window=window, soft_cap=cfg.attn.logit_soft_cap)
    out = out.reshape(B, H * hd)
    new_cache = {"pool_k": pool_k, "pool_v": pool_v}
    return out @ p["wo"].astype(x.dtype), new_cache, heat


def _decode_layer(cfg, plan, p, cache, x, lengths, block_table, layout,
                  pos3d=None, attn_impl="gather", sharded_table=None,
                  sharded_logical=None, works=None):
    h = _apply_norm(cfg, p["ln1"], x)
    heat = jnp.zeros((x.shape[0], layout.max_blocks), F32)
    new_cache = dict(cache)
    if plan.kind == "mamba":
        y, st = mamba_decode_step(p["mamba"], h, cache, cfg.mamba)
        x = x + y
        new_cache.update(st)
    else:
        y, st, heat = _decode_attn_layer(cfg, plan, p["attn"], cache, h, lengths,
                                         block_table, layout, pos3d, attn_impl,
                                         sharded_table, sharded_logical, works)
        x = x + y
        new_cache.update(st)
    if plan.xattn:
        hx = _apply_norm(cfg, p["lnx"], x)
        q = (hx @ p["xattn"]["wq"].astype(x.dtype)).reshape(
            x.shape[0], cfg.n_heads, cfg.head_dim)
        from .attention import decode_attention_dense
        xo = decode_attention_dense(
            q, cache["xk"], cache["xv"],
            jnp.full((x.shape[0],), cache["xk"].shape[1], jnp.int32))
        x = x + xo.reshape(x.shape[0], -1) @ p["xattn"]["wo"].astype(x.dtype)
    if plan.ffn:
        h2 = _apply_norm(cfg, p["ln2"], x)
        if plan.moe:
            y, _ = moe_apply(p["moe"], h2, cfg.moe, cfg.mlp)
            x = x + y
        else:
            x = x + _mlp_forward(cfg, p["mlp"], h2)
    return x, new_cache, heat


def decode_step(params: Pytree, cfg: ModelConfig, cache: Pytree,
                tokens: jax.Array, lengths: jax.Array,
                block_table: jax.Array, layout: PagedLayout, *,
                active: jax.Array | None = None,
                pos3d: jax.Array | None = None, compute_dtype=BF16,
                attn_impl: str = "gather", sharded_table=None,
                sharded_logical=None, works: dict | None = None):
    """One decode step for the batch.

    tokens: [B] int32 (the tokens at position ``lengths``); lengths: [B]
    current context length EXCLUDING the new token; block_table: [B, MB].
    ``active`` ([B] bool, optional) marks the rows that are real sequences
    this step; inactive rows see an all ``-1`` table, so their KV scatter is
    provably DROPPED (``write_token_kv`` routes them out of bounds) and
    their attention validity/heat is all-masked.  This matters once the
    block table is a PERSISTENT device buffer: a skipped or vacated slot's
    row still holds live-looking physical indices, and without the mask its
    length-0 decode would scatter garbage KV into its first block (the PR 1
    scatter-to-block-0 bug class).  ``active=None`` keeps the historical
    caller-builds-a-fresh-table behavior.
    ``attn_impl="paged"`` runs the Pallas kernel over ``works``
    (``decode_page_work`` of the same arguments, derived here when None).
    Returns (logits [B, V_pad], new_cache, heat [B, MB]).
    """
    B = tokens.shape[0]
    if attn_impl == "paged" and works is None:
        works = decode_page_work(cfg, block_table, lengths, layout, active)
    block_table, lengths = _mask_inactive(block_table, lengths, active)
    x = params["embed"].astype(compute_dtype)[tokens]
    segs = build_segments(build_layer_plans(cfg, decoder=True))
    if cfg.enc_dec:
        x = x + sinusoidal_position_at(lengths, cfg.d_model).astype(compute_dtype)
    heat_total = jnp.zeros((B, layout.max_blocks), F32)
    new_cache: dict = {}
    for si, seg in enumerate(segs):
        key = f"p{si}" if seg[0] == "plain" else f"s{si}"
        if seg[0] == "plain":
            x, c, h = _decode_layer(cfg, seg[1], params["blocks"][key],
                                    cache[key], x, lengths, block_table,
                                    layout, pos3d, attn_impl,
                                    sharded_table, sharded_logical, works)
            new_cache[key] = c
            heat_total = heat_total + h
        else:
            _, cycle, reps = seg

            def body(carry, xs):
                xx, hh = carry
                layer_params, layer_cache = xs
                new_lc = {}
                for j, pl in enumerate(cycle):
                    xx, cj, hj = _decode_layer(
                        cfg, pl, layer_params[f"m{j}"], layer_cache[f"m{j}"],
                        xx, lengths, block_table, layout, pos3d, attn_impl,
                        sharded_table, sharded_logical, works)
                    new_lc[f"m{j}"] = cj
                    hh = hh + hj
                return (xx, hh), new_lc

            (x, heat_total), nc = jax.lax.scan(
                body, (x, heat_total),
                (params["blocks"][key], cache[key]))
            new_cache[key] = nc
    x = _apply_norm(cfg, params["final_norm"], x)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x.astype(F32) @ head.astype(F32)
    return logits, new_cache, heat_total


# ---------------------------------------------------------------------------
# Prefill (full sequence, populating the paged pools)
# ---------------------------------------------------------------------------

def prefill_step(params: Pytree, cfg: ModelConfig, cache: Pytree,
                 tokens: jax.Array, block_table: jax.Array,
                 layout: PagedLayout, *, frames: jax.Array | None = None,
                 patches: jax.Array | None = None,
                 pos3d: jax.Array | None = None, compute_dtype=BF16,
                 chunk: int = 1024, last_index: jax.Array | None = None):
    """Forward the prompt and write K/V (or latents / SSM state) into the
    serving cache.  Returns (last-token logits [B,V_pad], new cache).
    ``last_index``: [B] index of each sequence's final REAL token (prompts
    are right-padded to a block multiple); defaults to the last position."""
    B, S = tokens.shape
    x = params["embed"].astype(compute_dtype)[tokens]
    if patches is not None:
        P = patches.shape[1]
        x = jnp.concatenate([patches.astype(compute_dtype), x[:, P:]], axis=1)
    positions = jnp.arange(S)[None, :].astype(F32)
    pos_info = {"positions": positions}
    if pos3d is not None:
        pos_info["pos3d"] = pos3d
    enc_out = None
    if cfg.enc_dec:
        enc_out = encoder_forward(params["encoder"], cfg, frames,
                                  compute_dtype=compute_dtype, chunk=chunk,
                                  remat=False)
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(compute_dtype)

    segs = build_segments(build_layer_plans(cfg, decoder=True))
    new_cache: dict = {}

    def prefill_layer(plan, p, layer_cache, x):
        h = _apply_norm(cfg, p["ln1"], x)
        nc = dict(layer_cache)
        if plan.kind == "mamba":
            y, st = mamba_apply(p["mamba"], h, cfg.mamba, return_state=True)
            x = x + y
            nc["ssm"] = st["ssm"].astype(layer_cache["ssm"].dtype)
            nc["conv"] = st["conv"].astype(layer_cache["conv"].dtype)
        elif cfg.mla is not None:
            ap = p["attn"]
            m = cfg.mla
            H = cfg.n_heads
            q = (h @ ap["wq"].astype(h.dtype)).reshape(B, S, H, m.qk_nope + m.qk_rope)
            q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
            dkv = h @ ap["w_dkv"].astype(h.dtype)
            c_kv = rms_norm(dkv[..., :m.kv_lora], ap["kv_norm"])
            k_rope = dkv[..., m.kv_lora:]
            q_rope = apply_rope(q_rope, positions, theta=cfg.attn.rope_theta)
            k_rope_r = apply_rope(k_rope[:, :, None, :], positions,
                                  theta=cfg.attn.rope_theta)[:, :, 0, :]
            from .attention import mla_expand_attention
            o = mla_expand_attention(q_nope, q_rope, c_kv, k_rope_r,
                                     ap["w_uk"].astype(h.dtype),
                                     ap["w_uv"].astype(h.dtype),
                                     causal=True, chunk=chunk)
            x = x + o.reshape(B, S, -1) @ ap["wo"].astype(h.dtype)
            lat = jnp.concatenate([c_kv, k_rope_r], axis=-1)
            nc["pool_ckv"] = write_prefill_kv(
                layer_cache["pool_ckv"], lat, block_table,
                block_tokens=layout.block_tokens)
        else:
            ap = p["attn"]
            q, k, v = _project_qkv(cfg, ap, h)
            if cfg.attn.mrope_sections is not None:
                q = apply_mrope(q, pos_info["pos3d"], cfg.attn.mrope_sections,
                                theta=cfg.attn.rope_theta)
                k = apply_mrope(k, pos_info["pos3d"], cfg.attn.mrope_sections,
                                theta=cfg.attn.rope_theta)
            elif cfg.attn.use_rope:
                q = apply_rope(q, positions, theta=cfg.attn.rope_theta)
                k = apply_rope(k, positions, theta=cfg.attn.rope_theta)
            window = cfg.attn.window if plan.local else None
            o = flash_attention(q, k, v, causal=plan.causal, window=window,
                                chunk=chunk, soft_cap=cfg.attn.logit_soft_cap)
            x = x + o.reshape(B, S, -1) @ ap["wo"].astype(h.dtype)
            nc["pool_k"] = write_prefill_kv(layer_cache["pool_k"], k,
                                            block_table,
                                            block_tokens=layout.block_tokens)
            nc["pool_v"] = write_prefill_kv(layer_cache["pool_v"], v,
                                            block_table,
                                            block_tokens=layout.block_tokens)
        if plan.xattn:
            hx = _apply_norm(cfg, p["lnx"], x)
            x = x + _xattn_forward(cfg, p["xattn"], hx, enc_out, chunk)
            kx = (enc_out @ p["xattn"]["wk"].astype(h.dtype)).reshape(
                B, enc_out.shape[1], cfg.kv_heads, cfg.head_dim)
            vx = (enc_out @ p["xattn"]["wv"].astype(h.dtype)).reshape(
                B, enc_out.shape[1], cfg.kv_heads, cfg.head_dim)
            nc["xk"], nc["xv"] = kx, vx
        if plan.ffn:
            h2 = _apply_norm(cfg, p["ln2"], x)
            if plan.moe:
                y, _ = moe_apply(p["moe"], h2.reshape(B * S, -1), cfg.moe, cfg.mlp)
                x = x + y.reshape(B, S, -1)
            else:
                x = x + _mlp_forward(cfg, p["mlp"], h2)
        return x, nc

    for si, seg in enumerate(segs):
        key = f"p{si}" if seg[0] == "plain" else f"s{si}"
        if seg[0] == "plain":
            x, nc = prefill_layer(seg[1], params["blocks"][key], cache[key], x)
            new_cache[key] = nc
        else:
            _, cycle, reps = seg

            def body(x, xs):
                layer_params, layer_cache = xs
                nlc = {}
                for j, pl in enumerate(cycle):
                    x, nlc[f"m{j}"] = prefill_layer(pl, layer_params[f"m{j}"],
                                                    layer_cache[f"m{j}"], x)
                return x, nlc

            x, nc = jax.lax.scan(body, x, (params["blocks"][key], cache[key]))
            new_cache[key] = nc
    if last_index is None:
        x_last = x[:, -1]
    else:
        x_last = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    x_last = _apply_norm(cfg, params["final_norm"], x_last)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x_last.astype(F32) @ head.astype(F32)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Suffix prefill (prefix-cache hit: prefix KV comes from the pool)
# ---------------------------------------------------------------------------

def write_suffix_kv(pool, kv_seq, block_table, start, *, block_tokens: int):
    """Scatter a suffix prefill's KV at token offset ``start`` (traced).

    kv_seq: [B, S, ...] — the KV of tokens [start, start+S); rows whose
    target position lands in an unmapped block (table -1) or past the
    table are dropped, like the other pool scatters."""
    B, S = kv_seq.shape[:2]
    bt = block_tokens
    MB = block_table.shape[1]
    pos = start + jnp.arange(S)                     # [S] absolute positions
    blk = pos // bt
    off = pos % bt
    raw = jnp.take_along_axis(
        block_table, jnp.broadcast_to(jnp.where(blk < MB, blk, 0)[None],
                                      (B, S)), axis=1)
    ok = (blk < MB)[None, :] & (raw >= 0)
    safe = jnp.where(ok, raw, pool.shape[0]).reshape(-1)
    flat = kv_seq.reshape((B * S,) + kv_seq.shape[2:])
    return pool.at[safe, jnp.tile(off, B)].set(flat.astype(pool.dtype),
                                               mode="drop")


def _gather_full_kv(pool, suffix, block_table, start, key_blocks: int,
                    block_tokens: int):
    """Assemble the FULL-length per-layer KV stream of one sequence:
    positions [0, start) gathered from the paged pool (the cached prefix),
    [start, start+S) from the freshly computed ``suffix``, the rest zero.

    ``key_blocks`` is static (the prompt's padded block count), so the
    result has the exact shape — and the exact flash-attention chunking —
    of the k/v stream the ordinary full prefill would have built; the
    zero/garbage tail past the valid tokens is causally masked for every
    valid query row, which is what makes the suffix path's attention
    outputs equal the full path's suffix rows."""
    B, S = suffix.shape[:2]
    bt = block_tokens
    KB = key_blocks * bt
    tbl = block_table[:, :key_blocks]                     # [B, nb]
    gathered = pool[jnp.maximum(tbl, 0)]                  # [B, nb, bt, ...]
    full = gathered.reshape((B, KB) + pool.shape[2:])
    posk = jnp.arange(KB)
    valid = (posk[None, :] < start) & (jnp.repeat(tbl, bt, axis=1) >= 0)
    full = jnp.where(valid.reshape(valid.shape + (1,) * (full.ndim - 2)),
                     full, 0).astype(suffix.dtype)
    idx = start + jnp.arange(S)
    return full.at[:, idx].set(suffix.astype(full.dtype), mode="drop")


def prefill_suffix_step(params: Pytree, cfg: ModelConfig, cache: Pytree,
                        tokens: jax.Array, block_table: jax.Array,
                        layout: PagedLayout, *, prefix_len: jax.Array,
                        key_blocks: int, compute_dtype=BF16,
                        chunk: int = 1024,
                        last_index: jax.Array | None = None):
    """Prefill ONLY the uncached suffix of a prompt whose first
    ``prefix_len`` tokens' KV already sits in the paged pool (a prefix-cache
    hit: shared blocks mapped read-only, the partial tail already
    copy-on-write-broken).

    tokens: [B, S] the SUFFIX tokens (block-padded); prefix_len: [B]-free
    traced scalar — absolute position of tokens[:, 0]; key_blocks: STATIC
    padded block count of the whole prompt (compile key, with S).  Each
    attention layer projects q/k/v for the suffix rows only, attends
    against pool-gathered prefix + computed suffix keys via
    ``flash_attention(q_offset=prefix_len)``, and scatters the suffix KV at
    its token offset.  Layer kinds with sequential state (mamba, cross-
    attn, enc-dec) cannot skip prefix compute and are rejected.
    Returns (last-token logits [B, V_pad], new cache).
    """
    B, S = tokens.shape
    bt = layout.block_tokens
    if cfg.enc_dec or cfg.vlm_patches or cfg.attn.mrope_sections is not None:
        raise ValueError("suffix prefill supports plain decoder LMs only")
    x = params["embed"].astype(compute_dtype)[tokens]
    positions = (prefix_len + jnp.arange(S))[None, :].astype(F32)
    segs = build_segments(build_layer_plans(cfg, decoder=True))
    new_cache: dict = {}

    def suffix_layer(plan, p, layer_cache, x):
        if plan.kind not in ("a", "attn") or plan.xattn:
            raise ValueError(
                f"suffix prefill cannot skip prefix compute for layer kind "
                f"{plan.kind!r} (sequential state)")
        h = _apply_norm(cfg, p["ln1"], x)
        nc = dict(layer_cache)
        if cfg.mla is not None:
            ap = p["attn"]
            m = cfg.mla
            H = cfg.n_heads
            q = (h @ ap["wq"].astype(h.dtype)).reshape(B, S, H,
                                                       m.qk_nope + m.qk_rope)
            q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
            dkv = h @ ap["w_dkv"].astype(h.dtype)
            c_kv = rms_norm(dkv[..., :m.kv_lora], ap["kv_norm"])
            k_rope = dkv[..., m.kv_lora:]
            q_rope = apply_rope(q_rope, positions, theta=cfg.attn.rope_theta)
            k_rope_r = apply_rope(k_rope[:, :, None, :], positions,
                                  theta=cfg.attn.rope_theta)[:, :, 0, :]
            lat = jnp.concatenate([c_kv, k_rope_r], axis=-1)
            lat_full = _gather_full_kv(layer_cache["pool_ckv"], lat,
                                       block_table, prefix_len, key_blocks, bt)
            from .attention import mla_expand_attention
            o = mla_expand_attention(q_nope, q_rope,
                                     lat_full[..., :m.kv_lora],
                                     lat_full[..., m.kv_lora:],
                                     ap["w_uk"].astype(h.dtype),
                                     ap["w_uv"].astype(h.dtype),
                                     causal=True, chunk=chunk,
                                     q_offset=prefix_len)
            x = x + o.reshape(B, S, -1) @ ap["wo"].astype(h.dtype)
            nc["pool_ckv"] = write_suffix_kv(
                layer_cache["pool_ckv"], lat, block_table, prefix_len,
                block_tokens=bt)
        else:
            ap = p["attn"]
            q, k, v = _project_qkv(cfg, ap, h)
            if cfg.attn.use_rope:
                q = apply_rope(q, positions, theta=cfg.attn.rope_theta)
                k = apply_rope(k, positions, theta=cfg.attn.rope_theta)
            window = cfg.attn.window if plan.local else None
            k_full = _gather_full_kv(layer_cache["pool_k"], k, block_table,
                                     prefix_len, key_blocks, bt)
            v_full = _gather_full_kv(layer_cache["pool_v"], v, block_table,
                                     prefix_len, key_blocks, bt)
            o = flash_attention(q, k_full, v_full, causal=plan.causal,
                                window=window, chunk=chunk,
                                soft_cap=cfg.attn.logit_soft_cap,
                                q_offset=prefix_len)
            x = x + o.reshape(B, S, -1) @ ap["wo"].astype(h.dtype)
            nc["pool_k"] = write_suffix_kv(layer_cache["pool_k"], k,
                                           block_table, prefix_len,
                                           block_tokens=bt)
            nc["pool_v"] = write_suffix_kv(layer_cache["pool_v"], v,
                                           block_table, prefix_len,
                                           block_tokens=bt)
        if plan.ffn:
            h2 = _apply_norm(cfg, p["ln2"], x)
            if plan.moe:
                y, _ = moe_apply(p["moe"], h2.reshape(B * S, -1), cfg.moe,
                                 cfg.mlp)
                x = x + y.reshape(B, S, -1)
            else:
                x = x + _mlp_forward(cfg, p["mlp"], h2)
        return x, nc

    for si, seg in enumerate(segs):
        key = f"p{si}" if seg[0] == "plain" else f"s{si}"
        if seg[0] == "plain":
            x, nc = suffix_layer(seg[1], params["blocks"][key], cache[key], x)
            new_cache[key] = nc
        else:
            _, cycle, reps = seg

            def body(x, xs):
                layer_params, layer_cache = xs
                nlc = {}
                for j, pl in enumerate(cycle):
                    x, nlc[f"m{j}"] = suffix_layer(pl, layer_params[f"m{j}"],
                                                   layer_cache[f"m{j}"], x)
                return x, nlc

            x, nc = jax.lax.scan(body, x, (params["blocks"][key], cache[key]))
            new_cache[key] = nc
    if last_index is None:
        x_last = x[:, -1]
    else:
        x_last = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    x_last = _apply_norm(cfg, params["final_norm"], x_last)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x_last.astype(F32) @ head.astype(F32)
    return logits, new_cache
