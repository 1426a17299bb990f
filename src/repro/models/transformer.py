"""Generic multi-family transformer: one assembly covering all 10 assigned
architectures via ModelConfig (dense GQA LMs, MLA, fine-grained MoE, Mamba-2,
Jamba-style hybrids, Whisper enc-dec, Qwen2-VL backbone).

Design for compile-time scalability: consecutive layers with the same
periodic structure are stacked and executed with ``lax.scan`` (params get a
leading "layers" axis), so HLO size and compile time are O(period), not
O(depth) — required for the 62-layer/512-device dry-runs.  Scan bodies are
``jax.checkpoint``-ed (activation remat) for training.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (decode_attention_dense, flash_attention,
                        mla_absorbed_decode, mla_expand_attention)
from .common import (BF16, F32, ParamSpec, activate, apply_mrope, apply_rope,
                     layer_norm, materialize, pad_vocab, rms_norm,
                     sinusoidal_positions)
from .mamba2 import (mamba_apply, mamba_decode_step, mamba_spec,
                     mamba_state_init)
from .moe import moe_apply, moe_spec

Pytree = Any


@dataclass(frozen=True)
class LayerPlan:
    kind: str          # "attn" | "mamba"
    local: bool = False
    moe: bool = False
    xattn: bool = False   # adds cross-attention (whisper decoder)
    causal: bool = True
    ffn: bool = True      # False for pure-SSM blocks (mamba2): no separate MLP


# ---------------------------------------------------------------------------
# Segmenting: express the layer list as prefix + repeated cycle + suffix
# ---------------------------------------------------------------------------

def build_layer_plans(cfg: ModelConfig, *, decoder: bool = True) -> list[LayerPlan]:
    kinds = cfg.layer_kinds()
    attn_kinds = cfg.attn_kinds()
    moes = cfg.moe_layers()
    plans = []
    for i in range(cfg.n_layers):
        plans.append(LayerPlan(
            kind=kinds[i],
            local=(attn_kinds[i] == "l"),
            moe=moes[i],
            xattn=cfg.enc_dec and decoder,
            causal=decoder,
            ffn=(cfg.family != "ssm"),
        ))
    return plans


def build_segments(plans: list[LayerPlan]) -> list[tuple]:
    """Return segments: ("plain", plan) or ("scan", (plans...), reps)."""
    n = len(plans)
    best = None
    for pre in range(0, 3):
        for p in (1, 2, 3, 4, 6, 8):
            if n - pre < p:
                continue
            cycle = tuple(plans[pre:pre + p])
            reps = (n - pre) // p
            if all(plans[pre + i] == cycle[i % p] for i in range(reps * p)):
                suffix = n - pre - reps * p
                score = (pre + suffix) * 10 + p
                if best is None or score < best[0]:
                    best = (score, pre, p, reps, suffix)
    if best is None:   # fully irregular; all plain
        return [("plain", pl) for pl in plans]
    _, pre, p, reps, suffix = best
    segs: list[tuple] = [("plain", plans[i]) for i in range(pre)]
    segs.append(("scan", tuple(plans[pre:pre + p]), reps))
    segs.extend(("plain", plans[pre + reps * p + i]) for i in range(suffix))
    return segs


# ---------------------------------------------------------------------------
# Per-layer parameter specs
# ---------------------------------------------------------------------------

def _norm_spec(cfg: ModelConfig, d: int) -> Pytree:
    if cfg.norm == "rms":
        return {"scale": ParamSpec((d,), ("embed",), init="ones")}
    return {"scale": ParamSpec((d,), ("embed",), init="ones"),
            "bias": ParamSpec((d,), ("embed",), init="zeros")}


def _apply_norm(cfg: ModelConfig, p: Pytree, x: jax.Array) -> jax.Array:
    if cfg.norm == "rms":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def _attn_spec(cfg: ModelConfig) -> Pytree:
    d, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "wq": ParamSpec((d, H * (m.qk_nope + m.qk_rope)), ("embed", "q_heads")),
            "w_dkv": ParamSpec((d, m.kv_lora + m.qk_rope), ("embed", None)),
            "kv_norm": ParamSpec((m.kv_lora,), (None,), init="ones"),
            "w_uk": ParamSpec((H, m.kv_lora, m.qk_nope), ("q_heads", "kv_lora", "head_dim")),
            "w_uv": ParamSpec((H, m.kv_lora, m.v_head), ("q_heads", "kv_lora", "head_dim")),
            "wo": ParamSpec((H * m.v_head, d), ("q_heads", "embed")),
        }
    spec = {
        "wq": ParamSpec((d, H * hd), ("embed", "q_heads")),
        "wk": ParamSpec((d, KVH * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, KVH * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((H * hd, d), ("q_heads", "embed")),
    }
    if cfg.attn.qk_norm:
        spec["q_norm"] = ParamSpec((hd,), (None,), init="ones")
        spec["k_norm"] = ParamSpec((hd,), (None,), init="ones")
    return spec


def _xattn_spec(cfg: ModelConfig) -> Pytree:
    d, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return {
        "wq": ParamSpec((d, H * hd), ("embed", "q_heads")),
        "wk": ParamSpec((d, KVH * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, KVH * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((H * hd, d), ("q_heads", "embed")),
    }


def _mlp_spec(cfg: ModelConfig) -> Pytree:
    d, f = cfg.d_model, cfg.d_ff
    spec = {
        "w_in": ParamSpec((d, f), ("embed", "ff")),
        "w_out": ParamSpec((f, d), ("ff", "embed")),
    }
    if cfg.mlp in ("swiglu", "geglu"):
        spec["w_gate"] = ParamSpec((d, f), ("embed", "ff"))
    return spec


def layer_spec(cfg: ModelConfig, plan: LayerPlan) -> Pytree:
    d = cfg.d_model
    if plan.kind == "mamba":
        spec = {"ln1": _norm_spec(cfg, d), "mamba": mamba_spec(d, cfg.mamba)}
    else:
        spec = {"ln1": _norm_spec(cfg, d), "attn": _attn_spec(cfg)}
    if plan.xattn:
        spec["lnx"] = _norm_spec(cfg, d)
        spec["xattn"] = _xattn_spec(cfg)
    if plan.ffn:
        spec["ln2"] = _norm_spec(cfg, d)
        if plan.moe:
            spec["moe"] = moe_spec(d, cfg.moe, cfg.mlp)
        else:
            spec["mlp"] = _mlp_spec(cfg)
    return spec


def _stack_spec(spec: Pytree, reps: int) -> Pytree:
    return jax.tree.map(
        lambda p: ParamSpec((reps,) + p.shape, ("layers",) + p.axes,
                            init=p.init, scale=p.scale, dtype=p.dtype),
        spec, is_leaf=lambda x: isinstance(x, ParamSpec))


def model_spec(cfg: ModelConfig) -> Pytree:
    V = pad_vocab(cfg.vocab)
    d = cfg.d_model
    spec: dict = {
        "embed": ParamSpec((V, d), ("vocab", "embed"), scale=0.02),
        "final_norm": _norm_spec(cfg, d),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = ParamSpec((d, V), ("embed", "vocab"))
    segs = build_segments(build_layer_plans(cfg, decoder=True))
    blocks: dict = {}
    for si, seg in enumerate(segs):
        if seg[0] == "plain":
            blocks[f"p{si}"] = layer_spec(cfg, seg[1])
        else:
            _, cycle, reps = seg
            member = {f"m{j}": layer_spec(cfg, pl) for j, pl in enumerate(cycle)}
            blocks[f"s{si}"] = _stack_spec(member, reps)
    spec["blocks"] = blocks
    if cfg.enc_dec:
        enc_plan = LayerPlan(kind="attn", causal=False)
        enc_member = {"m0": layer_spec(cfg, enc_plan)}
        spec["encoder"] = {
            "blocks": _stack_spec(enc_member, cfg.enc_layers),
            "final_norm": _norm_spec(cfg, d),
        }
    return spec


def serving_params(cfg: ModelConfig, seed: int = 0) -> Pytree:
    """Random weights to serve ``cfg`` with, held in bf16: a float32 tree of
    Phi-3-mini alone (15.3 GB) would leave no room for a KV pool on a 16 GB
    chip."""
    return materialize(jax.random.PRNGKey(seed), model_spec(cfg), dtype=BF16)


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def _project_qkv(cfg, p, h):
    B, S, d = h.shape
    H, KVH, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = (h @ p["wq"].astype(h.dtype)).reshape(B, S, H, hd)
    k = (h @ p["wk"].astype(h.dtype)).reshape(B, S, KVH, hd)
    v = (h @ p["wv"].astype(h.dtype)).reshape(B, S, KVH, hd)
    if cfg.attn.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def _attn_forward(cfg: ModelConfig, plan: LayerPlan, p: Pytree, h: jax.Array,
                  pos_info: dict, chunk: int) -> jax.Array:
    B, S, d = h.shape
    window = cfg.attn.window if plan.local else None
    if cfg.mla is not None:
        m = cfg.mla
        H = cfg.n_heads
        q = (h @ p["wq"].astype(h.dtype)).reshape(B, S, H, m.qk_nope + m.qk_rope)
        q_nope, q_rope = q[..., :m.qk_nope], q[..., m.qk_nope:]
        dkv = h @ p["w_dkv"].astype(h.dtype)
        c_kv = rms_norm(dkv[..., :m.kv_lora], p["kv_norm"])
        k_rope = dkv[..., m.kv_lora:]
        q_rope = apply_rope(q_rope, pos_info["positions"], theta=cfg.attn.rope_theta)
        k_rope = apply_rope(k_rope[:, :, None, :], pos_info["positions"],
                            theta=cfg.attn.rope_theta)[:, :, 0, :]
        out = mla_expand_attention(q_nope, q_rope, c_kv, k_rope,
                                   p["w_uk"].astype(h.dtype),
                                   p["w_uv"].astype(h.dtype),
                                   causal=plan.causal, chunk=chunk)
        out = out.reshape(B, S, H * m.v_head)
        return out @ p["wo"].astype(h.dtype)
    q, k, v = _project_qkv(cfg, p, h)
    if cfg.attn.mrope_sections is not None:
        q = apply_mrope(q, pos_info["pos3d"], cfg.attn.mrope_sections,
                        theta=cfg.attn.rope_theta)
        k = apply_mrope(k, pos_info["pos3d"], cfg.attn.mrope_sections,
                        theta=cfg.attn.rope_theta)
    elif cfg.attn.use_rope:
        q = apply_rope(q, pos_info["positions"], theta=cfg.attn.rope_theta)
        k = apply_rope(k, pos_info["positions"], theta=cfg.attn.rope_theta)
    out = flash_attention(q, k, v, causal=plan.causal, window=window,
                          chunk=chunk, soft_cap=cfg.attn.logit_soft_cap)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return out @ p["wo"].astype(h.dtype)


def _xattn_forward(cfg, p, h, enc_out, chunk):
    B, S, d = h.shape
    H, KVH, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = (h @ p["wq"].astype(h.dtype)).reshape(B, S, H, hd)
    k = (enc_out @ p["wk"].astype(h.dtype)).reshape(B, enc_out.shape[1], KVH, hd)
    v = (enc_out @ p["wv"].astype(h.dtype)).reshape(B, enc_out.shape[1], KVH, hd)
    out = flash_attention(q, k, v, causal=False, chunk=chunk)
    out = out.reshape(B, S, H * hd)
    return out @ p["wo"].astype(h.dtype)


def _mlp_forward(cfg, p, h):
    x = h @ p["w_in"].astype(h.dtype)
    if cfg.mlp in ("swiglu", "geglu"):
        g = h @ p["w_gate"].astype(h.dtype)
        x = (jax.nn.silu(g) if cfg.mlp == "swiglu"
             else jax.nn.gelu(g, approximate=True)) * x
    else:
        x = activate(x, cfg.mlp)
    return x @ p["w_out"].astype(h.dtype)


def layer_forward(cfg: ModelConfig, plan: LayerPlan, p: Pytree, x: jax.Array,
                  aux: jax.Array, pos_info: dict, *, enc_out=None,
                  chunk: int = 1024) -> tuple[jax.Array, jax.Array]:
    h = _apply_norm(cfg, p["ln1"], x)
    if plan.kind == "mamba":
        x = x + mamba_apply(p["mamba"], h, cfg.mamba)
    else:
        x = x + _attn_forward(cfg, plan, p["attn"], h, pos_info, chunk)
    if plan.xattn:
        hx = _apply_norm(cfg, p["lnx"], x)
        x = x + _xattn_forward(cfg, p["xattn"], hx, enc_out, chunk)
    if plan.ffn:
        h2 = _apply_norm(cfg, p["ln2"], x)
        if plan.moe:
            B, S, d = h2.shape
            y, a = moe_apply(p["moe"], h2.reshape(B * S, d), cfg.moe, cfg.mlp)
            x = x + y.reshape(B, S, d)
            aux = aux + a
        else:
            x = x + _mlp_forward(cfg, p["mlp"], h2)
    return x, aux


def _run_blocks(cfg: ModelConfig, blocks: Pytree, segs: list, x: jax.Array,
                pos_info: dict, *, enc_out=None, chunk: int, remat: bool
                ) -> tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), F32)
    for si, seg in enumerate(segs):
        if seg[0] == "plain":
            plan = seg[1]

            def plain_fwd(p_, x_, a_, _plan=plan):
                return layer_forward(cfg, _plan, p_, x_, a_, pos_info,
                                     enc_out=enc_out, chunk=chunk)
            if remat:
                plain_fwd = jax.checkpoint(plain_fwd, prevent_cse=False)
            x, aux = plain_fwd(blocks[f"p{si}"], x, aux)
        else:
            _, cycle, reps = seg
            stacked = blocks[f"s{si}"]

            def body(carry, layer_params):
                xx, aa = carry
                for j, pl in enumerate(cycle):
                    xx, aa = layer_forward(cfg, pl, layer_params[f"m{j}"], xx,
                                           aa, pos_info, enc_out=enc_out,
                                           chunk=chunk)
                return (xx, aa), None

            if remat:
                body = jax.checkpoint(body, prevent_cse=False)
            (x, aux), _ = jax.lax.scan(body, (x, aux), stacked)
    return x, aux


def model_forward(params: Pytree, cfg: ModelConfig, tokens: jax.Array, *,
                  frames: jax.Array | None = None,
                  patches: jax.Array | None = None,
                  pos3d: jax.Array | None = None,
                  compute_dtype=BF16, chunk: int = 1024,
                  remat: bool = True) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward -> (logits [B,S,V_pad], aux_loss).

    tokens: [B,S] int32.  frames: whisper stub encoder input [B,F,d].
    patches: qwen2-vl stub patch embeddings [B,P,d] replacing the first P
    positions.  pos3d: [3,B,S] M-RoPE positions.
    """
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
        if frames is None:
            raise ValueError("enc-dec model needs `frames`")
        enc_out = encoder_forward(params["encoder"], cfg, frames,
                                  compute_dtype=compute_dtype, chunk=chunk,
                                  remat=remat)
        # whisper decoder uses learned positions; sinusoidal stand-in
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(compute_dtype)

    segs = build_segments(build_layer_plans(cfg, decoder=True))
    x, aux = _run_blocks(cfg, params["blocks"], segs, x, pos_info,
                         enc_out=enc_out, chunk=chunk, remat=remat)
    x = _apply_norm(cfg, params["final_norm"], x)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x.astype(F32) @ head.astype(F32)
    return logits, aux


def encoder_forward(enc_params: Pytree, cfg: ModelConfig, frames: jax.Array,
                    *, compute_dtype=BF16, chunk: int = 1024,
                    remat: bool = True) -> jax.Array:
    """Whisper-style encoder over precomputed (stub) frame embeddings."""
    B, Fr, d = frames.shape
    x = frames.astype(compute_dtype)
    x = x + sinusoidal_positions(Fr, d)[None].astype(compute_dtype)
    pos_info = {"positions": jnp.arange(Fr)[None, :].astype(F32)}
    plan = LayerPlan(kind="attn", causal=False)

    def body(carry, layer_params):
        xx, aa = carry
        xx, aa = layer_forward(cfg, plan, layer_params["m0"], xx, aa, pos_info,
                               chunk=chunk)
        return (xx, aa), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), F32)),
                             enc_params["blocks"])
    return _apply_norm(cfg, enc_params["final_norm"], x)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss(params: Pytree, cfg: ModelConfig, batch: dict, *,
            compute_dtype=BF16, chunk: int = 1024, remat: bool = True,
            z_loss: float = 1e-4) -> tuple[jax.Array, dict]:
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    weights = batch.get("weights")
    if weights is None:
        weights = jnp.ones_like(labels, F32)
    else:
        weights = weights[:, 1:].astype(F32)
    logits, aux = model_forward(
        params, cfg, inputs,
        frames=batch.get("frames"), patches=batch.get("patches"),
        pos3d=batch.get("pos3d"),
        compute_dtype=compute_dtype, chunk=chunk, remat=remat)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - ll) * weights
    denom = jnp.maximum(weights.sum(), 1.0)
    loss = nll.sum() / denom
    zl = z_loss * (jnp.square(logz) * weights).sum() / denom
    total = loss + zl + aux
    return total, {"ce": loss, "z_loss": zl, "aux": aux}
