"""Plain float32 reference of a dense decoder LM with the Llama layout:
RMSNorm, rotary positions (rotate-half), multi-head causal attention,
SwiGLU MLP, untied LM head.  Phi-3-mini and DeepSeek-LLM-7B are both this.

It reads its sizes from the configuration file (HF ``config.json`` keys) and
imports nothing of the program.  The weights come as a tree with the served
model's layout: ``embed [V, d]``, ``final_norm.scale [d]``, ``lm_head
[d, V]`` and ``blocks.s0.m0`` holding every layer stacked on a leading axis
(``ln1.scale``, ``attn.{wq,wk,wv,wo}``, ``ln2.scale``,
``mlp.{w_gate,w_in,w_out}``).  It runs one layer at a time, with every
matmul in float32 at the highest precision, and attention in blocks of
queries, so it fits beside the weights.

``quant="fp8"`` is the control: the same computation with every weight
matmul's inputs rounded to float8_e4m3 (weights scaled per output column,
activations per token) and the keys and values rounded likewise.  It is
what a deployment one precision below the configuration's bfloat16 would
compute.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
PAD = 512           # sequences are padded to a multiple: few compiled shapes


def _fp8(x, axis):
    """Round to float8_e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(x, w, quant):
    w = w.astype(F32)
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None, None] * inv                        # [S, 1, hd/2]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta",
                                   "quant"))
def _layer(x, blocks, i, *, heads, kv_heads, eps, theta, quant):
    p = jax.tree.map(lambda a: a[i], blocks)
    S, d = x.shape
    hd = d // heads
    pos = jnp.arange(S, dtype=F32)
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = _rope(_mm(h, a["wq"], quant).reshape(S, heads, hd), pos, theta)
    k = _rope(_mm(h, a["wk"], quant).reshape(S, kv_heads, hd), pos, theta)
    v = _mm(h, a["wv"], quant).reshape(S, kv_heads, hd)
    if quant:
        k, v = _fp8(k, -1), _fp8(v, -1)
    g = heads // kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    outs = []
    for c0 in range(0, S, PAD):         # query blocks, causal key prefix
        c1 = c0 + PAD
        s = jnp.einsum("qhd,khd->hqk", q[c0:c1], k[:c1],
                       precision=HI) / np.sqrt(hd)
        causal = jnp.arange(c0, c1)[:, None] >= jnp.arange(c1)[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                               v[:c1], precision=HI))
    o = jnp.concatenate(outs).reshape(S, heads * hd)
    x = x + _mm(o, a["wo"], quant)
    m = p["mlp"]
    h = _rms(x, p["ln2"]["scale"], eps)
    act = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_in"], quant)
    return x + _mm(act, m["w_out"], quant)


@partial(jax.jit, static_argnames=("eps", "vocab", "quant"))
def _head(x, norm, head, *, eps, vocab, quant):
    return _mm(_rms(x, norm, eps), head[:, :vocab], quant)


def logits(params, cfg: dict, tokens, rows: slice, quant: str | None = None
           ) -> np.ndarray:
    """Logits ``[len(rows), vocab]`` at positions ``rows`` of ``tokens``."""
    q = quant == "fp8"
    n = len(tokens)
    S = -(-n // PAD) * PAD
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    x = params["embed"][jnp.asarray(toks)].astype(F32)
    blocks = params["blocks"]["s0"]["m0"]
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"],
              eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
              quant=q)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, blocks, jnp.int32(i), **kw)
    out = _head(x, params["final_norm"]["scale"], params["lm_head"],
                eps=kw["eps"], vocab=cfg["vocab_size"], quant=q)
    return np.asarray(out[rows], np.float64)
