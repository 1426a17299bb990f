"""Parameter-spec machinery shared by all model definitions.

Models are pure-JAX functional modules: a *spec* (nested dict of
:class:`ParamSpec`) describes every weight's shape, dtype, init and logical
axes.  From one spec we derive:

  * ``materialize(rng, spec)``   — real parameters (smoke tests, examples),
  * ``abstract(spec)``           — ShapeDtypeStructs (multi-pod dry-run; no
                                   host allocation for the full-size configs),
  * ``logical_axes(spec)``       — the logical-axis pytree the distributed
                                   sharding rule engine consumes.

Logical axis names (mapped to mesh axes by repro.distributed.sharding):
  vocab, embed, q_heads, kv_heads, head_dim, ff, expert, kv_lora, state,
  conv, layers (stacked scan axis; never sharded).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

F32 = jnp.float32
BF16 = jnp.bfloat16


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"         # normal | zeros | ones | scaled
    scale: float | None = None   # stddev override for normal/scaled
    dtype: Any = F32

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


def _fan_in(shape: tuple[int, ...]) -> int:
    # last axis is the output axis by convention (x @ w)
    return int(np.prod(shape[:-1])) if len(shape) > 1 else shape[0]


def materialize(rng: jax.Array, spec: Pytree, dtype=None) -> Pytree:
    """Random parameters for ``spec``; ``dtype`` overrides every leaf's
    dtype (bf16 serving weights).  Each leaf is drawn by its own compiled
    init, so building a tree never holds more than one leaf's f32 draw."""
    leaves, treedef = jax.tree.flatten(spec, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, p in zip(keys, leaves):
        std = p.scale if p.scale is not None else 1.0 / math.sqrt(max(1, _fan_in(p.shape)))
        out.append(_init_leaf(key, shape=p.shape, dtype=dtype or p.dtype,
                              init=p.init, std=std))
    return jax.tree.unflatten(treedef, out)


@partial(jax.jit, static_argnames=("shape", "dtype", "init", "std"))
def _init_leaf(key: jax.Array, *, shape: tuple[int, ...], dtype, init: str,
               std: float) -> jax.Array:
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    # the barrier keeps XLA from folding std into the draw's own scaling,
    # which would move the f32 values by an ulp from the unfused draw
    draw = jax.lax.optimization_barrier(jax.random.normal(key, shape, F32))
    return (draw * std).astype(dtype)


def abstract(spec: Pytree) -> Pytree:
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
        spec, is_leaf=lambda x: isinstance(x, ParamSpec))


def logical_axes(spec: Pytree) -> Pytree:
    return jax.tree.map(lambda p: p.axes, spec,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_count(spec: Pytree) -> int:
    leaves = jax.tree.leaves(spec, is_leaf=lambda x: isinstance(x, ParamSpec))
    return int(sum(np.prod(p.shape) for p in leaves))


def cast_tree(tree: Pytree, dtype) -> Pytree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


# ---------------------------------------------------------------------------
# Common numeric helpers
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
             offset: float = 0.0) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(F32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * (offset + scale.astype(F32))
    return y.astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, *,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(F32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale.astype(F32) + bias.astype(F32)
    return y.astype(dt)


def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu2":            # squared ReLU (Primer / nemotron-4)
        r = jax.nn.relu(x)
        return r * r
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {kind!r}")


def pad_vocab(vocab: int, multiple: int = 256) -> int:
    """Pad embedding tables so the vocab axis shards evenly (MaxText-style)."""
    return ((vocab + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard, and M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, *, theta: float = 10000.0) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., :, None].astype(F32) * freqs   # [..., seq, hd/2]
    cos = jnp.cos(ang)[..., None, :]                    # [..., seq, 1, hd/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions_3d: jax.Array, sections: tuple[int, int, int],
                *, theta: float = 10000.0) -> jax.Array:
    """Multimodal RoPE (qwen2-vl): the head_dim/2 frequency channels are
    partitioned into (temporal, height, width) sections, each rotated by its
    own position stream.

    x: [..., seq, heads, head_dim]; positions_3d: [3, ..., seq].
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    if sum(sections) != hd // 2:
        raise ValueError(f"M-RoPE sections {sections} must sum to {hd // 2}")
    sec_id = jnp.asarray(np.repeat(np.arange(3), np.asarray(sections)))  # [hd/2]
    # pick the position stream per frequency channel
    pos = positions_3d.astype(F32)                      # [3, ..., seq]
    pos_per_chan = jnp.take(pos, sec_id, axis=0)        # [hd/2, ..., seq]
    pos_per_chan = jnp.moveaxis(pos_per_chan, 0, -1)    # [..., seq, hd/2]
    ang = pos_per_chan[..., :, None, :] * freqs         # [..., seq, 1, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings [n, d]."""
    pos = np.arange(n)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d)
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], axis=1), F32)


def sinusoidal_position_at(pos: jax.Array, d: int) -> jax.Array:
    """Sinusoidal embedding for dynamic positions. pos: [B] -> [B, d]
    (computed on the fly so decode never materializes an [S, d] table)."""
    dim = jnp.arange(d // 2, dtype=F32)[None, :]
    ang = pos.astype(F32)[:, None] / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)
