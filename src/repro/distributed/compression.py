"""Gradient compression for the data-parallel all-reduce, with error feedback.

Modes:
  none  — plain f32 psum.
  bf16  — cast to bf16 before the wire (2x byte reduction), f32 accumulate.
  int8  — per-row int8 quantization + f32 scale, exchanged with all_gather
          over the data axis and reduced locally in f32 (the 1-bit-Adam-style
          formulation that keeps the sum exact per-shard).  ~4x byte
          reduction.  Error feedback carries the quantization residual into
          the next step so compression error does not bias convergence.

All collectives are expressed inside shard_map so the wire dtype is the
compressed one (a psum of int8 would up-cast; all_gather does not).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any
F32 = jnp.float32


def quantize_int8(x: jax.Array):
    """Symmetric per-row int8 quantization. x: [*, d]."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(F32)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(F32) * scale


def compress_residual(x: jax.Array, err: jax.Array):
    """Apply error feedback: quantize (x + err), return (q, scale, new_err)."""
    target = x.astype(F32) + err
    q, scale = quantize_int8(target.reshape(-1, x.shape[-1]) if x.ndim > 1
                             else target[None, :])
    deq = dequantize_int8(q, scale).reshape(target.shape)
    return q, scale, target - deq


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def compressed_psum_mean(x: jax.Array, mesh: Mesh, axis: str = "data", *,
                         mode: str = "int8", err: jax.Array | None = None):
    """Mean-reduce ``x`` (replicated-layout gradient shard pattern: every
    shard holds ITS microbatch's gradient of the full tensor) over ``axis``.

    Returns (mean, new_err).  ``err`` is the error-feedback state (int8 mode).
    """
    n = mesh.shape[axis]
    if mode == "none":
        def body(v):
            return jax.lax.psum(v, axis) / n
        fn = _shard_map(body, mesh, (P(axis),), P(axis))
        # caller handles layout; simple path for tests
        return fn(x), err
    if mode == "bf16":
        def body(v):
            return jax.lax.psum(v.astype(jnp.bfloat16).astype(F32), axis) / n
        fn = _shard_map(body, mesh, (P(axis),), P(axis))
        return fn(x), err
    if mode != "int8":
        raise ValueError(f"unknown compression mode {mode!r}")

    if err is None:
        err = jnp.zeros(x.shape[1:], F32)

    def body(v, e):
        # v: [1, *shape] local microbatch grad; e: [1, *shape] local residual
        g = v[0]
        q, scale, new_e = compress_residual(g, e[0])
        rows = q.shape[0]
        qg = jax.lax.all_gather(q, axis)                 # int8 on the wire
        sg = jax.lax.all_gather(scale, axis)
        total = jnp.sum(dequantize_int8(qg.reshape(n * rows, -1),
                                        sg.reshape(n * rows, 1))
                        .reshape((n,) + g.shape), axis=0)
        return (total / n)[None], new_e[None]

    fn = _shard_map(body, mesh, (P(axis), P(axis)), (P(axis), P(axis)))
    xs = jnp.broadcast_to(x[None], (n,) + x.shape) if x.ndim == err.ndim \
        else x
    # callers pass per-shard grads stacked on dim0 (size n)
    mean, new_err = fn(x, jnp.broadcast_to(err[None], (n,) + err.shape))
    return mean[0], new_err[0]


def compressed_grad_mean_tree(grads: Pytree, mesh: Mesh, axis: str = "data",
                              *, mode: str = "int8",
                              err_tree: Pytree | None = None):
    """Tree version for stacked per-shard grads [n_shards, ...] per leaf."""
    if err_tree is None:
        err_tree = jax.tree.map(lambda g: jnp.zeros(g.shape[1:], F32), grads)
    outs = jax.tree.map(
        lambda g, e: compressed_psum_mean(g, mesh, axis, mode=mode, err=e),
        grads, err_tree)
    mean = jax.tree.map(lambda t: t[0], outs,
                        is_leaf=lambda x: isinstance(x, tuple))
    err = jax.tree.map(lambda t: t[1], outs,
                       is_leaf=lambda x: isinstance(x, tuple))
    return mean, err
