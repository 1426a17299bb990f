"""Operations and bytes that a dense decoder's serving calls need, computed
from shapes alone (not from the program).

Counts are of the algorithm: every matmul of every layer, causal attention
over the keys each query may see, the LM head over the real vocabulary for
each sampled position.  Multiply-adds count 2 operations.  Elementwise work
(norms, rotary, softmax) is left out; it is under 1% of these counts at the
benchmark's widths.
"""

from __future__ import annotations


def _dims(cfg):
    hd = cfg.head_dim
    return (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads, hd,
            cfg.d_ff, cfg.vocab)


def layer_matmul_params(cfg) -> int:
    """Weights one layer multiplies by: q, k, v, o and the SwiGLU MLP."""
    L, d, H, KVH, hd, f, V = _dims(cfg)
    return 2 * d * H * hd + 2 * d * KVH * hd + 3 * d * f


def token_flops(cfg, ctx: int) -> int:
    """One token through every layer, attending to ``ctx`` keys (itself
    included), without the LM head."""
    L, d, H, KVH, hd, f, V = _dims(cfg)
    return L * (2 * layer_matmul_params(cfg) + 4 * H * hd * ctx)


def head_flops(cfg) -> int:
    L, d, H, KVH, hd, f, V = _dims(cfg)
    return 2 * d * V


def decode_flops(cfg, ctx_rows) -> int:
    """A decode step: each row attends to its context and is sampled."""
    return sum(token_flops(cfg, c) + head_flops(cfg) for c in ctx_rows)


def prefill_flops(cfg, prompt_len: int, computed: float) -> float:
    """A prefill of the last ``computed`` tokens of a prompt (all of it, or
    the suffix behind a prefix-cache hit), causal, one sampled position."""
    start = prompt_len - computed
    # sum over positions p in [start, prompt_len) of (p + 1) keys
    keys = (prompt_len * (prompt_len + 1) - start * (start + 1)) / 2
    L, d, H, KVH, hd, f, V = _dims(cfg)
    return (computed * L * 2 * layer_matmul_params(cfg)
            + L * 4 * H * hd * keys + head_flops(cfg))


def kv_bytes_per_token(cfg, dtype_bytes: int = 2) -> int:
    L, d, H, KVH, hd, f, V = _dims(cfg)
    return 2 * L * KVH * hd * dtype_bytes


def weight_bytes_read(cfg, rows: int, dtype_bytes: int = 2) -> int:
    """Weights a decode step reads: every layer's matrices and norms, the
    final norm and LM head, and the embedding rows of its tokens."""
    L, d, H, KVH, hd, f, V = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * d
    return dtype_bytes * (L * per_layer + d + d * V + rows * d)


def decode_bytes(cfg, ctx_rows, dtype_bytes: int = 2) -> int:
    """A decode step's HBM traffic: weights once, each row's K/V at its
    real length, and the new token's K/V written."""
    kv = kv_bytes_per_token(cfg, dtype_bytes)
    rows = len(ctx_rows)
    return (weight_bytes_read(cfg, rows, dtype_bytes)
            + kv * sum(ctx_rows) + kv * rows)
