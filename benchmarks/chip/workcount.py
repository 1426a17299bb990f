"""Operations and bytes that a decoder's serving calls need, computed from
shapes alone (not from the program).

Counts are of the algorithm, layer by layer from each layer's kind
(``cfg.layer_kinds()``, ``cfg.attn_kinds()``, ``cfg.moe_layers()``): every
matmul a token goes through, attention over the keys each query may see (a
local layer's window at most), the LM head over the real vocabulary for
each sampled position.  Multiply-adds count 2 operations.  Elementwise work
(norms, rotary, softmax, the router's top-k) is left out; it is under 1% of
these counts at the benchmark's widths.

Attention.  Grouped-query: q, k, v and o, and per key a score and a value
product in each head.  Latent (MLA, DeepSeek-V2 section 2.1): q, the joint
down-projection to the latent and the rotary key, the up-projections
W^UK and W^UV once per token, and o.  A prefill computes each key and value
from its latent and attends over them (per key and head: nope + rope
for the score, v_head for the value); a decode step absorbs W^UK into the
query and W^UV into the output and attends in the latent space (kv_lora +
rope for the score, kv_lora for the value), the cheaper of the two forms
for one query over a long cache.

MLP.  Dense: three matrices of ``d_ff`` for a gated unit (SwiGLU, GeGLU),
two otherwise.  Sparse experts: the router (``d_model`` x experts), the
``top_k`` routed experts of ``d_ff_expert`` and the shared experts.  A
config that holds a share of the routed experts (``moe.num_experts_here``)
counts the routed work that lands on them: ``top_k`` x held / experts a
token, routing taken as even.

State-space layers are not counted: a config that has them raises
``Uncounted``, and readers leave their metric out.

Decode bytes (HBM).  Every weight the step needs, once: each layer's
matrices and norms, the final norm and LM head, and the embedding rows of
its tokens; each row's keys and values (MLA: its latents) at its real
length, a local layer's window at most; the new token's written.  Of the
routed experts only those that some row of the step selected need to be
read, and shapes cannot tell how many that is: the count takes
``experts_read``, the distinct routed experts read in one MoE layer,
averaged over the step's MoE layers, from the program's own counter.
Without it a config with routed experts has no byte count (``Uncounted``),
so the rule never counts more bytes than an implementation that reads only
the selected experts needs.
"""

from __future__ import annotations


class Uncounted(ValueError):
    """The count cannot be given from shapes for this configuration."""


def _gated(cfg) -> int:
    return 3 if cfg.mlp in ("swiglu", "geglu") else 2


def _layers(cfg):
    """(local window or None, MoE or not) of each layer; a state-space
    layer raises ``Uncounted``."""
    if "m" in cfg.layer_kinds():
        raise Uncounted(f"{cfg.name}: state-space layers are not counted")
    window = cfg.attn.window
    return [(window if a == "l" and window else None, moe)
            for a, moe in zip(cfg.attn_kinds(), cfg.moe_layers())]


def experts_held(cfg) -> int:
    """Routed experts this chip holds: the config's share where it states
    one (``moe.num_experts_here``), else all of them."""
    m = cfg.moe
    return getattr(m, "num_experts_here", None) or m.num_experts


def attention_params(cfg) -> int:
    """Weights one token multiplies by in an attention layer."""
    d, H = cfg.d_model, cfg.n_heads
    if cfg.mla is not None:
        m = cfg.mla
        return (d * H * (m.qk_nope + m.qk_rope) + d * (m.kv_lora + m.qk_rope)
                + m.kv_lora * H * (m.qk_nope + m.v_head) + H * m.v_head * d)
    hd = cfg.head_dim
    return 2 * d * H * hd + 2 * d * cfg.kv_heads * hd


def mlp_params(cfg, moe: bool) -> float:
    """Weights one token multiplies by in a layer's MLP (a MoE layer's
    routed experts in expectation over the experts held here)."""
    d, g = cfg.d_model, _gated(cfg)
    if not moe:
        return g * d * cfg.d_ff
    m = cfg.moe
    routed = m.top_k * experts_held(cfg) / m.num_experts
    return d * m.num_experts + g * d * m.d_ff_expert * (routed + m.num_shared)


def layer_matmul_params(cfg, i: int = 0) -> float:
    """Weights one token multiplies by in layer ``i``: attention and MLP."""
    _, moe = _layers(cfg)[i]
    return attention_params(cfg) + mlp_params(cfg, moe)


def key_flops(cfg, decode: bool) -> int:
    """Operations of one query against one key in one attention layer."""
    H = cfg.n_heads
    if cfg.mla is not None:
        m = cfg.mla
        if decode:
            return 2 * H * (2 * m.kv_lora + m.qk_rope)
        return 2 * H * (m.qk_nope + m.qk_rope + m.v_head)
    return 4 * H * cfg.head_dim


def _params(cfg) -> float:
    return sum(attention_params(cfg) + mlp_params(cfg, moe)
               for _, moe in _layers(cfg))


def token_flops(cfg, ctx: int, decode: bool = True) -> float:
    """One token through every layer, attending to ``ctx`` keys (itself
    included), without the LM head."""
    keys = sum(min(ctx, w) if w else ctx for w, _ in _layers(cfg))
    return 2 * _params(cfg) + key_flops(cfg, decode) * keys


def head_flops(cfg) -> int:
    return 2 * cfg.d_model * cfg.vocab


def decode_flops(cfg, ctx_rows) -> float:
    """A decode step: each row attends to its context and is sampled."""
    return sum(token_flops(cfg, c) + head_flops(cfg) for c in ctx_rows)


def _causal_keys(prompt_len: int, start: float, window: int | None) -> float:
    """Keys seen by positions ``start .. prompt_len - 1``, position p seeing
    p + 1 of them (``window`` at most)."""
    def upto(n):            # sum over p < n of min(p + 1, window)
        if window is None or n <= window:
            return n * (n + 1) / 2
        return window * (window + 1) / 2 + (n - window) * window
    return upto(prompt_len) - upto(start)


def prefill_flops(cfg, prompt_len: int, computed: float) -> float:
    """A prefill of the last ``computed`` tokens of a prompt (all of it, or
    the suffix behind a prefix-cache hit), causal, one sampled position."""
    start = prompt_len - computed
    keys = sum(_causal_keys(prompt_len, start, w) for w, _ in _layers(cfg))
    return (computed * 2 * _params(cfg)
            + key_flops(cfg, decode=False) * keys + head_flops(cfg))


def _kv_bytes(cfg, dtype_bytes: int) -> int:
    """Cache bytes one token holds in one attention layer."""
    if cfg.mla is not None:
        return (cfg.mla.kv_lora + cfg.mla.qk_rope) * dtype_bytes
    return 2 * cfg.kv_heads * cfg.head_dim * dtype_bytes


def kv_bytes_per_token(cfg, dtype_bytes: int = 2) -> int:
    """Cache bytes one token holds over every layer."""
    return len(_layers(cfg)) * _kv_bytes(cfg, dtype_bytes)


def weight_bytes_read(cfg, rows: int, dtype_bytes: int = 2,
                      experts_read: float | None = None) -> float:
    """Weights a decode step reads: every layer's matrices and norms, the
    final norm and LM head, and the embedding rows of its tokens."""
    d, g = cfg.d_model, _gated(cfg)
    norms = 2 * d + (cfg.mla.kv_lora if cfg.mla is not None else 0)
    total = 0
    for _, moe in _layers(cfg):
        total += attention_params(cfg) + norms
        if not moe:
            total += mlp_params(cfg, False)
            continue
        if experts_read is None:
            raise Uncounted(f"{cfg.name}: the routed experts a decode step "
                            f"reads need the program's count")
        m = cfg.moe
        total += (d * m.num_experts
                  + g * d * m.d_ff_expert * (experts_read + m.num_shared))
    return dtype_bytes * (total + d + d * cfg.vocab + rows * d)


def decode_bytes(cfg, ctx_rows, dtype_bytes: int = 2,
                 experts_read: float | None = None) -> float:
    """A decode step's HBM traffic: weights once, each row's cache at its
    real length, and the new token's written."""
    rows = len(ctx_rows)
    read = sum(min(c, w) if w else c
               for c in ctx_rows for w, _ in _layers(cfg))
    return (weight_bytes_read(cfg, rows, dtype_bytes, experts_read)
            + _kv_bytes(cfg, dtype_bytes) * read
            + kv_bytes_per_token(cfg, dtype_bytes) * rows)
