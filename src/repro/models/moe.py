"""Mixture-of-Experts FFN: fine-grained routed experts + shared experts
(DeepSeekMoE-style), with sort-based capacity dispatch.

Dispatch is the jit-friendly argsort formulation (no [T,E,C] one-hot):
tokens are sorted by assigned expert, each expert processes a static-capacity
slab, and overflow tokens are dropped (their gate mass is lost, standard
capacity-factor semantics).  The expert dimension is the EP axis — stacked
expert weights [E, ...] shard over the "model" mesh axis, and GSPMD lowers
the dispatch/combine gathers into all-to-alls across the expert shards.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import MoECfg
from .common import F32, ParamSpec, activate


def moe_spec(d_model: int, cfg: MoECfg, mlp_kind: str) -> dict:
    E, fe = cfg.num_experts, cfg.d_ff_expert
    spec = {
        "router": ParamSpec((d_model, E), ("embed", None), scale=0.02),
        "w_in": ParamSpec((E, d_model, fe), ("expert", "embed", "ff")),
        "w_out": ParamSpec((E, fe, d_model), ("expert", "ff", "embed")),
    }
    if mlp_kind in ("swiglu", "geglu"):
        spec["w_gate"] = ParamSpec((E, d_model, fe), ("expert", "embed", "ff"))
    if cfg.num_shared > 0:
        fs = cfg.num_shared * fe
        spec["shared_in"] = ParamSpec((d_model, fs), ("embed", "ff"))
        spec["shared_out"] = ParamSpec((fs, d_model), ("ff", "embed"))
        if mlp_kind in ("swiglu", "geglu"):
            spec["shared_gate"] = ParamSpec((d_model, fs), ("embed", "ff"))
    return spec


def _expert_ffn(params, x_ec: jax.Array, mlp_kind: str) -> jax.Array:
    """x_ec: [E, C, d] -> [E, C, d] through per-expert FFNs."""
    h = jnp.einsum("ecd,edf->ecf", x_ec, params["w_in"].astype(x_ec.dtype))
    if mlp_kind == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", x_ec, params["w_gate"].astype(x_ec.dtype))
        h = jax.nn.silu(g) * h
    elif mlp_kind == "geglu":
        g = jnp.einsum("ecd,edf->ecf", x_ec, params["w_gate"].astype(x_ec.dtype))
        h = jax.nn.gelu(g, approximate=True) * h
    else:
        h = activate(h, mlp_kind)
    return jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(x_ec.dtype))


def _shared_ffn(params, x: jax.Array, mlp_kind: str) -> jax.Array:
    h = x @ params["shared_in"].astype(x.dtype)
    if mlp_kind in ("swiglu", "geglu"):
        g = x @ params["shared_gate"].astype(x.dtype)
        h = (jax.nn.silu(g) if mlp_kind == "swiglu"
             else jax.nn.gelu(g, approximate=True)) * h
    else:
        h = activate(h, mlp_kind)
    return h @ params["shared_out"].astype(x.dtype)


def moe_apply(params, x: jax.Array, cfg: MoECfg, mlp_kind: str,
              *, capacity: int | None = None) -> tuple[jax.Array, jax.Array]:
    """x: [T, d] -> ([T, d], aux_loss scalar).

    Dispatch path selection: when a runtime mesh is installed (launchers do
    this) and the expert/token counts divide it, dispatch goes through the
    shard_map expert-parallel all-to-all (moe_apply_ep) — the pjit global
    scatter was the dominant collective in MoE training cells (§Perf
    hillclimb #2).  Otherwise the single-device sort-based path runs."""
    from ..distributed.flashdecode import get_decode_mesh
    mesh = get_decode_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        M = mesh.shape["model"]
        data_axes = tuple(n for n in mesh.axis_names if n != "model")
        import numpy as _np
        D = int(_np.prod([mesh.shape[n] for n in data_axes]))
        if (cfg.num_experts % M == 0 and x.shape[0] % D == 0
                and x.shape[0] // D >= 1 and M > 1):
            return moe_apply_ep(params, x, cfg, mlp_kind, mesh,
                                capacity=capacity)
    return _moe_apply_local(params, x, cfg, mlp_kind, capacity=capacity)


def _moe_apply_local(params, x: jax.Array, cfg: MoECfg, mlp_kind: str,
                     *, capacity: int | None = None):
    """Single-shard sort-based dispatch (reference path)."""
    T, d = x.shape
    E, K = cfg.num_experts, cfg.top_k

    logits = (x.astype(F32) @ params["router"].astype(F32))        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)                # [T, K]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                    # norm_topk_prob

    # ---- load-balancing aux loss (GShard/DeepSeek form) ----
    me = probs.mean(axis=0)                                        # [E]
    ce = jnp.zeros(E, F32).at[expert_idx.reshape(-1)].add(1.0) / (T * K)
    aux = cfg.router_aux_weight * E * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    if capacity is None:
        capacity = max(8, int(T * K / E * cfg.capacity_factor) + 1)
    flat_expert = expert_idx.reshape(-1)                           # [T*K]
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(flat_expert)
    se, sg, stok = flat_expert[order], flat_gate[order], flat_token[order]
    # position of each entry within its expert group
    first_of_group = jnp.searchsorted(se, se, side="left")
    pos_in_group = jnp.arange(T * K) - first_of_group
    keep = pos_in_group < capacity
    dest = jnp.where(keep, se * capacity + pos_in_group, E * capacity)

    buf = jnp.zeros((E * capacity + 1, d), x.dtype)
    buf = buf.at[dest].set(x[stok])                                # drop row = E*C
    y_ec = _expert_ffn(params, buf[:-1].reshape(E, capacity, d), mlp_kind)

    y_flat = y_ec.reshape(E * capacity, d)
    gathered = jnp.where(keep[:, None], y_flat[jnp.minimum(dest, E * capacity - 1)], 0.0)
    out = jnp.zeros((T, d), x.dtype).at[stok].add(
        gathered * sg[:, None].astype(x.dtype))

    if cfg.num_shared > 0:
        out = out + _shared_ffn(params, x, mlp_kind)
    return out, aux


def moe_apply_ep(params, x: jax.Array, cfg: MoECfg, mlp_kind: str, mesh,
                 *, capacity: int | None = None):
    """Expert-parallel dispatch: tokens stay on their data shard; routed
    tokens cross the "model" axis with two all-to-alls (the Megatron/GShard
    EP pattern).  Per-device collective payload is T_local*K*d bytes instead
    of the global [E,C,d] buffer scatter GSPMD emits for the local path.
    """
    from jax.sharding import PartitionSpec as P
    import numpy as _np

    E, K = cfg.num_experts, cfg.top_k
    M = mesh.shape["model"]
    data_axes = tuple(n for n in mesh.axis_names if n != "model")
    D = int(_np.prod([mesh.shape[n] for n in data_axes]))
    T, d = x.shape
    T_loc = T // D
    E_loc = E // M
    if capacity is None:
        capacity = max(8, int(T_loc * K / E * cfg.capacity_factor) + 1)
    C = capacity

    def body(x_l, router, w_in, w_gate, w_out, shared):
        logits = x_l.astype(F32) @ router.astype(F32)         # [T_loc, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        me = probs.mean(axis=0)
        ce = jnp.zeros(E, F32).at[expert_idx.reshape(-1)].add(1.0) / (T_loc * K)
        aux = cfg.router_aux_weight * E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, data_axes) if data_axes else aux

        flat_expert = expert_idx.reshape(-1)
        flat_gate = gate_vals.reshape(-1)
        flat_token = jnp.repeat(jnp.arange(T_loc), K)
        order = jnp.argsort(flat_expert)
        se, sg, stok = flat_expert[order], flat_gate[order], flat_token[order]
        first = jnp.searchsorted(se, se, side="left")
        pos = jnp.arange(T_loc * K) - first
        keep = pos < C
        dest = jnp.where(keep, se * C + pos, E * C)
        buf = jnp.zeros((E * C + 1, d), x_l.dtype).at[dest].set(x_l[stok])
        send = buf[:-1].reshape(M, E_loc * C, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=False)                # [M, E_loc*C, d]
        x_ec = recv.reshape(M, E_loc, C, d).transpose(1, 0, 2, 3) \
                   .reshape(E_loc, M * C, d)
        h = jnp.einsum("ecd,edf->ecf", x_ec, w_in.astype(x_ec.dtype))
        if mlp_kind in ("swiglu", "geglu"):
            g = jnp.einsum("ecd,edf->ecf", x_ec, w_gate.astype(x_ec.dtype))
            h = (jax.nn.silu(g) if mlp_kind == "swiglu"
                 else jax.nn.gelu(g, approximate=True)) * h
        else:
            h = activate(h, mlp_kind)
        y_ec = jnp.einsum("ecf,efd->ecd", h, w_out.astype(x_ec.dtype))
        back = y_ec.reshape(E_loc, M, C, d).transpose(1, 0, 2, 3) \
                   .reshape(M, E_loc * C, d)
        got = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0,
                                 tiled=False)                 # [M, E_loc*C, d]
        y_flat = got.reshape(E * C, d)
        gathered = jnp.where(keep[:, None],
                             y_flat[jnp.minimum(dest, E * C - 1)], 0.0)
        out = jnp.zeros((T_loc, d), x_l.dtype).at[stok].add(
            gathered * sg[:, None].astype(x_l.dtype))
        if shared is not None:
            sh_in, sh_gate, sh_out = shared
            hs = x_l @ sh_in.astype(x_l.dtype)
            if sh_gate is not None:
                gs = x_l @ sh_gate.astype(x_l.dtype)
                hs = (jax.nn.silu(gs) if mlp_kind == "swiglu"
                      else jax.nn.gelu(gs, approximate=True)) * hs
            else:
                hs = activate(hs, mlp_kind)
            part = hs @ sh_out.astype(x_l.dtype)
            out = out + jax.lax.psum(part.astype(F32), "model").astype(x_l.dtype)
        return out, aux[None]

    glu = mlp_kind in ("swiglu", "geglu")
    shared_args = None
    shared_specs = None
    if cfg.num_shared > 0:
        shared_args = (params["shared_in"],
                       params.get("shared_gate") if glu else None,
                       params["shared_out"])
        shared_specs = (P(None, "model"),
                        P(None, "model") if glu else None,
                        P("model", None))

    def _sm(fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    tok_spec = P(data_axes) if data_axes else P()
    fn = _sm(
        body,
        in_specs=(tok_spec, P(None, None), P("model", None, None),
                  P("model", None, None) if glu else P(None),
                  P("model", None, None), shared_specs),
        out_specs=(tok_spec, P()))
    w_gate = params["w_gate"] if glu else jnp.zeros((1,), x.dtype)
    out, aux = fn(x, params["router"], params["w_in"], w_gate,
                  params["w_out"], shared_args)
    return out, aux[0]
