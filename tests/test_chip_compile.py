"""Compiles of the main path for one described TPU v5e chip, at Phi-3-mini's
published widths: what the chip's compiler refuses (block shapes, scoped
VMEM, device memory) fails here, with no chip attached.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import importlib.util
import os
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import (HWSpec, MemoryManager, PredicatedPolicy,
                        ebpf_mm_program, make_cost_model)
from repro.core.context import CTX_LEN
from repro.core.isa import NUM_REGS
from repro.kernels.block_copy.ops import apply_moves
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.launch.serve import default_profile
from repro.models import PagedLayout, cache_spec, model_spec
from repro.models.common import BF16, ParamSpec
from repro.models.decode import decode_step, prefill_step

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

CFG = get_config("phi3_mini_3p8b")
BT = chip_smoke.BLOCK_TOKENS
B = chip_smoke.MAX_BATCH
MB = chip_smoke.MAX_BLOCKS
NB = chip_smoke.POOL_BLOCKS
HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("kv_heads,head_dim,pool", [
    (32, 96, NB),         # Phi-3-mini: hd 96, padded to 128 lanes
    (32, 128, 768),       # DeepSeek-LLM-7B
    (8, 128, NB),         # GQA, 4 query heads per KV head
], ids=["phi3", "deepseek", "gqa"])
def test_paged_attention_kernel(one_chip, kv_heads, head_dim, pool):
    """The paged decode kernel and its work-list derivation, as the decode
    step runs them: one program for every page order (a 64-block page
    streams as 16-block slices, so the largest VMEM block is 16 blocks)."""
    H = CFG.n_heads
    fn = jax.jit(lambda q, k, v, t, n: paged_decode_attention(
        q, k, v, t, n, block_tokens=BT))
    kv = _shape(one_chip, (pool, BT, kv_heads, head_dim), BF16)
    compiled = fn.lower(_shape(one_chip, (B, H, head_dim), BF16), kv, kv,
                        _shape(one_chip, (B, MB), jnp.int32),
                        _shape(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_copy_kernel(one_chip):
    pool = _shape(one_chip, (NB, BT, CFG.kv_heads, CFG.head_dim), BF16)
    moves = _shape(one_chip, (64,), jnp.int32)
    compiled = apply_moves.lower(pool, moves, moves).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_policy_executor(one_chip):
    """The engine's fault program over the memory manager's maps (the
    launcher's profile, a decode-sized batch) compiles for the chip as ONE
    fused program, int64 and all."""
    mm = MemoryManager(NB, make_cost_model(HWSpec(), kv_heads=1, head_dim=1,
                                           block_tokens=BT))
    mm.load_profile(default_profile(MB))
    pol = PredicatedPolicy(ebpf_mm_program(max_regions=8), mm.maps)
    assert pol.fused
    with jax.enable_x64(True):
        arrays, lens = pol._map_args()
        args = (_shape(one_chip, (B, CTX_LEN), jnp.int64),
                tuple(_shape(one_chip, a.shape, a.dtype) for a in arrays),
                _shape(one_chip, lens.shape, lens.dtype),
                _shape(one_chip, (NUM_REGS, B), jnp.int64),
                _shape(one_chip, (B,), bool), _shape(one_chip, (B,), bool),
                _shape(one_chip, (B,), jnp.int64))
        pol._fused_fn.lower(*args).compile()


def _step_args(one_chip, n_layers):
    cfg = replace(CFG, n_layers=n_layers)
    params = jax.tree.map(lambda p: _shape(one_chip, p.shape, BF16),
                          model_spec(cfg),
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    layout = PagedLayout(num_blocks=NB, block_tokens=BT, max_blocks=MB)
    cache = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                         cache_spec(cfg, layout, B))
    return cfg, layout, params, cache


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("n_layers,attn_impl", [
    (2, "gather"), (32, "gather"), (2, "paged"), (32, "paged")],
    ids=["2", "32", "paged-2", "paged-32"])
def test_decode_step_fits_one_chip(one_chip, n_layers, attn_impl):
    """The engine's decode step (gather attention or the paged kernel,
    active mask) compiles, takes weights + pool as arguments, and with its
    un-donated output pool and temporaries fits the chip at chip_smoke's
    pool size."""
    cfg, layout, params, cache = _step_args(one_chip, n_layers)
    fn = jax.jit(lambda p, c, t, n, tb, a: decode_step(
        p, cfg, c, t, n, tb, layout, active=a, attn_impl=attn_impl))
    vec = _shape(one_chip, (B,), jnp.int32)
    compiled = fn.lower(params, cache, vec, vec,
                        _shape(one_chip, (B, MB), jnp.int32),
                        _shape(one_chip, (B,), bool)).compile()
    ma = compiled.memory_analysis()
    inputs = _tree_bytes(params) + _tree_bytes(cache)
    assert inputs <= ma.argument_size_in_bytes < inputs + (1 << 20)
    assert ma.output_size_in_bytes >= _tree_bytes(cache)
    assert _device_bytes(compiled) < HBM_BYTES


def test_prefill_step_fits_one_chip(one_chip):
    """The longest prompt's prefill at full depth fits beside the pool."""
    cfg, layout, params, cache = _step_args(one_chip, CFG.n_layers)
    S = chip_smoke.PROMPT_TOKENS[1]
    fn = jax.jit(lambda p, c, t, tb, last: prefill_step(
        p, cfg, c, t, tb, layout, chunk=256, last_index=last))
    compiled = fn.lower(params, cache,
                        _shape(one_chip, (1, S), jnp.int32),
                        _shape(one_chip, (1, MB), jnp.int32),
                        _shape(one_chip, (1,), jnp.int32)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
