"""The paged decode kernel on the served decode step, on the CPU with Pallas
in TPU interpret mode at small shapes: ``decode_step(attn_impl="paged")``
against the gather path (logits and the per-block heat), the on-device page
classes against the memory manager's own mapping, and the engine's choice
of backend."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import get_smoke_config
from repro.core import HWSpec, MemoryManager, NUM_ORDERS, make_cost_model
from repro.kernels.paged_attention.ops import page_classes
from repro.kernels.paged_attention.ref import page_classes_ref
from repro.models import PagedLayout, cache_init, materialize, model_spec
from repro.models.decode import (decode_page_work, decode_step, page_reads,
                                 paged_kernel_supported)
from repro.serving import engine as engine_mod
from repro.serving import Request, ServingEngine

BT, B, MB, NB = 4, 4, 72, 256
F32 = jnp.float32


def _cfg(window: bool = False):
    cfg = replace(get_smoke_config("phi3_mini_3p8b"), n_layers=2,
                  n_heads=4, kv_heads=2)          # GQA: 2 heads per KV head
    if window:
        cfg = replace(cfg, attn=replace(cfg.attn, window=10,
                                        pattern=("l", "g")))
    return cfg


def _run(t, lengths, active):
    """(table, lengths, active): lengths exclude the token being decoded."""
    return (np.asarray(t, np.int32), np.asarray(lengths, np.int32),
            np.asarray(active, bool))


def _table():
    return np.full((B, MB), -1, np.int32)


def case_mixed_orders():
    t = _table()
    t[0, :64] = 64 + np.arange(64)                 # order 3
    t[0, 64:68] = 16 + np.arange(4)                # order 1
    t[0, 68] = 5                                   # order 0
    t[1, :16] = 128 + np.arange(16)                # order 2
    t[1, 16:20] = 200 + np.arange(4)               # order 1
    t[1, 20] = 7
    t[2, :3] = [9, 3, 250]
    return _run(t, [271, 82, 10, 0], [1, 1, 1, 0])


def case_contiguous_order0():
    # mapped one block at a time, but physically in order and aligned: the
    # table alone makes it an order-2 page and an order-1 page
    t = _table()
    t[0, :20] = 32 + np.arange(20)
    t[1, :6] = [1, 2, 3, 4, 5, 6]                  # in order, not aligned
    return _run(t, [79, 22, 0, 0], [1, 1, 0, 0])


def case_shared_blocks():
    t = _table()
    shared = 40 + np.arange(8)                     # a prefix read by two rows
    t[0, :8], t[1, :8] = shared, shared
    t[0, 8:12] = [100, 101, 102, 103]
    t[1, 8:10] = [17, 90]
    t[2, :8] = shared
    return _run(t, [45, 39, 31, 0], [1, 1, 1, 0])


def case_inactive_row_and_hole():
    t = _table()
    t[0, :12] = 140 + np.arange(12)
    t[0, 4] = -1                                   # a hole inside the row
    t[1, :10] = 60 + np.arange(10)                 # stale row, inactive
    t[2, :4] = [8, 9, 10, 11]
    return _run(t, [47, 39, 13, 0], [1, 0, 1, 0])


def case_length_edges():
    t = _table()
    t[0, 0] = 3                                    # one token
    t[1, :2] = [20, 21]                            # ends on a block boundary
    t[2, :MB] = np.arange(MB)                      # the whole table
    t[3, :64] = 128 + np.arange(64)
    return _run(t, [0, 2 * BT - 1, MB * BT - 1, 64 * BT - 1], [1, 1, 1, 1])


CASES = {f.__name__[5:]: f for f in (
    case_mixed_orders, case_contiguous_order0, case_shared_blocks,
    case_inactive_row_and_hole, case_length_edges)}


_STEPS: dict = {}


def _step(cfg, params, cache, tbl, lengths, active, impl):
    """One decode step, jitted once per (config, backend): the cases share
    their shapes."""
    key = (cfg, impl)
    if key not in _STEPS:
        layout = PagedLayout(num_blocks=NB, block_tokens=BT, max_blocks=MB)
        _STEPS[key] = jax.jit(lambda p, c, t, n, a: decode_step(
            p, cfg, c, jnp.arange(B, dtype=jnp.int32) + 3, n, t, layout,
            active=a, attn_impl=impl,
            compute_dtype=jax.tree.leaves(c)[0].dtype))
    logits, _, heat = _STEPS[key](params, cache, jnp.asarray(tbl),
                                  jnp.asarray(lengths), jnp.asarray(active))
    return np.asarray(logits, np.float32), np.asarray(heat)


def _setup(cfg, dtype):
    layout = PagedLayout(num_blocks=NB, block_tokens=BT, max_blocks=MB)
    params = jax.tree.map(lambda x: x.astype(dtype), materialize(
        jax.random.PRNGKey(0), model_spec(cfg)))
    cache = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(1), x.shape,
                                    F32).astype(dtype),
        cache_init(cfg, layout, B))
    return params, cache


@pytest.mark.parametrize("case,dtype,window", [
    *[(c, "float32", False) for c in CASES],
    ("mixed_orders", "float32", True),
    ("length_edges", "float32", True),
    ("mixed_orders", "bfloat16", False),
])
def test_paged_decode_step_matches_gather(case, dtype, window):
    """Same logits and heat as the gather path, to f32 tolerance: the
    kernel takes K/V in the pool's dtype and computes in f32, as the gather
    path does, and only the order of its f32 sums differs."""
    cfg = _cfg(window)
    params, cache = _setup(cfg, jnp.dtype(dtype))
    tbl, lengths, active = CASES[case]()
    ref_logits, ref_heat = _step(cfg, params, cache, tbl, lengths, active,
                                 "gather")
    with pltpu.force_tpu_interpret_mode():
        logits, heat = _step(cfg, params, cache, tbl, lengths, active,
                             "paged")
    rows = active
    tol = 2e-5
    np.testing.assert_allclose(logits[rows], ref_logits[rows],
                               rtol=tol, atol=tol * np.abs(ref_logits).max())
    np.testing.assert_allclose(heat, ref_heat, rtol=tol, atol=tol)
    assert np.all(heat[~rows] == 0)
    # the kernel reads exactly the live blocks, each once per layer
    layout = PagedLayout(num_blocks=NB, block_tokens=BT, max_blocks=MB)
    works = decode_page_work(cfg, jnp.asarray(tbl), jnp.asarray(lengths),
                             layout, jnp.asarray(active))
    reads = np.asarray(page_reads(cfg, works))
    t = np.where(active[:, None], tbl, -1)
    end = np.where(active, (lengths + BT) // BT, 0)
    first = np.zeros(B, np.int64)
    want = np.zeros(NUM_ORDERS, np.int64)
    for w, n in ((None, 1 if window else 2), (10, 1 if window else 0)):
        if w is not None:
            first = np.maximum(lengths + 1 - w, 0) // BT
        cls = page_classes_ref(t, first, end)
        want += n * np.bincount(cls[cls >= 0], minlength=NUM_ORDERS)
    np.testing.assert_array_equal(reads, want)


def test_contiguous_order0_blocks_read_as_pages():
    tbl, lengths, active = case_contiguous_order0()
    cls = np.asarray(page_classes(jnp.asarray(tbl), jnp.zeros(B, jnp.int32),
                                  jnp.asarray((lengths + BT) // BT)))
    assert list(cls[0, :20]) == [2] * 16 + [1] * 4
    assert list(cls[1, :6]) == [0] * 6


def _mm_after_faults_compaction_and_borrow():
    mm = MemoryManager(64, make_cost_model(HWSpec(), kv_heads=4,
                                           head_dim=64), default_mode="never")
    # a process faulted in block by block, then fragmented: every other
    # block freed, so no aligned run of 4 is free
    mm.create_process(1, vma_blocks=64)
    mm.ensure_range(1, 0, 64)
    for lg in list(mm.procs[1].page_table)[::2]:
        mm.unmap(1, lg)
    # an order-2 page that has to compact first
    assert mm._install(mm.procs[1], 48, 2, hinted=False).order == 2
    assert mm.stats.compactions >= 1
    # a borrower of shared (prefix-cache) blocks, then pages of its own
    mm.create_process(2, vma_blocks=64)
    shared = [mm.cache_alloc_block() for _ in range(2)]
    mm.map_shared(2, 0, list(enumerate(shared)))
    assert mm._install(mm.procs[2], 4, 1, hinted=False).order == 1
    mm.ensure_range(2, 2, 4)
    return mm


def test_page_classes_cover_the_memory_managers_pages():
    """Every page the MM mapped with order c is found as a page of order c
    or larger: the device derivation never splits what the MM placed."""
    mm = _mm_after_faults_compaction_and_borrow()
    pids = [1, 2]
    tbl = np.stack([mm.block_table(p, MB) for p in pids])
    cls = np.asarray(page_classes(jnp.asarray(tbl), jnp.zeros(2, jnp.int32),
                                  jnp.full(2, MB, jnp.int32)))
    np.testing.assert_array_equal(
        cls, page_classes_ref(tbl, [0, 0], [MB, MB]))
    seen = 0
    for order, rows in mm.page_lists_by_order(pids).items():
        n = 4 ** order
        for slot, lpage, _phys in rows:
            span = cls[slot, lpage * n:(lpage + 1) * n]
            assert np.all(span >= order), (order, slot, lpage, span)
            seen += order > 0
    assert seen > 0
    assert np.all(cls[tbl < 0] == -1)


def test_paged_kernel_supported_by_layer_kind():
    assert paged_kernel_supported(get_smoke_config("phi3_mini_3p8b"))
    assert paged_kernel_supported(_cfg(window=True))
    capped = get_smoke_config("phi3_mini_3p8b")
    capped = replace(capped, attn=replace(capped.attn, logit_soft_cap=30.0))
    assert not paged_kernel_supported(capped)
    assert not paged_kernel_supported(
        get_smoke_config("deepseek_v2_lite_16b"))            # MLA
    assert not paged_kernel_supported(get_smoke_config("mamba2_1p3b"))


def _engine(cfg, **kw):
    cfg = replace(cfg, n_layers=2)
    params = materialize(jax.random.PRNGKey(0), model_spec(cfg))
    layout = PagedLayout(num_blocks=64, block_tokens=4, max_blocks=16)
    return ServingEngine(cfg, params, layout, max_batch=3, policy="thp",
                         cache_dtype=F32, **kw)


def _serve(eng):
    for rid, n in enumerate((9, 22)):
        eng.submit(Request(rid=rid, prompt=list(range(3, 3 + n)),
                           max_new_tokens=3))
    eng.run(max_steps=40)
    return eng.finished


def test_engine_chooses_gather_off_the_tpu():
    eng = _engine(get_smoke_config("deepseek_7b"))
    assert eng.attn_impl == "gather"
    capped = get_smoke_config("deepseek_7b")
    capped = replace(capped, attn=replace(capped.attn, logit_soft_cap=30.0))
    assert _engine(capped).attn_impl == "gather"


def test_engine_paged_serves_the_same_tokens_and_counts_reads(monkeypatch):
    """With the kernel chosen (the TPU's choice, steered here), the engine
    serves the gather engine's tokens and counts the blocks it read."""
    cfg = get_smoke_config("deepseek_7b")
    gather = _engine(cfg)
    want = _serve(gather)
    assert sum(gather.stats.kv_blocks_read_by_order) == 0
    monkeypatch.setattr(engine_mod, "decode_attn_impl",
                        lambda cfg, cache: "paged")
    with pltpu.force_tpu_interpret_mode():
        paged = _engine(cfg)
        assert paged.attn_impl == "paged"
        got = _serve(paged)
    assert got == want
    reads = paged.stats.kv_blocks_read_by_order
    assert reads[1] + reads[2] > 0 and all(r >= 0 for r in reads)
    assert paged.metrics()["engine_kv_blocks_read_by_order_0"] == reads[0]
