"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.block_copy.ops import apply_moves, expand_moves
from repro.kernels.block_copy.ref import block_copy_ref
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.models.decode import paged_decode_attention_gather

RNG = np.random.default_rng(7)


def rand(shape, dtype):
    x = RNG.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def make_pages(B, NB, MB, pb, seed=0):
    """A [B, MB] block table whose rows map a random number of pages of
    ``pb`` buddy-aligned pool blocks each, in random places (-1 after)."""
    rng = np.random.default_rng(seed)
    tbl = np.full((B, MB), -1, np.int32)
    for b in range(B):
        n = rng.integers(1, MB // pb + 1)
        starts = rng.choice(NB // pb, size=n, replace=False) * pb
        tbl[b, :n * pb] = (starts[:, None] + np.arange(pb)).reshape(-1)
    return jnp.asarray(tbl)


class TestPagedAttentionKernel:
    """The kernel against the gather path, its reference: the same output
    and the same softmax mass per block (heat)."""

    @staticmethod
    def check(q, pk, pv, tbl, lengths, bt, tol, window=None):
        out, heat = jax.jit(functools.partial(
            paged_decode_attention, block_tokens=bt, window=window,
            interpret=True))(q, pk, pv, tbl, lengths)
        ref, ref_heat = paged_decode_attention_gather(
            q, pk, pv, tbl, lengths, block_tokens=bt, window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(heat), np.asarray(ref_heat),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("order", [0, 1, 2])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,KVH,hd,bt,NB,MP", [
        (2, 8, 4, 32, 8, 128, 5),
        (1, 4, 1, 64, 16, 256, 3),
        (3, 4, 4, 16, 4, 192, 7),
    ])
    def test_partials_match_ref(self, order, dtype, B, H, KVH, hd, bt, NB, MP):
        """Tables of pages of one order (up to MP pages a row)."""
        pb = 4 ** order
        if NB // pb < MP:
            pytest.skip("pool too small for this class")
        q = rand((B, H, hd), dtype)
        pk = rand((NB, bt, KVH, hd), dtype)
        pv = rand((NB, bt, KVH, hd), dtype)
        tbl = make_pages(B, NB, MP * pb, pb, seed=order)
        lengths = jnp.asarray(
            RNG.integers(1, MP * pb * bt + 1, size=(B,)), jnp.int32)
        # bf16: both outputs are f32 sums rounded to bf16, so they may
        # differ by one bf16 step (2**-7 relative)
        self.check(q, pk, pv, tbl, lengths, bt,
                   2e-5 if dtype == jnp.float32 else 2 ** -7)

    def test_window_masking(self):
        B, H, KVH, hd, bt, NB, MB = 2, 4, 2, 32, 8, 128, 4
        q = rand((B, H, hd), jnp.float32)
        pk = rand((NB, bt, KVH, hd), jnp.float32)
        pv = rand((NB, bt, KVH, hd), jnp.float32)
        tbl = make_pages(B, NB, MB, 1, seed=3)
        lengths = jnp.asarray([20, 30], jnp.int32)
        self.check(q, pk, pv, tbl, lengths, bt, 2e-5, window=8)

    def test_multi_class_combine_matches_full_oracle(self):
        """Pages of orders 0, 1 and 2 in one row, and an inactive row."""
        B, H, KVH, hd, bt, NB, MB = 3, 4, 2, 32, 8, 256, 28
        q = rand((B, H, hd), jnp.float32)
        pk = rand((NB, bt, KVH, hd), jnp.float32)
        pv = rand((NB, bt, KVH, hd), jnp.float32)
        tbl = np.full((B, MB), -1, np.int32)
        tbl[0, :16] = 32 + np.arange(16)
        tbl[0, 16:20] = 100 + np.arange(4)
        tbl[0, 20:23] = [7, 3, 250]
        tbl[1, :8] = 200 + np.arange(8)
        lengths = jnp.asarray([MB * bt - 40, 60, 0], jnp.int32)
        self.check(q, pk, pv, jnp.asarray(tbl), lengths, bt, 2e-5)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,Sq,Sk,H,KVH,hd,causal,window", [
        (2, 64, 64, 4, 2, 32, True, None),
        (1, 96, 96, 4, 4, 16, True, 8),
        (2, 33, 65, 8, 2, 64, True, None),
        (1, 64, 64, 2, 1, 32, False, None),
        (1, 128, 128, 4, 1, 48, True, 32),
    ])
    def test_matches_ref(self, dtype, B, Sq, Sk, H, KVH, hd, causal, window):
        q = rand((B, Sq, H, hd), dtype)
        k = rand((B, Sk, KVH, hd), dtype)
        v = rand((B, Sk, KVH, hd), dtype)
        o = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                bq=32, bk=32, interpret=True)
        r = mha_ref(q, k, v, causal=causal, window=window)
        tol = 2e-5 if dtype == jnp.float32 else 2.5e-2
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=tol, atol=tol)

    def test_soft_cap(self):
        q = rand((1, 64, 2, 32), jnp.float32)
        k = rand((1, 64, 2, 32), jnp.float32)
        v = rand((1, 64, 2, 32), jnp.float32)
        o = flash_attention_fwd(q, k, v, soft_cap=20.0, bq=32, bk=32,
                                interpret=True)
        r = mha_ref(q, k, v, soft_cap=20.0)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-5)


class TestBlockCopyKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
    def test_moves_match_ref(self, dtype):
        pool = (jnp.arange(32 * 64).reshape(32, 4, 16) % 97).astype(dtype)
        plan = [(0, 16, 1), (8, 24, 0), (9, 25, 0)]
        src, dst = expand_moves(plan, pad_to=8)
        out = apply_moves(pool, jnp.asarray(src), jnp.asarray(dst),
                          interpret=True)
        ref = block_copy_ref(pool.reshape(32, -1), jnp.asarray(src),
                             jnp.asarray(dst)).reshape(pool.shape)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_mm_compaction_plan_applies(self):
        """End-to-end: MM compaction plan -> kernel moves keep data intact."""
        from repro.core import HWSpec, MemoryManager, make_cost_model
        mm = MemoryManager(64, make_cost_model(HWSpec(), 2, 8),
                           default_mode="never")
        mm.create_process(1, vma_blocks=64)
        mm.ensure_range(1, 0, 48)
        st = mm.procs[1]
        for lstart in list(st.page_table)[::2]:
            mm.unmap(1, lstart)
        pool = jnp.asarray(RNG.normal(size=(64, 4, 8)).astype(np.float32))
        expect = {m.phys_start: np.asarray(pool[m.phys_start])
                  for m in st.page_table.values()}
        keys = {m.phys_start: lg for lg, m in st.page_table.items()}
        mm._install(st, 60, 2, hinted=False)       # triggers compaction
        moves = mm.drain_moves()
        if moves:
            src, dst = expand_moves(moves, pad_to=None)
            pool = apply_moves(pool, jnp.asarray(src), jnp.asarray(dst),
                               interpret=True)
        for lg, m in st.page_table.items():
            if m.order == 0 and lg in keys.values():
                pass
        # verify moved rows carry their original contents
        remap = {s: d for s, d, _ in moves}
        for old_phys, data in expect.items():
            new_phys = remap.get(old_phys, old_phys)
            np.testing.assert_array_equal(np.asarray(pool[new_phys]), data)
