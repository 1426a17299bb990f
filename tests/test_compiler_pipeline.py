"""Unified compiler pipeline: lowering IR, segmented predicated unroll,
register-indexed LDCTXR, and the cross-session artifact cache.

The structural properties the pipeline must hold:

* one :func:`repro.core.lower.lower` pass produces the IR every executor
  consumes — absolute branch targets, resolved map slots, validated ctx
  offsets — and flattening/segmentation preserve decisions exactly;
* segment cuts land on loop-copy (back-edge) boundaries when one is in
  budget, and the chained dispatch is bit-identical to the single-segment
  compile and to the interpreter/JIT, whatever the cut pattern;
* ``LDCTXR`` is verified (initialized index register, const-tracked index
  inside the ctx struct) and lowered with one clamp by every backend;
* artifacts persist across "sessions" (fresh registries + caches over one
  directory) without changing a single decision.
"""

import numpy as np
import pytest

from repro.core import (Asm, ArrayMap, CTX, CTX_LEN, JitPolicy, MapRegistry,
                        PolicyVM, VerifierError, ebpf_mm_program,
                        tier_edge_admission_program)
from repro.core.cache import ArtifactCache
from repro.core.context import FaultContext
from repro.core.hooks import HOOK_FAULT, PRED_MAX_UNROLL, HookRegistry
from repro.core.lower import (lower, segment_code, unroll_lowered)
from repro.core.predicate import PredicatedPolicy


def _ctx_rows(rng, n, **kw):
    rows = []
    for _ in range(n):
        fc = FaultContext(
            addr=int(rng.integers(0, 256)), pid=1, vma_start=0,
            vma_end=256, fault_max_order=int(rng.integers(0, 4)),
            has_profile=kw.get("has_profile", 0),
            profile_map_id=0, profile_nregions=kw.get("nregions", 0),
            free_blocks=tuple(rng.integers(0, 200, 4)),
            frag=tuple(rng.integers(0, 1001, 4)),
            heat=tuple(rng.integers(0, 50, 4)),
            zero_ns_per_block=int(rng.integers(100, 2000)),
            compact_ns_per_block=int(rng.integers(100, 3000)),
            descriptor_ns=800, block_bytes=65536,
            mem_pressure=int(rng.integers(0, 1001)),
            page_tier=int(rng.integers(0, 4)),
            page_order=int(rng.integers(0, 4)),
            page_heat=int(rng.integers(0, 5000)),
            pcie_ns_per_block=int(rng.integers(100, 4000)),
            ntiers=4, tier_free=tuple(rng.integers(0, 64, 4)),
            tier_total=(64, 64, 64, 64),
            mig_cum_setup=(0, 2000, 5000, 30000),
            mig_cum_ns=(0, 800, 2800, 12800))
        rows.append(fc.vector())
    return np.stack(rows)


class TestLoweringIR:
    def test_lowered_targets_are_absolute(self):
        a = Asm()
        a.movi("r1", 3)
        a.jeqi("r1", 3, "hit")
        a.movi("r0", 0)
        a.exit()
        a.label("hit")
        a.movi("r0", 7)
        a.exit()
        lp = lower(a.build(), MapRegistry())
        jeq = lp.insns[1]
        assert jeq.target == 4          # absolute pc of "hit"
        assert lp.insns[0].target == -1

    def test_digest_covers_program_and_map_shape(self):
        maps_a, maps_b = MapRegistry(), MapRegistry()
        maps_b.register(ArrayMap(8))
        a = Asm()
        a.movi("r0", 1).exit()
        prog = a.build()
        assert lower(prog, maps_a).digest() != lower(prog, maps_b).digest()
        b = Asm()
        b.movi("r0", 2).exit()
        assert lower(prog, maps_a).digest() != \
            lower(b.build(), maps_a).digest()

    def test_unroll_cuts_on_loop_copy_boundaries(self):
        a = Asm()
        a.movi("r0", 0).movi("r1", 6)
        a.label("loop")
        for _ in range(10):
            a.addi("r0", 1)
        a.jnzdec("r1", "loop")
        a.exit()
        lp = lower(a.build(), MapRegistry())
        code, cuts = unroll_lowered(lp)
        # 2 prefix + 6 * (10-body + counter SUBI) + exit
        assert len(code) == 2 + 6 * 11 + 1
        assert set(cuts) == {2 + c * 11 for c in range(7)}
        segs = segment_code(code, cuts, limit=30)
        for start, end in segs[:-1]:
            assert end in cuts, "cut must land on a loop-copy boundary"
            assert end - start <= 30


class TestSegmentedParity:
    """Chained segments == single segment == interpreter == JIT, for cut
    budgets that slice the Fig-1 unroll every which way."""

    @pytest.fixture(scope="class")
    def fig1(self):
        maps = MapRegistry()
        m = ArrayMap(512)
        from repro.core import Profile, ProfileRegion
        Profile("app", [ProfileRegion(0, 64, (0, 9000, 90000, 900000)),
                        ProfileRegion(64, 256, (0, 30000, 0, 0))]
                ).load_into(m)
        maps.register(m)
        prog = ebpf_mm_program(0, max_regions=16)   # ~230-insn unroll
        rng = np.random.default_rng(21)
        mat = _ctx_rows(rng, 16, has_profile=1, nregions=2)
        host = [PolicyVM(prog, maps).run(r).ret for r in mat]
        return prog, maps, mat, host

    @pytest.mark.parametrize("limit", [48, 97, 200, 512])
    def test_any_cut_pattern_preserves_decisions(self, fig1, limit):
        prog, maps, mat, host = fig1
        pol = PredicatedPolicy(prog, maps, seg_limit=limit)
        if limit < pol.unrolled_len:
            assert pol.num_segments >= 2
        assert host == list(pol.run_batch(mat)), \
            f"seg_limit={limit} changed decisions"

    def test_matches_jit(self, fig1):
        prog, maps, mat, host = fig1
        assert host == list(JitPolicy(prog, maps).run_batch(mat))


class TestLDCTXR:
    def test_rejects_uninitialized_index_register(self):
        a = Asm()
        a.ldctxr("r0", "r4").exit()
        with pytest.raises(VerifierError, match="uninitialized"):
            PolicyVM(a.build(), MapRegistry())

    def test_rejects_const_index_out_of_bounds(self):
        for bad in (CTX_LEN, CTX_LEN + 9, -1):
            a = Asm()
            a.movi("r1", bad)
            a.ldctxr("r0", "r1")
            a.exit()
            with pytest.raises(VerifierError, match="out of ctx bounds"):
                PolicyVM(a.build(), MapRegistry())

    def test_const_index_in_bounds_accepted(self):
        a = Asm()
        a.movi("r1", CTX_LEN - 1)
        a.ldctxr("r0", "r1")
        a.exit()
        PolicyVM(a.build(), MapRegistry())      # must not raise

    def test_all_executors_clamp_dynamic_index_identically(self):
        # index = ADDR * 3 - 40: wanders below 0 and beyond CTX_LEN; each
        # backend must clamp to the same edge reads
        a = Asm()
        a.ldctx("r1", CTX.ADDR)
        a.muli("r1", 3)
        a.subi("r1", 40)
        a.ldctxr("r0", "r1")
        a.exit()
        prog = a.build("dyn_ldctxr")
        maps = MapRegistry()
        rng = np.random.default_rng(5)
        mat = _ctx_rows(rng, 24)
        host = [PolicyVM(prog, maps).run(r).ret for r in mat]
        assert host == list(JitPolicy(prog, maps).run_batch(mat))
        assert host == list(PredicatedPolicy(prog, maps).run_batch(mat))

    def test_edge_admission_reads_target_pool_free_list(self):
        """The upgraded tier_edge_admission_program vetoes a one-hop
        promotion when the TARGET pool's TIER_FREE_T{t} cannot back the
        page, and admits it when it can — on every backend."""
        prog = tier_edge_admission_program()
        maps = MapRegistry()
        vm = PolicyVM(prog, maps)

        def ctx(tier_free, order=2):
            return FaultContext(
                addr=0, pid=1, vma_start=0, vma_end=64, fault_max_order=0,
                has_profile=0, profile_map_id=0, profile_nregions=0,
                free_blocks=(8, 8, 8, 8), frag=(0, 0, 0, 0),
                heat=(0, 0, 0, 0), zero_ns_per_block=700,
                compact_ns_per_block=1300, descriptor_ns=800,
                block_bytes=65536, mem_pressure=100,    # plenty of headroom
                page_tier=2, page_order=order, page_heat=500_000,
                pcie_ns_per_block=3000, ntiers=4,
                tier_free=tier_free, tier_total=(64, 64, 64, 64),
                mig_cum_setup=(0, 2000, 5000, 30000),
                mig_cum_ns=(0, 800, 2800, 12800)).vector()

        room = ctx(tier_free=(64, 64, 64, 64))      # tier 1 can back 4^2
        full = ctx(tier_free=(64, 15, 64, 64))      # tier 1: 15 < 16 blocks
        assert vm.run(room).ret == 1, "hot page with room must promote"
        assert vm.run(full).ret == 2, \
            "promotion must be vetoed when the target pool is full"
        mat = np.stack([room, full])
        for backend in (JitPolicy(prog, maps),
                        PredicatedPolicy(prog, maps)):
            assert list(backend.run_batch(mat)) == [1, 2]


class TestArtifactCache:
    def _fig1_setup(self):
        maps = MapRegistry()
        m = ArrayMap(512)
        from repro.core import Profile, ProfileRegion
        Profile("app", [ProfileRegion(0, 64, (0, 9000, 90000, 900000)),
                        ProfileRegion(64, 512, (0, 0, 0, 0))]).load_into(m)
        maps.register(m)
        return ebpf_mm_program(max_regions=8), maps

    def test_cold_then_warm_identical_decisions(self, tmp_path):
        prog, maps = self._fig1_setup()
        rng = np.random.default_rng(31)
        mat = _ctx_rows(rng, 8, has_profile=1, nregions=2)
        outs, caches = [], []
        for _ in range(2):      # two "sessions" over one cache dir
            cache = ArtifactCache(tmp_path)
            reg = HookRegistry(cache=cache)
            reg.attach(HOOK_FAULT, prog, maps)
            outs.append(list(reg.run_batch(HOOK_FAULT, mat)))
            caches.append(cache)
        assert outs[0] == outs[1]
        assert caches[0].stats["unroll_misses"] == 1
        assert caches[1].stats["unroll_misses"] == 0, \
            "second session must reuse the persisted unroll artifact"
        assert caches[1].stats["unroll_hits"] == 1
        assert outs[0] == [PolicyVM(prog, maps).run(r).ret for r in mat]

    def test_corrupt_artifact_recomputes(self, tmp_path):
        prog, maps = self._fig1_setup()
        lp = lower(prog, maps)
        cache = ArtifactCache(tmp_path)
        cache.unrolled(lp)
        [p.write_bytes(b"not a pickle")
         for p in (tmp_path / "ebpf").glob("*.pkl")]
        fresh = ArtifactCache(tmp_path)
        code, _cuts = fresh.unrolled(lp)
        assert fresh.stats["unroll_misses"] == 1
        assert len(code) == len(cache._unrolled[lp.digest()][0])

    def test_disabled_cache_writes_nothing(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_CACHE_DIR", "off")
        cache = ArtifactCache()
        assert not cache.enabled
        prog, maps = self._fig1_setup()
        cache.unrolled(lower(prog, maps))   # must work purely in memory
        assert cache.stats["unroll_misses"] == 1


class TestTierSnapshotShape:
    def test_legacy_host_keys_removed_and_per_tier_list_is_api(self):
        from repro.core import (HWSpec, TieredMemoryManager, default_tier_chain,
                                make_cost_model)
        hw = HWSpec()
        cost = make_cost_model(hw, kv_heads=4, head_dim=64)
        mm = TieredMemoryManager(32, cost,
                                 tiers=default_tier_chain(hw, (16, 32, 16)))
        snap = mm.tier_snapshot()
        assert type(snap) is dict                   # plain dict, no warn shim
        assert len(snap["tiers"]) == 4
        assert snap["ntiers"] == 4
        # the deprecated 2-pool host_* aliases went through their removal
        # cycle: they named tier 1, which on this 4-tier chain is peer-HBM
        for key in ("host_blocks", "host_free_blocks",
                    "host_resident_blocks", "host_utilization_milli"):
            assert key not in snap
        assert snap["tiers"][1]["blocks"] == 16
        assert snap["tiers"][2]["blocks"] == 32
