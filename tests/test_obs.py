"""Telemetry subsystem tests: ring-buffer event parity across all three
policy executors (interpreter / while+switch JIT / segmented predicated),
histogram + counter behavior, exporter schema stability, hook-registry
drain semantics, and the artifact cache's LRU eviction.

The parity tests are the observability analogue of the differential
harness: ``bpf_ringbuf_output`` must produce BIT-IDENTICAL event streams
(including overflow drop counts) whichever executor ran the program —
otherwise a trace taken on the batched path lies about what the scalar
reference semantics did.
"""

from __future__ import annotations

import json
import re

import numpy as np
import pytest

from repro.core import (Asm, MapRegistry, MemoryManager, PolicyVM,
                        ebpf_mm_program, make_cost_model, HWSpec, JitPolicy,
                        Profile, ProfileRegion)
from repro.core.cache import ArtifactCache
from repro.core.context import CTX, CTX_LEN, FaultKind
from repro.core.hooks import HOOK_FAULT, HookRegistry
from repro.core.lower import RB_MAX_PER_RUN, lower
from repro.core.predicate import PredicatedPolicy
from repro.core.vm import HELPER_RINGBUF_OUTPUT, HELPER_TRACE
from repro.obs import (EV_FAULT, EV_HOOK, EV_MIGRATE_HOP, EV_PROG_BASE,
                       EV_PROG_TRACE, EventRing, Log2Hist, Telemetry,
                       chrome_trace, flatten_metrics, render_prometheus,
                       tag_name)


# ------------------------------------------------------------ ring buffer
class TestEventRing:
    def test_fifo_and_counters(self):
        r = EventRing(capacity=4)
        for i in range(3):
            assert r.push(100 + i, EV_PROG_BASE, i, 2 * i, 3 * i)
        assert len(r) == 3
        got = r.drain()
        assert [tuple(e) for e in got] == \
            [(100 + i, EV_PROG_BASE, i, 2 * i, 3 * i) for i in range(3)]
        assert len(r) == 0
        assert r.emitted == 3 and r.dropped == 0

    def test_overflow_drops(self):
        r = EventRing(capacity=2)
        assert r.push(1, 1, 0, 0, 0)
        assert r.push(2, 1, 0, 0, 0)
        assert not r.push(3, 1, 0, 0, 0)       # full -> dropped
        snap = r.snapshot()
        assert snap["pending"] == 2
        assert snap["emitted"] == 2
        assert snap["dropped"] == 1
        # drain frees capacity again
        assert len(r.drain()) == 2
        assert r.push(4, 1, 0, 0, 0)

    def test_tag_name(self):
        assert tag_name(EV_FAULT) == "mm_fault"
        assert tag_name(EV_PROG_BASE).startswith("prog")


# -------------------------------------------------------------- histogram
class TestLog2Hist:
    def test_bucket_edges(self):
        h = Log2Hist()
        h.observe(0)          # bucket 0
        h.observe(1)          # bucket 1
        h.observe(2)          # bucket 2
        h.observe(3)          # bucket 2
        h.observe(1024)       # bucket 11
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["sum"] == 1030
        assert snap["buckets"]["2"] == 2

    def test_percentile_upper_bound(self):
        h = Log2Hist()
        for v in (10, 20, 3000):
            h.observe(v)
        # p50 lands in the bucket holding 20 (bucket 5: 16..31)
        assert h.percentile(50) == 31
        assert h.percentile(99) >= 3000

    def test_observe_many_matches_loop(self):
        vals = np.array([0, 1, 5, 9, 120, 4096, 123456])
        a, b = Log2Hist(), Log2Hist()
        for v in vals:
            a.observe(int(v))
        b.observe_many(vals)
        assert np.array_equal(a.counts, b.counts)
        assert a.count == b.count and a.total == b.total


# ------------------------------------------------- executor parity (Asm)
def _emit_program(trips: int = 3):
    """A bounded loop emitting one custom event per trip, then a legacy
    HELPER_TRACE emission — covers both ring-buffer helpers."""
    a = Asm()
    a.movi("r6", trips)
    a.ldctx("r5", CTX.ADDR)
    a.label("loop")
    a.movi("r1", EV_PROG_BASE + 8)
    a.mov("r2", "r5")
    a.movi("r3", 7)
    a.mov("r4", "r6")
    a.call(HELPER_RINGBUF_OUTPUT)
    a.jnzdec("r6", "loop")
    a.movi("r1", 99)
    a.call(HELPER_TRACE)
    a.movi("r0", 1)
    a.exit()
    return a.build("emit_parity")


def _overflow_program(trips: int = 64):
    """Emits 2x RB_MAX_PER_RUN slots (two call sites in a max-trip loop):
    every executor must agree on the drop count and the -1 helper return."""
    a = Asm()
    a.movi("r6", trips)
    a.label("loop")
    for k in (9, 10):
        a.movi("r1", EV_PROG_BASE + k)
        a.movi("r2", 0)
        a.movi("r3", 0)
        a.mov("r4", "r6")
        a.call(HELPER_RINGBUF_OUTPUT)
    a.jnzdec("r6", "loop")
    a.mov("r0", "r0")   # r0 = last helper return (-1 once saturated)
    a.exit()
    return a.build("emit_overflow")


def _ctx_mat(n: int) -> np.ndarray:
    mat = np.zeros((n, CTX_LEN), dtype=np.int64)
    mat[:, CTX.ADDR] = np.arange(n) * 3 + 1
    mat[:, CTX.KTIME_NS] = 5_000 + np.arange(n)
    return mat


def _interp_events(vm: PolicyVM, mat: np.ndarray):
    evs, drops, rets = [], 0, []
    for row in mat:
        res = vm.run(row)
        evs.extend(tuple(e) for e in res.events)
        drops += res.dropped
        rets.append(res.ret)
    return evs, drops, rets


class TestExecutorEventParity:
    def test_identical_streams(self):
        prog = _emit_program(trips=3)
        maps = MapRegistry()
        vm = PolicyVM(prog, maps)
        lp = vm.lowered
        assert lp.facts["rb_cap"] >= 4    # 3 loop emissions + 1 trace
        mat = _ctx_mat(6)
        ref_ev, ref_drops, ref_r0 = _interp_events(vm, mat)
        assert ref_drops == 0
        assert any(e[1] == EV_PROG_TRACE for e in ref_ev)
        for backend in (JitPolicy(lp, maps),
                        PredicatedPolicy(lp, maps, seg_limit=8)):
            r0 = backend.run_batch(mat)
            ev, drops = backend.take_events(mat.shape[0])
            assert [tuple(e) for e in ev] == ref_ev, type(backend).__name__
            assert drops == ref_drops
            assert list(r0) == ref_r0
            # drained: a second take returns nothing
            assert backend.take_events(mat.shape[0]) == ([], 0)

    def test_overflow_drop_parity(self):
        prog = _overflow_program(trips=64)
        maps = MapRegistry()
        vm = PolicyVM(prog, maps)
        assert vm.lowered.facts["rb_cap"] == RB_MAX_PER_RUN
        mat = _ctx_mat(5)
        ref_ev, ref_drops, ref_r0 = _interp_events(vm, mat)
        assert ref_drops == 5 * (2 * 64 - RB_MAX_PER_RUN)
        assert all(r == -1 for r in ref_r0)   # saturated helper returns -1
        for backend in (JitPolicy(vm.lowered, maps),
                        PredicatedPolicy(vm.lowered, maps, seg_limit=64)):
            r0 = backend.run_batch(mat)
            ev, drops = backend.take_events(mat.shape[0])
            assert [tuple(e) for e in ev] == ref_ev, type(backend).__name__
            assert drops == ref_drops
            assert list(r0) == ref_r0

    def test_emit_free_program_has_no_rb_state(self):
        a = Asm()
        a.movi("r0", 4).exit()
        lp = lower(a.build(), MapRegistry())
        assert lp.facts["rb_cap"] == 0
        jit = JitPolicy(lp, MapRegistry())
        assert jit.rb_cap == 0
        assert jit.run_batch(_ctx_mat(4)).tolist() == [4] * 4
        assert jit.take_events(4) == ([], 0)


# ----------------------------------------------- hook registry ring drain
class TestHookRegistryDrain:
    def test_padding_lanes_excluded(self):
        tel = Telemetry()
        reg = HookRegistry(telemetry=tel)
        reg.attach(HOOK_FAULT, _emit_program(trips=2), MapRegistry())
        n = 5                          # pads to 8; 3 padded lanes discarded
        reg.run_batch(HOOK_FAULT, _ctx_mat(n))
        evs = tel.ring.drain()
        prog_evs = [e for e in evs if e[1] >= EV_PROG_BASE]
        assert len(prog_evs) == n * 2
        trace_evs = [e for e in evs if e[1] == EV_PROG_TRACE]
        assert len(trace_evs) == n
        hook_evs = [e for e in evs if e[1] == EV_HOOK]
        assert len(hook_evs) == 1 and hook_evs[0][3] == n
        assert tel.prog_lane_drops == 0

    def test_no_telemetry_is_silent(self):
        reg = HookRegistry()           # telemetry=None: the default config
        reg.attach(HOOK_FAULT, _emit_program(trips=2), MapRegistry())
        out = reg.run_batch(HOOK_FAULT, _ctx_mat(4))
        assert out.shape == (4,)


# ------------------------------------------------- workload-level parity
EXECUTORS = ("interp", "jit", "segmented")


def _run_traced_workload(mode, monkeypatch):
    """Drive a MemoryManager with the TRACED Fig-1 program through one
    executor; return the program-tag + fault event stream."""
    tel = Telemetry()
    cost = make_cost_model(HWSpec(), kv_heads=4, head_dim=64)
    # default_mode="never": unprofiled/fallback addresses fault per-block,
    # so the walk below produces a long stream of program + fault events
    mm = MemoryManager(160, cost, default_mode="never", telemetry=tel)
    mm.load_profile(Profile("app", [
        ProfileRegion(0, 8, (0, 150_000, 0, 0)),
        ProfileRegion(8, 24, (0, 0, 0, 0)),
    ]))
    mm.attach_fault_program(ebpf_mm_program(max_regions=8, trace=True))
    if mode == "jit":
        for ap in mm.hooks._hooks.values():
            if ap is not None:
                ap.pred_unfit = True
    elif mode == "segmented":
        import repro.core.hooks as hooks_mod
        monkeypatch.setattr(hooks_mod, "PRED_MAX_UNROLL", 64)
    for pid in (1, 2, 3):
        mm.create_process(pid, app="app", vma_blocks=24)
    rng = np.random.default_rng(0)
    for step in range(24):
        reqs = [(pid, step, FaultKind.FIRST_TOUCH) for pid in (1, 2, 3)]
        if mode == "interp":
            for pid, addr, kind in reqs:
                mm.ensure_mapped(pid, addr, kind)
        else:
            mm.fault_batch(reqs)
        for pid in (1, 2, 3):
            mm.record_access(pid, rng.random(step + 1) * 2)
        mm.tick()
    if mode == "segmented":
        ap = mm.hooks._hooks[HOOK_FAULT]
        assert ap.pred is not None and ap.pred.num_segments >= 2
    evs = [tuple(e) for e in tel.ring.drain()]
    # the scalar path interleaves program-event/install pairs while the
    # batched path drains a whole batch's program events before installing
    # — so parity is asserted PER TAG CLASS, where order is deterministic
    return {"prog": [e for e in evs if e[1] >= EV_PROG_BASE],
            "fault": [e for e in evs if e[1] == EV_FAULT]}


class TestWorkloadEventParity:
    def test_all_executors_identical(self, monkeypatch):
        streams = {m: _run_traced_workload(m, monkeypatch)
                   for m in EXECUTORS}
        ref = streams["interp"]
        assert len(ref["prog"]) > 30       # the program really traced
        assert len(ref["fault"]) > 30      # the mm tracepoints really fired
        for mode in ("jit", "segmented"):
            assert streams[mode]["prog"] == ref["prog"], \
                f"{mode} program event stream diverged from interpreter"
            assert streams[mode]["fault"] == ref["fault"], \
                f"{mode} fault event stream diverged from interpreter"


# -------------------------------------------------------- exporter schema
def _populated_telemetry() -> Telemetry:
    tel = Telemetry()
    tel.emit(EV_FAULT, 1, 5, 2, ts=1_000)
    tel.emit(EV_PROG_BASE, 5, 1, 3, ts=2_000)
    # a demotion hop (tier 0 -> 1, 4096 bytes, 30 us modeled)
    tel.emit(EV_MIGRATE_HOP, 1, 4096, 30_000, ts=3_000)
    tel.observe_hook("mm_fault", 12_000, 4)
    tel.observe_migrate(30_000)
    tel.inc("backend_builds")
    tel.observe_residency(np.array([0, 1]), np.array([1, 0]),
                          np.array([4, 1]))
    return tel


class TestTelemetrySchema:
    def test_snapshot_schema_stable(self):
        snap = _populated_telemetry().snapshot()
        assert set(snap) == {"enabled", "ring", "hooks", "migrate_path_ns",
                             "counters", "residency_block_ticks"}
        assert set(snap["ring"]) == {"capacity", "pending", "emitted",
                                     "dropped", "prog_lane_drops"}
        hook = snap["hooks"]["mm_fault"]
        assert set(hook) == {"invoke_ns", "batch_size"}
        assert set(hook["invoke_ns"]) == {"count", "sum", "p50", "p99",
                                          "buckets"}
        assert snap["counters"]["backend_builds"] == 1
        assert snap["residency_block_ticks"]["t0_o1"] == 4

    def test_disabled_snapshot(self):
        tel = Telemetry(enabled=False)
        assert tel.snapshot()["enabled"] is False
        tel.emit(EV_FAULT, 1, 2, 3)            # no-op, not an error
        assert tel.ring.snapshot()["pending"] == 0

    def test_chrome_trace_structure(self, tmp_path):
        tel = _populated_telemetry()
        doc = chrome_trace(tel)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        events = doc["traceEvents"]
        phases = {e["ph"] for e in events}
        assert "X" in phases            # migration-hop spans
        assert "i" in phases            # ring instants
        assert "M" in phases            # process/thread metadata
        for e in events:
            assert {"ph", "pid", "name"} <= set(e)
        # the ring's modeled-clock track only: host spans live in the JAX
        # profiler's trace
        assert {e["pid"] for e in events} == {2}
        hop = [e for e in events if e["ph"] == "X"]
        assert hop[0]["name"] == "hop t0->t1" and hop[0]["dur"] == 30.0
        # round-trips through JSON (perfetto-loadable)
        path = tmp_path / "trace.json"
        from repro.obs import write_chrome_trace
        write_chrome_trace(tel, path)
        assert json.loads(path.read_text())["traceEvents"]

    def test_metrics_flatten_and_prometheus(self):
        flat = flatten_metrics({
            "engine": {"steps": 3, "done": True},
            "tier": {"tiers": [{"blocks": 4}]},
            "skip": {"name": "str-dropped"},
        })
        assert flat["engine_steps"] == 3
        assert flat["engine_done"] == 1
        assert flat["tier_tiers_0_blocks"] == 4
        assert not any("name" in k for k in flat)
        text = render_prometheus(flat)
        assert text.endswith("\n")
        for line in text.strip().splitlines():
            assert re.fullmatch(r"repro_[a-zA-Z0-9_]+ -?[0-9.eE+-]+", line), \
                line
        # deterministic ordering
        assert text == render_prometheus(dict(reversed(list(flat.items()))))


# --------------------------------------------------------- cache eviction
class TestCacheLRUEviction:
    def test_size_cap_evicts_oldest(self, tmp_path):
        cache = ArtifactCache(tmp_path, max_bytes=1)   # everything over cap
        progs = []
        for trips in (3, 4, 5):
            lp = lower(_emit_program(trips=trips), MapRegistry())
            cache.unrolled(lp)
            progs.append(lp)
        pkls = list((tmp_path / "ebpf").glob("*.pkl"))
        # each write evicts the previous entry; the just-written one is kept
        assert len(pkls) == 1
        assert cache.stats["evictions"] == 2

    def test_generous_cap_keeps_all(self, tmp_path):
        cache = ArtifactCache(tmp_path, max_bytes=64 * 1024 * 1024)
        for trips in (3, 4):
            cache.unrolled(lower(_emit_program(trips=trips), MapRegistry()))
        assert len(list((tmp_path / "ebpf").glob("*.pkl"))) == 2
        assert cache.stats["evictions"] == 0
