"""Online profiling plane: verified profiler programs over the live DAMON
stream, the sampled HOOK_PROFILE surface, and host-side profile synthesis.

Four layers pinned here:

* the three shipped profiler programs (WSS/idle estimator, log2
  heat-histogram accumulator, promotion-benefit scorer) pass the verifier
  and decide + emit BIT-IDENTICALLY on the interpreter, the while+switch
  JIT and the segmented predicated executor — the profiling plane obeys
  the same parity contract as every other hook;
* ``mm.profile_scan``: one batched HOOK_PROFILE invocation per sampled
  process, rows aligned with the DAMON region snapshot;
* the ProfileSynthesizer: scans fold into profiles in the offline
  ``profile_from_heat`` mold, hot-reloads are map WRITEs (verified map
  ids survive), convergence stops the reload churn, and the EV_WSS /
  EV_PROFILE attribution events + WSS curve land in telemetry;
* exporter schema: the new event tags have stable names, and the Chrome
  trace grows the ``mm profiler`` track (WSS counter series, heat-bucket
  counters, reload instants).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.core import (HWSpec, JitPolicy, MapRegistry, MemoryManager,
                        PolicyVM, PredicatedPolicy, Profile, ProfileRegion,
                        ProfileSynthesizer, make_cost_model,
                        profile_benefit_program,
                        profile_heat_histogram_program, profile_wss_program)
from repro.core.context import CTX, FIXED_POINT, ctx_batch
from repro.core.hooks import HOOK_PROFILE
from repro.obs import (EV_PROFILE, EV_WSS, PROF_TAG_BENEFIT, PROF_TAG_HEAT,
                       PROF_TAG_WSS, Telemetry, chrome_trace, tag_name)

PROFILER_PROGRAMS = (profile_wss_program, profile_heat_histogram_program,
                     profile_benefit_program)


def mk_mm(blocks=256, **kw):
    cost = make_cost_model(HWSpec(), kv_heads=4, head_dim=64)
    return MemoryManager(blocks, cost, **kw)


def _region_ctx(n: int) -> np.ndarray:
    """A batch of synthetic DAMON-region rows spanning idle, lukewarm and
    hot regions of varying sizes (including spans too small for any large
    order — the benefit program's fit check)."""
    rng = np.random.default_rng(7)
    mat = ctx_batch(n)
    start = 0
    for i in range(n):
        span = int(rng.integers(1, 40))
        mat[i, CTX.PROF_REGION_START] = start
        mat[i, CTX.PROF_REGION_END] = start + span
        start += span
        mat[i, CTX.PROF_REGION_HEAT] = int(rng.integers(0, 9_000))
        mat[i, CTX.PROF_REGION_AGE] = int(rng.integers(0, 12))
    mat[:, CTX.PID] = 3
    mat[:, CTX.PROF_MAPPED_BLOCKS] = start
    mat[:, CTX.PROF_WINDOW] = 5
    mat[:, CTX.KTIME_NS] = 1_000_000 + np.arange(n)
    mat[:, CTX.DESCRIPTOR_NS] = 100
    return mat


# --------------------------------------------------- executor parity
class TestProfilerProgramParity:
    @pytest.mark.parametrize("factory", PROFILER_PROGRAMS,
                             ids=lambda f: f.__name__)
    def test_three_executors_bit_identical(self, factory):
        prog = factory()
        maps = MapRegistry()
        vm = PolicyVM(prog, maps)          # verifier accepts at attach
        assert vm.lowered.facts["rb_cap"] >= 1   # each lane emits once
        mat = _region_ctx(11)
        ref_ev, ref_drops, ref_r0 = [], 0, []
        for row in mat:
            res = vm.run(row)
            ref_ev.extend(tuple(e) for e in res.events)
            ref_drops += res.dropped
            ref_r0.append(res.ret)
        assert len(ref_ev) == 11           # exactly one emission per region
        for backend in (JitPolicy(vm.lowered, maps),
                        PredicatedPolicy(vm.lowered, maps, seg_limit=32)):
            r0 = backend.run_batch(mat)
            ev, drops = backend.take_events(mat.shape[0])
            name = type(backend).__name__
            assert [tuple(e) for e in ev] == ref_ev, name
            assert drops == ref_drops, name
            assert list(r0) == ref_r0, name

    def test_wss_semantics(self):
        vm = PolicyVM(profile_wss_program(idle_milli=50), MapRegistry())
        mat = _region_ctx(2)
        mat[0, CTX.PROF_REGION_START], mat[0, CTX.PROF_REGION_END] = 0, 10
        mat[0, CTX.PROF_REGION_HEAT] = 49          # idle: below threshold
        mat[1, CTX.PROF_REGION_START], mat[1, CTX.PROF_REGION_END] = 10, 16
        mat[1, CTX.PROF_REGION_HEAT] = 800
        cold = vm.run(mat[0])
        hot = vm.run(mat[1])
        assert cold.ret == 0                       # PROFILE_COLD
        assert hot.ret == 800                      # hot score = heat
        # emitted (tag, pid, wss_contribution, span)
        assert cold.events[0][1:] == (PROF_TAG_WSS, 3, 0, 10)
        assert hot.events[0][1:] == (PROF_TAG_WSS, 3, 6, 6)

    def test_heat_histogram_bucket(self):
        vm = PolicyVM(profile_heat_histogram_program(), MapRegistry())
        mat = _region_ctx(3)
        for row, heat in zip(mat, (0, 1024, 5000)):
            row[CTX.PROF_REGION_HEAT] = heat
        buckets = [vm.run(row).ret for row in mat]
        assert buckets[0] == 0
        assert buckets[1] == 10                    # floor(log2(1024))
        assert buckets[2] == 12                    # floor(log2(5000))

    def test_benefit_respects_region_fit(self):
        vm = PolicyVM(profile_benefit_program(), MapRegistry())
        mat = _region_ctx(2)
        for row in mat:
            row[CTX.PROF_REGION_HEAT] = 8_000
            row[CTX.DESCRIPTOR_NS] = 1_000
        mat[0, CTX.PROF_REGION_START], mat[0, CTX.PROF_REGION_END] = 0, 3
        mat[1, CTX.PROF_REGION_START], mat[1, CTX.PROF_REGION_END] = 0, 64
        small = vm.run(mat[0])
        big = vm.run(mat[1])
        # a 3-block region fits no order >= 1: nothing scores
        assert small.ret == 0 and small.events[0][3] == 0
        # a 64-block hot region scores some order with positive net benefit
        assert big.ret > 0
        assert 1 <= big.events[0][3] <= 3          # a1 = chosen order


# --------------------------------------------------------- profile_scan
class TestProfileScan:
    def test_rows_align_with_damon_regions(self):
        mm = mk_mm()
        mm.create_process(1, app="app", vma_blocks=64)
        mm.attach_profile_program(profile_wss_program())
        heat = np.zeros(64)
        heat[:16] = 8.0
        for _ in range(5):
            mm.record_access(1, heat)
            mm.tick()
        rows = mm.profile_scan(1)
        regions = mm.procs[1].damon.regions
        assert len(rows) == len(regions)
        for (start, end, heat_milli, age, _score), r in zip(rows, regions):
            assert (start, end) == (r.start, r.end)
            assert heat_milli == int(r.nr_accesses * FIXED_POINT)
            assert age == r.age
        # the hot span scored hot, the cold tail cold
        assert any(s > 0 for st, _e, _h, _a, s in rows if st < 16)
        assert all(s == 0 for st, _e, _h, _a, s in rows if st >= 32)

    def test_no_program_returns_none(self):
        mm = mk_mm()
        mm.create_process(1, app="app", vma_blocks=8)
        assert mm.profile_scan(1) is None
        assert not mm.hooks.attached(HOOK_PROFILE)


# ----------------------------------------------------------- synthesizer
def _warmed_mm(tel=None):
    mm = mk_mm(telemetry=tel)
    mm.create_process(1, app="chat", vma_blocks=64)
    mm.attach_profile_program(profile_wss_program())
    heat = np.zeros(64)
    heat[:16] = 9.0
    for _ in range(6):
        mm.record_access(1, heat)
        mm.tick()
    return mm


class TestProfileSynthesizer:
    def test_synthesizes_and_hot_reloads(self):
        tel = Telemetry()
        mm = _warmed_mm(tel)
        # preload an empty profile so the reload demonstrably reuses the
        # registered map slot (the verified-map-id contract)
        slot_before = mm.load_profile(Profile("chat", []))
        syn = ProfileSynthesizer(mm, mm.cost, period=1, max_regions=8,
                                 telemetry=tel)
        assert syn.tick([(1, "chat")]) == ["chat"]
        prof, slot_after = mm.profiles["chat"]
        assert slot_after == slot_before           # map WRITE, not a new map
        assert prof.regions, "synthesized profile has a hot region"
        assert prof.regions[0].start == 0
        assert 8 <= prof.regions[0].end <= 24      # the hot [0, 16) span
        assert max(prof.regions[0].benefit) > 0
        evs = [tuple(e) for e in tel.ring.drain()]
        assert any(e[1] == EV_WSS and e[2] == 1 for e in evs)
        assert any(e[1] == EV_PROFILE for e in evs)
        assert tel.counters["profile_scans"] == 1
        assert tel.counters["profile_reloads"] == 1

    def test_convergence_stops_reload_churn(self):
        mm = _warmed_mm()
        syn = ProfileSynthesizer(mm, mm.cost, period=1, max_regions=8)
        assert syn.tick([(1, "chat")]) == ["chat"]
        v1 = syn.versions["chat"]
        # identical DAMON state -> identical profile -> no reload
        assert syn.tick([(1, "chat")]) == []
        assert syn.versions["chat"] == v1
        assert syn.reloads == 1 and syn.scans == 2

    def test_period_rate_limits_scans(self):
        mm = _warmed_mm()
        syn = ProfileSynthesizer(mm, mm.cost, period=4)
        for _ in range(7):
            syn.tick([(1, "chat")])
        assert syn.scans == 1                      # only the 4th tick scans

    def test_wss_curve_and_snapshot(self, tmp_path):
        mm = _warmed_mm()
        syn = ProfileSynthesizer(mm, mm.cost, period=1, max_regions=8)
        syn.tick([(1, "chat")])
        snap = syn.snapshot()
        assert set(snap) == {"scans", "reloads", "wss_blocks", "apps"}
        assert snap["wss_blocks"]["1"] > 0
        app = snap["apps"]["chat"]
        assert set(app) == {"version", "regions", "region_start",
                            "region_end", "region_benefit_top"}
        assert len(app["region_start"]) == app["regions"]
        path = tmp_path / "wss.json"
        syn.write_wss_curve(path)
        curve = json.loads(path.read_text())
        assert len(curve["1"]) == 1
        t, wss, mapped = curve["1"][0]
        assert wss == snap["wss_blocks"]["1"]

    def test_detached_profiler_is_inert(self):
        mm = mk_mm()
        mm.create_process(1, app="chat", vma_blocks=16)
        syn = ProfileSynthesizer(mm, mm.cost, period=1)
        assert syn.tick([(1, "chat")]) == []       # no program attached
        assert syn.scans == 0 and syn.reloads == 0


# ------------------------------------------------------- exporter schema
class TestProfilerEventSchema:
    def test_tag_names_stable(self):
        assert tag_name(EV_PROFILE) == "profile_reload"
        assert tag_name(EV_WSS) == "wss_sample"
        assert tag_name(PROF_TAG_WSS) == "prof_wss"
        assert tag_name(PROF_TAG_HEAT) == "prof_heat"
        assert tag_name(PROF_TAG_BENEFIT) == "prof_benefit"

    def test_trace_grows_profiler_track(self):
        tel = Telemetry()
        tel.emit(EV_WSS, 1, 12, 20, ts=1_000)
        tel.emit(PROF_TAG_HEAT, 1, 5, 8, ts=1_500)
        tel.emit(EV_PROFILE, 1, 2, 3, ts=2_000)
        doc = chrome_trace(tel)
        ev = doc["traceEvents"]
        names = [e["args"]["name"] for e in ev
                 if e["ph"] == "M" and e["name"] == "thread_name"
                 and e.get("pid") == 2]
        assert "mm profiler" in names
        wss = [e for e in ev if e["ph"] == "C" and e["name"] == "wss pid1"]
        assert wss and wss[0]["args"] == {"wss_blocks": 12,
                                          "mapped_blocks": 8}
        heat = [e for e in ev if e["ph"] == "C"
                and e["name"] == "heat b5 pid1"]
        assert heat and heat[0]["args"] == {"blocks": 8}
        reload_ = [e for e in ev if e["name"] == "profile reload v3"]
        assert reload_ and reload_[0]["tid"] == 3
        assert reload_[0]["args"] == {"pid": 1, "regions": 2, "version": 3}
