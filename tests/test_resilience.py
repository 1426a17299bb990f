"""Resilience subsystem units: injector determinism, the quarantine/backoff
state machine, supervisor strike/detach behavior on both dispatch routes,
migration retry/rollback, degraded engine modes, and the live ring consumer.

The chaos DIFFERENTIAL (identical seeded failure schedule across
scalar/batched routes and executors => bit-identical state) lives in
``test_differential.py``; this file covers the state machines and the
engine-level acceptance behaviors directly.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import (Asm, HWSpec, MemoryManager, TieredMemoryManager,
                        make_cost_model, tier_damon_program)
from repro.core.context import (CTX, POLICY_DETACHED, POLICY_FALLBACK,
                                FaultKind)
from repro.core.hooks import HOOK_FAULT, HOOK_TIER, HookRegistry
from repro.core.tiering import (MIGRATE_MAX_ATTEMPTS, TIER_HBM, TIER_HOST)
from repro.obs import EV_DETACH, EV_QUARANTINE, EV_READMIT, EV_RETRY
from repro.obs.telemetry import Telemetry
from repro.resilience import (BACKOFF_BASE_NS, DETACH_THRESHOLD,
                              QUARANTINE_THRESHOLD, SITE_HOOK_RUN,
                              SITE_MIGRATE_COPY, SITE_TIER_ALLOC, SITES,
                              BackoffState, FailureInjector, PolicySupervisor)

pytestmark = pytest.mark.chaos


def mk_cost():
    return make_cost_model(HWSpec(), kv_heads=4, head_dim=64)


def bad_return_program(value: int = -5):
    """A verifier-clean program whose return is BELOW the sentinel range —
    the one thing a policy must never produce (it would be misread as
    POLICY_FALLBACK/POLICY_DETACHED)."""
    a = Asm()
    a.movi("r0", value)
    a.exit()
    return a.build("bad_return")


# --------------------------------------------------------------- injector
class TestFailureInjector:
    def test_pure_and_deterministic(self):
        a = FailureInjector(7, {s: 0.3 for s in SITES})
        b = FailureInjector(7, {s: 0.3 for s in SITES})
        keys = [(s, pid, addr, t) for s in SITES
                for pid in range(4) for addr in range(8)
                for t in (0, 1_000_000)]
        va = [a.fires(s, *k) for s, *k in keys]
        vb = [b.fires(s, *k) for s, *k in keys]
        assert va == vb
        # re-asking the same keys gives the same answers (pure, not a stream)
        assert va == [a.fires(s, *k) for s, *k in keys]
        assert 0 < sum(va) < len(va)

    def test_seed_changes_schedule(self):
        keys = [(pid, addr, 0) for pid in range(16) for addr in range(16)]
        a = FailureInjector(1, {SITE_TIER_ALLOC: 0.3})
        b = FailureInjector(2, {SITE_TIER_ALLOC: 0.3})
        assert [a.fires(SITE_TIER_ALLOC, *k) for k in keys] != \
            [b.fires(SITE_TIER_ALLOC, *k) for k in keys]

    def test_rate_zero_and_unknown_sites(self):
        inj = FailureInjector(0, {SITE_TIER_ALLOC: 0.0})
        assert not inj.armed
        assert not inj.fires(SITE_TIER_ALLOC, 1, 2, 3)
        assert inj.checks[SITE_TIER_ALLOC] == 0     # disarmed: one dict probe
        with pytest.raises(ValueError):
            FailureInjector(0, {"no_such_site": 0.5})

    def test_rate_statistics(self):
        inj = FailureInjector(3, {SITE_HOOK_RUN: 0.25})
        n = 4000
        hits = sum(inj.fires(SITE_HOOK_RUN, i) for i in range(n))
        assert 0.2 < hits / n < 0.3

    def test_link_flap_windows_cohere(self):
        inj = FailureInjector.uniform(11, 0.5, sites=("link_flap",))
        w = inj.flap_window_ns
        for edge in range(3):
            for base in range(0, 6):
                vals = {inj.link_down(edge, base * w + off)
                        for off in (0, w // 3, w - 1)}
                assert len(vals) == 1, "intra-window verdicts must agree"

    def test_snapshot_numeric(self):
        from repro.obs.metrics import flatten_metrics
        inj = FailureInjector(5, {SITE_MIGRATE_COPY: 0.5})
        inj.fires(SITE_MIGRATE_COPY, 1, 2, 0, 1, 0)
        flat = flatten_metrics({"injector": inj.snapshot()})
        assert any(k.endswith("checks") and v > 0 for k, v in flat.items())


# ------------------------------------------------------ backoff/quarantine
class TestBackoffState:
    def test_threshold_then_quarantine(self):
        st = BackoffState()
        for _ in range(QUARANTINE_THRESHOLD - 1):
            assert st.record_error(0) is False
        assert st.ok(0)
        assert st.record_error(0) is True           # newly quarantined
        assert not st.ok(0)
        assert st.level == 1 and st.quarantines == 1
        assert st.quarantined_until == BACKOFF_BASE_NS

    def test_probe_failure_escalates_probe_success_decays(self):
        st = BackoffState()
        for _ in range(QUARANTINE_THRESHOLD):
            st.record_error(0)
        t1 = st.quarantined_until
        assert st.ok(t1)                            # window expired: probe
        # probe fails: window doubles and the edge re-enters quarantine
        assert st.record_error(t1) is True
        assert st.level == 2 and st.quarantines == 2
        assert st.quarantined_until == t1 + (BACKOFF_BASE_NS << 1)
        t2 = st.quarantined_until
        # two successful probes decay level 2 -> 0 and re-admit
        assert st.record_success(t2) is False and st.level == 1
        assert st.record_success(t2) is True and st.level == 0
        assert st.ok(t2) and st.readmits == 1 and st.quarantined_until == -1

    def test_success_resets_consecutive_errors(self):
        st = BackoffState()
        for _ in range(QUARANTINE_THRESHOLD - 1):
            st.record_error(0)
        st.record_success(0)
        assert st.record_error(0) is False          # streak restarted
        assert st.level == 0

    def test_backoff_level_caps(self):
        st = BackoffState()
        now = 0
        for _ in range(40):
            st.record_error(now)
            now = st.quarantined_until
        assert st.level == st.max_level
        assert st.backoff_ns() == st.base_ns << st.max_level


# ------------------------------------------------------------- supervisor
class TestPolicySupervisor:
    def test_detach_at_threshold(self):
        sup = PolicySupervisor(threshold=3)
        assert not sup.strike("mm_fault", 0)
        assert not sup.strike("mm_fault", 1)
        assert sup.strike("mm_fault", 0)
        snap = sup.snapshot()
        assert snap["mm_fault"]["strikes"] == 3

    def test_disabled_counts_but_never_detaches(self):
        sup = PolicySupervisor(threshold=2, enabled=False)
        for _ in range(10):
            assert not sup.strike("mm_fault", 0)
        assert sup.snapshot()["mm_fault"]["strikes"] == 10

    def test_rb_streak_strikes_once_per_limit(self):
        sup = PolicySupervisor(rb_streak_limit=3)
        assert not sup.note_rb_drops("mm_fault", 2)
        assert not sup.note_rb_drops("mm_fault", 1)
        assert sup.note_rb_drops("mm_fault", 4)     # third consecutive
        assert not sup.note_rb_drops("mm_fault", 1)  # streak reset
        sup.note_rb_clean("mm_fault")
        assert not sup.note_rb_drops("mm_fault", 1)  # clean call reset it

    def test_reset_preserves_lifetime_detaches(self):
        sup = PolicySupervisor(threshold=1)
        assert sup.strike("mm_fault", 1)
        sup.record_detach("mm_fault", 1, "prog")
        sup.reset("mm_fault")
        snap = sup.snapshot()["mm_fault"]
        assert snap["strikes"] == 0 and snap["detaches"] == 1

    def test_scalar_route_detaches_bad_program(self):
        mm = MemoryManager(64, mk_cost(), default_mode="never")
        mm.create_process(1, vma_blocks=48)
        mm.attach_fault_program(bad_return_program())
        for addr in range(DETACH_THRESHOLD):
            mm.ensure_mapped(1, addr)
        assert not mm.hooks.attached(HOOK_FAULT)
        snap = mm.hooks.supervisor.snapshot()
        assert snap["mm_fault"]["detaches"] == 1
        assert snap["mm_fault"]["invalid_return"] == DETACH_THRESHOLD
        # strikes fell back to the default path, and post-detach faults run
        # the kernel default with no further accounting
        assert mm.stats.fallback_faults == DETACH_THRESHOLD
        mm.ensure_mapped(1, 40)
        assert mm.stats.fallback_faults == DETACH_THRESHOLD

    def test_batched_route_mid_batch_detach_tail(self):
        reg = HookRegistry(supervisor=PolicySupervisor(threshold=3))
        from repro.core.maps import MapRegistry
        reg.attach(HOOK_FAULT, bad_return_program(), MapRegistry())
        ap = reg._hooks[HOOK_FAULT]
        from repro.core.context import CTX_LEN
        ctx = np.zeros((8, CTX_LEN), dtype=np.int64)
        out = reg.run_batch(HOOK_FAULT, ctx)
        # rows 0..2 strike (-> FALLBACK), row 2 crosses the threshold, and
        # the tail takes the detached sentinel
        assert list(out[:3]) == [POLICY_FALLBACK] * 3
        assert list(out[3:]) == [POLICY_DETACHED] * 5
        assert reg._hooks[HOOK_FAULT] is None and ap is not None

    def test_reattach_resets_strikes(self):
        mm = MemoryManager(64, mk_cost(), default_mode="never")
        mm.create_process(1, vma_blocks=48)
        mm.attach_fault_program(bad_return_program())
        for addr in range(DETACH_THRESHOLD):
            mm.ensure_mapped(1, addr)
        assert not mm.hooks.attached(HOOK_FAULT)
        mm.attach_fault_program(bad_return_program())
        assert mm.hooks.attached(HOOK_FAULT)
        snap = mm.hooks.supervisor.snapshot()["mm_fault"]
        assert snap["strikes"] == 0 and snap["detaches"] == 1

    def test_injected_hook_errors_detach_and_emit(self):
        tel = Telemetry()
        inj = FailureInjector.uniform(3, 1.0, sites=(SITE_HOOK_RUN,))
        mm = MemoryManager(64, mk_cost(), default_mode="never",
                           telemetry=tel, injector=inj)
        mm.create_process(1, vma_blocks=48)
        mm.attach_fault_program(bad_return_program())  # never even runs
        for addr in range(DETACH_THRESHOLD):
            mm.ensure_mapped(1, addr)
        assert not mm.hooks.attached(HOOK_FAULT)
        snap = mm.hooks.supervisor.snapshot()["mm_fault"]
        assert snap["runtime_error"] == DETACH_THRESHOLD
        events = tel.poll_events()
        assert any(e["tag"] == EV_DETACH for e in events)
        assert tel.counters.get("policy_detaches") == 1


# --------------------------------------------------- migration containment
def mk_chaos_tmm(rates, seed=0, containment=True, hbm=32, host=64):
    cost = mk_cost()
    return TieredMemoryManager(
        hbm, cost, host_blocks=host, default_mode="never",
        injector=FailureInjector(seed, rates), containment=containment,
        telemetry=Telemetry())


class TestMigrationContainment:
    def test_copy_failure_retries_then_aborts_with_rollback(self):
        # rate 1.0: every copy attempt fails -> bounded retries, then abort
        mm = mk_chaos_tmm({SITE_MIGRATE_COPY: 1.0})
        mm.create_process(1, vma_blocks=8)
        mm.ensure_range(1, 0, 8)
        host_free0 = mm.host_buddy.free_blocks_total()
        m = mm.procs[1].page_table[0]
        assert not mm.migrate_page(1, 0, TIER_HOST)
        # rollback: page stays put, the dst allocation was released
        assert m.tier == TIER_HBM
        assert mm.host_buddy.free_blocks_total() == host_free0
        assert mm.stats.migrate_retries == MIGRATE_MAX_ATTEMPTS - 1
        assert mm.stats.migrate_aborts == 1
        tags = [e["tag"] for e in mm.telemetry.poll_events()]
        assert tags.count(EV_RETRY) == MIGRATE_MAX_ATTEMPTS - 1

    def test_no_containment_single_shot(self):
        mm = mk_chaos_tmm({SITE_MIGRATE_COPY: 1.0}, containment=False)
        mm.create_process(1, vma_blocks=8)
        mm.ensure_range(1, 0, 8)
        assert not mm.migrate_page(1, 0, TIER_HOST)
        assert mm.stats.migrate_retries == 0
        assert mm.stats.migrate_aborts == 1

    def test_repeated_failures_quarantine_then_readmit(self):
        mm = mk_chaos_tmm({SITE_MIGRATE_COPY: 1.0})
        mm.create_process(1, vma_blocks=16)
        mm.ensure_range(1, 0, 16)
        lgs = sorted(mm.procs[1].page_table)
        fails = 0
        while not mm.health.quarantined_edges(mm.ktime_ns):
            assert not mm.migrate_page(1, lgs[fails % len(lgs)], TIER_HOST)
            fails += 1
            assert fails < 10, "edge never quarantined"
        assert mm.health.edges[0].level >= 1
        events = [e for e in mm.telemetry.poll_events()
                  if e["tag"] == EV_QUARANTINE]
        assert len(events) == 1 and events[0]["a0"] == 0
        # while quarantined, migrate_page skips the edge without any attempt
        retries0 = mm.stats.migrate_retries
        assert not mm.migrate_page(1, lgs[-1], TIER_HOST)
        assert mm.stats.migrate_retries == retries0
        # heal the link: advance modeled time past the window, stop injecting
        mm.injector.rates.clear()
        while not mm.health.edges[0].ok(mm.ktime_ns):
            mm.tick()
        level = mm.health.edges[0].level
        for i in range(level):
            assert mm.migrate_page(1, lgs[i], TIER_HOST)
        assert mm.health.edges[0].level == 0     # fully re-admitted
        assert any(e["tag"] == EV_READMIT
                   for e in mm.telemetry.poll_events())

    def test_alloc_failures_counted_and_hopped(self):
        mm = mk_chaos_tmm({SITE_TIER_ALLOC: 1.0})
        mm.create_process(1, vma_blocks=8)
        mm.ensure_range(1, 0, 8)
        assert not mm.migrate_page(1, 0, TIER_HOST)
        assert mm.stats.tier_alloc_failures > 0
        assert mm.health.tier_alloc_failures[TIER_HOST] > 0

    def test_failure_free_run_untouched_by_machinery(self):
        """containment=True with no injector must behave exactly like the
        seed: no retries, no aborts, health monitor never activates."""
        mm = mk_tiered_pair()[0]
        mm.create_process(1, vma_blocks=8)
        mm.ensure_range(1, 0, 8)
        assert mm.migrate_page(1, 0, TIER_HOST)
        assert mm.stats.migrate_retries == 0
        assert mm.stats.migrate_aborts == 0
        assert mm.health.active is False


def mk_tiered_pair():
    cost_a = mk_cost()
    cost_b = mk_cost()
    a = TieredMemoryManager(32, cost_a, host_blocks=64, default_mode="never")
    b = TieredMemoryManager(32, cost_b, host_blocks=64, default_mode="never",
                            containment=False)
    return a, b


# ------------------------------------------------------- engine-level lanes
@pytest.fixture(scope="module")
def engine_setup():
    import jax
    from repro.configs.base import get_smoke_config
    from repro.models import PagedLayout, materialize, model_spec
    cfg = get_smoke_config("deepseek_7b")
    params = materialize(jax.random.PRNGKey(0), model_spec(cfg))
    layout = PagedLayout(num_blocks=48, block_tokens=4, max_blocks=32)
    return cfg, params, layout


def run_engine(engine_setup, n_req=4, max_steps=200, **kw):
    from repro.core import Profile, ProfileRegion
    from repro.serving import Request, ServingEngine
    cfg, params, layout = engine_setup
    kw.setdefault("policy", "never")
    if kw["policy"] == "ebpf" and "profile" not in kw:
        kw["profile"] = Profile("chat", [
            ProfileRegion(0, 8, (0, 150_000, 600_000, 2_500_000)),
            ProfileRegion(8, 32, (0, 0, 0, 0))])
    eng = ServingEngine(cfg, params, layout, max_batch=4, **kw)
    rng = np.random.default_rng(0)
    for r in range(n_req):
        eng.submit(Request(rid=r,
                           prompt=rng.integers(1, cfg.vocab, 40).tolist(),
                           max_new_tokens=24, app="chat"))
    steps = 0
    while eng.step():
        steps += 1
        if steps >= max_steps:
            break
    return eng


@pytest.mark.timeout(300)
class TestEngineResilience:
    def test_chaos_run_completes_with_containment(self, engine_setup):
        eng = run_engine(engine_setup, host_blocks=128,
                         tier_policy="ebpf-tier", chaos=7, chaos_rate=0.1,
                         telemetry=True)
        assert eng.stats.completed == 4
        m = eng.metrics()
        assert m["resilience_injector_seed"] == 7
        fired = sum(v for k, v in m.items()
                    if k.startswith("resilience_injector") and
                    k.endswith("fired"))
        assert fired > 0, "chaos engine run never injected"

    def test_persistent_spill_failure_degrades_to_preempt(self, engine_setup):
        """Degraded mode: every spill-tier allocation fails -> demotion can
        never relieve pressure, so the engine must fall back to preempt-only
        and still finish the workload (zero crashes)."""
        inj = FailureInjector(1, {SITE_TIER_ALLOC: 1.0})
        eng = run_engine(engine_setup, host_blocks=128,
                         tier_policy="ebpf-tier", chaos=inj)
        assert eng.stats.completed == 4
        assert eng.stats.preemptions > 0        # preempt-only fallback
        assert eng.mm.stats.demotions == 0      # the spill tier never took
        assert eng.mm.stats.tier_alloc_failures > 0

    def test_detach_visible_in_metrics_and_trace(self, engine_setup, tmp_path):
        inj = FailureInjector(3, {SITE_HOOK_RUN: 1.0})
        eng = run_engine(engine_setup, policy="ebpf", chaos=inj,
                         telemetry=True)
        assert eng.stats.completed == 4          # fallback kept serving
        assert not eng.mm.hooks.attached(HOOK_FAULT)
        m = eng.metrics()
        assert m["resilience_supervisor_detaches"] >= 1
        assert m["resilience_supervisor_mm_fault_detaches"] == 1
        # EV_DETACH lands in the Chrome trace (write BEFORE poll_events —
        # the live consumer drains the ring destructively)
        trace = tmp_path / "trace.json"
        eng.write_trace(trace)
        assert '"detach"' in trace.read_text()

    def test_poll_events_live_consumer(self, engine_setup):
        eng = run_engine(engine_setup, host_blocks=128,
                         tier_policy="ebpf-tier", chaos=9, chaos_rate=0.15,
                         telemetry=True, max_steps=40)
        batch1 = eng.poll_events()
        assert batch1, "armed chaos run should publish ring events"
        assert all({"tag", "name", "ts", "a0"} <= set(e) for e in batch1)
        # drained: an immediate re-poll returns nothing new
        assert eng.poll_events() == []
        # untelemetered engines return [] instead of raising
        eng2 = run_engine(engine_setup, max_steps=4)
        assert eng2.poll_events() == []

    def test_containment_off_keeps_counters_but_no_detach(self, engine_setup):
        inj = FailureInjector(3, {SITE_HOOK_RUN: 1.0})
        eng = run_engine(engine_setup, policy="ebpf", chaos=inj,
                         containment=False)
        assert eng.stats.completed == 4
        assert eng.mm.hooks.attached(HOOK_FAULT)   # never detached
        m = eng.metrics()
        assert m["resilience_supervisor_mm_fault_strikes"] > DETACH_THRESHOLD
        assert m["resilience_supervisor_detaches"] == 0


# ----------------------------------------------------- cache + placement
class TestArtifactCacheChaos:
    def test_injected_corruption_recompiles(self, tmp_path):
        from repro.core.cache import ArtifactCache
        from repro.core.maps import MapRegistry
        cache = ArtifactCache(root=tmp_path)
        reg1 = HookRegistry(cache=cache)
        reg1.attach(HOOK_FAULT, tier_damon_program(), MapRegistry())
        from repro.core.context import CTX_LEN
        reg1.run_batch(HOOK_FAULT, np.zeros((4, CTX_LEN), dtype=np.int64))
        assert cache.stats["unroll_misses"] == 1
        # fresh session, same disk cache, corruption injected on read
        cache2 = ArtifactCache(root=tmp_path)
        inj = FailureInjector.uniform(0, 1.0, sites=("cache_corrupt",))
        reg2 = HookRegistry(cache=cache2, injector=inj)
        reg2.attach(HOOK_FAULT, tier_damon_program(), MapRegistry())
        out = reg2.run_batch(HOOK_FAULT,
                             np.zeros((4, CTX_LEN), dtype=np.int64))
        assert out is not None                     # recompiled, never raised
        assert cache2.stats["miss_corrupt"] == 1
        assert cache2.stats["unroll_misses"] == 1


class TestDecodePlacement:
    def test_first_touch_batch_consults_tier_hook(self):
        """FIRST_TOUCH fault batches run decode-time placement: with a
        demote-everything tier program attached, freshly installed decode
        blocks land in the spill tier in the same step."""
        from repro.core.context import TIER_DEMOTE
        mm = TieredMemoryManager(32, mk_cost(), host_blocks=64,
                                 default_mode="never")
        a = Asm()
        a.movi("r0", TIER_HOST)
        a.exit()
        mm.attach_tier_program(a.build("demote_all"))
        mm.create_process(1, vma_blocks=8)
        mm.fault_batch([(1, 0, FaultKind.FIRST_TOUCH)])
        assert mm.procs[1].page_table and all(
            m.tier == TIER_HOST for m in mm.procs[1].page_table.values())

    def test_scalar_place_decode_matches_batched(self):
        mms = []
        for batched in (False, True):
            mm = TieredMemoryManager(32, mk_cost(), host_blocks=64,
                                     default_mode="never")
            mm.attach_tier_program(tier_damon_program())
            mm.create_process(1, vma_blocks=8)
            reqs = [(1, a, FaultKind.FIRST_TOUCH) for a in range(4)]
            if batched:
                mm.fault_batch(reqs)
            else:
                for pid, a, kind in reqs:
                    mm.ensure_mapped(pid, a, kind)
                mm.place_decode(reqs)
            mms.append(mm)
        t0 = sorted((m.logical_start, m.tier)
                    for m in mms[0].procs[1].page_table.values())
        t1 = sorted((m.logical_start, m.tier)
                    for m in mms[1].procs[1].page_table.values())
        assert t0 == t1
