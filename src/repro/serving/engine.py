"""Continuous-batching serving engine with an eBPF-mm-managed paged KV cache.

The paper's workflow, end to end:

  * every sequence is a "process" with a growing KV address space;
  * each decode step that crosses a block boundary is a PAGE FAULT —
    the MemoryManager runs the attached policy program (profile search +
    cost/benefit) to pick the page size backing the new mapping;
  * the paged-attention path emits per-block attention mass, which feeds the
    per-process DAMON monitors (the benefit signal);
  * the khugepaged analogue runs between engine steps, collapsing hot
    regions into larger pages; migrations/compactions come back as explicit
    block-copy move lists applied to the device pools;
  * pool exhaustion triggers the reclaim hook; with a host-DRAM tier
    configured (``host_blocks > 0``) the engine DEMOTES the victim's cold
    blocks to the host tier instead of evicting the whole process
    (demote-before-preempt): the mm_tier hook program vets each candidate
    (TierBPF-style admission control), approved pages migrate over PCIe via
    the same block-copy move lists, and a background promotion scan brings
    re-heated pages back to HBM between steps.  Whole-sequence preemption
    (requeue + recompute) remains only as the fallback when BOTH tiers are
    exhausted or the tier policy vetoes every demotion.

Policies (``policy=``): "ebpf" (profile + Figure-1 program), "thp"
(kernel-default greedy PMD-size), "never" (base pages), "thp-prog"/
"never-prog" (same baselines expressed as loaded programs, for measuring
hook overhead).  The Figure-2 benchmark sweeps these.  Orthogonally,
``tier_policy=`` selects the mm_tier program: "ebpf-tier" (DAMON-heat
admission control), "lru-tier" (age-based demotion baseline), "never-tier"
(veto all demotions -> preempt-only), "heat-tier" (heat-banded N-tier
placement incl. prefill-time cold-prefix placement), "edge-tier"
(TierBPF-style single-hop per-edge admission control), or "default"
(kernel-default path, no program attached).  The tier topology comes from
``host_blocks`` (classic HBM + host-DRAM) or ``tier_blocks`` (a chain of
spill-tier capacities: peer-HBM over ICI, host DRAM over PCIe, NVMe).  The
capacity-sweep benchmark sweeps these.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import (Telemetry, flatten_metrics, render_prometheus,
                   write_chrome_trace)
from ..obs.ringbuf import EV_PREEMPT
from ..obs.spans import span, spanned

from ..configs.base import ModelConfig
from ..core import (MAX_PROFILE_REGIONS, NUM_ORDERS, FaultKind, HWSpec,
                    Khugepaged, KhugepagedConfig, MemoryManager,
                    MMOutOfMemory, Profile, ProfileSynthesizer,
                    TieredMemoryManager,
                    default_tier_chain, ebpf_mm_program, make_cost_model,
                    never_program, profile_wss_program, reclaim_lru_program,
                    thp_always_program, tier_damon_program,
                    tier_edge_admission_program, tier_heat_band_program,
                    tier_lru_program, tier_never_program)
from ..core.buddy import order_blocks
from ..core.hooks import HOOK_EVICT, HOOK_FAULT, HOOK_PROFILE, HOOK_TIER
from ..core.programs import (evict_ghost_program, evict_lfu_program,
                             evict_lru_program)
from ..resilience import FailureInjector
from ..models.decode import (PagedLayout, cache_init, decode_page_work,
                             decode_step, page_reads, paged_kernel_supported,
                             prefill_step, prefill_suffix_step)
from ..models.transformer import build_layer_plans
from .prefix_cache import PrefixCache
from .program import DeviceProgram
from .sampler import Sampler
from .tables import DeviceBlockTables

Pytree = Any

# finished requests whose times the engine keeps (the newest)
REQUEST_LOG = 4096


def decode_attn_impl(cfg: ModelConfig, cache: Pytree) -> str:
    """Decode attention backend: the Pallas paged kernel where the pool
    lives on a TPU and every attention layer is one it computes, else the
    gather path (the CPU engine, MLA, soft-capped logits)."""
    dev = next(iter(jax.tree.leaves(cache)[0].devices()))
    if dev.platform == "tpu" and paged_kernel_supported(cfg):
        return "paged"
    return "gather"


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16       # reserved capacity (VMA is sized for this)
    app: str | None = None
    temperature: float = 0.0
    stop_after: int | None = None  # EOS point; None = runs to max_new_tokens


@dataclass
class SeqState:
    req: Request
    pid: int
    slot: int
    generated: list = field(default_factory=list)
    length: int = 0           # tokens currently in KV (prompt + generated)
    prefix: Any = None        # pinned PrefixMatch when admitted via the cache


@dataclass
class RequestTimes:
    """One request's way through the engine, on ``time.perf_counter``:
    handed to ``submit``, admitted into a slot (first admission, before its
    prefill) and its first token sampled."""
    rid: int
    submitted: float
    admitted: float | None = None
    first_token: float | None = None


@dataclass
class EngineStats:
    steps: int = 0
    prefills: int = 0
    prefill_tokens: int = 0        # tokens actually run through the kernel
    decode_tokens: int = 0
    preemptions: int = 0
    tier_reliefs: int = 0          # OOMs resolved by demotion, not preemption
    step_wall_s: float = 0.0       # host wall time inside step(), summed
    completed: int = 0
    # first calls of a device-program variant (compiled or loaded from the
    # persistent cache), in all and per entry
    compiles: int = 0
    compiles_by_entry: dict = field(default_factory=dict)
    # K/V blocks the paged decode kernel read, over every attention layer,
    # by the order of the page that held them (the kernel's own count)
    kv_blocks_read_by_order: list = field(
        default_factory=lambda: [0] * NUM_ORDERS)

    def count_compile(self, entry: str) -> None:
        self.compiles += 1
        self.compiles_by_entry[entry] = \
            self.compiles_by_entry.get(entry, 0) + 1

    def count_reads(self, reads) -> None:
        self.kv_blocks_read_by_order = [
            a + int(b) for a, b in zip(self.kv_blocks_read_by_order, reads)]

    def snapshot(self) -> dict:
        out = dict(self.__dict__)
        out["compiles_by_entry"] = dict(self.compiles_by_entry)
        out["kv_blocks_read_by_order"] = list(self.kv_blocks_read_by_order)
        return out


class ServingEngine:
    # tier_policy name -> mm_tier program factory (None = kernel default)
    TIER_PROGRAMS = {
        "ebpf-tier": tier_damon_program,
        "lru-tier": tier_lru_program,
        "never-tier": tier_never_program,
        "heat-tier": tier_heat_band_program,
        "edge-tier": tier_edge_admission_program,
        "default": None,
    }
    # 2-tier baselines: their demote target never passes tier 1 (ebpf-tier
    # additionally gates on tier-1 free space alone), so on a deeper chain
    # they strand tiers 2.. and reclaim degrades back to preemption while
    # deep capacity sits free — reject the pairing instead of livelocking.
    TWO_TIER_POLICIES = frozenset({"ebpf-tier", "lru-tier"})
    # evict_policy name -> mm_evict program factory (None = kernel default:
    # the cache's built-in conservative LRU fallback, no program attached)
    EVICT_PROGRAMS = {
        "lru-evict": evict_lru_program,
        "lfu-evict": evict_lfu_program,
        "ghost-evict": evict_ghost_program,
        "default": None,
    }

    def __init__(self, cfg: ModelConfig, params: Pytree, layout: PagedLayout,
                 *, max_batch: int = 4, policy: str = "ebpf",
                 profile: "Profile | list | str | None" = None,
                 profile_period: int = 4, hw: HWSpec | None = None,
                 khugepaged: bool = True, seed: int = 0,
                 cache_dtype=jnp.bfloat16,
                 host_blocks: int = 0, tier_blocks=None,
                 tier_policy: str = "ebpf-tier",
                 prefix_cache: "bool | int" = False,
                 evict_policy: str = "lru-evict",
                 batch_faults: bool = True,
                 telemetry: "Telemetry | bool | None" = None,
                 chaos: "int | FailureInjector | None" = None,
                 chaos_rate: float = 0.02,
                 containment: bool = True):
        # telemetry: None (default — zero-overhead no-op), True (counters/
        # histograms/ring), or a repro.obs.Telemetry instance.  Host spans
        # (repro.obs.spans) need no option: they record into a running JAX
        # profiler session.
        if telemetry is True:
            telemetry = Telemetry()
        self.telemetry: Telemetry | None = telemetry or None
        # chaos: None (default — no injection, zero overhead), an int seed
        # (uniform chaos_rate at every failure site), or a pre-configured
        # FailureInjector.  containment=False keeps the injector but turns
        # OFF the resilience responses (supervisor detach, migration retry,
        # quarantine routing, degraded demote) — the chaos benchmark's
        # no-containment baseline.
        if chaos is None or isinstance(chaos, FailureInjector):
            self.injector = chaos
        else:
            self.injector = FailureInjector.uniform(int(chaos),
                                                    float(chaos_rate))
        self.containment = bool(containment)
        self.cfg = cfg
        self.params = params
        self.layout = layout
        self.max_batch = max_batch
        self.policy = policy
        # tier_blocks: spill-tier capacities walking down the chain — 1 pool
        # = (host-DRAM,), 2 = (peer-HBM, host-DRAM), 3 = (peer-HBM,
        # host-DRAM, NVMe).  host_blocks is the classic 2-pool shorthand.
        if tier_blocks is None and host_blocks > 0:
            tier_blocks = (host_blocks,)
        self.tier_blocks = tuple(int(b) for b in tier_blocks) \
            if tier_blocks else ()
        tiered = bool(self.tier_blocks)
        self.tier_policy = tier_policy if tiered else None
        # batch_faults=False keeps the pre-batching scalar fault path (one
        # policy invocation per fault) — the hot-path benchmark's baseline
        self.batch_faults = batch_faults
        self._modal_cache: dict = {}
        hw = hw or HWSpec()

        n_attn = sum(1 for k in cfg.layer_kinds() if k == "a")
        if cfg.mla is not None:
            slab = cfg.mla.kv_lora + cfg.mla.qk_rope
        else:
            slab = cfg.kv_heads * cfg.head_dim * 2
        cost = make_cost_model(hw, kv_heads=1, head_dim=1,
                               block_tokens=layout.block_tokens)
        cost.block_bytes = layout.block_tokens * slab * 2 * max(1, n_attn)

        default_mode = {"never": "never", "never-prog": "never"}.get(policy, "thp")
        if tiered:
            # tiered pool: HBM buddy + one buddy per spill tier; the device
            # cache below is materialized over the COMBINED index space so
            # tier crossings are ordinary block_copy moves
            self.mm = TieredMemoryManager(
                layout.num_blocks, cost,
                tiers=default_tier_chain(hw, self.tier_blocks),
                default_mode=default_mode, damon_seed=seed,
                telemetry=self.telemetry, injector=self.injector,
                containment=self.containment)
            if tier_policy not in self.TIER_PROGRAMS:
                raise ValueError(f"unknown tier_policy {tier_policy!r}")
            if len(self.tier_blocks) > 1 \
                    and tier_policy in self.TWO_TIER_POLICIES:
                raise ValueError(
                    f"tier_policy {tier_policy!r} is a 2-tier baseline that "
                    f"can never demote past tier 1; use 'heat-tier' or "
                    f"'edge-tier' for a {len(self.tier_blocks) + 1}-tier "
                    f"chain")
            prog = self.TIER_PROGRAMS[tier_policy]
            if prog is not None:
                self.mm.attach_tier_program(prog())
        else:
            self.mm = MemoryManager(layout.num_blocks, cost,
                                    default_mode=default_mode, damon_seed=seed,
                                    telemetry=self.telemetry,
                                    injector=self.injector,
                                    containment=self.containment)
        self._pool_blocks = layout.num_blocks + sum(self.tier_blocks)
        self.mm.attach_reclaim_program(reclaim_lru_program())
        self.profiler: ProfileSynthesizer | None = None
        if policy == "ebpf":
            if profile is None:
                raise ValueError(
                    "policy='ebpf' needs a profile (or list, or 'auto')")
            if isinstance(profile, str):
                if profile != "auto":
                    raise ValueError(f"unknown profile mode {profile!r}")
                # Online profiling plane: start with NO profile loaded.  A
                # verified profiler program samples the live DAMON regions
                # on the mm tick (HOOK_PROFILE) and the ProfileSynthesizer
                # hot-reloads synthesized profiles mid-run, so placement
                # converges to what an offline profiling run would load.
                # max_regions=8 keeps the fault program's verified search
                # loop the same shape as a small offline profile's.
                bound = 8
                # an EMPTY profile registers the map slot the verifier's
                # indirect-load check needs; the synthesizer's reloads are
                # map WRITEs into slots registered the same way
                self.mm.load_profile(Profile("_default", []))
                self.mm.attach_fault_program(
                    ebpf_mm_program(max_regions=bound))
                self.mm.attach_profile_program(profile_wss_program())
                self.profiler = ProfileSynthesizer(
                    self.mm, cost, period=profile_period,
                    max_regions=bound, telemetry=self.telemetry)
                self.mm.hooks.warm(HOOK_PROFILE, max_batch=16)
            else:
                profiles = profile if isinstance(profile, (list, tuple)) \
                    else [profile]
                for prof in profiles:
                    self.mm.load_profile(prof)
                # One program serves every app via the indirect profile-map
                # load.  The verified search loop is right-sized to the
                # profiles actually loaded (rounded up to a power of two):
                # it keeps the predicated batch executor's one-time compile
                # fast without changing any decision.
                nreg = max((len(p.regions) for p in profiles), default=0)
                bound = min(max(8, 1 << max(0, nreg - 1).bit_length()),
                            MAX_PROFILE_REGIONS)
                self.mm.attach_fault_program(
                    ebpf_mm_program(max_regions=bound))
        elif policy == "thp-prog":
            self.mm.attach_fault_program(thp_always_program())
        elif policy == "never-prog":
            self.mm.attach_fault_program(never_program())
        elif policy not in ("thp", "never"):
            raise ValueError(f"unknown policy {policy!r}")
        if self.batch_faults:
            # Build + compile the hook batch backends NOW (decode-sized
            # bucket), not on the first faulting step or the first batched
            # tier placement: warmup consults the cross-session artifact
            # cache (.cache/), so a process that has seen these programs
            # before skips the unroll and the XLA compile instead of
            # re-paying them mid-serve.
            self.mm.hooks.warm(HOOK_FAULT, max_batch=max_batch)
            self.mm.hooks.warm(HOOK_TIER, max_batch=max_batch)

        # Cross-request KV prefix cache: content-addressed shared prefix
        # blocks, admission via read-only borrows + CoW, HOOK_EVICT-driven
        # eviction into the tier chain.  prefix_cache=True sizes the budget
        # at a quarter of HBM; an int is an explicit cap in blocks.
        self.prefix_cache: PrefixCache | None = None
        if prefix_cache:
            bad = [k for k in cfg.layer_kinds() if k != "a"]
            if bad or cfg.enc_dec or cfg.vlm_patches \
                    or cfg.attn.mrope_sections is not None:
                raise ValueError(
                    "prefix_cache requires a plain all-attention decoder "
                    "(sequential state — mamba/enc-dec/vlm — cannot skip "
                    "prefix compute)")
            cap = (int(prefix_cache) if not isinstance(prefix_cache, bool)
                   else max(1, layout.num_blocks // 4))
            self.prefix_cache = PrefixCache(
                self.mm, layout.block_tokens, cap_blocks=cap,
                telemetry=self.telemetry)
            if evict_policy not in self.EVICT_PROGRAMS:
                raise ValueError(f"unknown evict_policy {evict_policy!r}")
            eprog = self.EVICT_PROGRAMS[evict_policy]
            if eprog is not None:
                self.mm.attach_evict_program(eprog())
                # a scan's ctx batch is ONE ROW PER ENTRY, and the entry
                # count can transiently reach ~2x the budget between scans
                # — warm every pow2 bucket up to that, or the first
                # over-budget scan compiles mid-serve
                warm_to = 1 << max(4, (2 * cap - 1).bit_length())
                self.mm.hooks.warm(HOOK_EVICT,
                                   max_batch=min(512, warm_to))
        self.evict_policy = evict_policy if self.prefix_cache else None

        self.khugepaged = (Khugepaged(self.mm, KhugepagedConfig())
                           if (khugepaged and policy == "ebpf") else None)
        pool_layout = layout if not tiered else PagedLayout(
            num_blocks=self._pool_blocks, block_tokens=layout.block_tokens,
            max_blocks=layout.max_blocks)
        self.cache = cache_init(cfg, pool_layout, max_batch, cache_dtype)
        self.sampler = Sampler(seed=seed)
        self.stats = EngineStats()

        self.waiting: list[Request] = []
        self.active: dict[int, SeqState] = {}    # slot -> seq
        self._next_pid = 1
        self.finished: dict[int, list[int]] = {}
        # request times: live requests by rid, then the newest finished ones
        self._times: dict[int, RequestTimes] = {}
        self.request_log: deque[RequestTimes] = deque(maxlen=REQUEST_LOG)
        # per-app aggregate per-logical-block heat — the DAMON trace used by
        # offline profiling (profile_from_heat)
        self.heat_histograms: dict[str, np.ndarray] = {}

        # Device-resident management plane: the [B, max_blocks] block table
        # lives ON DEVICE as a persistent buffer; the host ships only dirty
        # rows (version-tracked in DeviceBlockTables) and both compiled
        # entries fold the row install into their single dispatch — the
        # decode step is table-install + policy-consume + kernel in ONE jit.
        MB = layout.max_blocks
        self._tables = DeviceBlockTables(max_batch, MB)
        self._table_buf = jnp.full((max_batch, MB), -1, jnp.int32)

        def _install_rows(buf, didx, drows, tri):
            # dirty rows are bucket-padded with idx -1: route pads out of
            # bounds and drop, same convention as the KV scatter.  Delta
            # triples (row, col, value) follow the same -1-row pad route.
            safe = jnp.where(didx >= 0, didx, buf.shape[0])
            buf = buf.at[safe].set(drows, mode="drop")
            trow = jnp.where(tri[:, 0] >= 0, tri[:, 0], buf.shape[0])
            return buf.at[trow, tri[:, 1]].set(tri[:, 2], mode="drop")

        self.attn_impl = impl = decode_attn_impl(cfg, self.cache)

        def _decode_entry(p, c, buf, didx, drows, tri, t, l, act, pos3d):
            buf = _install_rows(buf, didx, drows, tri)
            works = (decode_page_work(cfg, buf, l, layout, act)
                     if impl == "paged" else None)
            logits, new_cache, heat = decode_step(
                p, cfg, c, t, l, buf, layout, active=act, pos3d=pos3d,
                attn_impl=impl, works=works)
            reads = (page_reads(cfg, works) if works is not None
                     else jnp.zeros((NUM_ORDERS,), jnp.int32))
            # what the step's attention saw: per-block heat and the blocks
            # its kernel read by page order
            return logits, new_cache, (heat, reads), buf

        def _prefill_entry(p, c, buf, didx, drows, tri, t, slot, last, **kw):
            buf = _install_rows(buf, didx, drows, tri)
            table = jax.lax.dynamic_slice_in_dim(buf, slot, 1, 0)
            logits, new_cache = prefill_step(
                p, cfg, c, t, table, layout, chunk=256, last_index=last,
                **kw)
            return logits, new_cache, buf

        def _prefill_sfx_entry(p, c, buf, didx, drows, tri, t, slot, plen,
                               last, *, key_blocks):
            # cache-hit admission: prefill ONLY the uncached suffix; the
            # prefix KV is already in the pool behind the shared mappings
            buf = _install_rows(buf, didx, drows, tri)
            table = jax.lax.dynamic_slice_in_dim(buf, slot, 1, 0)
            logits, new_cache = prefill_suffix_step(
                p, cfg, c, t, table, layout, prefix_len=plen,
                key_blocks=key_blocks, chunk=256, last_index=last)
            return logits, new_cache, buf

        pool_blocks = self._pool_blocks

        def _moves_entry(cache, src, dst):
            # one fused KV block-copy over every paged pool leaf; pad
            # entries carry dst=-1 -> routed out of bounds and dropped
            def move(path, leaf):
                key = path[-1].key if hasattr(path[-1], "key") \
                    else str(path[-1])
                if key not in self._POOL_KEYS:
                    return leaf
                if leaf.ndim >= 2 and leaf.shape[0] != pool_blocks:
                    d = jnp.where(dst >= 0, dst, leaf.shape[1])
                    return leaf.at[:, d].set(leaf[:, src], mode="drop")
                d = jnp.where(dst >= 0, dst, leaf.shape[0])
                return leaf.at[d].set(leaf[src], mode="drop")
            return jax.tree_util.tree_map_with_path(move, cache)

        st = self.stats
        self._decode = DeviceProgram(_decode_entry, st)
        self._prefill = DeviceProgram(_prefill_entry, st)
        self._prefill_sfx = DeviceProgram(_prefill_sfx_entry, st,
                                    static_argnames=("key_blocks",))
        self._moves = DeviceProgram(_moves_entry, st)

    # ----------------------------------------------------------------- admin
    def submit(self, req: Request) -> None:
        if req.rid not in self._times:
            self._times[req.rid] = RequestTimes(req.rid, time.perf_counter())
        self.waiting.append(req)

    def request_times(self) -> list[RequestTimes]:
        """Times of the newest finished requests, then of the live ones."""
        return list(self.request_log) + list(self._times.values())

    def _free_slots(self) -> list[int]:
        return [s for s in range(self.max_batch) if s not in self.active]

    def _blocks_needed(self, tokens: int) -> int:
        return -(-tokens // self.layout.block_tokens)

    # --------------------------------------------------------------- prefill
    @spanned("serve/admit")
    def _admit(self) -> None:
        bt = self.layout.block_tokens
        for slot in self._free_slots():
            if not self.waiting:
                break
            req = self.waiting.pop(0)
            pid = self._next_pid
            self._next_pid += 1
            total = len(req.prompt) + req.max_new_tokens
            vma_blocks = min(self._blocks_needed(total) + 1,
                             self.layout.max_blocks)
            app = req.app
            if app is None and self.profiler is not None:
                # auto-profiling keys synthesized profiles by app — give
                # unlabeled requests the shared default bucket so the
                # profile map lookup has something to hit
                app = "_default"
            self.mm.create_process(pid, app=app, vma_blocks=vma_blocks)
            nblocks = self._blocks_needed(len(req.prompt))
            # prefix-cache admission: borrow the longest cached prefix
            # read-only (page-table surgery, no kernel work), fault only the
            # uncached suffix blocks, CoW-break a partially shared tail
            match, n_shared = None, 0
            if self.prefix_cache is not None:
                with span("serve/prefix"):
                    match = self.prefix_cache.acquire(pid, req.prompt)
                    if match is not None:
                        self.mm.map_shared(pid, 0, match.blocks)
                        n_shared = len(match.blocks)
            if self.batch_faults:
                # the whole prefill span resolves through ONE policy
                # invocation (bulk FaultKind.PREFILL placement hints)
                fault_fn = lambda p=pid, s=n_shared, n=nblocks: \
                    self.mm.fault_range(p, s, n)  # noqa: E731
            else:
                fault_fn = lambda p=pid, s=n_shared, n=nblocks: \
                    self.mm.ensure_range(p, s, n)  # noqa: E731
            ok = self._ensure_with_reclaim(fault_fn, pid, nblocks - n_shared,
                                           allow_preempt=False)
            if ok and match is not None and match.cow_logical is not None:
                # the suffix prefill writes INSIDE the last borrowed block —
                # break the share first (private copy rides the move list)
                ok = self._ensure_with_reclaim(
                    lambda p=pid, a=match.cow_logical: self.mm.cow_break(p, a),
                    pid, 1, allow_preempt=False)
            if not ok:
                if match is not None:
                    self.prefix_cache.release(match)
                self.mm.free_process(pid)
                self.waiting.insert(0, req)
                break
            # land any demotion/compaction/CoW copies before prefill writes
            # the pool (same pre-kernel ordering as the decode path)
            self._apply_pending_moves()
            seq = SeqState(req=req, pid=pid, slot=slot,
                           length=len(req.prompt), prefix=match)
            self.active[slot] = seq
            times = self._times.get(req.rid)
            if times is not None and times.admitted is None:
                times.admitted = time.perf_counter()
            self._run_prefill(seq)
            if times is not None and times.first_token is None:
                # the prefill above sampled the request's first token
                times.first_token = time.perf_counter()
            if self.prefix_cache is not None:
                # cache every whole block of the freshly prefilled prompt
                # (existing chain entries are skipped; copies ride the next
                # move-list drain, before any kernel can touch the donor)
                with span("serve/prefix"):
                    self.prefix_cache.insert(pid, req.prompt)
            self.stats.prefills += 1

    def _slot_pids(self) -> list:
        """Current slot -> pid assignment (None for empty slots)."""
        sp: list = [None] * self.max_batch
        for slot, seq in self.active.items():
            sp[slot] = seq.pid
        return sp

    @spanned("serve/tables")
    def _sync_tables(self, slot_pids) -> tuple:
        """Dirty-row sync of the device-resident block tables.

        Returns ``(didx, drows, active, triples)`` with both the full-row
        dirty set and the delta-triple set bucket-padded to a power of two
        (pad idx / pad row = -1, dropped by the install scatter) so the
        fused entries compile once per bucket pair, not once per dirty
        count."""
        idx, rows, active, tri = self._tables.sync(self.mm, slot_pids)
        K = len(idx)
        bucket = 1 << (K - 1).bit_length() if K else 0
        if bucket > K:
            idx = np.concatenate(
                [idx, np.full(bucket - K, -1, np.int32)])
            rows = np.concatenate(
                [rows, np.zeros((bucket - K, self.layout.max_blocks),
                                np.int32)])
        T = len(tri)
        tbucket = 1 << (T - 1).bit_length() if T else 0
        if tbucket > T:
            pad = np.zeros((tbucket - T, 3), np.int32)
            pad[:, 0] = -1          # row -1 routes the pad out of bounds
            tri = np.concatenate([tri, pad])
        return jnp.asarray(idx), jnp.asarray(rows), active, jnp.asarray(tri)

    @spanned("serve/prefill")
    def _run_prefill(self, seq: SeqState) -> None:
        bt = self.layout.block_tokens
        prompt = np.asarray(seq.req.prompt, np.int32)
        match = seq.prefix
        if match is not None and match.tokens > 0:
            self._run_prefill_suffix(seq, match)
            return
        S_pad = self._blocks_needed(len(prompt)) * bt
        toks = np.zeros((1, S_pad), np.int32)
        toks[0, :len(prompt)] = prompt
        # the new pid's row arrives as a dirty-row upload; the prefill jit
        # installs it and slices the slot's row from the persistent buffer
        didx, drows, _active, tri = self._sync_tables(self._slot_pids())
        kw = self._modality_kwargs(1, S_pad)
        logits, new_cache, self._table_buf = self._prefill(
            self.params, self._slot_cache_view(seq.slot), self._table_buf,
            didx, drows, tri, jnp.asarray(toks),
            jnp.asarray(seq.slot, jnp.int32),
            jnp.asarray([len(prompt) - 1], jnp.int32),
            **kw)
        self._merge_slot_cache(seq.slot, new_cache)
        self.mm.record_access(seq.pid,
                              np.ones(self._blocks_needed(len(prompt))))
        self.stats.prefill_tokens += len(prompt)
        self._sample_first(seq, logits)

    def _sample_first(self, seq: SeqState, logits) -> None:
        """The prefill's token: wait for its logits, then sample."""
        with span("serve/sync"):
            row = np.asarray(logits)[0]
        with span("serve/sample"):
            seq.generated.append(int(self.sampler.sample(
                row, self.cfg.vocab, seq.req.temperature)))

    def _run_prefill_suffix(self, seq: SeqState, match) -> None:
        """Cache-hit prefill: the first ``match.tokens`` tokens' KV is
        already in the pool (shared mappings + a CoW-broken tail), so only
        the suffix runs through the kernel.  The suffix jit assembles the
        full-length key stream — pool-gathered prefix + computed suffix —
        with the SAME padded length and chunking as the full prefill, which
        is what keeps its outputs bit-identical to the full path's suffix
        rows (the garbage tail past the valid tokens is causally masked to
        an exact-zero contribution)."""
        bt = self.layout.block_tokens
        prompt = np.asarray(seq.req.prompt, np.int32)
        L = len(prompt)
        S0 = match.tokens
        KB = self._blocks_needed(L)         # static: whole prompt's blocks
        SB = self._blocks_needed(L - S0) * bt
        toks = np.zeros((1, SB), np.int32)
        toks[0, :L - S0] = prompt[S0:]
        didx, drows, _active, tri = self._sync_tables(self._slot_pids())
        logits, new_cache, self._table_buf = self._prefill_sfx(
            self.params, self._slot_cache_view(seq.slot), self._table_buf,
            didx, drows, tri, jnp.asarray(toks),
            jnp.asarray(seq.slot, jnp.int32),
            jnp.asarray(S0, jnp.int32),
            jnp.asarray([L - S0 - 1], jnp.int32),
            key_blocks=KB)
        self._merge_slot_cache(seq.slot, new_cache)
        self.mm.record_access(seq.pid, np.ones(KB))
        self.stats.prefill_tokens += L - S0
        self._sample_first(seq, logits)

    # -------------------------------------------------- per-slot cache views
    # Pools (block dim) are global — shared across slots.  Per-sequence state
    # (mamba ssm/conv, whisper cross-attn) is slot-indexed.  The prefill runs
    # with batch=1, so slice those leaves out and merge them back.
    _POOL_KEYS = ("pool_k", "pool_v", "pool_ckv")

    def _slot_cache_view(self, slot: int) -> Pytree:
        def f(path, leaf):
            key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if key in self._POOL_KEYS:
                return leaf
            # batch-indexed leaf: [reps, B, ...] or [B, ...]
            if leaf.ndim >= 2 and key in ("ssm", "conv", "xk", "xv"):
                axis = 1 if leaf.shape[0] != self.max_batch else 0
                return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis)
            return leaf
        return jax.tree_util.tree_map_with_path(f, self.cache)

    def _merge_slot_cache(self, slot: int, new_cache: Pytree) -> None:
        def f(path, old, new):
            key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if key in self._POOL_KEYS:
                return new
            if old.ndim >= 2 and key in ("ssm", "conv", "xk", "xv"):
                axis = 1 if old.shape[0] != self.max_batch else 0
                return jax.lax.dynamic_update_slice_in_dim(
                    old, new.astype(old.dtype), slot, axis)
            return new
        self.cache = jax.tree_util.tree_map_with_path(f, self.cache, new_cache)

    def _modality_kwargs(self, batch: int, seq_len: int) -> dict:
        """Synthetic modality inputs (audio frames / vision patches).

        The prefill path always calls with ``batch == 1``, so they are a
        fixed function of the seed: generated ONCE and sliced per call —
        regenerating them from a fresh numpy RNG on every prefill was pure
        host overhead; slicing the cached full-size draw yields exactly the
        values the per-call draw produced (row-major fill order)."""
        assert batch == 1, "prefill runs one sequence at a time"
        kw = {}
        if self.cfg.enc_dec or self.cfg.vlm_patches:
            if not self._modal_cache:
                rng = np.random.default_rng(0)
                if self.cfg.enc_dec:
                    self._modal_cache["frames"] = jnp.asarray(rng.normal(
                        size=(1, self.cfg.enc_frames, self.cfg.d_model))
                        .astype(np.float32))
                if self.cfg.vlm_patches:
                    self._modal_cache["patches"] = rng.normal(
                        size=(1, self.cfg.vlm_patches, self.cfg.d_model)
                        ).astype(np.float32)
                    self._modal_cache["patch_views"] = {}
                    self._modal_cache["pos3d"] = {}
        if self.cfg.enc_dec:
            kw["frames"] = self._modal_cache["frames"]
        if self.cfg.vlm_patches:
            P = min(self.cfg.vlm_patches, seq_len)
            views = self._modal_cache["patch_views"]
            if P not in views:
                views[P] = jnp.asarray(self._modal_cache["patches"][:, :P])
            kw["patches"] = views[P]
            pos_cache = self._modal_cache["pos3d"]
            if seq_len not in pos_cache:
                pos_cache[seq_len] = jnp.asarray(np.tile(
                    np.arange(seq_len, dtype=np.float32), (3, 1, 1)))
            kw["pos3d"] = pos_cache[seq_len]
        return kw

    # ---------------------------------------------------------------- reclaim
    @spanned("serve/fault")
    def _ensure_with_reclaim(self, fault_fn, faulting_pid: int,
                             need_blocks: int, *,
                             allow_preempt: bool = True) -> bool:
        """Run a fault entry point, relieving pressure on MMOutOfMemory.

        Demote-before-preempt: each OOM first tries to free HBM by demoting
        cold blocks to the host tier — scanning all processes coldest-first
        with the nominated victim's pages preferred (a single long sequence
        spills its own cold prefix this way).  Demotion reliefs retry as
        often as they make progress; whole-sequence preemption is the
        fallback when both tiers are exhausted (or the tier policy vetoes
        every candidate) and fires AT MOST ONCE per fault.  Admission passes
        ``allow_preempt=False`` (the waiting-queue watermark): a request that
        does not fit waits for completions instead of evicting the running
        batch — the admission-evicts-actives livelock the ROADMAP calls out.
        """
        preempted = False
        for _ in range(4 + 2 * need_blocks + self.max_batch):
            try:
                fault_fn()
                return True
            except MMOutOfMemory as oom:
                # cheapest relief first: evict/demote UNPINNED prefix-cache
                # entries (cache blocks are speculative capacity — a live
                # sequence always outranks them)
                if self.prefix_cache is not None and \
                        self.prefix_cache.scan(max(1, need_blocks)) > 0:
                    continue
                if isinstance(self.mm, TieredMemoryManager) and \
                        self.mm.demote_cold_global(
                            need_blocks, prefer_pid=oom.victim_pid) > 0:
                    self.stats.tier_reliefs += 1
                    continue
                if not allow_preempt or preempted or oom.victim_pid is None:
                    return False
                self._preempt(oom.victim_pid)
                preempted = True
        return False

    # ---------------------------------------------------------------- decode
    def _preempt(self, victim_pid: int | None) -> None:
        if victim_pid is None:
            raise MMOutOfMemory("pool exhausted and nothing to evict")
        tel = self.telemetry
        for slot, seq in list(self.active.items()):
            if seq.pid == victim_pid:
                if seq.prefix is not None:
                    self.prefix_cache.release(seq.prefix)
                self.mm.evict_process(victim_pid)
                del self.active[slot]
                self.waiting.insert(0, seq.req)   # recompute-from-scratch
                self.stats.preemptions += 1
                if tel is not None and tel.enabled:
                    tel.emit(EV_PREEMPT, victim_pid, seq.req.rid, seq.length,
                             ts=self.mm.ktime_ns)
                return
        self.mm.evict_process(victim_pid)

    @spanned("serve/step")
    def step(self) -> bool:
        """One engine iteration. Returns False when all work is done."""
        t0 = time.perf_counter()
        self._admit()
        if not self.active and not self.waiting:
            return False
        if self.active:
            self._decode_once()
        with span("serve/tick"):
            if self.khugepaged is not None:
                self.khugepaged.tick()
            if isinstance(self.mm, TieredMemoryManager):
                # background promotion: bring re-heated host-tier pages
                # back to HBM
                self.mm.promotion_scan()
            if self.prefix_cache is not None:
                # periodic eviction cadence (batched HOOK_EVICT scan)
                self.prefix_cache.tick()
            self._apply_pending_moves()
            self.mm.tick()
            if self.profiler is not None and self.active:
                # sampled HOOK_PROFILE scan + profile synthesis/reload
                self.profiler.tick(
                    [(seq.pid, self.mm.procs[seq.pid].app)
                     for seq in self.active.values()
                     if seq.pid in self.mm.procs])
        self.stats.steps += 1
        self.stats.step_wall_s += time.perf_counter() - t0
        return bool(self.active or self.waiting)

    @spanned("serve/fault")
    def _fault_slots_batched(self) -> set[int]:
        """Resolve every active slot's potential boundary crossing through a
        single ``fault_batch`` — with a fault program attached, a full decode
        step issues exactly ONE policy invocation.  OOM relief mirrors the
        scalar path: demote-before-preempt on a tiered pool (retrying while
        demotion makes progress, preempting at most once), plain preemption
        otherwise.  Returns the slots whose block is mapped (safe to decode).
        """
        bt = self.layout.block_tokens
        tiered = isinstance(self.mm, TieredMemoryManager)
        pending = [(slot, seq.pid, seq.length // bt)
                   for slot, seq in sorted(self.active.items())]
        preempted = False
        for _ in range(4 + 2 * len(pending) + self.max_batch):
            pending = [(s, p, a) for s, p, a in pending
                       if s in self.active and self.active[s].pid == p]
            if not pending:
                break
            try:
                self.mm.fault_batch([(p, a, FaultKind.FIRST_TOUCH)
                                     for _, p, a in pending])
                break
            except MMOutOfMemory as oom:
                if self.prefix_cache is not None and \
                        self.prefix_cache.scan(1) > 0:
                    continue
                if tiered and self.mm.demote_cold_global(
                        1, prefer_pid=oom.victim_pid) > 0:
                    self.stats.tier_reliefs += 1
                    continue
                if oom.victim_pid is None or (tiered and preempted):
                    break
                self._preempt(oom.victim_pid)
                preempted = True
        return {slot for slot, seq in self.active.items()
                if (seq.length // bt) in self.mm.procs[seq.pid].mapped}

    @spanned("serve/fault")
    def _fault_slots_scalar(self) -> set[int]:
        """Pre-batching fault path: one ``ensure_mapped`` (one ctx build, one
        policy invocation) per faulting slot.  Kept for the hot-path
        benchmark baseline and as the reference semantics."""
        tiered = isinstance(self.mm, TieredMemoryManager)
        ok: set[int] = set()
        for slot, seq in list(self.active.items()):
            if slot not in self.active:       # preempted earlier this pass
                continue
            addr = seq.length // self.layout.block_tokens
            if tiered:
                good = self._ensure_with_reclaim(
                    lambda p=seq.pid, a=addr: self.mm.ensure_mapped(p, a),
                    seq.pid, 1)
                if good:
                    ok.add(slot)
                continue
            try:
                self.mm.ensure_mapped(seq.pid, addr)
                ok.add(slot)
            except MMOutOfMemory as oom:
                self._preempt(oom.victim_pid)
        # drop slots preempted while relieving a later slot's fault
        ok = {s for s in ok if s in self.active}
        if tiered and ok:
            # decode-time tier placement: consult HOOK_TIER for the blocks
            # this step just installed, mirroring the batched route (where
            # fault_batch runs the first-touch placement pass itself)
            bt = self.layout.block_tokens
            self.mm.place_decode(
                [(self.active[s].pid, self.active[s].length // bt,
                  FaultKind.FIRST_TOUCH) for s in sorted(ok)])
        return ok

    @spanned("serve/decode")
    def _decode_once(self) -> None:
        B, MB = self.max_batch, self.layout.max_blocks
        tokens = np.zeros(B, np.int32)
        lengths = np.zeros(B, np.int32)
        # page-fault path: each active slot's new token may cross a block
        # boundary; the batched route resolves the whole step in one policy
        # invocation
        if self.batch_faults:
            ok_slots = self._fault_slots_batched()
        else:
            ok_slots = self._fault_slots_scalar()
        # Flush demotion/promotion/compaction copies BEFORE the kernel
        # touches the pool: a fault above may have freed block A and
        # re-allocated it — the copy must land before decode overwrites A —
        # and BEFORE syncing the device tables, which a later slot's reclaim
        # or compaction may have remapped (the move bumps table_version, so
        # the sync below re-uploads the row; syncing earlier would publish
        # the pre-move mapping to the device for this step).
        self._apply_pending_moves()
        skipped: set[int] = set()     # slots that must not advance this step
        slot_pids: list = [None] * B
        for slot, seq in self.active.items():
            if slot not in ok_slots:
                # pool truly exhausted for this slot (retry next step) or it
                # was preempted relieving another slot
                skipped.add(slot)
                continue
            tokens[slot] = seq.generated[-1]
            lengths[slot] = seq.length
            slot_pids[slot] = seq.pid
        # dirty-row upload: only rows whose table_version moved since the
        # last sync cross to the device; skipped slots sync as vacant so
        # their persistent rows cannot alias live pool blocks
        didx, drows, active, tri = self._sync_tables(slot_pids)
        pos3d = None
        if self.cfg.vlm_patches:
            pos3d = jnp.asarray(
                np.tile(lengths.astype(np.float32)[None, :, None], (3, 1, 1)))
        logits, self.cache, (heat, reads), self._table_buf = self._decode(
            self.params, self.cache, self._table_buf, didx, drows, tri,
            jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(active), pos3d)
        with span("serve/sync"):
            logits_np, heat_np, reads_np = jax.device_get(
                (logits, heat, reads))
        self.stats.count_reads(reads_np)
        self._fold_and_sample(skipped, logits_np, heat_np)

    @spanned("serve/sample")
    def _fold_and_sample(self, skipped, logits_np, heat_np) -> None:
        """A decode step's host tail: fold each row's attention mass into
        DAMON (and the prefix cache's heat), sample its token and retire
        the rows that reached their limit."""
        for slot, seq in list(self.active.items()):
            if slot in skipped:
                # its batch row decoded with no block table — the logits are
                # garbage; the sequence stays put and refaults next step
                continue
            nb = self._blocks_needed(seq.length + 1)
            self.mm.record_access(seq.pid, heat_np[slot, :nb])
            if seq.prefix is not None:
                # fold the borrower's attention mass over the shared span
                # into the matched entries' heat EMAs (the DAMON signal the
                # eviction programs read as PAGE_HEAT)
                self.prefix_cache.note_heat(seq.prefix, heat_np[slot, :nb])
            app = seq.req.app or "_default"
            if app not in self.heat_histograms:
                self.heat_histograms[app] = np.zeros(self.layout.max_blocks,
                                                     np.float64)
            self.heat_histograms[app][:nb] += heat_np[slot, :nb]
            tok = self.sampler.sample(logits_np[slot], self.cfg.vocab,
                                      seq.req.temperature)
            seq.generated.append(int(tok))
            seq.length += 1
            self.stats.decode_tokens += 1
            limit = seq.req.max_new_tokens
            if seq.req.stop_after is not None:
                limit = min(limit, seq.req.stop_after)
            if len(seq.generated) >= limit:
                self.finished[seq.req.rid] = list(seq.generated)
                if seq.prefix is not None:
                    self.prefix_cache.release(seq.prefix)
                self.mm.free_process(seq.pid)
                del self.active[slot]
                self.stats.completed += 1
                times = self._times.pop(seq.req.rid, None)
                if times is not None:
                    self.request_log.append(times)

    @spanned("serve/moves")
    def _apply_pending_moves(self) -> None:
        moves = self.mm.drain_moves()
        if not moves:
            return
        # A batched .at[dst].set(leaf[src]) reads every src from the PRE-move
        # pool, so a chain within one drain (compact A->B, then demote B->H)
        # would copy stale data; and a repeated destination (block freed and
        # re-allocated within the drain) makes the scatter winner undefined.
        # Segment the list so no batch reads OR writes a block an earlier
        # move in the same batch wrote; batches apply in order.
        batches: list[list[tuple[int, int, int]]] = [[]]
        written: set[int] = set()
        for s, d, o in moves:
            n = order_blocks(o)
            if any(b in written for b in range(s, s + n)) or \
                    any(b in written for b in range(d, d + n)):
                batches.append([])
                written = set()
            batches[-1].append((s, d, o))
            written.update(range(d, d + n))
        for batch in batches:
            self._apply_move_batch(batch)

    def _apply_move_batch(self, moves: list[tuple[int, int, int]]) -> None:
        src = np.concatenate([np.arange(s, s + order_blocks(o))
                              for s, _, o in moves]).astype(np.int32)
        dst = np.concatenate([np.arange(d, d + order_blocks(o))
                              for _, d, o in moves]).astype(np.int32)
        # pow2 bucket so the fused tree-wide copy compiles once per bucket
        # (pad dst = -1 routes out of bounds and is dropped); one dispatch
        # replaces an eager scatter per pool leaf — prefix-cache insert
        # copies and steady migration traffic both ride this path
        P = 1 << (len(src) - 1).bit_length()
        if P > len(src):
            src = np.concatenate([src, np.zeros(P - len(src), np.int32)])
            dst = np.concatenate([dst, np.full(P - len(dst), -1, np.int32)])
        self.cache = self._moves(self.cache, jnp.asarray(src),
                                 jnp.asarray(dst))

    # ------------------------------------------------------------------ run
    def run(self, max_steps: int = 10_000) -> dict:
        steps = 0
        while self.step():
            steps += 1
            if steps >= max_steps:
                break
        out = {"engine": self.stats.snapshot(), "mm": self.mm.stats.snapshot(),
               "huge_fraction": self.mm.hugepage_block_fraction(),
               "tables": {"syncs": self._tables.syncs,
                          "synced_rows": self._tables.synced_rows,
                          "blank_rows": self._tables.blank_rows,
                          "full_rows": self._tables.full_rows,
                          "delta_rows": self._tables.delta_rows,
                          "delta_cells": self._tables.delta_cells}}
        if isinstance(self.mm, TieredMemoryManager):
            out["tier"] = self.mm.tier_snapshot()
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.snapshot()
        if self.khugepaged is not None:
            out["khugepaged"] = {"collapsed": self.khugepaged.collapsed,
                                 "considered": self.khugepaged.considered}
        if self.profiler is not None:
            out["profiler"] = self.profiler.snapshot()
        if self.telemetry is not None and self.telemetry.enabled:
            out["telemetry"] = self.telemetry.snapshot()
        return out

    # ------------------------------------------------------------ telemetry
    def write_trace(self, path) -> None:
        """Write the Chrome trace-event JSON (load in Perfetto / chrome://
        tracing) of the mm/program ring events, on the modeled clock."""
        if self.telemetry is None:
            raise ValueError("engine was built without telemetry "
                             "(pass telemetry=...)")
        write_chrome_trace(self.telemetry, path)

    def write_wss_curve(self, path) -> None:
        """Dump the online profiler's per-process WSS curve as JSON
        (samples of modeled time / WSS blocks / mapped blocks)."""
        if self.profiler is None:
            raise ValueError("engine has no online profiler "
                             "(pass profile='auto')")
        self.profiler.write_wss_curve(path)

    def metrics(self) -> dict:
        """Flat ``{metric_name: number}`` snapshot across every subsystem:
        engine stats, request latencies, mm stats, hook counters,
        artifact-cache stats, tier pools, and (when telemetry is on)
        histograms/counters/ring stats."""
        sections = {
            "engine": self.stats.snapshot(),
            "requests": self._request_percentiles(),
            "mm": self.mm.stats.snapshot(),
            "huge_fraction": self.mm.hugepage_block_fraction(),
            "hooks": {"invocations": self.mm.hooks.invocations,
                      "calls": self.mm.hooks.calls,
                      "batch_calls": self.mm.hooks.batch_calls},
            "cache": self.mm.hooks._artifact_cache().stats,
        }
        res: dict = {"supervisor": self.mm.hooks.supervisor.snapshot()}
        if self.injector is not None:
            res["injector"] = self.injector.snapshot()
        if isinstance(self.mm, TieredMemoryManager):
            sections["tier"] = self.mm.tier_snapshot()
            res["health"] = self.mm.health.snapshot()
        if self.prefix_cache is not None:
            sections["prefix_cache"] = self.prefix_cache.snapshot()
        if self.profiler is not None:
            sections["profiler"] = self.profiler.snapshot()
        sections["resilience"] = res
        if self.telemetry is not None and self.telemetry.enabled:
            sections["telemetry"] = self.telemetry.snapshot()
        return flatten_metrics(sections)

    def _request_percentiles(self) -> dict:
        """Admit wait (submit -> admission) and time to first token (submit
        -> first token) in ms, p50 and p99, over the requests the engine
        has times of."""
        times = self.request_times()
        out: dict = {"count": len(times)}
        for name, attr in (("admit_wait", "admitted"),
                           ("ttft", "first_token")):
            v = [1e3 * (getattr(t, attr) - t.submitted) for t in times
                 if getattr(t, attr) is not None]
            if v:
                out[f"{name}_ms_p50"] = float(np.percentile(v, 50))
                out[f"{name}_ms_p99"] = float(np.percentile(v, 99))
        return out

    def metrics_text(self) -> str:
        """Prometheus-style text exposition of :meth:`metrics`."""
        return render_prometheus(self.metrics())

    def poll_events(self) -> list[dict]:
        """Drain and decode any ring events published since the last poll —
        the LIVE consumer path (mid-run), as opposed to the end-of-run
        ``write_trace`` export.  ``[]`` when telemetry is off."""
        if self.telemetry is None:
            return []
        return self.telemetry.poll_events()
