"""Figure-2 analogue: policy sweep on the serving engine.

The paper's preliminary result (astar, SPEC06): eBPF-mm reaches THP-level
performance while allocating a fraction of the 2MiB pages, by backing only
the AT-intensive regions.  Our workload is the serving version of that
motivation ("different applications benefit from different page sizes"): a
MIXED tenancy of
  * "rag"  — long-context requests: every KV block is re-read each step
             (AT-intensive; huge pages pay off), and
  * "chat" — short-lived requests with reserved-but-unused tail capacity
             (huge pages waste zeroing + compaction under fragmentation),
on a deliberately fragmented pool.  Profiles are DERIVED from a DAMON
profiling pass (profile_from_heat) exactly per the paper's workflow, and one
Fig-1 program serves both apps via the indirect profile-map load.

Reported per policy: modeled device time (management + paged reads),
descriptors touched (TLB-miss analogue), huge-page fraction, compactions,
blocks zeroed — plus the hook-overhead microbench ("zero overhead on
non-hinted faults").
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs.base import get_smoke_config
from repro.core import (HWSpec, MemoryManager, Profile, ProfileRegion,
                        ebpf_mm_program, make_cost_model, never_program,
                        profile_from_heat)
from repro.core.mm import MMStats
from repro.models import PagedLayout, materialize, model_spec
from repro.serving import Request, ServingEngine

LAYOUT = PagedLayout(num_blocks=256, block_tokens=4, max_blocks=32)


def _submit_workload(eng, cfg, rng) -> int:
    n = 0
    for r in range(3):          # long-context, AT-intensive
        plen = int(rng.integers(80, 112))
        eng.submit(Request(rid=n, prompt=rng.integers(1, cfg.vocab, plen).tolist(),
                           max_new_tokens=16, app="rag"))
        n += 1
    for r in range(5):          # short-lived, early EOS, reserved capacity
        plen = int(rng.integers(8, 20))
        eng.submit(Request(rid=n, prompt=rng.integers(1, cfg.vocab, plen).tolist(),
                           max_new_tokens=48, app="chat",
                           stop_after=int(rng.integers(4, 10))))
        n += 1
    return n


def _fragment_pool(eng) -> None:
    """Fill the pool with order-0 blocks, keep every 4th pinned: free space
    becomes runs of 3 blocks, so every huge-page alloc needs compaction."""
    frag_pids = []
    for i in range(eng.layout.num_blocks):
        pid = 90_000 + i
        eng.mm.create_process(pid, vma_blocks=2)
        try:
            eng.mm.ensure_mapped(pid, 0)
            frag_pids.append(pid)
        except Exception:
            eng.mm.free_process(pid)
            break
    for j, pid in enumerate(frag_pids):
        if j % 4 != 0:
            eng.mm.free_process(pid)


def run_policy(policy: str, *, seed: int = 0, profiles=None) -> dict:
    cfg = get_smoke_config("gemma3_27b")
    params = materialize(jax.random.PRNGKey(0), model_spec(cfg))
    eng = ServingEngine(cfg, params, LAYOUT, max_batch=3, policy=policy,
                        profile=profiles, seed=seed)
    rng = np.random.default_rng(seed)
    n_req = _submit_workload(eng, cfg, rng)
    _fragment_pool(eng)
    eng.mm.stats = MMStats()      # measure the serving phase only

    peak_huge, steps = 0.0, 0
    while eng.step():
        peak_huge = max(peak_huge, eng.mm.hugepage_block_fraction())
        steps += 1
        if steps > 600:
            break
    mm = eng.mm.stats.snapshot()
    return {
        "heat_histograms": {k: v / max(1, eng.stats.steps)
                            for k, v in eng.heat_histograms.items()},
        "policy": policy,
        "modeled_device_us": (mm["mgmt_ns"] + mm["access_ns"]) / 1e3,
        "descriptors": mm["descriptors_touched"],
        "peak_huge_fraction": peak_huge,
        "pages_per_order": mm["pages_per_order"],
        "compactions": mm["compactions"],
        "compaction_blocks": mm["compaction_blocks_moved"],
        "blocks_zeroed": mm["blocks_zeroed"],
        "completed": eng.stats.completed,
        "expected": n_req,
        "step_wall_s": eng.stats.step_wall_s,
    }


def derive_profiles(heat_histograms: dict) -> list[Profile]:
    """DAMON replay -> per-app userspace profiles (paper workflow step 2)."""
    cost = make_cost_model(HWSpec(), kv_heads=2, head_dim=16, block_tokens=4)
    profs = []
    for app, hist in sorted(heat_histograms.items()):
        p = profile_from_heat(app, hist, cost, hot_quantile=0.3,
                              min_region_blocks=4)
        profs.append(p if p.regions else Profile(app, []))
    return profs


def bench_hook_overhead(n_faults: int = 2000) -> dict:
    """Per-fault host cost on the SAME allocation pattern (all order-0):
    no program attached (paper's zero-overhead default path) vs a loaded
    never-program (hook + ctx build + VM run) vs the full Fig-1 program."""
    hw = HWSpec()
    out = {}
    for mode in ("default", "never-prog", "ebpf-cold"):
        mm = MemoryManager(2 * n_faults + 64,
                           make_cost_model(hw, kv_heads=8, head_dim=128),
                           default_mode="never")
        if mode == "never-prog":
            mm.attach_fault_program(never_program())
        elif mode == "ebpf-cold":
            prof = Profile("app", [ProfileRegion(0, n_faults + 8,
                                                 (0, 0, 0, 0))])
            mm.load_profile(prof)
            mm.attach_fault_program(ebpf_mm_program())
        mm.create_process(1, app="app" if mode == "ebpf-cold" else None,
                          vma_blocks=n_faults + 8)
        t0 = time.perf_counter()
        for addr in range(n_faults):
            mm.ensure_mapped(1, addr)
        dt = time.perf_counter() - t0
        out[mode] = dt / n_faults * 1e6
    out["hook_overhead_us"] = out["never-prog"] - out["default"]
    out["policy_overhead_us"] = out["ebpf-cold"] - out["default"]
    return out


def main() -> list[str]:
    lines = []
    base = None
    profiles = None
    for policy in ("never", "thp", "ebpf"):
        r = run_policy(policy, profiles=profiles)
        if policy == "never":
            base = r["modeled_device_us"]
            profiles = derive_profiles(r["heat_histograms"])
        speedup = base / max(r["modeled_device_us"], 1e-9)
        lines.append(
            f"fig2_{policy},{r['modeled_device_us']:.1f},"
            f"speedup={speedup:.2f};desc={r['descriptors']};"
            f"huge={r['peak_huge_fraction']:.2f};"
            f"orders={'/'.join(map(str, r['pages_per_order']))};"
            f"compactions={r['compactions']};"
            f"zeroed={r['blocks_zeroed']};"
            f"completed={r['completed']}/{r['expected']}")
    ho = bench_hook_overhead()
    lines.append(f"hook_overhead,{ho['never-prog']:.2f},"
                 f"default_us={ho['default']:.2f};"
                 f"hook_delta_us={ho['hook_overhead_us']:.2f};"
                 f"fig1_policy_us={ho['ebpf-cold']:.2f}")
    return lines


if __name__ == "__main__":
    for line in main():
        print(line)
