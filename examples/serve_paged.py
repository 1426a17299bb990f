"""End-to-end serving driver: continuous batching over a real (smoke-size)
model with the eBPF-mm paged KV cache — batched requests, page faults on
block crossings, DAMON heat from attention mass, and (with --host-blocks)
the tiered-memory subsystem: under pressure cold KV blocks are demoted to a
host-DRAM tier over PCIe instead of preempting whole sequences, and promoted
back when they re-heat.  Without a host tier, preemption under pressure.

Run:  PYTHONPATH=src python examples/serve_paged.py [--arch gemma3_27b]
      PYTHONPATH=src python examples/serve_paged.py \
          --hbm-blocks 48 --host-blocks 256 --tier ebpf-tier   # 2-tier
      PYTHONPATH=src python examples/serve_paged.py \
          --hbm-blocks 48 --tier-blocks 32,160,64 \
          --tier heat-tier                     # 4-tier: +peer-HBM, +NVMe
      PYTHONPATH=src python examples/serve_paged.py \
          --trace out/trace.json --metrics out/metrics.txt  # telemetry:
          # Chrome trace (load in Perfetto) + Prometheus-style metrics
      PYTHONPATH=src python examples/serve_paged.py \
          --hbm-blocks 48 --host-blocks 256 --chaos 7   # chaos: seeded
          # deterministic fault injection + live ring-event consumption
      PYTHONPATH=src python examples/serve_paged.py \
          --profile auto --trace out/trace.json \
          --wss-curve out/wss.json   # online profiling: no profile loaded,
          # a verified profiler program samples the live DAMON regions and
          # synthesized profiles hot-reload mid-run (WSS curve + reloads
          # appear on the trace's "mm profiler" track)
"""

import argparse
import json

import numpy as np

from repro.configs.base import get_smoke_config
from repro.core import Profile, ProfileRegion
from repro.models import PagedLayout, serving_params
from repro.serving import Request, ServingEngine

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="gemma3_27b")
ap.add_argument("--policy", default="ebpf",
                choices=["ebpf", "thp", "never"])
ap.add_argument("--profile", default="demo", metavar="auto|FILE|none",
                help="profile source for --policy ebpf: 'auto' = online "
                     "synthesis (a verified profiler program samples the "
                     "live DAMON regions and synthesized profiles hot-"
                     "reload mid-run), FILE = a profile JSON "
                     "(Profile.to_json), 'none' = no profile (non-ebpf "
                     "policies only), default = the built-in hot-prefix "
                     "demo profile")
ap.add_argument("--wss-curve", default="", metavar="FILE",
                help="with --profile auto: dump the online profiler's "
                     "per-process WSS curve JSON to FILE at exit")
ap.add_argument("--requests", type=int, default=8)
ap.add_argument("--hbm-blocks", type=int, default=512,
                help="modeled HBM pool size in blocks")
ap.add_argument("--host-blocks", type=int, default=0,
                help="host-DRAM tier size in blocks (0 = no tiering)")
ap.add_argument("--tier-blocks", default="",
                help="comma-separated spill-tier capacities for an N-pool "
                     "chain, e.g. '64,192,256' = peer-HBM, host DRAM, NVMe "
                     "(overrides --host-blocks)")
ap.add_argument("--tier", default="ebpf-tier",
                choices=["ebpf-tier", "lru-tier", "never-tier", "heat-tier",
                         "edge-tier", "default"],
                help="mm_tier hook policy (used when a tier chain is set)")
ap.add_argument("--prefix-cache", type=int, default=0, metavar="BLOCKS",
                help="enable the cross-request KV prefix cache with an HBM "
                     "budget of BLOCKS; requests then share a common system "
                     "prompt so later admissions hit")
ap.add_argument("--prefix-share", type=float, default=0.5,
                help="fraction of requests opening with the shared prefix "
                     "(with --prefix-cache; default 0.5)")
ap.add_argument("--evict-policy", default="lru-evict",
                choices=["lru-evict", "lfu-evict", "ghost-evict", "default"],
                help="HOOK_EVICT program deciding which cached prefixes to "
                     "demote/drop (with --prefix-cache)")
ap.add_argument("--scalar-faults", action="store_true",
                help="pre-batching fault path: one policy invocation per "
                     "fault instead of one per engine step")
ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                help="arm the deterministic failure injector with SEED: "
                     "migration copy errors, tier-alloc failures, link "
                     "flaps, hook runtime errors (same seed = same "
                     "failure schedule)")
ap.add_argument("--chaos-rate", type=float, default=0.02,
                help="per-site failure probability for --chaos "
                     "(default 0.02)")
ap.add_argument("--no-containment", action="store_true",
                help="disable the resilience machinery (no retry/backoff, "
                     "no quarantine, no policy detach) — the chaos "
                     "baseline lane")
ap.add_argument("--trace", default="", metavar="FILE",
                help="enable telemetry and write a Chrome trace-event JSON "
                     "(mm/program ring events) to FILE")
ap.add_argument("--metrics", nargs="?", const="-", default="", metavar="FILE",
                help="enable telemetry and dump a Prometheus-style metrics "
                     "snapshot to FILE (default: stdout)")
args = ap.parse_args()

cfg = get_smoke_config(args.arch)
tier_blocks = tuple(int(b) for b in args.tier_blocks.split(",") if b) or None
tier_note = (f", {args.tier} over tiers {tier_blocks}" if tier_blocks
             else f", {args.tier} over {args.host_blocks} host blocks"
             if args.host_blocks else "")
print(f"serving {cfg.name} ({args.policy} policy{tier_note})")
params = serving_params(cfg)
layout = PagedLayout(num_blocks=args.hbm_blocks, block_tokens=4,
                     max_blocks=32)

if args.policy != "ebpf" or args.profile == "none":
    if args.policy == "ebpf":
        ap.error("--profile none requires a non-ebpf --policy "
                 "(the eBPF policy needs a profile source; try "
                 "--profile auto)")
    profile = None
elif args.profile == "auto":
    profile = "auto"
elif args.profile == "demo":
    profile = Profile("chat", [
        ProfileRegion(0, 8, (0, 150_000, 600_000, 2_500_000)),  # hot prefix
        ProfileRegion(8, 32, (0, 0, 0, 0)),                     # cold tail
    ])
else:
    with open(args.profile) as f:
        profile = Profile.from_json(f.read())

telemetry = True if (args.trace or args.metrics or
                     args.chaos is not None) else None
engine = ServingEngine(cfg, params, layout, max_batch=4, policy=args.policy,
                       profile=profile, host_blocks=args.host_blocks,
                       tier_blocks=tier_blocks, tier_policy=args.tier,
                       batch_faults=not args.scalar_faults,
                       telemetry=telemetry,
                       chaos=args.chaos, chaos_rate=args.chaos_rate,
                       containment=not args.no_containment,
                       prefix_cache=args.prefix_cache or False,
                       evict_policy=args.evict_policy)
if args.prefix_cache:
    print(f"prefix cache: {args.prefix_cache} HBM blocks, "
          f"{args.evict_policy}, {args.prefix_share:.0%} shared traffic")
if args.chaos is not None:
    print(f"chaos armed: seed={args.chaos} rate={args.chaos_rate} "
          f"containment={'off' if args.no_containment else 'on'}")
rng = np.random.default_rng(0)
shared_prefix = rng.integers(1, cfg.vocab, 24).tolist()
for r in range(args.requests):
    if args.prefix_cache and rng.random() < args.prefix_share:
        prompt = shared_prefix + rng.integers(1, cfg.vocab, 8).tolist()
    else:
        plen = int(rng.integers(16, 48))
        prompt = rng.integers(1, cfg.vocab, plen).tolist()
    engine.submit(Request(
        rid=r, prompt=prompt,
        max_new_tokens=24, app="chat", temperature=0.0))

# With chaos armed (and no trace export pending — poll_events drains the
# ring destructively) consume the event ring LIVE every few steps, the way
# a monitoring sidecar would: detach / quarantine / retry events surface
# mid-run instead of only in the end-of-run snapshot.
live_counts: dict[str, int] = {}
if args.chaos is not None and not args.trace:
    steps = 0
    while engine.step() and steps < 10_000:
        steps += 1
        if steps % 8 == 0:
            for ev in engine.poll_events():
                live_counts[ev["name"]] = live_counts.get(ev["name"], 0) + 1
    for ev in engine.poll_events():
        live_counts[ev["name"]] = live_counts.get(ev["name"], 0) + 1
    out = {"engine": engine.stats.snapshot(),
           "mm": engine.mm.stats.snapshot(),
           "huge_fraction": engine.mm.hugepage_block_fraction()}
else:
    out = engine.run()
print(json.dumps(out, indent=1, default=float))
if args.chaos is not None:
    m = engine.metrics()
    resil = {k: v for k, v in sorted(m.items())
             if k.startswith("resilience_") and v}
    print("resilience:", json.dumps(resil, default=float))
    if live_counts:
        print("live ring events:",
              json.dumps(dict(sorted(live_counts.items()))))
for rid in sorted(engine.finished)[:3]:
    print(f"request {rid}: generated {engine.finished[rid][:10]}...")

if engine.profiler is not None:
    p = engine.profiler.snapshot()
    print(f"online profiler: {p['scans']} scans, {p['reloads']} reloads, "
          f"apps={json.dumps(p['apps'])}")
    if args.wss_curve:
        engine.write_wss_curve(args.wss_curve)
        print(f"wrote WSS curve to {args.wss_curve}")
if args.trace:
    engine.write_trace(args.trace)
    print(f"wrote Chrome trace to {args.trace} (open in ui.perfetto.dev)")
if args.metrics:
    text = engine.metrics_text()
    if args.metrics == "-":
        print(text, end="")
    else:
        with open(args.metrics, "w") as f:
            f.write(text)
        print(f"wrote metrics snapshot to {args.metrics}")
