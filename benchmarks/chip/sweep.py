"""Find a cell's knee: serve its traffic at several fixed rates, in one
process, and report what each rate sustains.

    python3 benchmarks/chip/sweep.py --workload phi3.chat \
        --rates 1,2,3,4 --seconds 20 --seed 5

The engine is built and warmed once; before each rate the queue is drained.
Per rate (requests per second, replacing the traffic file's) one JSON line:
output tokens/s, time to first token p50/p90, the inter-token gap p50, the
requests due in the window and those not finished at its end, and the
queue left waiting.  The knee is the highest rate whose queue stays short
and whose time to first token has not run away; the cell's rate is then
written into its traffic file by hand.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    import cell as cell_mod
    c = cell_mod.open_cell(args.workload, smoke=args.smoke)
    import jax
    import harness
    import traffic as traffic_mod
    import warmup
    import weights

    t0 = time.perf_counter()
    params = jax.block_until_ready(weights.make(c.cfg, args.seed))
    engine = cell_mod.build_engine(c, params, args.seed)
    warmed = warmup.warm(engine, c.traffic)
    print(f"setup: {warmed} in {time.perf_counter() - t0:.3f} s", flush=True)
    base = 0
    for rate in (float(r) for r in args.rates.split(",")):
        tr = copy.deepcopy(c.traffic)
        tr["arrival"]["rate_per_s"] = rate
        reqs = traffic_mod.schedule(tr, args.seconds, args.seed, c.cfg.vocab)
        for r in reqs:          # rids stay unique across rates
            r.rid += base
        base += len(reqs)
        engine.finished.clear()
        win = harness.serve(engine, reqs, origin=time.perf_counter(),
                            lead_in_s=tr["lead_in_s"], seconds=args.seconds,
                            app=c.engine_cfg.get("app"))
        ttft = harness.ttft_s(win)
        due = harness.due_in_window(win)
        print(json.dumps({
            "rate": rate,
            "output_tok_s": harness.tokens_in_window(win) / args.seconds,
            "ttft_p50_ms": 1e3 * harness.pct(ttft, 50),
            "ttft_p90_ms": 1e3 * harness.pct(ttft, 90),
            "itl_p50_ms": 1e3 * (harness.pct(harness.gaps_s(win), 50) or 0),
            "due": len(due),
            "unfinished": sum(r.finished is None for r in due),
            "waiting": len(engine.waiting),
            "preemptions": (win.stats1["preemptions"]
                            - win.stats0["preemptions"])}), flush=True)
        while engine.active or engine.waiting:
            engine.step()


if __name__ == "__main__":
    main()
