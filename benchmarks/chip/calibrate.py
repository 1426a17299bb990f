"""The readings a cell's correctness limit is set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload phi3.chat \
        --seeds 1,2,3 --seconds 15 [--control] [--smoke]

For each seed it makes that seed's weights, serves that seed's traffic
through the same engine (built and warmed once; the queue is drained and
the weights swapped between seeds) for the lead-in and a window of
``--seconds`` at the cell's own load, and compares the sample of finished
requests that a run compares with the float32 reference: the program's
widest logit gap (the lower reading's candidates).  With ``--control`` it
also reads the control on the same prompts and served tokens: the widest
gap of the token that the reference computed in float8 puts first (the
upper reading's candidates).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    import cell as cell_mod
    c = cell_mod.open_cell(args.workload, smoke=args.smoke)
    import jax
    import check
    import harness
    import traffic as traffic_mod
    import warmup
    import weights
    from run import load_module

    ref = load_module(HERE / "refs" / f"{c.cfile['reference']}.py", "ref")
    engine = None
    for seed in seeds:
        t0 = time.perf_counter()
        if engine is not None:
            # one set of weights on the device at a time
            engine.params = None
            gc.collect()
        params = jax.block_until_ready(weights.make(c.cfg, seed))
        if engine is None:
            engine = cell_mod.build_engine(c, params, seed)
            warmup.warm(engine, c.traffic)
        else:
            engine.params = params
        engine.finished.clear()
        reqs = traffic_mod.schedule(c.traffic, args.seconds, seed,
                                    c.cfg.vocab)
        win = harness.serve(engine, reqs, origin=time.perf_counter(),
                            lead_in_s=c.traffic["lead_in_s"],
                            seconds=args.seconds,
                            app=c.engine_cfg.get("app"))
        finished = {rid: list(t) for rid, t in engine.finished.items()}
        sample = check.sample(win, finished, reqs, seed, c.traffic["check"])
        out = {"cell": args.workload, "seed": seed,
               "requests": len(sample),
               "preempted": sum(win.records[r].preempted > 0
                                for r in sample)}
        got, n_tok = check.compare(ref, params, c.ref_cfg, reqs, finished,
                                   sample, c.traffic)
        out["program_max_logit_gap"] = got["max_logit_gap"]["value"]
        out["served_tokens"] = n_tok
        out["wrong_length_requests"] = got["wrong_length_requests"]["value"]
        if args.control:
            ctl, _ = check.compare(ref, params, c.ref_cfg, reqs, finished,
                                   sample, c.traffic, quant="fp8")
            out["control_max_logit_gap"] = ctl["max_logit_gap"]["value"]
        # finish the window's leftovers on these weights before the swap
        while engine.active or engine.waiting:
            engine.step()
        del params
        gc.collect()
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
