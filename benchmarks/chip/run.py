"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload phi3.chat --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``).
The run makes the weights on the device from the seed, builds the
ServingEngine the configuration describes, warms up every program variant
the traffic can use, runs the traffic's lead-in, and then measures an
open-loop window of ``--seconds``.  After the window it reads the device's
peak memory, frees the engine and checks a sample of the finished requests
against the float32 reference (``refs/<reference>.py``).

Facts go to stdout one per line; the numbers compared go to stderr as the
last lines; the last line of stdout is the JSON result.  With ``--trace 0``
the result's metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics (read by ``metrics/<name>.py``) from a profiler trace
of the window.  Without a TPU (or with fewer chips than the cell asks for)
it exits non-zero and prints no result; ``--smoke`` runs a smoke-size model
of the same architecture on whatever JAX finds, for a CPU rehearsal.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))


def fail(msg: str, code: int = 2):
    print(f"run: {msg}", file=sys.stderr)
    sys.exit(code)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(b: dict, cell: str, kind: str) -> list[dict]:
    """The cell's metrics of one kind (``end_to_end`` or ``per_layer``): a
    metric with ``workloads`` lists its cells; one without is reported
    wherever the end-to-end metric it moves is (or, end to end, everywhere)."""
    e2e = {m["name"] for m in b["end_to_end"]
           if cell in m.get("workloads", [cell])}
    out = []
    for m in b[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


@dataclass
class Run:
    """What a metric reader (``metrics/<name>.py``: ``read(run)``) sees."""
    cell: str
    seconds: float
    setup_s: float
    win: object                  # harness.Window
    engine: object
    cfg: object                  # the served ModelConfig
    max_batch: int
    peaks: dict | None           # peaks.json entry of this device, or None
    memory: dict | None          # device.memory_stats() after the window
    trace: object | None = None  # reduce.Trace of the window (--trace 1)
    trace_from: float | None = None  # where the trace starts (s from origin)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-size model on whatever platform JAX finds")
    args = ap.parse_args()

    import cell as cell_mod
    try:
        c = cell_mod.open_cell(args.workload, smoke=args.smoke)
    except cell_mod.CellError as e:
        fail(str(e))
    import jax
    import harness
    import traffic as traffic_mod
    import warmup
    import weights

    dev, cfg, traffic, eng_cfg = c.device, c.cfg, c.traffic, c.engine_cfg
    chips = c.entry["chips"]
    devs = jax.devices()[:chips]
    print(f"platform: {dev.platform}")
    print(f"device_kind: {dev.device_kind}")
    print(f"device_count: {len(jax.devices())}")
    print(f"config: {c.entry['config']} layers={cfg.n_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab}")
    counter = harness.CompileCounter()
    t = time.perf_counter()
    params = jax.block_until_ready(weights.make(cfg, args.seed))
    print(f"weight_bytes: {sum(x.nbytes for x in jax.tree.leaves(params))} "
          f"in {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    try:
        engine = cell_mod.build_engine(c, params, args.seed)
    except cell_mod.CellError as e:
        fail(str(e))
    jax.block_until_ready(engine.cache)
    print(f"engine_build_s: {time.perf_counter() - t:.3f}")
    t_warm = time.perf_counter()
    warmed = warmup.warm(engine, traffic)
    jax.block_until_ready(engine.cache)
    print(f"warmup: {warmed} in {time.perf_counter() - t_warm:.3f} s")
    print(f"pool_blocks: {traffic['pool_blocks']} "
          f"max_blocks: {traffic['max_blocks']}")

    import check
    reqs = traffic_mod.schedule(traffic, args.seconds, args.seed, cfg.vocab)
    annotate = tracer = None
    if args.trace:
        import tracing
        tracer = tracing.Tracer(ROOT / ".cache" / "trace" / args.workload,
                                HERE / "spans.json", chips)
        tracer.wrap(engine, log=print)
        annotate = tracer.annotate
    # outside the spans: the policy.run_batch span times the executor alone
    policy = check.PolicyRecorder(engine)

    def on_window_start():
        policy.armed = True

    # the traced run profiles the window's last seconds only: the trace
    # stays small, and stopping it (which writes the file) falls after the
    # window
    w_end = traffic["lead_in_s"] + args.seconds
    trace_from = w_end - min(args.seconds, tracer.seconds) if tracer \
        else None

    def on_step(now):
        if not tracer.started and now >= trace_from:
            tracer.start(now)

    origin = time.perf_counter()
    win = harness.serve(engine, reqs, origin=origin,
                        lead_in_s=traffic["lead_in_s"], seconds=args.seconds,
                        app=eng_cfg.get("app"), annotate=annotate,
                        on_window_start=on_window_start,
                        on_step=on_step if tracer is not None else None,
                        counter=counter)
    jax.block_until_ready(engine.cache)
    policy.armed = False
    setup_s = origin + win.start - T_PROCESS
    trace = tracer.stop_and_reduce() if tracer is not None else None
    if tracer is not None:
        print(f"traced_from_s: {tracer.t_start} of a window "
              f"{win.start}-{win.end}")
    memory = dev.memory_stats()
    if memory:
        print(f"memory: peak_bytes_in_use={memory.get('peak_bytes_in_use')} "
              f"bytes_limit={memory.get('bytes_limit')}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips,
              "memory_peak_bytes": int(max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devs))}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s

    due = harness.due_in_window(win)
    unfinished = [r for r in due if r.finished is None]
    late = harness.lateness_s(win)
    print(f"requests_due_in_window: {len(due)}")
    print(f"requests_started_in_window: "
          f"{sum(1 for r in due if r.admitted_step is not None)}")
    print(f"requests_unfinished_at_window_end: {len(unfinished)}")
    print(f"requests_finished_in_run: "
          f"{sum(1 for r in win.records.values() if r.finished)}")
    print(f"generator_lateness_s: p50={harness.pct(late, 50)} "
          f"max={max(late) if late else None}")
    detail = win.compiles.pop("detail", [])
    print(f"window_compiles: {win.compiles}")
    for d in detail:
        print(f"window_compile: {d}")
    print(f"window_steps: {len(harness.window_steps(win))} "
          f"idle_s={win.idle_s}")
    print(f"preemptions: "
          f"{win.stats1['preemptions'] - win.stats0['preemptions']} in the "
          f"window, {win.stats1['preemptions']} in the run; "
          f"{sum(r.preempted > 0 for r in due)} of the requests due in the "
          f"window preempted")

    run = Run(cell=args.workload, seconds=args.seconds, setup_s=setup_s,
              win=win, engine=engine, cfg=cfg,
              max_batch=eng_cfg["max_batch"], peaks=c.peaks, memory=memory,
              trace=trace,
              trace_from=tracer.t_start if tracer is not None else None)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(c.bench, args.workload, kind):
        reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                             f"metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = trace.breakdown() if trace is not None else None

    # correctness: the engine's state goes first, the reference runs after
    t_check = time.perf_counter()
    extra = check.finish_some(engine, traffic["check"]["min_requests"])
    if extra:
        print(f"check_served_after_window_s: {extra}")
    finished = {rid: list(engine.finished[rid]) for rid in engine.finished}
    rows, bad = policy.mismatches()
    print(f"policy_rows_compared: {rows}")
    del engine, run, policy, tracer
    gc.collect()
    sample = check.sample(win, finished, reqs, args.seed, traffic["check"])
    ref = load_module(HERE / "refs" / f"{c.cfile['reference']}.py", "ref")
    compared, n_tok = check.compare(ref, params, c.ref_cfg, reqs, finished,
                                    sample, traffic, policy=(rows, bad))
    print(f"check_s: {time.perf_counter() - t_check}")
    print(f"check_requests: {len(sample)} preempted: "
          f"{sum(win.records[r].preempted > 0 for r in sample)} "
          f"served_tokens_compared: {n_tok}")
    for k, v in compared.items():
        print(f"{k}: {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    result = {"correct": all(v["ok"] for v in compared.values()),
              "attempted": len(due), "failed": 0, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v["value"], "limit": v["limit"]}
                          for k, v in compared.items()}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
