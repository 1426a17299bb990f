"""The serving program's own host spans in a traced run.

The engine writes constant-named spans into the profiler's trace whenever a
profiler session runs (``repro.obs.spans``): ``serve/*`` in the serving
engine, ``mm/fault`` in the memory manager, ``policy/run`` in the policy
executor.  ``of(run)`` re-opens the traced run's trace (the newest
``*.xplane.pb`` under ``.cache/trace/<cell>/plugins/profile/``) with
``reduce.load_events`` and those names, and reduces the spans that start in
the traced window to a count, a total and a self time (the total less the
time in the program spans directly inside) per name.  Each stretch of the
window in which the device runs nothing is charged to the innermost program
span that covers it (``reduce.attribute_idle`` over ``run.trace.busy``);
idle time under ``harness.wait`` is charged to it, and idle time under
neither to ``host idle``.  The result is cached on the run and printed once
as a ``program_spans:`` line.

A run without a trace, a trace that is not this run's (its window differs
from ``run.trace``'s) or a program that writes no such spans gives None.
"""

from __future__ import annotations

import bisect
import json
import statistics
from dataclasses import dataclass
from pathlib import Path

import reduce

ROOT = Path(__file__).resolve().parents[2]
SPANS = ("serve/step", "serve/admit", "serve/prefix", "serve/prefill",
         "serve/decode", "serve/fault", "serve/moves", "serve/tables",
         "serve/dispatch", "serve/compile", "serve/sync", "serve/sample",
         "serve/tick", "mm/fault", "policy/run")
WAIT = "harness.wait"
ENTRIES = ("_decode_entry", "_prefill_entry", "_prefill_sfx_entry",
           "_moves_entry")
CACHE = "program_spans"


@dataclass
class ProgramSpans:
    count: dict         # span name -> spans that start in the window
    total_s: dict       # span name -> their summed duration (s)
    self_s: dict        # span name -> the same less their program children
    idle_s: dict        # span name, WAIT or "host idle" -> device idle (s)
    window_s: float

    @property
    def steps(self) -> int:
        return self.count.get("serve/step", 0)

    def summary(self) -> dict:
        def ms(d):
            return {k: round(v * 1e3, 3) for k, v in d.items()}
        return {"count": self.count, "total_ms": ms(self.total_s),
                "self_ms": ms(self.self_s), "idle_ms": ms(self.idle_s),
                "window_s": self.window_s}


def trace_file(cell: str) -> Path | None:
    files = sorted((ROOT / ".cache" / "trace" / cell).glob(
        "plugins/profile/*/*.xplane.pb"))
    return files[-1] if files else None


def from_events(run, host) -> ProgramSpans | None:
    """Reduce host events ``(name, t0_ns, t1_ns)`` over ``run.trace``'s
    window and busy intervals, and cache the result on the run."""
    lo, hi = run.trace.window
    spans = [(n, a, b) for n, a, b in host if n in SPANS]
    out = None
    if any(n == "serve/step" and lo <= a < hi for n, a, _ in spans):
        count: dict = {}
        total: dict = {}
        self_: dict = {}
        stack: list = []                 # open spans: [name, t0, t1, self]
        done = []
        for n, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
            while stack and stack[-1][2] <= a:
                done.append(stack.pop())
            if stack:
                stack[-1][3] -= b - a
            stack.append([n, a, b, b - a])
        done += stack
        for n, a, b, own in done:
            if lo <= a < hi:
                count[n] = count.get(n, 0) + 1
                total[n] = total.get(n, 0.0) + (b - a) / 1e9
                self_[n] = self_.get(n, 0.0) + own / 1e9
        waits = [(n, a, b) for n, a, b in host if n == WAIT]
        idle = reduce.attribute_idle(run.trace.busy, spans + waits, lo, hi)
        out = ProgramSpans(count, total, self_, idle, run.trace.window_s)
    setattr(run, CACHE, out)
    return out


def of(run, path: Path | None = None) -> ProgramSpans | None:
    """The program's spans in this run's trace (``path``, by default the
    cell's newest), cached on the run."""
    if hasattr(run, CACHE):
        return getattr(run, CACHE)
    if run.trace is not None and path is None:
        path = trace_file(run.cell)
    if run.trace is None or path is None:
        setattr(run, CACHE, None)
        return None
    ev = reduce.load_events(path, set(SPANS) | {WAIT, reduce.WINDOW_SPAN})
    win = [(a, b) for n, a, b in ev["host"] if n == reduce.WINDOW_SPAN]
    if not win or win[0] != run.trace.window:
        setattr(run, CACHE, None)
        return None
    out = from_events(run, ev["host"])
    if out is not None:
        print(f"program_spans: {json.dumps(out.summary())} "
              f"launch_lag_ms: {launch_lag_ms(ev)}")
    return out


def launch_lag_ms(ev) -> float | None:
    """Median time from the start of a ``serve/dispatch`` or
    ``serve/compile`` span to the start of the next engine program on the
    first device, on the host's clock: about a ms where
    ``reduce.clock_offset`` aligned the two clocks, far off where it did
    not."""
    devs = [ev["devices"][k] for k in sorted(ev["devices"])]
    if not devs:
        return None
    starts = sorted(a for n, a, _ in ev["host"]
                    if n in ("serve/dispatch", "serve/compile"))
    lags = []
    for n, a, _ in devs[0]["modules"]:
        i = bisect.bisect_right(starts, a) - 1
        if reduce.module_name(n) in ENTRIES and i >= 0:
            lags.append((a - starts[i]) / 1e6)
    return round(statistics.median(lags), 3) if lags else None
