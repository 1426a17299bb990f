"""The open-loop serving window and its bookkeeping.

The window drives ``ServingEngine.submit`` and ``ServingEngine.step`` and
reads nothing but the engine's public state: ``active`` (slot -> sequence,
with ``req.rid``, ``generated`` and ``length``), ``finished`` (rid -> tokens)
and ``stats``.  A request is submitted at the first step boundary after it
is due; a token is delivered when the step that made it returns (the step
ends in a device->host copy of the logits, so the device work is done).
A request preempted and recomputed delivers nothing until it passes the
tokens it had already delivered.
"""

from __future__ import annotations

import logging
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

clock = time.perf_counter


@dataclass
class Record:
    rid: int
    due: float                   # seconds from the origin (lead-in start)
    prompt_len: int
    max_new: int
    submitted: float | None = None
    admitted_step: float | None = None   # start of the step that admitted it
    deliveries: list = field(default_factory=list)   # (time, new tokens)
    delivered: int = 0
    preempted: int = 0
    finished: float | None = None


@dataclass
class StepRec:
    t0: float
    t1: float
    decode_tokens: int           # rows the step decoded
    prefill_tokens: int          # prompt tokens it computed
    kv_rows: list                # context length of each decoded row
    admitted: list               # (prompt_len, computed tokens) per admission


@dataclass
class Window:
    start: float                 # seconds from the origin
    end: float
    records: dict
    steps: list
    stats0: dict
    stats1: dict
    idle_s: float = 0.0
    compiles: dict = field(default_factory=dict)


class CompileCounter(logging.Handler):
    """Counts JAX's compile events (jit cache misses) while ``armed``, and
    keeps the names of the programs it compiled."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
              "/jax/core/compile/backend_compile_duration": "compiled"}

    def __init__(self):
        import jax
        super().__init__(logging.DEBUG)
        self._armed = False
        self.counts = {"lowered": 0, "compiled": 0, "cache_hits": 0}
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)
        logging.getLogger("jax").addHandler(self)

    @property
    def armed(self) -> bool:
        return self._armed

    @armed.setter
    def armed(self, on: bool) -> None:
        import jax
        self._armed = on
        jax.config.update("jax_log_compiles", on)

    def emit(self, record):
        msg = record.getMessage()
        if self._armed and msg.startswith("Compiling "):
            name, _, shapes = msg[len("Compiling "):].partition(" with ")
            shapes = shapes.split(". Argument mapping")[0]
            self.names.append(f"{name} ...{shapes[-240:]}")

    def _dur(self, event, secs, **kw):
        if self.armed and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _ev(self, event, **kw):
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1


def _stats(engine) -> dict:
    out = dict(engine.stats.snapshot())
    pc = getattr(engine, "prefix_cache", None)
    out["tokens_skipped"] = pc.tokens_skipped if pc is not None else 0
    return out


def serve(engine, reqs, *, origin: float, lead_in_s: float, seconds: float,
          app: str | None, annotate=None, on_window_start=None,
          on_step=None, counter: CompileCounter | None = None) -> Window:
    """Run the schedule from ``origin`` (host clock) to the window's end.

    ``annotate(name)`` returns a context manager for a host span (a
    TraceAnnotation in traced runs); ``on_window_start()`` runs once at
    the window's opening, before any of its steps; ``on_step(now)`` runs
    before each step boundary inside the window (``now`` in seconds from
    the origin)."""
    from repro.serving import Request

    span = annotate or (lambda name: nullcontext())
    w0, w1 = lead_in_s, lead_in_s + seconds
    recs = {r.rid: Record(r.rid, r.due_s, len(r.prompt), r.max_new)
            for r in reqs}
    steps: list[StepRec] = []
    i, n = 0, len(reqs)
    live: dict[int, int] = {}           # rid -> generated seen last step
    in_window = False
    stats0 = None
    idle = 0.0
    now = clock() - origin
    while now < w1:
        if not in_window and now >= w0:
            in_window = True
            if on_window_start is not None:
                on_window_start()
            if counter is not None:
                counter.armed = True
            stats0 = _stats(engine)
            now = clock() - origin
        if in_window and on_step is not None:
            on_step(now)
        while i < n and reqs[i].due_s <= now:
            r = reqs[i]
            engine.submit(Request(rid=r.rid, prompt=r.prompt,
                                  max_new_tokens=r.max_new, app=app))
            recs[r.rid].submitted = now
            i += 1
        if not (engine.active or engine.waiting):
            nxt = reqs[i].due_s if i < n else w1
            wait = min(nxt, w0 if not in_window else w1, w1) - now
            if in_window and on_step is not None:
                wait = min(wait, 0.1)       # on_step sees the idle time too
            if wait > 0:
                with span("harness.wait"):
                    time.sleep(wait)
                if in_window:
                    idle += wait
            now = clock() - origin
            continue
        before = _stats(engine)
        t0 = now
        with span("harness.step"):
            engine.step()
        t1 = clock() - origin
        with span("harness.account"):
            live = _account(engine, recs, steps, live, before, t0, t1)
        now = t1
    if stats0 is None:
        stats0 = _stats(engine)
    if counter is not None:
        counter.armed = False
    compiles = {}
    if counter is not None:
        compiles = dict(counter.counts,
                        names=sorted({n.split(" with ")[0]
                                      for n in counter.names}))
        compiles["detail"] = counter.names
    return Window(w0, w1, recs, steps, stats0, _stats(engine), idle,
                  compiles)


def _account(engine, recs, steps, live, before, t0, t1) -> dict:
    """Book one step's deliveries; returns the rids live after it."""
    after = _stats(engine)
    kv_rows, admitted = [], []
    seen: dict[int, int] = {}
    # kv_rows: tokens each decoded row attended to (its new length)
    for seq in engine.active.values():
        rid = seq.req.rid
        g = len(seq.generated)
        seen[rid] = g
        if rid not in live:                 # admitted by this step
            admitted.append(rid)
        if g > live.get(rid, 1):            # decoded by this step
            kv_rows.append(seq.length)
    for rid in live.keys() - seen.keys():
        toks = engine.finished.get(rid)
        if toks is None:                    # preempted, back in waiting
            recs[rid].preempted += 1
            continue
        g = len(toks)
        kv_rows.append(recs[rid].prompt_len + g - 1)
        recs[rid].finished = t1
        seen[rid] = g
    for rid, g in seen.items():
        rec = recs[rid]
        if rid not in live:
            rec.admitted_step = t0 if rec.admitted_step is None \
                else rec.admitted_step
        if g > rec.delivered:
            rec.deliveries.append((t1, g - rec.delivered))
            rec.delivered = g
    dtok = after["prefill_tokens"] - before["prefill_tokens"]
    skipped = after["tokens_skipped"] - before["tokens_skipped"]
    adm = [recs[r].prompt_len for r in admitted]
    share = skipped / len(adm) if adm else 0.0
    steps.append(StepRec(
        t0, t1, after["decode_tokens"] - before["decode_tokens"], dtok,
        kv_rows, [(L, max(0.0, L - share)) for L in adm]))
    return {rid: g for rid, g in seen.items()
            if rid not in engine.finished}


# ------------------------------------------------------------ end to end
def pct(values, q: float) -> float | None:
    """The ``q``-th percentile (nearest rank, as a latency tail)."""
    if not len(values):
        return None
    v = np.sort(np.asarray(values, np.float64))
    k = max(0, int(np.ceil(q / 100.0 * len(v))) - 1)
    return float(v[k])


def due_in_window(win: Window):
    return [r for r in win.records.values() if win.start <= r.due < win.end]


def ttft_s(win: Window) -> list[float]:
    """First token minus due time, of every request due in the window; one
    with no first token by the window's end counts as (end - due)."""
    out = []
    for r in due_in_window(win):
        t = r.deliveries[0][0] if r.deliveries else None
        out.append((t if t is not None and t <= win.end else win.end) - r.due)
    return out


def gaps_s(win: Window) -> list[float]:
    """Gaps between consecutive deliveries to a request, of every delivery
    inside the window."""
    out = []
    for r in win.records.values():
        d = r.deliveries
        for (ta, _), (tb, _) in zip(d, d[1:]):
            if win.start <= tb <= win.end:
                out.append(tb - ta)
    return out


def tokens_in_window(win: Window) -> int:
    return sum(k for r in win.records.values() for t, k in r.deliveries
               if win.start <= t <= win.end)


def window_steps(win: Window) -> list[StepRec]:
    return [s for s in win.steps if win.start <= s.t0 and s.t1 <= win.end]


def lateness_s(win: Window) -> list[float]:
    return [r.submitted - r.due for r in win.records.values()
            if r.submitted is not None]
