"""From a JAX profiler trace to the numbers the per-layer metrics read.

A trace (``*.xplane.pb``, read with ``jax.profiler.ProfileData``) holds host
planes (``/host:CPU``; the benchmark's TraceAnnotation spans are events
there) and one plane per device (``/device:TPU:<n>``) whose ``XLA Ops``
line has an event per operation run and whose ``XLA Modules`` line has an
event per program run.  Device times are moved onto the host's clock by
the offset that lines program starts up with the runtime's completion marks
on the host (``clock_offset``): on a v5e the two clocks differ by about
2 ms, which is the size of the gaps being attributed.

  * window: the ``harness.window`` host span (the whole trace without one);
  * busy: the union of the device's operation intervals inside the window;
    ``busy_s`` averages it over the devices used, idle share is
    ``1 - busy_s / window_s``;
  * program time: the durations of a program's module events, by name;
  * idle attribution: each stretch of the window in which no operation runs
    is charged to the innermost host span that covers it (``host idle``
    where none does).
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "harness.window"
DONE_EVENT = "tpu::System::Execute=>Done"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def load_events(path: str | Path, host_names: set[str]) -> dict:
    """``{"host": [(name, t0, t1)], "devices": {plane: {"ops": [...],
    "modules": [...]}}}`` with times in ns on the host's clock, host events
    restricted to ``host_names``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host, devices, done = [], {}, []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
                    elif e.name == DONE_EVENT:
                        done.append(e.start_ns)
        elif DEVICE_PLANE.match(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for e in line.events:
                    d[key].append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns))
    for d in devices.values():
        off = clock_offset([a for _, a, _ in d["modules"]], done)
        for key in ("ops", "modules"):
            d[key] = [(n, a + off, b + off) for n, a, b in d[key]]
    return {"host": host, "devices": devices}


def clock_offset(modules, done, tol: float = 50_000) -> float:
    """Offset from the device's clock to the host's.  The TPU runtime marks
    each program's completion on a host thread (``DONE_EVENT``) at the
    program's own start plus a fixed offset; the offset that lines up the
    most module starts with such marks (within ``tol`` ns) is taken, 0 when
    fewer than half line up."""
    modules, done = sorted(modules)[:64], sorted(done)
    if not modules or not done:
        return 0.0
    best, score = 0.0, 0
    for m in modules[:16]:
        for d in done[:64]:
            off = d - m
            n = 0
            for a in modules:
                i = bisect.bisect_left(done, a + off - tol)
                n += i < len(done) and done[i] <= a + off + tol
            if n > score:
                best, score = off, n
    return best if score * 2 >= len(modules) else 0.0


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def module_name(name: str) -> str:
    """``jit__decode_entry(123)`` -> ``_decode_entry``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


@dataclass
class Trace:
    window: tuple[float, float]
    busy: list                   # merged busy intervals of the first device
    busy_s: float
    window_s: float
    modules: list                # (program name, t0, t1) in the window
    ops: dict                    # op name -> seconds in the window
    host: list                   # (span name, t0, t1)
    idle: dict = field(default_factory=dict)   # host span -> idle seconds

    def program(self, names) -> tuple[float, int]:
        """Device seconds and calls of the programs named ``names``."""
        sel = [(a, b) for n, a, b in self.modules if n in names]
        return sum(b - a for a, b in sel) / 1e9, len(sel)

    def span(self, names) -> tuple[float, int]:
        """Host seconds and count of the spans named ``names`` that start in
        the window."""
        lo, hi = self.window
        sel = [(a, b) for n, a, b in self.host
               if n in names and lo <= a < hi]
        return sum(b - a for a, b in sel) / 1e9, len(sel)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce(ev: dict, chips: int = 1) -> Trace | None:
    """The window's numbers; None when no device ran any operation."""
    devs = [ev["devices"][k] for k in sorted(ev["devices"])][:chips]
    if not devs or not any(d["ops"] for d in devs):
        return None
    host = ev["host"]
    win = [(a, b) for n, a, b in host if n == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        times = [t for d in devs for _, a, b in d["ops"] for t in (a, b)]
        lo, hi = min(times), max(times)
    busy_each = [merge(clip([(a, b) for _, a, b in d["ops"]], lo, hi))
                 for d in devs]
    busy_s = sum(b - a for bs in busy_each for a, b in bs) / len(devs) / 1e9
    d0 = devs[0]
    modules = [(module_name(n), a, b) for n, a, b in d0["modules"]
               if a >= lo and b <= hi]
    ops: dict[str, float] = {}
    for n, a, b in d0["ops"]:
        for x, y in clip([(a, b)], lo, hi):
            ops[n] = ops.get(n, 0.0) + (y - x) / 1e9
    spans = [(n, a, b) for n, a, b in host if n != WINDOW_SPAN]
    return Trace((lo, hi), busy_each[0], busy_s, (hi - lo) / 1e9, modules,
                 ops, host, attribute_idle(busy_each[0], spans, lo, hi))


def attribute_idle(busy, spans, lo: float, hi: float) -> dict:
    """Seconds of device idle time per innermost covering host span."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    # cut points: every span edge inside a gap; within a piece the set of
    # covering spans is constant, the innermost being the latest started
    edges = sorted({e for _, a, b in spans for e in (a, b)})
    spans = sorted(spans, key=lambda s: s[1])
    out: dict[str, float] = {}
    starts = [s[1] for s in spans]
    for g0, g1 in gaps:
        cuts = [g0] + [e for e in edges[bisect.bisect_right(edges, g0):
                                         bisect.bisect_left(edges, g1)]] + [g1]
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            name = "host idle"
            # spans nest a few deep: an ancestor started among the last few
            hi_i = bisect.bisect_right(starts, mid)
            for i in range(hi_i - 1, max(-1, hi_i - 1 - 256), -1):
                n, a, b = spans[i]
                if a <= mid < b:
                    name = n
                    break
            out[name] = out.get(name, 0.0) + (y - x) / 1e9
    return out


def load(path: str | Path, host_names: set[str], chips: int = 1
         ) -> Trace | None:
    return reduce(load_events(path, host_names | {WINDOW_SPAN}), chips)
