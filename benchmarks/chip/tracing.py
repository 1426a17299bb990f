"""The traced run's profiler session and host spans.

The spans named in ``spans.json`` wrap bound methods of the engine object
(and of objects it holds) with ``jax.profiler.TraceAnnotation``, so host
spans land in the profiler's trace on the device events' clock.  The
harness adds its own: ``harness.window`` (the traced window),
``harness.step`` (one ``engine.step()``), ``harness.account`` (its
bookkeeping after a step) and ``harness.wait`` (sleeping until the next
request is due).
"""

from __future__ import annotations

import json
import shutil
from functools import wraps
from pathlib import Path

HARNESS_SPANS = ("harness.step", "harness.account", "harness.wait")


class Tracer:
    def __init__(self, logdir: Path, spans_file: Path, chips: int = 1):
        self.logdir = Path(logdir)
        spec = json.loads(Path(spans_file).read_text())
        self.spans = spec["spans"]
        self.seconds = float(spec["trace_seconds"])
        self.chips = chips
        self._window = None
        self._undo: list = []
        self.t_start: float | None = None

    @property
    def started(self) -> bool:
        return self.t_start is not None

    def wrap(self, engine, log=print) -> None:
        import jax
        for s in self.spans:
            *path, attr = s["target"].split(".")
            obj = engine
            for p in path:
                obj = getattr(obj, p, None)
            fn = getattr(obj, attr, None) if obj is not None else None
            if fn is None:
                log(f"span {s['name']}: target {s['target']} not found")
                continue

            def wrapped(*a, _fn=fn, _name=s["name"], **kw):
                with jax.profiler.TraceAnnotation(_name):
                    return _fn(*a, **kw)
            setattr(obj, attr, wraps(fn)(wrapped))
            self._undo.append((obj, attr))

    def unwrap(self) -> None:
        for obj, attr in self._undo:
            try:
                delattr(obj, attr)
            except AttributeError:
                pass
        self._undo = []

    @staticmethod
    def annotate(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self, now: float = 0.0) -> None:
        import jax
        self.t_start = now
        shutil.rmtree(self.logdir, ignore_errors=True)
        self.logdir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.logdir), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("harness.window")
        self._window.__enter__()

    def stop_and_reduce(self):
        import jax
        import reduce
        if self._window is None:
            return None
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.unwrap()
        files = sorted(self.logdir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        names = {s["name"] for s in self.spans} | set(HARNESS_SPANS)
        return reduce.load(files[-1], names, self.chips)
