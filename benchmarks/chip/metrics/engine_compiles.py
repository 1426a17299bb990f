"""First calls of a device-program variant inside the window: the engine's
``compiles`` counter (``EngineStats``) at the window's end less at its start
(count)."""


def read(run):
    a = run.win.stats0.get("compiles")
    b = run.win.stats1.get("compiles")
    return None if a is None or b is None else float(b - a)
