"""The engine's device programs: jitted entries that count their variants.

A ``DeviceProgram`` is called like the ``jax.jit`` of its function.  JAX
compiles one variant of a jitted function per argument signature; the first
call of a signature in the process traces, lowers and compiles it (or loads
it from the persistent compile cache), every later call only enqueues it.
The program keeps the signatures it has seen, runs a first call under the
``serve/compile`` span and counts it in ``EngineStats``, and runs every
other call under ``serve/dispatch``.  The signature is what the jit cache
keys these entries on: the argument tree, each leaf's shape, dtype and weak
type (a Python scalar's type), and the values of the static arguments.
"""

from __future__ import annotations

import jax

from ..obs.spans import span


def _leaf_key(x):
    shape = getattr(x, "shape", None)
    if shape is None:
        return type(x)
    return shape, x.dtype, getattr(x, "weak_type", False)


class DeviceProgram:
    def __init__(self, fn, stats, *, static_argnames: tuple = ()):
        self.name = fn.__name__
        self.jitted = jax.jit(fn, static_argnames=static_argnames)
        self._static = frozenset(static_argnames)
        self._stats = stats
        self._seen: set = set()

    def __call__(self, *args, **kwargs):
        static = tuple(sorted((k, v) for k, v in kwargs.items()
                              if k in self._static))
        leaves, tree = jax.tree_util.tree_flatten(
            (args, {k: v for k, v in kwargs.items()
                    if k not in self._static}))
        key = (tree, static, tuple(map(_leaf_key, leaves)))
        if key in self._seen:
            with span("serve/dispatch"):
                return self.jitted(*args, **kwargs)
        self._seen.add(key)
        self._stats.count_compile(self.name)
        with span("serve/compile"):
            return self.jitted(*args, **kwargs)
