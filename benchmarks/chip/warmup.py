"""Set-up's warm-up: compile (or load from the persistent cache) every
program variant the cell's traffic can make the engine run, so that nothing
compiles inside the measured window.

The engine compiles one program per argument shape.  Besides the prompt's
padded length, its fused entries take the dirty block-table rows and the
delta triples of each step, padded to power-of-two buckets, and its move
drain pads the move list likewise.  The (rows, triples) pairs that occur
depend on which physical blocks the allocator hands out, so not every one
can be warmed; the set below is the one a smoke-size rehearsal of the
cells' traffic at their rates, each step held to the chip's step time,
showed at all but about one call in a thousand:

  * decode: 0 or 1 rewritten rows x 0-64 appended cells, 2 rewritten
    rows (two slots changed in one step) x 0-8 cells, 4 rows x 0-1 and 8
    rows x 0;
  * prefill, per padded prompt length: a reused slot's row rewritten, a
    blank row appended to (the bucket of the prompt's blocks, or twice it
    when another row grew in the same step), both, or 2 rows rewritten
    (a slot reused while another was freed);
  * suffix prefill (prefix-cache hits): the same, per (suffix length,
    whole prompt blocks); a partial hit (part of an evicted prefix) has a
    suffix length of its own and is not warmed;
  * block moves: up to 256 blocks per drain;
  * the policy executor: fault batches up to the longest prompt's blocks.

A variant outside the set compiles (or loads from the persistent cache)
when it first occurs; the run counts those inside the window.

Each variant is called once with inputs that change nothing: every table
row and move is a padding entry (index -1, dropped by the engine's
scatters) and no slot is active, and the outputs are thrown away.  A
variant whose engine entry is missing is reported and skipped.
"""

from __future__ import annotations

import numpy as np


def buckets(limit: int) -> list[int]:
    """0 and the powers of two up to the bucket that holds ``limit``."""
    out, b = [0], 1
    while True:
        out.append(b)
        if b >= limit:
            return out
        b *= 2


def _pads(K: int, T: int, MB: int):
    import jax.numpy as jnp
    didx = jnp.asarray(np.full(K, -1, np.int32))
    drows = jnp.asarray(np.zeros((K, MB), np.int32))
    tri = np.zeros((T, 3), np.int32)
    tri[:, 0] = -1
    return didx, drows, jnp.asarray(tri)


def variants(traffic: dict, layout, max_batch: int) -> dict:
    """The argument buckets of the variants this traffic makes the engine
    run often enough to matter (see the module's docstring)."""
    from traffic import prompt_shapes
    bt = layout.block_tokens
    # a decode step: a row blanked or remapped, or none; cells appended by
    # faults, up to a largest page (64 blocks)
    out = {"decode": [(K, T) for K in (0, 1) for T in buckets(64)]
           + [(2, T) for T in buckets(8)] + [(4, 0), (4, 1), (8, 0)],
           "prefill": [], "suffix": [], "moves": buckets(4 * 64)[1:]}
    pre = traffic.get("prefix")
    for L in prompt_shapes(traffic):
        nb = -(-L // bt)
        # a reused slot's row rewritten, a blank row appended to, or both;
        # a second row rewritten (a slot freed in the same step); another
        # row's appended cells on top of the prompt's
        tb = buckets(nb)[-1]
        rows = [(1, 0), (0, tb), (1, tb), (2, 0), (0, 2 * tb)]
        out["prefill"] += [(nb * bt, K, T) for K, T in rows]
        if pre and pre.get("pool", 0) > 0:
            # a hit shares the whole prefix; the suffix is the user part
            sb = -(-(L - pre["tokens"] // bt * bt) // bt) * bt
            out["suffix"] += [(sb, nb, K, T) for K, T in rows]
    out["fault_batch"] = max(-(-L // bt) for L in prompt_shapes(traffic)) + 1
    return out


def warm(engine, traffic: dict, log=print) -> dict:
    """Run every variant once; returns counts per kind."""
    import jax
    import jax.numpy as jnp
    from repro.core.hooks import HOOK_FAULT

    lay, B = engine.layout, engine.max_batch
    MB = lay.max_blocks
    v = variants(traffic, lay, B)
    done = {k: 0 for k in ("decode", "prefill", "suffix", "moves")}
    engine.mm.hooks.warm(HOOK_FAULT, max_batch=v["fault_batch"])
    p, c, buf = engine.params, engine.cache, engine._table_buf
    zeros = jnp.asarray(np.zeros(B, np.int32))
    off = jnp.asarray(np.zeros(B, bool))
    for name, fn, calls in (
            ("decode", "_decode",
             [lambda f, K=K, T=T: f(p, c, buf, *_pads(K, T, MB), zeros, zeros,
                                    off, None) for K, T in v["decode"]]),
            ("prefill", "_prefill",
             [lambda f, S=S, K=K, T=T: f(
                 p, c, buf, *_pads(K, T, MB),
                 jnp.asarray(np.zeros((1, S), np.int32)),
                 jnp.asarray(0, jnp.int32), jnp.asarray([S - 1], jnp.int32))
              for S, K, T in v["prefill"]]),
            ("suffix", "_prefill_sfx",
             [lambda f, S=S, KB=KB, K=K, T=T: f(
                 p, c, buf, *_pads(K, T, MB),
                 jnp.asarray(np.zeros((1, S), np.int32)),
                 jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                 jnp.asarray([S - 1], jnp.int32), key_blocks=KB)
              for S, KB, K, T in v["suffix"]]),
            ("moves", "_moves",
             [lambda f, P=P: f(c, jnp.asarray(np.zeros(P, np.int32)),
                               jnp.asarray(np.full(P, -1, np.int32)))
              for P in v["moves"]])):
        f = getattr(engine, fn, None)
        if f is None:
            log(f"warm: engine has no {fn}; {name} variants not warmed")
            continue
        for call in calls:
            # one at a time: each output holds a whole copy of the pool
            jax.block_until_ready(call(f))
            done[name] += 1
    return done
