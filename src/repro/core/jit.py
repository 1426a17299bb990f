"""JIT: compile a verified policy program to a jnp function.

Linux runs one eBPF invocation per page fault.  On a TPU serving tier the
scheduler frequently has to resolve *hundreds* of faults per engine step
(every sequence that crossed a block boundary).  Because verified programs
are bounded, we can compile the bytecode to XLA once and ``vmap`` it over the
whole fault batch — a beyond-paper optimization recorded in EXPERIMENTS.md.
The tiered-memory migration engine (:mod:`repro.core.tiering`) runs its
demote/promote scans through the same batch path: one compiled mm_tier
program vets every candidate page in a single vectorized call.

Compilation strategy: the program becomes an instruction-pointer machine
  state = (pc, regs[11], fuel)
  lax.while_loop(pc != EXIT_PC, lax.switch(pc, per-insn updates))
with every instruction lowered to a tiny pure function.  Verified programs
terminate within ``max_steps``, so fuel gives a hard bound that also lets
``vmap`` batch lanes with divergent control flow (lanes that finish early
spin on EXIT until all are done).

Since the unified pipeline, this backend consumes the shared lowered IR
(:mod:`repro.core.lower`): one verifier pass, absolute branch targets,
resolved map slots — and the per-op LDCTX/LDCTXR/helper/map-op bodies are
the SAME functions the predicated compiler lowers through (``alu_jnp``,
``helper_jnp``, ``map_lookup``...), so the two compiled executors cannot
drift apart opcode by opcode.

Maps are passed in as padded int64 arrays (capacity-sized), so profile
updates from userspace do NOT trigger recompilation — only reloading data.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .isa import (ALU_IMM_OPS, ALU_REG_OPS, COND_JUMP_IMM, COND_JUMP_REG,
                  NUM_REGS, Op, Program)
from .lower import (LIns, LoweredProgram, RB_FIELDS, VecCtx,
                    alu_jnp as _alu_jnp, cmp_jnp as _cmp_jnp,
                    collect_rb_events, helper_jnp, ldctx_dyn, lower,
                    map_lookup, map_lookup_dyn, rb_words)
from .maps import MapRegistry
from .vm import _IMM2REG, _JIMM2REG, RB_HELPERS

I64 = jnp.int64


def compile_program(program: Program | LoweredProgram, maps: MapRegistry):
    """Compile to ``fn(ctx_vec, map_arrays, map_lens) -> r0`` (all jnp).

    The returned function is jit/vmap-compatible.  ``map_arrays`` is a tuple
    of capacity-padded int64 arrays, ``map_lens`` an int64 vector of live
    lengths (dynamic, so userspace can reload profiles without recompiling).

    Programs that call a ring-buffer helper (``facts["rb_cap"] > 0``) thread
    a per-lane event-slot buffer through the machine state, and the compiled
    function returns ``(r0, events [rb_cap, 5], count, drops)`` instead of
    bare ``r0`` — callers drain the extra outputs host-side.  Programs that
    never emit keep the original state/signature exactly.
    """
    lp = program if isinstance(program, LoweredProgram) else \
        lower(program, maps)
    insns = list(lp.insns)
    n = len(insns)
    exit_pc = n  # virtual halt pc
    rb_cap = int(lp.facts.get("rb_cap", 0))

    def make_step(pc: int, insn: LIns):
        op = insn.op

        def step(state, ctx, map_arrays, map_lens):
            regs = state["regs"]
            cv = VecCtx(ctx)
            if op in ALU_REG_OPS:
                val = _alu_jnp(op, regs[insn.dst], regs[insn.src])
                regs = regs.at[insn.dst].set(val)
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op in ALU_IMM_OPS:
                imm = jnp.asarray(insn.imm, I64)
                if op == Op.MOVI:
                    val = imm
                else:
                    val = _alu_jnp(_IMM2REG[op], regs[insn.dst], imm)
                regs = regs.at[insn.dst].set(val)
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op == Op.NEG:
                regs = regs.at[insn.dst].set(-regs[insn.dst])
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op == Op.LDCTX:
                regs = regs.at[insn.dst].set(cv.col(insn.imm))
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op == Op.LDCTXR:
                regs = regs.at[insn.dst].set(ldctx_dyn(cv, regs[insn.src]))
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op == Op.LDMAP:
                val = map_lookup(map_arrays, map_lens, insn.imm,
                                 regs[insn.src])
                regs = regs.at[insn.dst].set(val)
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op == Op.LDMAPX:
                val = map_lookup_dyn(map_arrays, map_lens, regs[insn.src2],
                                     regs[insn.src], cv.zeros_like_lane())
                regs = regs.at[insn.dst].set(val)
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op == Op.MAPSZ:
                regs = regs.at[insn.dst].set(map_lens[insn.imm])
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op == Op.JA:
                return dict(state, pc=jnp.int32(insn.target))
            if op in COND_JUMP_REG or op in COND_JUMP_IMM:
                if op in COND_JUMP_REG:
                    taken = _cmp_jnp(op, regs[insn.dst], regs[insn.src])
                else:
                    taken = _cmp_jnp(_JIMM2REG[op], regs[insn.dst],
                                     jnp.asarray(insn.src2, I64))
                nxt = jnp.where(taken, insn.target, pc + 1).astype(jnp.int32)
                return dict(state, pc=nxt)
            if op == Op.JNZDEC:
                newv = regs[insn.dst] - 1
                regs = regs.at[insn.dst].set(newv)
                nxt = jnp.where(newv != 0, insn.target, pc + 1).astype(jnp.int32)
                return dict(state, regs=regs, pc=nxt)
            if op == Op.CALL:
                if rb_cap and insn.imm in RB_HELPERS:
                    words = rb_words(insn.imm, lambda i: regs[i], cv)
                    ev, cnt, dr, r0 = cv.event_write(
                        state["ev"], state["ecnt"], state["edrop"], words,
                        True)
                    regs = regs.at[0].set(r0)
                    return dict(state, regs=regs, ev=ev, ecnt=cnt, edrop=dr,
                                pc=jnp.int32(pc + 1))
                r0 = helper_jnp(insn.imm, lambda i: regs[i], cv)
                regs = regs.at[0].set(r0)
                return dict(state, regs=regs, pc=jnp.int32(pc + 1))
            if op == Op.EXIT:
                return dict(state, pc=jnp.int32(exit_pc))
            raise ValueError(f"unhandled opcode {op}")

        return step

    steps = [make_step(pc, insn) for pc, insn in enumerate(insns)]

    def halt_step(state, ctx, map_arrays, map_lens):
        return state

    branches = steps + [halt_step]

    fuel0 = lp.facts["max_steps"] + 8

    def run(ctx, map_arrays, map_lens):
        ctx = jnp.asarray(ctx, I64)
        state = {
            "pc": jnp.int32(0),
            "regs": jnp.zeros(NUM_REGS, I64),
            "fuel": jnp.int32(fuel0),
        }
        if rb_cap:
            state["ev"] = jnp.zeros((rb_cap, RB_FIELDS), I64)
            state["ecnt"] = jnp.zeros((), I64)
            state["edrop"] = jnp.zeros((), I64)

        def cond(state):
            return (state["pc"] != exit_pc) & (state["fuel"] > 0)

        def body(state):
            new = jax.lax.switch(state["pc"], branches, state, ctx,
                                 map_arrays, map_lens)
            new["fuel"] = state["fuel"] - 1
            return new

        final = jax.lax.while_loop(cond, body, state)
        if rb_cap:
            return (final["regs"][0], final["ev"], final["ecnt"],
                    final["edrop"])
        return final["regs"][0]

    return run, lp.facts


class JitPolicy:
    """Convenience wrapper: compiled program + its maps, batched execution."""

    def __init__(self, program: Program | LoweredProgram,
                 maps: MapRegistry) -> None:
        self.maps = maps
        self._fn, self.facts = compile_program(program, maps)
        self._batched = jax.jit(jax.vmap(self._fn, in_axes=(0, None, None)))
        self._single = jax.jit(self._fn)
        self._map_cache: tuple | None = None   # (version, arrays, lens)
        self.rb_cap = int(self.facts.get("rb_cap", 0))
        self._last_rb: tuple | None = None     # (ev, cnt, drops) device arrays

    def _map_args(self):
        ver = self.maps.version()
        if self._map_cache is None or self._map_cache[0] != ver:
            arrays = tuple(jnp.asarray(self.maps[i].live_array())
                           for i in range(len(self.maps)))
            lens = jnp.asarray(self.maps.lens(), I64)
            if not arrays:
                arrays = (jnp.zeros(1, I64),)
                lens = jnp.zeros(1, I64)
            self._map_cache = (ver, arrays, lens)
        return self._map_cache[1], self._map_cache[2]

    def run(self, ctx_vec: np.ndarray) -> int:
        # enable_x64 scopes true 64-bit ALU semantics to the policy VM without
        # flipping global dtype promotion for the rest of the framework.
        with jax.enable_x64(True):
            arrays, lens = self._map_args()
            out = self._single(jnp.asarray(ctx_vec, I64), arrays, lens)
            if self.rb_cap:
                r0, ev, cnt, dr = out
                self._last_rb = (ev[None], cnt[None], dr[None])
                return int(r0)
            return int(out)

    def run_batch(self, ctx_mat: np.ndarray) -> np.ndarray:
        """ctx_mat: [batch, CTX_LEN] -> int64[batch] decisions."""
        with jax.enable_x64(True):
            arrays, lens = self._map_args()
            out = self._batched(jnp.asarray(ctx_mat, I64), arrays, lens)
            if self.rb_cap:
                r0, ev, cnt, dr = out
                self._last_rb = (ev, cnt, dr)
                return np.asarray(r0)
            return np.asarray(out)

    def take_events(self, n: int) -> tuple[list, int]:
        """Drain the last run's ring-buffer records for the first ``n``
        lanes (and their slot-drop count); empty until the next run."""
        if self._last_rb is None:
            return [], 0
        ev, cnt, dr = self._last_rb
        self._last_rb = None
        return collect_rb_events(ev, cnt, dr, n)
