"""Predicated compiler: verified policy program -> straight-line masked jnp.

EXPERIMENTS.md §Perf iteration #5 found the while+switch XLA build of the VM
no faster than the host interpreter (lax.switch under vmap executes every
branch each step).  The verifier's guarantees enable the classic fix:

  1. bounded-loop UNROLLING — JNZDEC trip counts are verifier-proven exact
     constants, so every loop expands to exactly `trips` copies of its body
     (the flattening lives in :func:`repro.core.lower.unroll_lowered`, over
     the shared lowered IR); the result has only FORWARD jumps;
  2. IF-CONVERSION — forward-jump-only code executes as one straight line
     with a per-lane active mask: conditional jumps move lanes into a
     pending-mask at their target, register writes are `where(active, ...)`.

SEGMENTED UNROLL (the unified-pipeline addition): the XLA compile time of
one straight-line program grows superlinearly with its length, which used
to cap this backend at 512 unrolled insns and push the default 64-region
Fig-1 program (900 insns) onto the slow while+switch JIT.  Instead, the
flattened code is now SPLIT at loop-copy (back-edge) boundaries into
predicated segments of at most ``seg_limit`` insns, each compiled as its
own small XLA program, chained by a host dispatch loop that threads
``(regs, active, done, r0)`` plus the cross-segment pending masks from one
segment to the next.  Because the flattened code is forward-only, ONE pass
over the segments in order is exact — a jump out of segment *i* lands in a
pending mask that segment *j > i* ORs into its active lanes when the pc
walks over the target.  Per-segment artifacts are exactly the unit the
cross-session cache (:mod:`repro.core.cache`) persists.

The compiled function is fully vectorized over a fault batch — within a
segment there is no control flow at all, exactly the shape TPUs (and CPUs)
like.  `PredicatedPolicy` is the drop-in batch executor the engine uses.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .isa import (ALU_IMM_OPS, ALU_REG_OPS, COND_JUMP_IMM, COND_JUMP_REG,
                  NUM_REGS, Op, Program)
from .lower import (LIns, LoweredProgram, BatchCtx, MAX_UNROLLED, RB_FIELDS,
                    alu_jnp as _alu_jnp, cmp_jnp as _cmp_jnp,
                    collect_rb_events, helper_jnp, ldctx_dyn, lower,
                    map_lookup, map_lookup_dyn, plan_scan_stages, rb_words,
                    segment_code, unroll_lowered)
from .maps import MapRegistry
from .vm import _IMM2REG, _JIMM2REG, RB_HELPERS

I64 = jnp.int64

# Per-SEGMENT predicated-compile budget: one straight-line XLA program never
# exceeds this many lowered insns; longer programs chain segments.
SEG_LIMIT = 512


def unroll(program: Program | LoweredProgram, maps: MapRegistry
           ) -> tuple[LIns, ...]:
    """Flatten all bounded loops; returns the forward-only lowered code.

    Thin wrapper over the shared pipeline (lower once, expand from verifier
    trip counts) kept as the public sizing entry point — ``len(unroll(p,
    maps))`` is the number the segment planner budgets against."""
    lp = program if isinstance(program, LoweredProgram) else \
        lower(program, maps)
    code, _cuts = unroll_lowered(lp)
    return code


class _Segment:
    """Static plan for one predicated segment of the flattened program."""
    __slots__ = ("start", "end", "entry_targets", "exit_targets", "fn")

    def __init__(self, start: int, end: int, entry_targets: tuple[int, ...],
                 exit_targets: tuple[int, ...], fn: Callable):
        self.start = start
        self.end = end
        self.entry_targets = entry_targets
        self.exit_targets = exit_targets
        self.fn = fn


def _plan_segments(code: tuple[LIns, ...], cuts: tuple[int, ...],
                   seg_limit: int) -> list[tuple[int, int, tuple, tuple]]:
    """Split ``code`` into spans and compute each span's cross-segment
    interface: the targets it must accept masks FOR (jumps from earlier
    segments landing inside it) and the targets it emits masks TO (its own
    jumps landing at/after its end)."""
    spans = segment_code(code, cuts, seg_limit)
    plans = []
    for start, end in spans:
        entry = sorted({ins.target for pc, ins in enumerate(code[:start])
                        if ins.target is not None and ins.target >= 0
                        and start <= ins.target < end})
        exits = sorted({ins.target for ins in code[start:end]
                        if ins.target is not None and ins.target >= end})
        plans.append((start, end, tuple(entry), tuple(exits)))
    return plans


def _exec_span(code: tuple[LIns, ...], start: int, end: int, cv,
               map_arrays, map_lens, regs, active, done, r0_final,
               pending: dict, ev=None, ecnt=None, edrop=None,
               rb_cap: int = 0):
    """Execute the straight-line span ``[start, end)`` predicated.

    ``regs`` is a list of per-register ``[B]`` vectors; ``pending`` maps
    absolute jump-target pc -> lane mask — targets inside the span are
    consumed as the walk passes them, targets at/after ``end`` are left in
    (or OR-ed into) ``pending`` for the caller.  This is the ONE lowering
    walk shared by the chained per-segment compile, the fused one-dispatch
    executor and the ``lax.scan`` loop-copy body."""
    B = active.shape[0]

    def write(regs, dst, val, active):
        regs = list(regs)
        regs[dst] = jnp.where(active, val, regs[dst])
        return regs

    for pc in range(start, end):
        if pc in pending:
            active = active | pending.pop(pc)
        insn = code[pc]
        op = insn.op
        if op in ALU_REG_OPS:
            val = _alu_jnp(op, regs[insn.dst], regs[insn.src])
            regs = write(regs, insn.dst, val, active)
        elif op in ALU_IMM_OPS:
            imm = jnp.asarray(insn.imm, I64)
            val = imm if op == Op.MOVI else _alu_jnp(
                _IMM2REG[op], regs[insn.dst], imm)
            regs = write(regs, insn.dst, val, active)
        elif op == Op.NEG:
            regs = write(regs, insn.dst, -regs[insn.dst], active)
        elif op == Op.LDCTX:
            regs = write(regs, insn.dst, cv.col(insn.imm), active)
        elif op == Op.LDCTXR:
            regs = write(regs, insn.dst, ldctx_dyn(cv, regs[insn.src]),
                         active)
        elif op == Op.LDMAP:
            val = map_lookup(map_arrays, map_lens, insn.imm,
                             regs[insn.src])
            regs = write(regs, insn.dst, val, active)
        elif op == Op.LDMAPX:
            val = map_lookup_dyn(map_arrays, map_lens, regs[insn.src2],
                                 regs[insn.src], cv.zeros_like_lane())
            regs = write(regs, insn.dst, val, active)
        elif op == Op.MAPSZ:
            regs = write(regs, insn.dst,
                         jnp.broadcast_to(map_lens[insn.imm], (B,)),
                         active)
        elif op == Op.JA:
            pending[insn.target] = pending.get(
                insn.target, jnp.zeros(B, bool)) | active
            active = jnp.zeros(B, bool)
        elif op in COND_JUMP_REG or op in COND_JUMP_IMM:
            if op in COND_JUMP_REG:
                taken = _cmp_jnp(op, regs[insn.dst], regs[insn.src])
            else:
                taken = _cmp_jnp(_JIMM2REG[op], regs[insn.dst],
                                 jnp.asarray(insn.src2, I64))
            taken = taken & active
            pending[insn.target] = pending.get(
                insn.target, jnp.zeros(B, bool)) | taken
            active = active & ~taken
        elif op == Op.CALL:
            if rb_cap and insn.imm in RB_HELPERS:
                words = rb_words(insn.imm, lambda i: regs[i], cv)
                ev, ecnt, edrop, r0 = cv.event_write(
                    ev, ecnt, edrop, words, active)
            else:
                r0 = helper_jnp(insn.imm, lambda i: regs[i], cv)
            regs = write(regs, 0, r0, active)
        elif op == Op.EXIT:
            r0_final = jnp.where(active & ~done, regs[0], r0_final)
            done = done | active
            active = jnp.zeros(B, bool)
        else:   # pragma: no cover
            raise ValueError(f"unhandled opcode {op}")
    return regs, active, done, r0_final, ev, ecnt, edrop


def _make_segment_fn(code: tuple[LIns, ...], start: int, end: int,
                     entry_targets: tuple[int, ...],
                     exit_targets: tuple[int, ...],
                     rb_cap: int = 0) -> Callable:
    """Build the traced body of one segment.

    Signature: ``(ctx[B,C], map_arrays, map_lens, regs[R,B], active[B],
    done[B], r0[B], entry_masks tuple) -> (regs, active, done, r0,
    exit_masks tuple)`` — ``active`` out is the fall-through mask into the
    next segment.  When the program emits ring-buffer events (``rb_cap >
    0``) the per-lane event buffers ``(ev[B,cap,5], ecnt[B], edrop[B])``
    are threaded through as three extra leading-state params/results;
    emit-free programs keep the original signature (and thus their cached
    XLA executables) exactly."""

    def seg(ctx, map_arrays, map_lens, regs_in, active, done, r0_final,
            entry_masks, ev=None, ecnt=None, edrop=None):
        B = ctx.shape[0]
        cv = BatchCtx(ctx)
        regs = [regs_in[i] for i in range(NUM_REGS)]
        pending: dict[int, jax.Array] = dict(zip(entry_targets, entry_masks))
        regs, active, done, r0_final, ev, ecnt, edrop = _exec_span(
            code, start, end, cv, map_arrays, map_lens, regs, active, done,
            r0_final, pending, ev, ecnt, edrop, rb_cap)
        exit_masks = tuple(pending.pop(t, jnp.zeros(B, bool))
                           for t in exit_targets)
        # forward-only code: anything still pending must be an exit target
        assert not pending, f"unconsumed jump targets {sorted(pending)}"
        if rb_cap:
            return (jnp.stack(regs), active, done, r0_final, exit_masks,
                    ev, ecnt, edrop)
        return jnp.stack(regs), active, done, r0_final, exit_masks

    return seg


def _make_fused_fn(code: tuple[LIns, ...], stages: list[tuple],
                   rb_cap: int = 0) -> Callable:
    """Build the ONE-dispatch executor: the whole flattened program as a
    single traced function — plain stages inline, congruent loop-copy runs
    (see :func:`repro.core.lower.plan_scan_stages`) as a ``lax.scan`` over
    ONE copy body with carry ``(regs, active, done, r0, exit masks)`` plus
    the ring-buffer state when the program emits.  Where the chained path
    pays one XLA dispatch per segment per batch, this costs exactly one,
    and the traced length collapses from the full unroll to prologue + one
    copy per loop + epilogue.

    Signature: ``(ctx, map_arrays, map_lens, regs[R,B], active, done, r0
    [, ev, ecnt, edrop]) -> r0 [, ev, ecnt, edrop]``."""

    def fused(ctx, map_arrays, map_lens, regs_in, active, done, r0_final,
              ev=None, ecnt=None, edrop=None):
        B = ctx.shape[0]
        cv = BatchCtx(ctx)
        zeros = jnp.zeros(B, bool)
        regs = [regs_in[i] for i in range(NUM_REGS)]
        pending: dict[int, jax.Array] = {}
        for st in stages:
            if st[0] == "plain":
                _, s, e = st
                regs, active, done, r0_final, ev, ecnt, edrop = _exec_span(
                    code, s, e, cv, map_arrays, map_lens, regs, active,
                    done, r0_final, pending, ev, ecnt, edrop, rb_cap)
                continue
            _, s, e, trips, blen = st
            if s in pending:
                active = active | pending.pop(s)
            exits = tuple(sorted({ins.target for ins in code[s:s + blen]
                                  if ins.target >= e}))
            exit_acc = tuple(pending.pop(t, zeros) for t in exits)

            def body(carry, _, s=s, blen=blen, exits=exits):
                if rb_cap:
                    (regs_c, act_c, done_c, r0_c, acc,
                     ev_c, ecnt_c, edrop_c) = carry
                else:
                    regs_c, act_c, done_c, r0_c, acc = carry
                    ev_c = ecnt_c = edrop_c = None
                regs_l = [regs_c[i] for i in range(NUM_REGS)]
                local: dict[int, jax.Array] = {}
                regs_l, act_c, done_c, r0_c, ev_c, ecnt_c, edrop_c = \
                    _exec_span(code, s, s + blen, cv, map_arrays, map_lens,
                               regs_l, act_c, done_c, r0_c, local,
                               ev_c, ecnt_c, edrop_c, rb_cap)
                acc = tuple(m | local.pop(t, zeros)
                            for t, m in zip(exits, acc))
                assert not local, \
                    f"scan body leaked targets {sorted(local)}"
                out = (jnp.stack(regs_l), act_c, done_c, r0_c, acc)
                if rb_cap:
                    out += (ev_c, ecnt_c, edrop_c)
                return out, None

            init = (jnp.stack(regs), active, done, r0_final, exit_acc)
            if rb_cap:
                init += (ev, ecnt, edrop)
            carry, _ = jax.lax.scan(body, init, None, length=trips)
            if rb_cap:
                regs_s, active, done, r0_final, exit_acc, ev, ecnt, edrop \
                    = carry
            else:
                regs_s, active, done, r0_final, exit_acc = carry
            regs = [regs_s[i] for i in range(NUM_REGS)]
            for t, m in zip(exits, exit_acc):
                pending[t] = (pending[t] | m) if t in pending else m
        if rb_cap:
            return r0_final, ev, ecnt, edrop
        return r0_final

    return fused


def compile_predicated(program: Program | LoweredProgram, maps: MapRegistry,
                       code=None) -> Callable:
    """Returns fn(ctx [B, CTX_LEN], map_arrays, map_lens) -> r0 [B].

    Single-segment convenience entry (the pre-segmentation surface, kept for
    direct use and tests): the whole flattened program compiles as ONE
    straight-line XLA function.  ``code`` lets a caller that already
    unrolled the program pass the result in instead of unrolling twice."""
    pol = PredicatedPolicy(program, maps, code=code,
                           seg_limit=MAX_UNROLLED)

    def run(ctx, map_arrays, map_lens):
        return pol._run_segments(ctx, map_arrays, map_lens)

    return run


class PredicatedPolicy:
    """Batch fault-decision executor (drop-in for JitPolicy.run_batch).

    Two execution shapes over the same flattened code:

    * **fused** (preferred): when :func:`plan_scan_stages` compresses the
      unroll to a traced length within ``seg_limit`` — congruent loop-copy
      runs become ``lax.scan`` stages — the WHOLE program compiles as one
      XLA function and every ``run_batch`` costs exactly ONE dispatch.
    * **chained** (fallback): a chain of ≤ ``seg_limit``-insn predicated
      segments driven by a host loop threading ``(regs, active, done, r0)``
      plus cross-segment pending masks — one dispatch per segment.

    ``num_segments`` always reports the chained PLAN size (the historical
    invariant the boundary/regression guards pin); ``fused`` /
    ``dispatches`` say what actually executes."""

    def __init__(self, program: Program | LoweredProgram, maps: MapRegistry,
                 code=None, cuts: tuple[int, ...] | None = None,
                 seg_limit: int = SEG_LIMIT) -> None:
        self.maps = maps
        lp = program if isinstance(program, LoweredProgram) else \
            lower(program, maps)
        if code is None:
            code, cuts = unroll_lowered(lp)
        elif code and not isinstance(code[0], LIns):
            raise TypeError("code must be lowered-IR (see core.lower)")
        code = tuple(code)
        cuts = tuple(cuts or ())
        self.unrolled_len = len(code)
        self.seg_limit = seg_limit
        self.rb_cap = int(lp.facts.get("rb_cap", 0))
        self._last_rb: tuple | None = None     # (ev, cnt, drops) device arrays
        self._plans = _plan_segments(code, cuts, seg_limit)
        stages, traced = plan_scan_stages(code, cuts)
        self.traced_len = traced
        self.scan_stages = sum(1 for st in stages if st[0] == "scan")
        self.fused = traced <= seg_limit
        self.segments: list[_Segment] = []
        if self.fused:
            self._fused_fn = jax.jit(
                _make_fused_fn(code, stages, rb_cap=self.rb_cap))
        else:
            self._fused_fn = None
            for start, end, entry, exits in self._plans:
                fn = jax.jit(_make_segment_fn(code, start, end, entry,
                                              exits, rb_cap=self.rb_cap))
                self.segments.append(_Segment(start, end, entry, exits, fn))
        # dispatches per run_batch on the path actually taken, plus a
        # lifetime counter the bench's crossing audit reads
        self.dispatches = 1 if self.fused else len(self._plans)
        self.total_dispatches = 0
        self._map_cache: tuple | None = None   # (version, arrays, lens)
        # per-batch-size initial machine state, built once: jnp constants are
        # immutable, and re-allocating five tiny device arrays per dispatch
        # dominated the per-call cost at decode-sized batches
        self._state_cache: dict[int, tuple] = {}

    @property
    def num_segments(self) -> int:
        return len(self._plans)

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

    def _init_state(self, B: int) -> tuple:
        st = self._state_cache.get(B)
        if st is None:
            st = (jnp.zeros((NUM_REGS, B), I64), jnp.ones(B, bool),
                  jnp.zeros(B, bool), jnp.zeros(B, I64))
            if self.rb_cap:
                st += (jnp.zeros((B, self.rb_cap, RB_FIELDS), I64),
                       jnp.zeros(B, I64), jnp.zeros(B, I64))
            self._state_cache[B] = st
        return st

    def _run_segments(self, ctx, map_arrays, map_lens):
        B = ctx.shape[0]
        if self.rb_cap:
            regs, active, done, r0, ev, ecnt, edrop = self._init_state(B)
        else:
            regs, active, done, r0 = self._init_state(B)
        if self._fused_fn is not None:
            self.total_dispatches += 1
            if self.rb_cap:
                r0, ev, ecnt, edrop = self._fused_fn(
                    ctx, map_arrays, map_lens, regs, active, done, r0,
                    ev, ecnt, edrop)
                self._last_rb = (ev, ecnt, edrop)
            else:
                r0 = self._fused_fn(ctx, map_arrays, map_lens, regs,
                                    active, done, r0)
            return r0
        self.total_dispatches += len(self.segments)
        zeros = done
        pending: dict[int, jax.Array] = {}
        for seg in self.segments:
            entry = tuple(pending.pop(t, zeros) for t in seg.entry_targets)
            if self.rb_cap:
                regs, active, done, r0, exits, ev, ecnt, edrop = seg.fn(
                    ctx, map_arrays, map_lens, regs, active, done, r0,
                    entry, ev, ecnt, edrop)
            else:
                regs, active, done, r0, exits = seg.fn(
                    ctx, map_arrays, map_lens, regs, active, done, r0, entry)
            for t, m in zip(seg.exit_targets, exits):
                pending[t] = (pending[t] | m) if t in pending else m
        if self.rb_cap:
            self._last_rb = (ev, ecnt, edrop)
        return r0

    def run_batch(self, ctx_mat: np.ndarray) -> np.ndarray:
        with jax.enable_x64(True):
            arrays, lens = self._map_args()
            return np.asarray(self._run_segments(
                jnp.asarray(ctx_mat, I64), arrays, lens))

    def take_events(self, n: int) -> tuple[list, int]:
        """Drain the last batch's ring-buffer records for the first ``n``
        lanes (and their slot-drop count); empty until the next batch."""
        if self._last_rb is None:
            return [], 0
        ev, cnt, dr = self._last_rb
        self._last_rb = None
        return collect_rb_events(ev, cnt, dr, n)
