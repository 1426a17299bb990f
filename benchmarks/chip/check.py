"""What decides ``correct``: the served tokens against the float32
reference, and the fused policy executor's fault decisions against the
policy interpreter.

Served tokens.  After the window, a sample of the finished requests, drawn
from the seed with the longest among them (and one that was preempted and
recomputed, where one was), is run through the reference once: the prompt
followed by the served tokens.  At each served position the reference's
best logit minus its logit for the served token is the gap; a greedy
server that agrees with the reference leaves gaps at rounding level, a
wrong key, value, position or token leaves gaps at the logits' own scale.
The widest gap is held to the cell's limit (traffic file, ``check``).

Policy decisions.  Every HOOK_FAULT batch the window ran through the
engine's compiled executor is recorded (up to a cap) and replayed through
the program's interpreter; the count of rows that decide differently is
held to 0.
"""

from __future__ import annotations

import time

import numpy as np

POLICY_BATCHES = 400          # fault batches recorded per run, at most


class PolicyRecorder:
    """Wraps ``mm.hooks.run_batch`` to keep the fault batches it ran."""

    def __init__(self, engine):
        from repro.core.hooks import HOOK_FAULT
        self.hook = HOOK_FAULT
        self.batches: list[tuple[np.ndarray, np.ndarray]] = []
        self.armed = False
        hooks = engine.mm.hooks
        inner = hooks.run_batch

        def run_batch(hook, ctx_mat, *a, **kw):
            out = inner(hook, ctx_mat, *a, **kw)
            if self.armed and hook == self.hook and out is not None and \
                    len(self.batches) < POLICY_BATCHES:
                # references only: the fault path builds a fresh matrix per
                # call and keeps neither, so nothing is copied in the window
                self.batches.append((ctx_mat, out))
            return out

        hooks.run_batch = run_batch
        self._hooks = hooks

    def mismatches(self) -> tuple[int, int]:
        """(rows compared, rows whose interpreter decision differs)."""
        from repro.core.context import POLICY_FALLBACK
        ap = self._hooks._hooks.get(self.hook)
        if ap is None:
            return 0, 0
        rows = bad = 0
        for ctx, out in self.batches:
            for i in range(len(out)):
                if int(out[i]) < POLICY_FALLBACK:
                    continue
                rows += 1
                bad += int(ap.vm.run(ctx[i]).ret) != int(out[i])
        return rows, bad


def finish_some(engine, need: int, limit_s: float = 60.0) -> float:
    """After the window: when fewer than ``need`` requests have finished,
    step the engine (nothing new is submitted) until they have, for at most
    ``limit_s``.  Returns the seconds spent."""
    t0 = time.perf_counter()
    steps = 0
    while len(engine.finished) < need and (engine.active or engine.waiting) \
            and time.perf_counter() - t0 < limit_s:
        engine.step()
        steps += 1
    return time.perf_counter() - t0 if steps else 0.0


def sample(win, finished: dict, reqs, seed: int, opts: dict) -> list[int]:
    """Finished requests to compare: the longest, one recomputed after a
    preemption where there is one, then a draw from the seed until
    ``min_tokens`` served tokens and ``min_requests`` requests."""
    min_tokens = opts.get("min_tokens", 256)
    min_req = opts.get("min_requests", 4)
    max_req = opts.get("max_requests", 8)
    done = sorted(finished)
    if not done:
        return []
    size = {rid: len(reqs[rid].prompt) + len(finished[rid]) for rid in done}
    pick = [max(done, key=lambda r: (size[r], r))]
    pre = [r for r in done if win.records[r].preempted and r not in pick]
    rng = np.random.default_rng(seed)
    if pre:
        pick.append(pre[int(rng.integers(len(pre)))])
    rest = [r for r in rng.permutation(done).tolist() if r not in pick]
    while rest and len(pick) < max_req and (
            len(pick) < min_req
            or sum(len(finished[r]) for r in pick) < min_tokens):
        pick.append(rest.pop(0))
    return pick


def gaps(ref, params, ref_cfg: dict, prompt: list[int], served: list[int],
         quant: str | None = None) -> np.ndarray:
    """Per served position: the reference's best logit minus its logit for
    the served token.  With ``quant`` the served tokens are replaced by the
    tokens the quantized reference puts first."""
    L = len(prompt)
    toks = prompt + served[:-1]
    rows = slice(L - 1, L - 1 + len(served))
    want = ref.logits(params, ref_cfg, toks, rows)
    pick = np.asarray(served)
    if quant:
        pick = np.argmax(ref.logits(params, ref_cfg, toks, rows, quant=quant),
                         axis=-1)
    return want.max(axis=-1) - want[np.arange(len(pick)), pick]


def compare(ref, params, ref_cfg: dict, reqs, finished: dict,
            picked: list[int], traffic: dict, policy=(0, 0),
            quant: str | None = None) -> tuple[dict, int]:
    """The numbers compared, each ``{"value", "limit", "ok"}``, and the
    count of served tokens compared."""
    vocab = ref_cfg["vocab_size"]
    widest, n_tok = 0.0, 0
    for rid in picked:
        g = gaps(ref, params, ref_cfg, reqs[rid].prompt, finished[rid], quant)
        widest = max(widest, float(g.max()))
        n_tok += len(g)
    wrong_len = sum(1 for rid, t in finished.items()
                    if len(t) != reqs[rid].max_new)
    oov = sum(1 for t in finished.values() for x in t if not 0 <= x < vocab)
    limit = float(traffic["check"]["max_logit_gap"])
    out = {
        "max_logit_gap": {"value": widest, "limit": limit,
                          "ok": bool(picked) and widest <= limit},
        "wrong_length_requests": {"value": wrong_len, "limit": 0,
                                  "ok": wrong_len == 0},
        "out_of_vocab_tokens": {"value": oov, "limit": 0, "ok": oov == 0},
        "policy_mismatches": {"value": policy[1], "limit": 0,
                              "ok": policy[1] == 0},
    }
    return out, n_tok
