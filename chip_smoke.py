"""Serve Phi-3-mini at its published widths once on one TPU chip and check
what comes out.

    python chip_smoke.py                           # full width, needs a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --smoke # smoke-size CPU rehearsal

One process, no workers.  It builds random bf16 weights from ``--seed`` and
a ``ServingEngine`` with the eBPF-mm fault policy and the serving launcher's
profile, then:

  1. check: one request served alone, its prefill logits and its decode
     logits compared with ``model_forward`` over the same tokens and weights
     (computed in float32 at the highest matmul precision);
  2. serve: 8 requests with 256-1024-token prompts and 64 new tokens each,
     run to completion with ``engine.run()``.

Facts go to stdout one per line; the last line is the JSON result.  Any
failed phase raises, and the script then exits non-zero with no result.
Without ``--smoke`` it refuses to run anywhere but on a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config, get_smoke_config  # noqa: E402
from repro.core.hooks import HOOK_FAULT, policy_device  # noqa: E402
from repro.launch.serve import default_profile  # noqa: E402
from repro.models import (PagedLayout, model_forward,  # noqa: E402
                          serving_params)
from repro.serving import Request, Sampler, ServingEngine  # noqa: E402

ARCH = "phi3_mini_3p8b"
BLOCK_TOKENS = 16
MAX_BATCH = 8
REQUESTS = 8
PROMPT_TOKENS = (256, 1024)
NEW_TOKENS = 64
# block-table length, with the serving launcher's slack of 8 blocks
MAX_BLOCKS = -(-(PROMPT_TOKENS[1] + NEW_TOKENS) // BLOCK_TOKENS) + 8
CHECK_NEW_TOKENS = 4        # the check request: its prefill + 3 decode steps
# KV pool: 512 blocks x 16 tokens x 384 KiB/token = 3 GiB.  On a described
# v5e the full-depth decode step's memory_analysis() is 10.87 GB of
# arguments (7.64 GB weights + 3.22 GB pool) + 3.22 GB output (the pool is
# not donated, so a step holds it twice) + 0.31 GB temporaries = 14.4 GB of
# the chip's 16 GB.  The 8 requests need at most 8 x 69 = 552 blocks; a
# shortfall makes admission wait, it never fails a request.
POOL_BLOCKS = 512
# Logit agreement, measured as ||served - ref|| / ||ref|| over the vocab row.
# The served path keeps activations and the paged KV in bf16 (unit roundoff
# 2^-9); each of the 32 layers rounds its residual stream, q/k/v and MLP
# activations independently of the float32 reference, so the rounding walks
# to a few percent of the logits' scale.  A wrong KV block, position or
# mask moves the logits by their own scale (relative error ~1).
LOGIT_RTOL = 0.05


class RecordingSampler(Sampler):
    """Greedy sampler that keeps every logits row the engine samples."""

    def __init__(self) -> None:
        super().__init__()
        self.rows: list[np.ndarray] = []

    def sample(self, logits, vocab, temperature=0.0):
        self.rows.append(np.asarray(logits, np.float32)[:vocab])
        return super().sample(logits, vocab, temperature)


def pool_bytes(cache) -> int:
    return sum(leaf.nbytes for path, leaf in
               jax.tree_util.tree_leaves_with_path(cache)
               if path[-1].key in ServingEngine._POOL_KEYS)


def check_logits(engine: ServingEngine, params, cfg, prompt: list[int]
                 ) -> list[float]:
    """Serve ``prompt`` alone and compare every logits row the engine
    sampled from with the reference forward; returns the relative errors
    (prefill first, then one per decode step)."""
    rec, served = RecordingSampler(), engine.sampler
    engine.sampler = rec
    engine.submit(Request(rid=-1, prompt=prompt, app="chat",
                          max_new_tokens=CHECK_NEW_TOKENS))
    engine.run()
    engine.sampler = served
    generated = engine.finished.pop(-1)
    if len(rec.rows) != CHECK_NEW_TOKENS:
        raise RuntimeError(f"check request sampled {len(rec.rows)} rows, "
                           f"expected {CHECK_NEW_TOKENS}")
    # the reference sees the prompt and every generated token but the last,
    # which was sampled and never fed back
    tokens = jnp.asarray([prompt + generated[:-1]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: model_forward(
            p, cfg, t, compute_dtype=jnp.float32)[0])(params, tokens)
    ref = np.asarray(ref[0, len(prompt) - 1:, :cfg.vocab], np.float64)
    errs = []
    for row, want in zip(rec.rows, ref):
        if not np.isfinite(row).all():
            raise RuntimeError("served logits are not finite")
        errs.append(float(np.linalg.norm(row - want) / np.linalg.norm(want)))
    return errs


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-size model config; runs on any platform")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the weights and the requests")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.smoke:
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    cfg = get_smoke_config(ARCH) if args.smoke else get_config(ARCH)
    print(f"platform: {dev.platform}")
    print(f"device_kind: {dev.device_kind}")
    print(f"config: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab}")

    t0 = time.perf_counter()
    params = jax.block_until_ready(serving_params(cfg, args.seed))
    layout = PagedLayout(num_blocks=POOL_BLOCKS, block_tokens=BLOCK_TOKENS,
                         max_blocks=MAX_BLOCKS)
    engine = ServingEngine(cfg, params, layout, max_batch=MAX_BATCH,
                           policy="ebpf", profile=default_profile(MAX_BLOCKS),
                           seed=args.seed)
    jax.block_until_ready(engine.cache)
    setup_s = time.perf_counter() - t0
    print(f"weight_bytes: {sum(x.nbytes for x in jax.tree.leaves(params))}")
    print(f"pool_blocks: {POOL_BLOCKS}")
    print(f"pool_bytes: {pool_bytes(engine.cache)}")
    print(f"setup_s: {setup_s}")

    rng = np.random.default_rng(args.seed)

    def prompt() -> list[int]:
        n = int(rng.integers(PROMPT_TOKENS[0], PROMPT_TOKENS[1] + 1))
        return rng.integers(1, cfg.vocab, n).tolist()

    t0 = time.perf_counter()
    errs = check_logits(engine, params, cfg, prompt())
    jax.block_until_ready(engine.cache)
    print(f"check_s: {time.perf_counter() - t0}")
    print(f"logit_rel_err_prefill: {errs[0]}")
    print(f"logit_rel_err_decode: {errs[1:]}")
    if max(errs) > LOGIT_RTOL:
        raise RuntimeError(f"served logits differ from model_forward by "
                           f"{max(errs)} > {LOGIT_RTOL}")

    faults0 = engine.mm.hooks.batch_calls[HOOK_FAULT]
    for rid in range(REQUESTS):
        engine.submit(Request(rid=rid, prompt=prompt(), app="chat",
                              max_new_tokens=NEW_TOKENS))
    t0 = time.perf_counter()
    engine.run()
    jax.block_until_ready(engine.cache)
    serve_s = time.perf_counter() - t0
    fault_calls = engine.mm.hooks.batch_calls[HOOK_FAULT] - faults0
    done = [engine.finished.get(rid) for rid in range(REQUESTS)]
    tokens = sum(len(d) for d in done if d is not None)
    print(f"serve_s: {serve_s}")
    print(f"requests_completed: {sum(d is not None for d in done)}")
    print(f"tokens_generated: {tokens}")
    print(f"hook_fault_batch_invocations: {fault_calls}")
    print(f"preemptions: {engine.stats.preemptions}")
    pol = policy_device()
    print(f"policy_executor_device: {pol.platform} ({pol.device_kind})")
    stats = dev.memory_stats()
    print("peak_bytes_in_use: "
          f"{stats['peak_bytes_in_use'] if stats else 'not reported'}")
    if any(d is None or len(d) != NEW_TOKENS for d in done):
        raise RuntimeError(f"not every request finished with {NEW_TOKENS} "
                           f"tokens: {[None if d is None else len(d) for d in done]}")
    if not all(0 <= t < cfg.vocab for d in done for t in d):
        raise RuntimeError("a generated token is outside the vocabulary")
    if fault_calls < 1:
        raise RuntimeError("no HOOK_FAULT batch invocation while serving")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
