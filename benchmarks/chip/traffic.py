"""Open-loop request schedules, generated from a traffic file and a seed.

A traffic file (``traffic/<name>.json``) holds only parameters; this one
generator reads every file.  Its keys:

  * ``arrival``: ``{"process": "poisson", "rate_per_s": r}`` or
    ``{"process": "onoff", "rate_per_s": r, "on_s": a, "off_s": b}``
    (requests arrive at ``r`` while on, none while off);
  * ``lead_in_s``: traffic that runs before the measured window opens;
  * ``prompt`` and ``output``: ``{"median", "sigma", "min", "max"}`` of a
    lognormal length distribution, clipped; ``prompt`` may add ``"grid"``,
    to which a prompt's length is rounded up;
  * ``prefix`` (optional): ``{"tokens": n, "pool": k, "zipf_s": s}`` puts
    one of ``k`` shared prefixes of ``n`` tokens, chosen Zipf(s), in front
    of every prompt (the drawn prompt length is then the user part);
    ``"pool": 0`` gives every request a prefix of its own.

Every seed gets the same work in another order: each segment (lead-in,
window) holds ``round(rate x length)`` requests, whose inter-arrival gaps
are the quantiles of the exponential distribution and whose lengths are the
quantiles of the length distributions, permuted by the seed.  Only the
order, the pairing of lengths and the token ids change with the seed, so
two seeds differ about as much as two runs of one seed.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass
class Req:
    rid: int
    due_s: float            # from the start of the lead-in
    prompt: list[int]
    max_new: int
    prefix_id: int          # shared prefix it starts with, -1 for none


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles of a clipped lognormal, rounded up to the grid."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.ceil(x), spec["min"], spec["max"])
    grid = spec.get("grid", 1)
    x = np.ceil(x / grid) * grid
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def grid_lengths(spec: dict) -> list[int]:
    """Every length a spec can produce (the shapes set-up must warm)."""
    grid = spec.get("grid", 1)
    lo = -(-spec["min"] // grid) * grid
    return list(range(lo, spec["max"] + 1, grid))


def _arrivals(arrival: dict, start: float, length: float,
              rng: np.random.Generator) -> np.ndarray:
    rate = float(arrival["rate_per_s"])
    if arrival["process"] == "poisson":
        spans = [(start, length)]
    elif arrival["process"] == "onoff":
        on, off = float(arrival["on_s"]), float(arrival["off_s"])
        spans, t = [], 0.0
        while t < length:
            spans.append((start + t, min(on, length - t)))
            t += on + off
    else:
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    out = []
    for s0, span in spans:
        n = max(1, round(rate * span))
        gaps = -np.log1p(-_quantiles(n))
        gaps = rng.permutation(gaps * (span / gaps.sum()))
        out.append(s0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
    return np.concatenate(out)


def _prefix_counts(n: int, k: int, s: float) -> np.ndarray:
    """Largest-remainder rounding of ``n`` draws over Zipf(s) ranks."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    c = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - c), kind="stable")[:n - c.sum()]:
        c[i] += 1
    return c


def schedule(traffic: dict, seconds: float, seed: int, vocab: int
             ) -> list[Req]:
    """The run's requests, in due order: the lead-in's, then the window's."""
    rng = np.random.default_rng(seed)
    pre = traffic.get("prefix")
    shared = []
    if pre and pre.get("pool", 0) > 0:
        shared = [rng.integers(1, vocab, pre["tokens"]).tolist()
                  for _ in range(pre["pool"])]
    reqs: list[Req] = []
    lead = float(traffic["lead_in_s"])
    for start, length in ((0.0, lead), (lead, float(seconds))):
        if length <= 0:
            continue
        due = _arrivals(traffic["arrival"], start, length, rng)
        n = len(due)
        plen = rng.permutation(lengths(traffic["prompt"], n))
        olen = rng.permutation(lengths(traffic["output"], n))
        pid = np.full(n, -1)
        if shared:
            pid = rng.permutation(np.repeat(
                np.arange(pre["pool"]),
                _prefix_counts(n, pre["pool"], pre.get("zipf_s", 1.0))))
        for i in range(n):
            user = rng.integers(1, vocab, int(plen[i])).tolist()
            if shared:
                prompt = shared[pid[i]] + user
            elif pre:
                prompt = rng.integers(1, vocab, pre["tokens"]).tolist() + user
            else:
                prompt = user
            reqs.append(Req(rid=0, due_s=float(due[i]), prompt=prompt,
                            max_new=int(olen[i]), prefix_id=int(pid[i])))
    reqs.sort(key=lambda r: r.due_s)
    for i, r in enumerate(reqs):
        r.rid = i
    return reqs


def prompt_shapes(traffic: dict) -> list[int]:
    """Every prompt length (in tokens) the traffic can send."""
    pre = traffic.get("prefix")
    base = pre["tokens"] if pre else 0
    return [base + n for n in grid_lengths(traffic["prompt"])]


def max_context(traffic: dict) -> int:
    return max(prompt_shapes(traffic)) + traffic["output"]["max"]
