"""The traffic generator: a seed gives one schedule, every seed the same
work, and lengths stay on the grid."""

import sys
from collections import Counter
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import traffic  # noqa: E402

CELLS = sorted(p.stem for p in (Path(traffic.HERE) / "traffic").glob("*.json"))
# mixes a later PR can add as data alone, derived from the committed files:
# every prefix drawn anew (the prefix-cache control), and bursts
DERIVED = {
    "ds7b.sysprompt+unique": ("ds7b.sysprompt", {"prefix": {"pool": 0}}),
    "phi3.chat+onoff": ("phi3.chat", {"arrival": {
        "process": "onoff", "rate_per_s": 5.0, "on_s": 10, "off_s": 10}}),
}
MIXES = CELLS + sorted(DERIVED)


def _load(name):
    if name not in DERIVED:
        return traffic.load(name)
    base, change = DERIVED[name]
    t = traffic.load(base)
    for key, val in change.items():
        t[key] = dict(t[key], **val)
    return t


def _due(arrival, length):
    """Requests the arrival process puts in a span of ``length`` seconds."""
    rate = arrival["rate_per_s"]
    if arrival["process"] == "poisson":
        return max(1, round(rate * length))
    on, off = arrival["on_s"], arrival["off_s"]
    return sum(max(1, round(rate * min(on, length - t)))
               for t in range(0, int(length), int(on + off)))


def _sig(reqs):
    return [(r.due_s, len(r.prompt), r.max_new, r.prefix_id, r.prompt[:3])
            for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    t = _load(name)
    a = traffic.schedule(t, 20, 2**33 + 5, 32064)
    b = traffic.schedule(t, 20, 2**33 + 5, 32064)
    assert _sig(a) == _sig(b)
    c = traffic.schedule(t, 20, 6, 32064)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_work(name):
    t = _load(name)
    runs = [traffic.schedule(t, 20, s, 1000) for s in (1, 2, 3)]
    for reqs in runs[1:]:
        assert Counter(len(r.prompt) for r in reqs) == \
            Counter(len(r.prompt) for r in runs[0])
        assert Counter(r.max_new for r in reqs) == \
            Counter(r.max_new for r in runs[0])
    lead = t["lead_in_s"]
    reqs = runs[0]
    assert sum(r.due_s < lead for r in reqs) == _due(t["arrival"], lead)
    assert sum(r.due_s >= lead for r in reqs) == _due(t["arrival"], 20)
    assert all(0 <= r.due_s < lead + 20 for r in reqs)
    assert [r.rid for r in reqs] == list(range(len(reqs)))
    assert all(a.due_s <= b.due_s for a, b in zip(reqs, reqs[1:]))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_on_grid(name):
    t = _load(name)
    shapes = set(traffic.prompt_shapes(t))
    p, o = t["prompt"], t["output"]
    for r in traffic.schedule(t, 30, 11, 1000):
        assert len(r.prompt) in shapes
        assert o["min"] <= r.max_new <= o["max"]
        user = len(r.prompt) - (t["prefix"]["tokens"] if "prefix" in t
                                else 0)
        grid = p.get("grid", 1)
        assert user % grid == 0
        assert -(-p["min"] // grid) * grid <= user <= p["max"]
        assert all(1 <= x < 1000 for x in r.prompt)
    assert traffic.max_context(t) <= t["max_blocks"] * 16


def test_shared_prefixes_are_zipf_and_shared():
    t = traffic.load("ds7b.sysprompt")
    reqs = traffic.schedule(t, 30, 3, 1000)
    n = t["prefix"]["tokens"]
    by = {}
    for r in reqs:
        by.setdefault(r.prefix_id, set()).add(tuple(r.prompt[:n]))
    assert set(by) <= set(range(t["prefix"]["pool"]))
    assert all(len(v) == 1 for v in by.values())
    counts = Counter(r.prefix_id for r in reqs)
    assert counts[0] == max(counts.values())


def test_unique_prefixes_for_the_control():
    # the sysprompt mix with no shared pool: every prefix is drawn anew
    t = traffic.load("ds7b.sysprompt")
    t["prefix"]["pool"] = 0
    reqs = traffic.schedule(t, 30, 3, 100000)
    n = t["prefix"]["tokens"]
    assert len({tuple(r.prompt[:n]) for r in reqs}) == len(reqs)
    assert all(r.prefix_id == -1 for r in reqs)
