"""The serving engine's host spans, compile counter and request times.

A smoke-size engine (eBPF fault policy, prefix cache) serves requests that
share a prompt prefix under ``jax.profiler.trace``; the host plane of the
trace must hold every span of the engine, the memory manager and the policy
executor, each nested under the span it belongs to.  The compile counter
must agree with JAX's own jit caches, and every request's times must be in
order."""

from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core import Profile, ProfileRegion
from repro.models import PagedLayout, materialize, model_spec
from repro.serving import Request, ServingEngine
from repro.serving.engine import REQUEST_LOG

PROGRAMS = ("_decode", "_prefill", "_prefill_sfx", "_moves")
# span -> the spans it may sit directly inside (serve/step is outermost)
PARENTS = {
    "serve/step": set(),
    "serve/admit": {"serve/step"},
    "serve/decode": {"serve/step"},
    "serve/tick": {"serve/step"},
    "serve/prefix": {"serve/admit"},
    "serve/prefill": {"serve/admit"},
    "serve/fault": {"serve/admit", "serve/decode"},
    "serve/moves": {"serve/admit", "serve/decode", "serve/tick"},
    "serve/tables": {"serve/prefill", "serve/decode"},
    "serve/dispatch": {"serve/prefill", "serve/decode", "serve/moves"},
    "serve/compile": {"serve/prefill", "serve/decode", "serve/moves"},
    "serve/sync": {"serve/prefill", "serve/decode"},
    "serve/sample": {"serve/prefill", "serve/decode"},
    "mm/fault": {"serve/fault"},
    "policy/run": {"mm/fault", "serve/fault", "serve/prefix", "serve/tick"},
}


def _engine():
    cfg = get_smoke_config("deepseek_7b")
    params = materialize(jax.random.PRNGKey(0), model_spec(cfg))
    layout = PagedLayout(num_blocks=64, block_tokens=4, max_blocks=32)
    prof = Profile("chat", [
        ProfileRegion(0, 8, (0, 150_000, 600_000, 2_500_000)),
        ProfileRegion(8, 32, (0, 0, 0, 0))])
    return cfg, ServingEngine(cfg, params, layout, max_batch=4,
                              policy="ebpf", profile=prof, prefix_cache=16)


def _host_spans(logdir: Path) -> list[tuple[str, str, int, int]]:
    """(name, thread, start, end) of the program's spans in the trace."""
    from jax.profiler import ProfileData
    path = sorted(logdir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PARENTS:
                    out.append((e.name, line.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The engine after serving 6 requests (3 share a 24-token prefix
    twice over) inside a profiler session, with its spans and the jit
    cache sizes before and after."""
    cfg, eng = _engine()
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab, 24).tolist()
    for r in range(6):
        tail = rng.integers(1, cfg.vocab, 6 + 4 * (r % 2)).tolist()
        eng.submit(Request(rid=r, prompt=shared + tail, max_new_tokens=8,
                           app="chat"))
    sizes0 = {p: getattr(eng, p).jitted._cache_size() for p in PROGRAMS}
    logdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(logdir)):
        while eng.step():
            pass
    sizes1 = {p: getattr(eng, p).jitted._cache_size() for p in PROGRAMS}
    return eng, _host_spans(logdir), sizes0, sizes1


def test_every_span_recorded_and_nested(served):
    eng, spans, _, _ = served
    assert eng.stats.completed == 6
    assert set(PARENTS) == {n for n, *_ in spans}
    by_thread: dict[str, list] = {}
    for s in spans:
        by_thread.setdefault(s[1], []).append(s)
    seen = Counter()
    for evs in by_thread.values():
        for name, _, a, b in evs:
            # the innermost program span that encloses this one
            outer = [(a0, n) for n, _, a0, b0 in evs
                     if a0 <= a and b <= b0 and (a0, b0) != (a, b)]
            parent = max(outer)[1] if outer else None
            if PARENTS[name]:
                assert parent in PARENTS[name], (name, parent)
            else:
                assert parent is None, (name, parent)
            seen[name] += 1
    assert seen["serve/step"] == eng.stats.steps


def test_compile_counter_matches_the_jit_caches(served):
    eng, spans, sizes0, sizes1 = served
    by_entry = eng.stats.snapshot()["compiles_by_entry"]
    for p in PROGRAMS:
        grown = sizes1[p] - sizes0[p]
        assert grown > 0, p
        assert by_entry[getattr(eng, p).name] == grown, p
    assert eng.stats.compiles == sum(sizes1[p] - sizes0[p]
                                     for p in PROGRAMS)
    names = Counter(n for n, *_ in spans)
    assert names["serve/compile"] == eng.stats.compiles
    # a repeated shape is neither a compile nor a new cache entry
    prog = eng._moves
    src = jnp.asarray(np.zeros(1, np.int32))
    dst = jnp.asarray(np.full(1, -1, np.int32))
    jax.block_until_ready(prog(eng.cache, src, dst))
    size = prog.jitted._cache_size()
    before = eng.stats.snapshot()
    assert before["compiles"] == sum(
        getattr(eng, p).jitted._cache_size() - sizes0[p] for p in PROGRAMS)
    jax.block_until_ready(prog(eng.cache, src, dst))
    assert prog.jitted._cache_size() == size
    assert eng.stats.snapshot()["compiles_by_entry"] == \
        before["compiles_by_entry"]


def test_compile_span_only_on_a_variants_first_call(tmp_path):
    _, eng = _engine()
    prog = eng._moves
    with jax.profiler.trace(str(tmp_path)):
        for P in (4, 4, 8, 4, 8):
            src = jnp.asarray(np.zeros(P, np.int32))
            dst = jnp.asarray(np.full(P, -1, np.int32))
            jax.block_until_ready(prog(eng.cache, src, dst))
    order = [n for n, *_ in sorted(_host_spans(tmp_path),
                                   key=lambda s: s[2])]
    assert order == ["serve/compile", "serve/dispatch", "serve/compile",
                     "serve/dispatch", "serve/dispatch"]
    assert eng.stats.compiles_by_entry == {"_moves_entry": 2}
    assert prog.jitted._cache_size() == 2


def test_request_times_in_order(served):
    eng, _, _, _ = served
    times = eng.request_times()
    assert sorted(t.rid for t in times) == list(range(6))
    for t in times:
        assert t.submitted <= t.admitted <= t.first_token, t
    assert eng.request_log.maxlen == REQUEST_LOG
    m = eng.metrics()
    assert m["requests_count"] == 6
    assert 0 < m["requests_admit_wait_ms_p50"] <= \
        m["requests_admit_wait_ms_p99"]
    assert m["requests_admit_wait_ms_p50"] < m["requests_ttft_ms_p50"]
    assert "engine_step_wall_s" in m and "engine_wall_host_s" not in m
