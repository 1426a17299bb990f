"""Every metric a cell lists in BENCHMARK.json has a reader that finds
something to read in a run of that cell (here a hand-made one), and the
shares stay within 0-100%."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402
import reduce  # noqa: E402
from run import Run, cell_metrics, load_module  # noqa: E402

BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
PHI3 = SimpleNamespace(n_layers=32, d_model=3072, n_heads=32, kv_heads=32,
                       head_dim=96, d_ff=8192, vocab=32064)
MS = 1_000_000


def _run(cell: str) -> Run:
    recs = {}
    steps = []
    for i in range(20):
        t = 1.0 + 0.05 * i
        r = harness.Record(i, due=t - 0.01, prompt_len=512, max_new=32,
                           submitted=t, admitted_step=t,
                           deliveries=[(t + 0.04, 2), (t + 0.09, 1),
                                       (t + 0.13, 1)], delivered=4,
                           finished=t + 0.13 if i % 2 else None,
                           preempted=int(i == 3))
        recs[i] = r
        steps.append(harness.StepRec(t, t + 0.04, 6, 480, [600] * 6,
                                     [(512, 480.0)]))
    win = harness.Window(1.0, 3.0, recs, steps,
                         {"completed": 0, "preemptions": 0},
                         {"completed": 10, "preemptions": 1})
    host = [("harness.window", 1000 * MS, 3000 * MS)]
    ops, mods = [], []
    for s in steps:
        a, b = s.t0 * 1e9, s.t1 * 1e9
        host += [("harness.step", a, b), ("mm.fault_batch", a, a + 1 * MS),
                 ("policy.run_batch", a, a + MS / 2)]
        mods += [("jit__prefill_entry(1)", a + 2 * MS, a + 12 * MS),
                 ("jit__decode_entry(2)", a + 13 * MS, a + 33 * MS)]
        ops += [("fusion", a + 2 * MS, a + 33 * MS)]
    trace = reduce.reduce({"host": host, "devices": {
        "/device:TPU:0": {"ops": ops, "modules": mods}}})
    engine = SimpleNamespace(
        mm=SimpleNamespace(hugepage_block_fraction=lambda: 0.25))
    return Run(cell=cell, seconds=2.0, setup_s=42.0, win=win, engine=engine,
               cfg=PHI3, max_batch=8,
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               memory={"peak_bytes_in_use": 15e9, "bytes_limit": 16e9},
               trace=trace)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_reads(cell, kind):
    run = _run(cell)
    for m in cell_metrics(BENCH, cell, kind):
        reader = load_module(CHIP / "metrics" / f"{m['name']}.py", m["name"])
        v = reader.read(run)
        assert v is not None and v == v, m["name"]
        if m["unit"] == "%":
            assert 0 <= v <= 100, (m["name"], v)


def test_metric_files_match_the_benchmark():
    names = {m["name"] for k in ("end_to_end", "per_layer")
             for m in BENCH[k]}
    files = {p.stem for p in (CHIP / "metrics").glob("*.py")}
    assert names == files


def test_per_layer_cells_report_what_they_move():
    for cell in CELLS:
        e2e = {m["name"] for m in cell_metrics(BENCH, cell, "end_to_end")}
        for m in cell_metrics(BENCH, cell, "per_layer"):
            assert m["moves"] in e2e, (cell, m["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell_metrics(BENCH, cell, "per_layer")


def test_trace_readers_count_only_the_traced_steps():
    """A traced run profiles the window's last seconds: readers that divide
    trace time by host counts take the counts of those steps alone."""
    run = _run("phi3.chat")
    whole = load_module(CHIP / "metrics" / "prefill_ms_per_ktok.py",
                        "prefill_ms_per_ktok").read(run)
    half = run.win.steps[len(run.win.steps) // 2].t0
    run.trace_from = half
    late = sum(s.prefill_tokens for s in run.win.steps if s.t0 >= half)
    assert late < sum(s.prefill_tokens for s in run.win.steps)
    part = load_module(CHIP / "metrics" / "prefill_ms_per_ktok.py",
                       "prefill_ms_per_ktok").read(run)
    secs, _ = run.trace.program(("_prefill_entry", "_prefill_sfx_entry"))
    assert part == pytest.approx(secs * 1e6 / late)
    assert part > whole
