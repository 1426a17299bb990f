"""The comparison that decides ``correct`` fails a broken timed path.

Each test drives a whole smoke-size run of phi3.chat (the smoke switch
skips the harness's look for a chip) with a fault planted in the program
underneath, and sees ``correct`` come out false: a decode step that
returns its KV state unchanged, half of the batch's rows left out of each
decode step, a token altered where the sampler produces it.  The cell has
no exchange between chips to leave out.  The control is in
``test_chipbench_control.py``.
"""

import shutil

import pytest

import rehearse

SRC = str(rehearse.CHIP.parents[1] / "src")

ENGINE = f"""
sys.path.insert(0, {SRC!r})
from repro.serving import engine as E
_init = E.ServingEngine.__init__
def _patched(self, *a, **k):
    _init(self, *a, **k)
    self._decode = fault(self._decode)
E.ServingEngine.__init__ = _patched
"""

FAULTS = {
    "state_unchanged": """
def fault(f):
    def dec(p, c, *rest):
        logits, _, heat, buf = f(p, c, *rest)
        return logits, c, heat, buf
    return dec
""" + ENGINE,
    "half_batch_left_out": """
import jax.numpy as jnp
def fault(f):
    n = [0]
    def dec(p, c, buf, didx, drows, tri, t, l, act, pos):
        n[0] += 1
        keep = (jnp.arange(act.shape[0]) + n[0]) % 2 == 0
        return f(p, c, buf, didx, drows, tri, t, l, act & keep, pos)
    return dec
""" + ENGINE,
    "token_altered": f"""
sys.path.insert(0, {SRC!r})
from repro.serving import sampler as S
_sample = S.Sampler.sample
_n = [0]
def _altered(self, logits, vocab, temperature=0.0):
    t = _sample(self, logits, vocab, temperature)
    _n[0] += 1
    return (t + 1) % vocab if _n[0] % 5 == 0 else t
S.Sampler.sample = _altered
""",
}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """One compile cache for the module's runs: the same programs load."""
    return tmp_path_factory.mktemp("runs")


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(cache, fault):
    rc, out, err = rehearse.run("phi3.chat", cache, seconds=3,
                                prelude=FAULTS[fault])
    assert rc == 0, err[-3000:]
    res = rehearse.result(out)
    assert res["correct"] is False, res["compared"]
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    # the numbers compared are the last lines on stderr, each with its limit
    tail = err.strip().splitlines()[-len(res["compared"]):]
    assert [t.split(":")[0] for t in tail] == list(res["compared"])
    assert all(" limit " in t for t in tail)


def test_no_tpu_no_result(tmp_path):
    """Without --smoke a run on the CPU exits non-zero and prints no
    result."""
    rc, out, err = rehearse.run("phi3.chat", tmp_path, smoke=False)
    assert rc != 0
    assert not any(line.startswith("{") for line in out)
    assert "needs a TPU" in err


def test_benchmark_files_alone_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files cannot run the program: non-zero exit, no result."""
    root = rehearse.CHIP.parents[1]
    shutil.copy(root / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(rehearse.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, out, err = rehearse.run(
        "phi3.chat", tmp_path, cwd=tmp_path,
        script=tmp_path / "benchmarks" / "chip" / "run.py")
    assert rc != 0
    assert not any(line.startswith("{") for line in out)
