"""CPU rehearsal of the ds7b.sysprompt cell: a smoke-size model of its architecture
through run.py for 2 s; the result line has the contract's keys, names the
CPU and is correct."""

import rehearse


def test_rehearse_ds7b_sysprompt(tmp_path):
    rc, out, err = rehearse.run("ds7b.sysprompt", tmp_path)
    assert rc == 0, err[-3000:]
    res = rehearse.result(out)
    assert set(res) == rehearse.RESULT_KEYS
    assert list(res)[-1] == "compared"
    assert res["device"]["platform"] == "cpu"
    assert "platform: cpu" in out
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "setup_s" in res["metrics"]
    assert all(m["unit"] for m in res["metrics"].values())
