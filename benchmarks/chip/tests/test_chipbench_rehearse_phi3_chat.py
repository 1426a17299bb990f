"""CPU rehearsal of the phi3.chat cell: a smoke-size model of its architecture
through run.py for 2 s; the result line has the contract's keys, names the
CPU and is correct."""

import rehearse


def test_rehearse_phi3_chat(tmp_path):
    rc, out, err = rehearse.run("phi3.chat", tmp_path)
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
