"""The control: the float32 reference computed in float8 in the program's
place fails the cell's limit, read by ``calibrate.py --control`` at smoke
size on the CPU, on the same prompts and served tokens where the program
passes."""

import json
import subprocess
import sys

import rehearse


def test_control_is_not_correct(tmp_path):
    env = dict(rehearse.os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    p = subprocess.run(
        [sys.executable, str(rehearse.CHIP / "calibrate.py"), "--workload",
         "phi3.chat", "--seeds", "5,6", "--seconds", "3", "--control",
         "--smoke"], env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    limit = json.loads((rehearse.CHIP / "traffic" / "phi3.chat.json")
                       .read_text())["check"]["max_logit_gap"]
    for line in p.stdout.strip().splitlines():
        r = json.loads(line)
        assert r["program_max_logit_gap"] <= limit < \
            r["control_max_logit_gap"], r
