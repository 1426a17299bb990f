"""Helpers for the CPU rehearsals: run ``run.py --smoke`` for a cell in a
child process (JAX on the CPU, a compile cache of its own) and parse the
result line."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def run(cell: str, tmp: Path, *, seed: int = 2**31 + 11, seconds: float = 2,
        trace: int = 0, prelude: str = "", smoke: bool = True,
        cwd: Path | None = None, script: Path | None = None):
    """(returncode, stdout lines, stderr) of one run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp / "xla"))
    script = script or CHIP / "run.py"
    argv = [str(script), "--workload", cell, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    if smoke:
        argv.append("--smoke")
    if prelude:
        # plant a fault in the program, then run the harness in-process
        code = (f"import sys, runpy\nsys.argv = {argv!r}\n{prelude}\n"
                f"runpy.run_path({str(script)!r}, run_name='__main__')\n")
        cmd = [sys.executable, "-c", code]
    else:
        cmd = [sys.executable] + argv
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       cwd=cwd or CHIP.parents[1], timeout=600)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


def result(lines: list[str]) -> dict:
    return json.loads(lines[-1])
