"""Without an accelerator the benchmark's entry points exit non-zero and
print no result."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "granite-moe-3b-a800m.chat-prefix"


@pytest.mark.parametrize("script,args", [
    ("run.py", ["--workload", CELL, "--seed", str(2**31 + 17),
                "--seconds", "1", "--trace", "0"]),
    ("run.py", ["--workload", CELL, "--seed", "1", "--seconds", "1",
                "--trace", "1"]),
    ("knee.py", ["--workload", CELL, "--seed", "1", "--seconds", "1",
                 "--rates", "1"]),
    ("calibrate.py", ["--workload", CELL, "--seeds", "1", "--seconds",
                      "1"]),
], ids=["run", "run_traced", "knee", "calibrate"])
def test_cpu_only_exits_nonzero_without_result(script, args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "chip" / script), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "accelerator" in p.stderr
    for line in p.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj and "correct" not in obj
