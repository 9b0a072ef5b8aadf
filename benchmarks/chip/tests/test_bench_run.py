"""The benchmark's command never falls back to the CPU: without a TPU it
exits non-zero and prints no result; nor does it run without the
program's sources beside it."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]


@pytest.mark.parametrize("where", ["checkout", "benchmark_alone"])
def test_run_fails_without_tpu(tmp_path, where):
    root = ROOT
    if where == "benchmark_alone":
        root = tmp_path / "co"
        shutil.copytree(CHIP, root / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"),
         "--workload", "qwen2-0.5b.zero_topo.s4096", "--seed", "2147483659",
         "--seconds", "10", "--trace", "0"],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    msg = "no TPU" if where == "checkout" else "sources (src/repro) are not"
    assert msg in r.stderr, r.stderr
