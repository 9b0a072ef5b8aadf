"""chip_smoke.py refuses to run without a TPU: no CPU fallback, and no
result line when the repo's sources are not beside it."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    script = SCRIPT
    if where == "alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    msg = "no TPU" if where == "repo" else "sources (src/repro) are not"
    assert msg in r.stderr, r.stderr
