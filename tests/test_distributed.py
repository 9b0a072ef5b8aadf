"""Distributed-semantics tests: each case runs tests/_scenarios.py in a
subprocess with 8 fake CPU devices (XLA_FLAGS must be set before jax import,
and the main pytest process keeps the real single-device view)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SCENARIOS = ["collectives", "reshard_roundtrip",
             "schemes_equivalent", "auto_scheme",
             "kernel_impl_equivalence", "attn_scan_impl_equivalence",
             "stream_grads_equivalence",
             "dp_vs_single", "serve_sharded",
             "hlo_census_real", "multipod_mesh", "resident_and_sp",
             "serve_resident_quant_equivalence",
             "obs_trace_equivalence", "obs_step_scopes"]


@pytest.mark.parametrize("name", SCENARIOS)
def test_scenario(name):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "_scenarios.py"), name],
        capture_output=True, text=True, timeout=900, env=env)
    tail = (r.stdout + r.stderr)[-4000:]
    assert r.returncode == 0, f"scenario {name} failed:\n{tail}"
    assert f"SCENARIO_OK {name}" in r.stdout, tail
