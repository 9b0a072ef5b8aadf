"""The check that decides ``correct`` passes a sound run and fails a run
whose timed path is broken underneath, once for each fault a one-chip
training cell can have; and the control (the reference one precision step
below the cell's) fails it too. Tiny sizes, on the CPU, without the chip
check."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(__file__))
import bench_tiny  # noqa: E402


def test_sound_run_is_correct(tmp_path):
    out = bench_tiny.run(tmp_path, "qwen2-0.5b")
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gb",
                                   "setup_s"}


def _unchanged(monkeypatch):
    from repro.core.engine import ZeroEngine
    monkeypatch.setattr(ZeroEngine, "_apply_updates",
                        lambda self, state, g: (state, self._lr(state["step"])))


def _half_batch(monkeypatch):
    from repro.core.engine import ZeroEngine
    orig = ZeroEngine._make_local_grads

    def make(self, loss_fn):
        inner = orig(self, loss_fn)
        return lambda p, batch: inner(
            p, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    monkeypatch.setattr(ZeroEngine, "_make_local_grads", make)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    out = bench_tiny.run(tmp_path, "qwen2-0.5b")
    assert not out["correct"], out["check"]
    assert out["failed"] > 0


def test_control_is_not_correct(tmp_path):
    """The reference at INT4 weights, put in the program's place."""
    from benchmarks.chip import catalog, check, harness
    bench, name = bench_tiny.make(tmp_path, "qwen2-0.5b")
    c = catalog.cell(name, bench, tmp_path, tmp_path)
    cfg = c["config"]
    table = catalog.reference(cfg["reference"]).param_table(cfg)
    import jax
    dev = jax.devices()[:1]
    ref = harness.reference_readings(c, table, 3, dev)
    ctl = harness.reference_readings(c, table, 3, dev,
                                     precision=c["traffic"]["control"])
    ok, report = check.decide(check.numbers(ctl, ref), c["limits"])
    assert not ok, report


EXCHANGE = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, {tests!r})
    import bench_tiny
    from pathlib import Path
    if {broken}:
        from jax import lax
        from repro.core import schedule
        orig = schedule.grad_rs_issue
        def local_only(flat, axes, cfg, **kw):
            tok = orig(flat, axes, cfg, **kw)
            if tok[0] != "rs":
                return tok
            d = cfg.size(axes)
            i = lax.axis_index(tuple(axes))
            return ("rs", lax.dynamic_slice_in_dim(
                flat, i * (flat.shape[-1] // d), flat.shape[-1] // d, -1))
        schedule.grad_rs_issue = local_only
    out = bench_tiny.run(Path({tmp!r}), "gpt-neox-20b")
    print(json.dumps(dict(correct=out["correct"], check=out["check"])))
""")


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_four_device_exchange(tmp_path, broken):
    """zero3 over four (virtual) devices: correct, and not correct once the
    gradient reduce-scatter keeps each device's own share unsummed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE.format(tests=os.path.dirname(__file__), broken=broken,
                           tmp=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not broken), out["check"]
