"""launch.distributed config resolution — pure env/flag logic, no cluster.
The live rendezvous paths are covered by tests/test_multiprocess.py."""
from pathlib import Path

import jax
import pytest

from repro.launch.distributed import DistConfig, detect, enable_compile_cache


def test_explicit_flags_win(monkeypatch):
    monkeypatch.setenv("SLURM_PROCID", "3")
    monkeypatch.setenv("SLURM_NTASKS", "4")
    d = detect("host:1234", 2, 1)
    assert (d.coordinator, d.num_processes, d.process_id, d.source) == \
        ("host:1234", 2, 1, "flags")


def test_partial_flags_refused():
    with pytest.raises(ValueError, match="together"):
        detect("host:1234", None, None)


def test_slurm_autodetect(monkeypatch):
    monkeypatch.setenv("SLURM_PROCID", "3")
    monkeypatch.setenv("SLURM_NTASKS", "4")
    monkeypatch.setenv("SLURM_NODELIST", "frontier[00123-00170]")
    d = detect()
    assert d.source == "slurm" and d.process_id == 3
    assert d.coordinator == "frontier00123:12621"
    # explicit coordinator override
    monkeypatch.setenv("REPRO_COORDINATOR", "login1:9000")
    assert detect().coordinator == "login1:9000"


def test_ompi_needs_coordinator(monkeypatch):
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "1")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "2")
    assert detect().source == "single"    # no rank-0 address -> fall through
    monkeypatch.setenv("REPRO_COORDINATOR", "c:9")
    d = detect()
    assert d.source == "ompi" and d.num_processes == 2 and d.process_id == 1


def test_env_vars_and_single_default(monkeypatch):
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "2")
    monkeypatch.setenv("REPRO_PROCESS_ID", "1")
    monkeypatch.setenv("REPRO_COORDINATOR", "c:9")
    d = detect()
    assert d.source == "env" and d.process_id == 1 and d.is_distributed
    for k in ("REPRO_NUM_PROCESSES", "REPRO_PROCESS_ID", "REPRO_COORDINATOR"):
        monkeypatch.delenv(k)
    d = detect()
    assert d.source == "single" and not d.is_distributed


def test_invalid_configs_refused():
    with pytest.raises(AssertionError):
        DistConfig(None, 2, 0)            # distributed without coordinator
    with pytest.raises(AssertionError):
        DistConfig("c:9", 2, 2)           # rank out of range


def test_cli_args_roundtrip():
    import argparse
    from repro.launch.distributed import add_cli_args, from_args
    ap = argparse.ArgumentParser()
    add_cli_args(ap)
    args = ap.parse_args(["--coordinator", "h:1", "--num-processes", "2",
                          "--process-id", "1"])
    d = from_args(args)
    assert d == DistConfig("h:1", 2, 1, "flags")
    assert not from_args(ap.parse_args([])).is_distributed


def test_compile_cache_dir(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed,
    git-ignored directory inside the checkout on an accelerator, and no
    cache on the CPU backend."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert enable_compile_cache() == "/elsewhere"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = Path(__file__).resolve().parents[1]
    assert path == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
