"""Cross-process execution: ``jax.distributed.initialize`` wiring.

The paper's inter tier (Slingshot between Frontier nodes) is a *process*
boundary on real hardware — one training process per node (or per GCD).
This module is the single place that boundary is crossed:

* ``DistConfig`` — coordinator address + process rank/count, resolved from
  (in priority order) explicit CLI flags, SLURM, OpenMPI, or the
  ``REPRO_*`` env vars. Absent all of those, the run is single-process and
  ``initialize`` is a no-op, so every existing entry point keeps working
  unchanged.
* ``initialize(dcfg)`` — selects the CPU collectives backend (gloo; real
  GPU/TPU clusters bring their own), then calls
  ``jax.distributed.initialize``. Must run before the first device access.
* ``add_cli_args`` / ``from_args`` — the ``--coordinator`` /
  ``--num-processes`` / ``--process-id`` flags shared by
  ``launch/train.py`` and ``launch/dryrun.py``.

Mesh construction stays in ``launch/mesh.py``; the contract between the two
is that ``jax.devices()`` is process-major (all of process 0's devices, then
process 1's, ...) so the *leading* mesh axes span processes — pinning the
process boundary to the inter tier (``mesh.process_axes`` verifies it).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from pathlib import Path


@dataclass(frozen=True)
class DistConfig:
    """One process's view of the cluster. ``num_processes == 1`` means the
    ordinary single-process mode (no distributed runtime is started)."""
    coordinator: str | None = None
    num_processes: int = 1
    process_id: int = 0
    source: str = "single"     # single | flags | slurm | ompi | env

    def __post_init__(self):
        assert self.num_processes >= 1, self
        assert 0 <= self.process_id < self.num_processes, self
        if self.num_processes > 1:
            assert self.coordinator, \
                f"multi-process launch needs a coordinator address: {self}"

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1


def _from_slurm() -> DistConfig | None:
    """srun sets the full rank layout; coordinator = first node of the job.

    SLURM_STEP_NODELIST can be a compressed range expression; we only need
    the first hostname, which scontrol would expand — but to stay
    dependency-free we take the simple prefix (exact for the common
    ``host[1-4]``-style lists srun emits, and overridable via
    REPRO_COORDINATOR when it is not).
    """
    if "SLURM_PROCID" not in os.environ or "SLURM_NTASKS" not in os.environ:
        return None
    n = int(os.environ["SLURM_NTASKS"])
    if n == 1:
        return None
    host = os.environ.get("REPRO_COORDINATOR")
    if not host:
        nodelist = os.environ.get("SLURM_STEP_NODELIST",
                                  os.environ.get("SLURM_NODELIST", ""))
        first = nodelist.split(",")[0]
        if "[" in first:      # "frontier[00123-00170]" -> "frontier00123"
            prefix, rng = first.split("[", 1)
            first = prefix + rng.split("-")[0].split(",")[0].rstrip("]")
        host = f"{first}:{_DEFAULT_PORT}" if first else None
    if not host:
        return None
    return DistConfig(host, n, int(os.environ["SLURM_PROCID"]), "slurm")


def _from_ompi() -> DistConfig | None:
    """mpirun/mpiexec (OpenMPI): world size/rank from the OMPI env."""
    if "OMPI_COMM_WORLD_RANK" not in os.environ:
        return None
    n = int(os.environ.get("OMPI_COMM_WORLD_SIZE", "1"))
    if n == 1:
        return None
    host = os.environ.get("REPRO_COORDINATOR")
    if not host:
        return None     # OpenMPI does not expose rank 0's hostname portably
    return DistConfig(host, n, int(os.environ["OMPI_COMM_WORLD_RANK"]), "ompi")


def _from_env() -> DistConfig | None:
    """Manual launch: REPRO_COORDINATOR / REPRO_NUM_PROCESSES /
    REPRO_PROCESS_ID (the two-terminal quickstart in the README)."""
    n = int(os.environ.get("REPRO_NUM_PROCESSES", "1"))
    if n == 1:
        return None
    return DistConfig(os.environ.get("REPRO_COORDINATOR"), n,
                      int(os.environ.get("REPRO_PROCESS_ID", "0")), "env")


_DEFAULT_PORT = 12621


def detect(coordinator: str | None = None, num_processes: int | None = None,
           process_id: int | None = None) -> DistConfig:
    """Resolve the cluster layout: explicit args > SLURM > OpenMPI > env.

    Explicit args must come as a complete set (coordinator + count + id);
    a partial set is an error rather than a silent fallback.
    """
    explicit = [coordinator, num_processes, process_id]
    if any(v is not None for v in explicit):
        if any(v is None for v in explicit):
            raise ValueError(
                "--coordinator, --num-processes and --process-id must be "
                f"given together (got {explicit})")
        return DistConfig(coordinator, num_processes, process_id, "flags")
    for probe in (_from_slurm, _from_ompi, _from_env):
        dcfg = probe()
        if dcfg is not None:
            return dcfg
    return DistConfig()


_INITIALIZED: DistConfig | None = None


def initialize(dcfg: DistConfig | None = None, *,
               local_devices: int | None = None) -> DistConfig:
    """Start the distributed runtime for this process (idempotent).

    Call before the first jax device access. ``local_devices`` forces the
    fake-CPU device count *per process* (tests/CI; a real launch inherits
    the visible accelerators). Single-process configs return immediately —
    the whole module is then dead weight, by design.
    """
    global _INITIALIZED
    dcfg = dcfg or detect()
    if _INITIALIZED is not None:
        assert _INITIALIZED == dcfg, (_INITIALIZED, dcfg)
        return dcfg
    if local_devices:
        _force_local_devices(local_devices, dcfg)
    if not dcfg.is_distributed:
        _INITIALIZED = dcfg
        return dcfg

    from ..compat import enable_cpu_collectives
    import jax
    # The backend can't be probed here — jax.default_backend() would
    # instantiate the runtime before jax.distributed gets to. Select gloo
    # unconditionally: it only affects the CPU client, and a CPU cluster
    # without it forms fine but deadlocks on the first collective.
    enable_cpu_collectives()
    jax.distributed.initialize(coordinator_address=dcfg.coordinator,
                               num_processes=dcfg.num_processes,
                               process_id=dcfg.process_id)
    assert jax.process_count() == dcfg.num_processes, \
        (jax.process_count(), dcfg)
    _INITIALIZED = dcfg
    return dcfg


def _force_local_devices(n: int, dcfg: DistConfig) -> None:
    """Pin this process's fake-CPU device count to its share of the mesh.

    A pre-set XLA_FLAGS with a *different* forced count would silently give
    every process the global count (8 local x 2 procs = 16 global devices,
    then a hung or mis-built mesh), so a conflicting value is an error in
    distributed mode rather than something to quietly keep or override —
    the env was set deliberately and we can't know what else relies on it.
    Single-process, the pre-set env wins (the historical behavior).
    """
    import re
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (flags + " " if flags else "") + \
            f"--xla_force_host_platform_device_count={n}"
        return
    if dcfg.is_distributed and int(m.group(1)) != n:
        raise RuntimeError(
            f"XLA_FLAGS forces {m.group(1)} host devices but this "
            f"{dcfg.num_processes}-process launch needs {n} per process "
            f"(the per-process share of the global mesh). Unset XLA_FLAGS "
            f"or set --xla_force_host_platform_device_count={n}.")


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache for an accelerator run;
    returns its directory. Call after ``initialize`` and before the first
    compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and
    nothing is set here. Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    the cache key and a directory that moves never hits. On the CPU backend
    no cache is set: its compiles take seconds, and XLA:CPU warns about the
    host's machine features whenever it loads a cached entry.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() == "cpu":
        return None
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def process_count() -> int:
    import jax
    return jax.process_count()


def process_index() -> int:
    import jax
    return jax.process_index()


# -- rank heartbeat / stall detection (trace mode) ---------------------------

@dataclass(frozen=True)
class Heartbeat:
    """Per-rank stall detector bound to the live process layout.

    Every collective is a barrier: one slow or dead rank hangs the whole
    cluster with no indication of *which*. In trace mode each process calls
    ``stamp(step)`` before every step (an atomic per-rank file write,
    ``obs.heartbeat``); any process can then call ``report()`` to classify
    every expected rank — ``dead`` (never stamped), ``stalled`` (stamp too
    old), ``behind`` (step trails the cluster max) — instead of the run
    hanging silently. tests/test_multiprocess.py's delayed-rank scenario
    pins the detection.
    """
    directory: str
    rank: int
    n_ranks: int

    def stamp(self, step: int):
        from ..obs import heartbeat as hb
        return hb.stamp(self.directory, self.rank, step)

    def report(self, *, stall_s: float = 30.0) -> dict:
        from ..obs import heartbeat as hb
        return hb.straggler_report(self.directory, self.n_ranks,
                                   stall_s=stall_s)

    def format_report(self, *, stall_s: float = 30.0) -> str:
        from ..obs import heartbeat as hb
        return hb.format_report(self.report(stall_s=stall_s))


def heartbeat(directory) -> Heartbeat:
    """Heartbeat handle for this process (requires a live jax runtime —
    rank/count come from ``jax.process_index``/``process_count``)."""
    return Heartbeat(str(directory), process_index(), process_count())


# -- CLI wiring (launch/train.py, launch/dryrun.py) --------------------------

def add_cli_args(ap) -> None:
    g = ap.add_argument_group(
        "distributed", "multi-process launch (omit all three to autodetect "
        "SLURM / OpenMPI / REPRO_* env, or run single-process)")
    g.add_argument("--coordinator", default=None,
                   help="rank 0 address, host:port")
    g.add_argument("--num-processes", type=int, default=None)
    g.add_argument("--process-id", type=int, default=None)


def from_args(args) -> DistConfig:
    return detect(args.coordinator, args.num_processes, args.process_id)
