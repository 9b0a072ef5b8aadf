"""Structured per-step metrics stream (JSONL) for trained/traced runs.

One JSON object per line per step. Cluster-global scalars (loss, gnorm,
tokens) arrive already reduced through the engine's ``det_psum`` path;
host-only fields (per-phase ms, memory high-water) are per-process, so in
multi-process runs every rank writes its own *lane* — ``<stem>.rank<k>``
suffixed files — and readers merge on ``(step, rank)``. The schema below is
the contract README documents and tests/test_obs.py round-trips; the CI
``obs`` leg gates its field list (not its values) in ``BENCH_obs.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

# every record carries these; absence is a schema violation
REQUIRED_FIELDS = (
    "step", "rank", "loss", "grad_norm", "lr", "tokens",
    "dt_s", "tokens_per_s", "tflops_per_gpu",
    "phase_ms", "overlap_efficiency",
    "memory_hw_bytes", "memory_pred_bytes",
)

# serving runs (serve/scheduler.py) write the same JSONL transport with a
# serving schema: throughput + queue/SLO state per scheduler step. Readers
# auto-detect by the presence of "loss" (train) vs "queue_depth" (serve);
# the CI serve leg gates this field list in BENCH_serve.json
SERVE_REQUIRED_FIELDS = (
    "step", "rank", "tokens", "dt_s", "tokens_per_s",
    "queue_depth", "active_slots",
    "admitted", "rejected", "preempted", "retired", "free_pages",
    "p50_ms", "p99_ms", "phase_ms",
)


def _fields_for(rec: dict) -> tuple[str, ...]:
    return REQUIRED_FIELDS if "loss" in rec else SERVE_REQUIRED_FIELDS


def model_flops_per_token(param_count: int) -> float:
    """Dense-transformer step FLOPs per token: 6·N (fwd 2·N + bwd 4·N) —
    the same accounting as topo.cost.tflops_per_device and
    benchmarks/scaling_model.py (cross-checked in tests/test_obs.py)."""
    return 6.0 * float(param_count)


def tflops_per_gpu(param_count: int, tokens: float, dt_s: float,
                   n_devices: int) -> float:
    """Achieved model-TFLOPS per device for one step: ``tokens`` is the
    cluster-global token count, so divide the FLOP total across devices."""
    if dt_s <= 0.0 or n_devices <= 0:
        return 0.0
    return model_flops_per_token(param_count) * tokens / dt_s / n_devices / 1e12


def lane_path(path, rank: int, n_ranks: int) -> Path:
    """Single-process runs write ``path`` itself; multi-process runs write
    per-rank lanes next to it so no cross-process file contention exists."""
    p = Path(path)
    if n_ranks <= 1:
        return p
    return p.with_name(f"{p.stem}.rank{rank}{p.suffix}")


class MetricsWriter:
    """Append-mode JSONL writer; one instance per process/lane.

    ``fields`` selects the schema contract each record must satisfy:
    ``REQUIRED_FIELDS`` (train, the default) or ``SERVE_REQUIRED_FIELDS``
    (the continuous batcher's per-step stream)."""

    def __init__(self, path, rank: int = 0, n_ranks: int = 1,
                 fields: tuple[str, ...] = REQUIRED_FIELDS):
        self.rank = rank
        self.fields = fields
        self.path = lane_path(path, rank, n_ranks)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._fh = open(self.path, "w")

    def write(self, record: dict) -> dict:
        rec = dict(record)
        rec.setdefault("rank", self.rank)
        missing = [k for k in self.fields if k not in rec]
        if missing:
            raise ValueError(f"metrics record missing fields: {missing}")
        self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
        self._fh.flush()
        return rec

    def close(self):
        self._fh.close()


def read_jsonl(path, fields: tuple[str, ...] | None = None) -> list[dict]:
    """Read one metrics lane, validating the schema per line.

    ``fields=None`` auto-detects train vs serve records per line, so mixed
    tooling (``dryrun --compare``, the calibration loop) reads both."""
    records = []
    for line in Path(path).read_text().splitlines():
        if not line.strip():
            continue
        rec = json.loads(line)
        want = fields if fields is not None else _fields_for(rec)
        missing = [k for k in want if k not in rec]
        if missing:
            raise ValueError(f"{path}: record missing fields: {missing}")
        records.append(rec)
    return records


def read_lanes(path, fields: tuple[str, ...] | None = None) -> list[dict]:
    """Read a metrics stem plus any ``.rank<k>`` lanes, merged and sorted
    by (step, rank)."""
    p = Path(path)
    records = []
    if p.exists():
        records += read_jsonl(p, fields)
    for lane in sorted(p.parent.glob(f"{p.stem}.rank*{p.suffix}")):
        records += read_jsonl(lane, fields)
    return sorted(records, key=lambda r: (r["step"], r["rank"]))


def aggregates(records: list[dict]) -> dict:
    """Run-level throughput summary. The first recorded step is the compile
    step — its dt includes tracing+compilation and would skew every rate —
    so throughput/dt aggregates exclude it (satellite: TrainLog discipline).
    Loss/gnorm means keep all steps."""
    if not records:
        return {}
    steps = sorted({r["step"] for r in records})
    post = [r for r in records if r["step"] != steps[0]] or records
    mean = lambda rows, k: sum(r[k] for r in rows) / len(rows)  # noqa: E731
    return dict(
        n_steps=len(steps),
        n_timed_steps=len(sorted({r["step"] for r in post})),
        loss_mean=mean(records, "loss"),
        grad_norm_mean=mean(records, "grad_norm"),
        dt_s_mean=mean(post, "dt_s"),
        tokens_per_s_mean=mean(post, "tokens_per_s"),
        tflops_per_gpu_mean=mean(post, "tflops_per_gpu"),
    )


def serve_aggregates(records: list[dict]) -> dict:
    """Run-level serving summary from a serve-schema lane: totals from the
    final record's monotone counters, rates excluding the compile step
    (first record), latency percentiles from the last record that saw a
    completion."""
    if not records:
        return {}
    last = records[-1]
    post = records[1:] or records
    tok = sum(r["tokens"] for r in post)
    dt = sum(r["dt_s"] for r in post)
    return dict(
        n_steps=len(records),
        tokens=sum(r["tokens"] for r in records),
        tokens_per_s=(tok / dt if dt > 0 else 0.0),
        admitted=last["admitted"], rejected=last["rejected"],
        preempted=last["preempted"], retired=last["retired"],
        queue_depth_max=max(r["queue_depth"] for r in records),
        p50_ms=last["p50_ms"], p99_ms=last["p99_ms"],
    )


def last_phase_ms(records: list[dict]) -> dict[str, float]:
    """Per-phase ms from the last record that carries a non-empty
    ``phase_ms`` (used by ``launch/dryrun.py --compare``)."""
    for rec in reversed(records):
        if rec.get("phase_ms"):
            return {k: float(v) for k, v in rec["phase_ms"].items()}
    return {}


def memory_high_water() -> int:
    """Peak device-memory bytes across live devices, 0 where the backend
    does not expose memory stats (CPU fake devices)."""
    import jax
    peak = 0
    for d in jax.local_devices():
        ms = d.memory_stats()
        if ms:
            peak = max(peak, int(ms.get("peak_bytes_in_use",
                                        ms.get("bytes_in_use", 0))))
    return peak
