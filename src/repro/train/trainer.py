"""Training loop: data -> sharded batches -> engine train_step -> logs/ckpt.

The loop is deliberately thin — all distribution logic lives in
``ZeroEngine.make_train_step`` — but it is the piece a real run launches:
deterministic data, periodic eval, checkpointing, throughput accounting and
a modeled-TFLOPS report (6·N·D / step-time; on CPU wall-time is meaningless,
on TPU this is the paper's TFLOPS-per-GPU metric).

Every step runs inside a profiler step marker (``obs.spans.step_span``)
whose children name the loop's phases: ``train.data`` (next batch),
``train.shard`` (placing it), ``train.dispatch`` (the step call),
``train.wait`` (``block_until_ready``), ``train.fetch`` (metrics and the step
counter to the host), ``train.log`` and ``train.ckpt``. They cost about a
microsecond each with no profiler session active; under one
(``jax.profiler.trace``) they share the device ops' clock. The process's
compile counter (``obs.spans.compile_counter``) gives each step its
``TrainLog.compiles`` / ``compile_s``, and a step after a run's first that
compiles prints one line.

Trace mode (``TraceConfig``, DESIGN.md §10): the loop swaps the monolithic
step for the phased one (``obs.phased.PhasedStep`` — same math, fenced per
phase), streams a per-step JSONL metrics record (``obs.metrics``), stamps
per-rank heartbeats (``obs.heartbeat``) and can export the collected spans
as a Chrome/Perfetto trace. With ``trace=None`` the monolithic step runs.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax

from ..core.engine import TrainHparams, ZeroEngine, host_scalar
from ..data.pipeline import BatchSpec, SyntheticTokens, shard_batch, spec_for
from ..models.config import ArchConfig, ShapeConfig
from ..models.registry import ModelDef, batch_axes
from ..obs import heartbeat as obs_heartbeat
from ..obs import metrics as obs_metrics
from ..obs import spans
from ..obs.spans import SpanRecorder, TraceConfig, write_chrome_trace
from . import checkpoint


def _host_int(x) -> int:
    """Scalar fetch that works on multi-process (replicated) arrays too."""
    return int(host_scalar(x))


@dataclass
class TrainLog:
    steps: list[int] = field(default_factory=list)
    losses: list[float] = field(default_factory=list)
    grad_norms: list[float] = field(default_factory=list)
    step_times: list[float] = field(default_factory=list)
    lrs: list[float] = field(default_factory=list)
    tokens: list[float] = field(default_factory=list)
    tokens_per_s: list[float] = field(default_factory=list)
    tflops_per_gpu: list[float] = field(default_factory=list)
    compiles: list[int] = field(default_factory=list)
    compile_s: list[float] = field(default_factory=list)
    meta: dict = field(default_factory=dict)   # scheme/overlap/mesh, for A/Bs

    def record(self, step, metrics, dt, *, tokens_per_s: float = 0.0,
               tflops_per_gpu: float = 0.0, compiles: int = 0,
               compile_s: float = 0.0):
        """Persist the FULL metrics dict the step emits, not just
        loss/gnorm — lr and token counts are what make two logs comparable
        after the fact. ``compiles`` / ``compile_s``: what the process
        compiled during the step's iteration (``obs.spans.CompileCounter``)."""
        self.steps.append(_host_int(step))
        self.losses.append(float(metrics["loss"]))
        self.grad_norms.append(float(metrics["grad_norm"]))
        self.step_times.append(dt)
        self.lrs.append(float(metrics.get("lr", 0.0)))
        self.tokens.append(float(metrics.get("tokens", 0.0)))
        self.tokens_per_s.append(tokens_per_s)
        self.tflops_per_gpu.append(tflops_per_gpu)
        self.compiles.append(compiles)
        self.compile_s.append(compile_s)

    def aggregates(self) -> dict:
        """Run summary. The first recorded step's dt includes trace+compile
        time, so every throughput/dt aggregate EXCLUDES it (a one-step run
        has nothing else to offer and keeps its only sample). Loss/gnorm
        means keep all steps."""
        if not self.steps:
            return {}
        timed = slice(1, None) if len(self.steps) > 1 else slice(None)

        def mean(xs):
            return sum(xs) / len(xs) if xs else 0.0

        return dict(
            n_steps=len(self.steps),
            n_timed_steps=len(self.step_times[timed]),
            loss_mean=mean(self.losses),
            grad_norm_mean=mean(self.grad_norms),
            dt_s_mean=mean(self.step_times[timed]),
            tokens_per_s_mean=mean(self.tokens_per_s[timed]),
            tflops_per_gpu_mean=mean(self.tflops_per_gpu[timed]),
        )

    def save(self, path):
        payload = dict(self.__dict__)
        payload["aggregates"] = self.aggregates()
        Path(path).write_text(json.dumps(payload))


class Trainer:
    def __init__(self, model: ModelDef, engine: ZeroEngine, mesh,
                 shape: ShapeConfig, *, seed: int = 0,
                 data=None, trace: TraceConfig | None = None):
        self.model = model
        self.engine = engine
        self.mesh = mesh
        self.shape = shape
        self.trace = trace
        self.baxes = batch_axes(
            mesh, shape.global_batch,
            candidates=tuple(a for a in mesh.axis_names if a != "pod"))
        shapes = model.train_batch_shapes(shape)
        self.bspecs = model.batch_pspecs(shapes, self.baxes)
        self.step_fn = engine.make_train_step(model.loss_fn(), self.bspecs)
        self.data = data or SyntheticTokens(spec_for(model.arch, shape),
                                            seed=seed)
        spans.compile_counter()         # counts from here on, process-wide
        # a profile names the step's phases by its ops' op_name metadata,
        # which JAX leaves out of the persistent cache's key by default: a
        # hit could then hand back an executable compiled from another
        # version of the step, carrying that version's names
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        self.log = TrainLog(meta=dict(
            arch=model.arch.name, scheme=engine.cfg.name,
            overlap=engine.cfg.overlap, mesh=dict(mesh.shape),
            traced=trace is not None))

    def _shard_batch(self, np_batch):
        # process-aware: each process feeds only its addressable shards from
        # the deterministic global batch (pipeline.shard_batch)
        return shard_batch(np_batch, self.mesh, self.bspecs)

    def run(self, state, n_steps: int, *, log_every: int = 10,
            ckpt_dir: str | None = None, ckpt_every: int = 0,
            print_fn=print):
        n_params = self.engine.param_count()
        n_dev = int(self.mesh.devices.size)
        tokens_per_step = self.shape.global_batch * self.shape.seq_len
        mem_pred = self.engine.memory_report()["total"]
        rank, n_ranks = jax.process_index(), jax.process_count()

        trace = self.trace
        rec = writer = phased = None
        if trace is not None:
            from ..obs.phased import PhasedStep
            rec = SpanRecorder()
            phased = PhasedStep(self.engine, self.model.loss_fn(),
                                self.bspecs)
            if trace.metrics_path:
                writer = obs_metrics.MetricsWriter(
                    trace.metrics_path, rank=rank, n_ranks=n_ranks)

        compiles = spans.compile_counter()
        step = _host_int(state["step"])
        it = iter(self.data)
        for i in range(n_steps):
            before = compiles.reading()
            k = step + 1                 # the step this iteration makes
            with spans.step_span(k):
                with spans.span("train.data", step=k):
                    np_batch = next(it)
                with spans.span("train.shard", step=k):
                    batch = self._shard_batch(np_batch)
                if trace is not None and trace.heartbeat_dir:
                    obs_heartbeat.stamp(trace.heartbeat_dir, rank, i)
                t0 = time.time()
                if phased is not None:
                    rec.step = i
                    state, metrics = phased(state, batch, rec)
                    dt = time.time() - t0    # segments are fenced: dt is wall
                    if trace.probe_every and i % trace.probe_every == 0:
                        phased.run_probes(state, batch, rec)
                else:
                    with spans.span("train.dispatch", step=k):
                        state, metrics = self.step_fn(state, batch)
                    with spans.span("train.wait", step=k):
                        jax.tree.map(lambda x: x.block_until_ready(), metrics)
                    dt = time.time() - t0
                with spans.span("train.fetch", step=k):
                    # metrics are cluster-global (psum over all axes inside
                    # the step); this fetch works on every process of a
                    # multi-host run
                    metrics = self.engine.metrics_to_host(metrics)
                    step = _host_int(state["step"])
                toks = metrics.get("tokens") or float(tokens_per_step)
                tps = toks / dt if dt > 0 else 0.0
                tfl = obs_metrics.tflops_per_gpu(n_params, toks, dt, n_dev)
                if writer is not None:
                    with spans.span("train.log", step=k):
                        phase = phased.phase_seconds(rec, i)
                        writer.write(dict(
                            step=step, rank=rank,
                            loss=metrics["loss"],
                            grad_norm=metrics["grad_norm"],
                            lr=metrics["lr"], tokens=toks, dt_s=dt,
                            tokens_per_s=tps, tflops_per_gpu=tfl,
                            phase_ms={p: v * 1e3 for p, v in phase.items()},
                            overlap_efficiency=phased.overlap_efficiency(
                                rec, i),
                            memory_hw_bytes=obs_metrics.memory_high_water(),
                            memory_pred_bytes=mem_pred,
                        ))
                if log_every and i % log_every == 0:
                    with spans.span("train.log", step=k):
                        tflops = 6.0 * n_params * tokens_per_step / dt / 1e12
                        print_fn(f"step {step:5d} "
                                 f"loss {metrics['loss']:.4f} "
                                 f"gnorm {metrics['grad_norm']:.3f} "
                                 f"lr {metrics['lr']:.2e} "
                                 f"{dt:.2f}s/step  "
                                 f"model-TFLOPS(total) {tflops:.2f}")
                if ckpt_dir and ckpt_every and (i + 1) % ckpt_every == 0:
                    with spans.span("train.ckpt", step=k):
                        checkpoint.save(
                            state, ckpt_dir, step,
                            scheme=self.engine.scheme_fingerprint())
            n_c, s_c, hits, misses = (
                now - was for now, was in zip(compiles.reading(), before))
            self.log.record(step, metrics, dt, tokens_per_s=tps,
                            tflops_per_gpu=tfl, compiles=n_c, compile_s=s_c)
            if i and n_c:
                print_fn(f"step {step}: recompiled ({n_c} compilations; "
                         f"persistent cache: {hits} hits, {misses} misses; "
                         f"{s_c:.2f} s of tracing, lowering and compiling)")
        if trace is not None:
            if trace.heartbeat_dir:
                obs_heartbeat.stamp(trace.heartbeat_dir, rank, n_steps)
            if trace.chrome_trace:
                write_chrome_trace(rec.chrome_events(rank=rank),
                                   trace.chrome_trace)
        if writer is not None:
            writer.close()
        return state

    def restore(self, ckpt_dir, step: int | None = None, *,
                reshard: bool = True):
        """Restore a checkpoint into this trainer's engine layout.

        ``reshard=True`` (default): a checkpoint written under a different
        mesh/process layout or partition scheme is resharded onto this
        engine through the partition formulas (checkpoint.py, DESIGN.md
        §11) — this is what makes ``--resume`` elastic. ``reshard=False``
        restores strictly, failing loudly (checkpoint.SchemeMismatch /
        MeshMismatch) on any layout difference.
        """
        step = checkpoint.latest_step(ckpt_dir) if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
        return checkpoint.restore(ckpt_dir, step,
                                  self.engine.state_shardings(),
                                  expect_scheme=self.engine.scheme_fingerprint(),
                                  reshard=reshard)
