"""Names inside the program: step scopes, loop spans, the compile counter,
and the fenced timers of the phased step.

Always on. ``scope(name)`` is ``jax.named_scope(name)``: it names the
step's phases (``SEGMENTS``, opened in ``core/engine.py``) and the
schedule's issue/wait sites (``core/schedule.py``) in every instruction's
``op_name`` metadata, which profilers (xprof, Perfetto) show beside each
device op. A named scope changes metadata only, never the instructions,
their fusion or their numerics, so the production step stays bitwise the
same (tests/test_obs.py pins it). ``span(name, **args)`` and
``step_span(step)`` are profiler annotations (``TraceAnnotation``,
``StepTraceAnnotation``) on the host's clock, which a profiler trace lines
up with the device's ops; ``Trainer.run`` opens them around each phase of
its loop. With no profiler session active an annotation costs about a
microsecond. ``compile_counter()`` is a process-wide tally of JAX's
compilation work from its monitoring events; ``Trainer.run`` takes its
deltas per step (``TrainLog.compiles`` / ``compile_s``).

Trace mode (``--trace``, DESIGN.md §10) runs the *phased* step
(``obs.phased``): the same math split at the schedule's machine
boundaries into separately-jitted segments, each executed under a
``SpanRecorder.fenced`` timer that blocks until every output is ready
before reading the clock. Fencing changes XLA's fusion boundaries, so
traced losses are only required to agree with the seed step within float
tolerance (tests/_scenarios.py ``obs_trace_equivalence``).
``site_inventory`` re-derives the schedule-site census from a tagged trace
so the obs layer and the static verifier can never disagree about what the
schedule contains.
"""
from __future__ import annotations

import bisect
import json
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

# the step's phases: named scopes of the monolithic step (core/engine.py),
# the fenced segments of the phased step (summing to the traced step's wall
# time, the 10% acceptance bound) and the cost model's phases (topo.cost)
SEGMENTS = ("fwd_bwd", "grad_rs_e", "cross_replica", "gnorm_clip", "update")
# attribution probes (obs.phased.run_probes): serial re-executions of the
# in-loop collectives, measured out-of-band and NOT counted in the wall sum
PROBES = ("fwd", "fwd_allgather", "bwd_allgather", "grad_rs_w",
          "update_gather")


def scope(name: str):
    """``jax.named_scope(name)``: every op traced inside carries ``name``
    in its ``op_name`` path."""
    import jax
    return jax.named_scope(name)


def span(name: str, **args):
    """Host-side profiler annotation named ``name``; ``args`` (the step
    number, say) ride along as the event's stats."""
    import jax
    return jax.profiler.TraceAnnotation(name, **args)


def step_span(step: int):
    """One training step as the profiler's step marker (xprof/TensorBoard
    step view): an annotation named ``train`` with ``step_num=step``."""
    import jax
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


# JAX's compilation phases, each a timed span: tracing to a jaxpr, lowering
# to an MLIR module, and the backend compile, which takes in a fetch from
# the persistent cache (``cache_retrieval_time_sec`` times part of it)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Process-wide tally of compilation work, fed by ``jax.monitoring``.

    ``count``: executables compiled or fetched from the persistent cache
    (one backend-compile event each); ``cache_hits`` / ``cache_misses``:
    the persistent cache's; ``seconds``: wall time inside any of
    ``COMPILE_EVENTS``, as the union of their spans, so a jit traced inside
    another's trace counts once."""

    def __init__(self):
        self.count = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.seconds = 0.0
        self._spans: list[tuple[float, float]] = []   # disjoint, by start
        self._lock = threading.Lock()

    def reading(self) -> tuple[int, float, int, int]:
        """(count, seconds, cache_hits, cache_misses) so far."""
        return self.count, self.seconds, self.cache_hits, self.cache_misses

    def _span(self, name, start, end, **_):
        if name not in COMPILE_EVENTS:
            return
        with self._lock:
            if name == COMPILE_EVENTS[-1]:
                self.count += 1
            spans = self._spans
            i = bisect.bisect_left(spans, (start,))
            if i and spans[i - 1][1] >= start:
                i -= 1
            j = i
            while j < len(spans) and spans[j][0] <= end:
                start, end = min(start, spans[j][0]), max(end, spans[j][1])
                self.seconds -= spans[j][1] - spans[j][0]
                j += 1
            spans[i:j] = [(start, end)]
            self.seconds += end - start

    def _event(self, name, **_):
        with self._lock:
            if name == CACHE_HITS:
                self.cache_hits += 1
            elif name == CACHE_MISSES:
                self.cache_misses += 1


_COUNTER: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    """The process's one ``CompileCounter``, registered with JAX on first
    use; it sees every compilation from then on."""
    global _COUNTER
    if _COUNTER is None:
        import jax
        c = CompileCounter()
        jax.monitoring.register_event_time_span_listener(c._span)
        jax.monitoring.register_event_listener(c._event)
        _COUNTER = c
    return _COUNTER


@dataclass
class Span:
    name: str
    t0: float        # process-relative seconds (time.perf_counter)
    dur: float       # seconds
    step: int = -1


@dataclass
class SpanRecorder:
    """Collects fenced spans; one recorder spans a whole traced run (the
    ``step`` attribute is bumped per step so Chrome export can lane them)."""
    step: int = -1
    spans: list[Span] = field(default_factory=list)

    def fenced(self, name: str, fn, *args):
        """Run ``fn(*args)``, block until every output is device-ready, and
        record the wall duration as one span. The fence is the point of the
        phased step: without it XLA's async dispatch would attribute every
        phase's time to whichever call finally blocks."""
        import jax
        t0 = time.perf_counter()
        with span(name, step=self.step):
            out = fn(*args)
            jax.block_until_ready(out)
        self.spans.append(Span(name, t0, time.perf_counter() - t0, self.step))
        return out

    def timed(self, name: str, seconds: float):
        """Record an externally-measured duration (probe aggregates)."""
        self.spans.append(Span(name, time.perf_counter() - seconds,
                               seconds, self.step))

    def step_seconds(self, step: int) -> dict[str, float]:
        """Per-name summed seconds for one step."""
        out: dict[str, float] = {}
        for s in self.spans:
            if s.step == step:
                out[s.name] = out.get(s.name, 0.0) + s.dur
        return out

    def chrome_events(self, rank: int = 0) -> list[dict]:
        """Chrome/Perfetto ``traceEvents`` (complete events, us units):
        pid = rank, tid = span name, args carry the step index."""
        return [dict(name=s.name, ph="X", ts=s.t0 * 1e6, dur=s.dur * 1e6,
                     pid=rank, tid=s.name, args={"step": s.step})
                for s in self.spans]


def write_chrome_trace(events: list[dict], path) -> str:
    """Write a chrome://tracing / Perfetto-loadable trace.json."""
    Path(path).write_text(json.dumps(
        {"traceEvents": events, "displayTimeUnit": "ms"}))
    return str(path)


def site_inventory(step_fn, *abstract_args) -> dict[str, int]:
    """Schedule-site census of a traced step: ``{machine/role: count}`` of
    every contract-tag site, by tracing under ``analysis.tags.tagging()``
    and counting tag primitives — the same counter the static verifier's
    census uses (``analysis.dataflow``), so the two inventories are equal by
    construction (tests/test_obs.py pins it)."""
    import jax

    from ..analysis import tags
    from ..analysis.dataflow import _count_tags
    with tags.tagging():
        jx = jax.make_jaxpr(step_fn)(*abstract_args)
    return {k: int(v) for k, v in sorted(_count_tags(jx.jaxpr).items())}


@dataclass
class TraceConfig:
    """Opt-in runtime tracing for Trainer.run (launch/train.py ``--trace``).

    ``probe_every``: cadence (in steps) of the serial comm-attribution
    probes; 0 disables them. ``heartbeat_dir`` enables the per-rank stall
    detector (obs.heartbeat via launch.distributed.heartbeat). Trace mode is
    excluded from the bitwise contract (DESIGN.md §10) — with ``trace=None``
    the Trainer runs the untouched monolithic step.
    """
    metrics_path: str | None = None     # JSONL stream (obs.metrics)
    chrome_trace: str | None = None     # trace.json written at end of run
    heartbeat_dir: str | None = None    # per-rank heartbeat files
    probe_every: int = 4                # 0 = never run attribution probes
