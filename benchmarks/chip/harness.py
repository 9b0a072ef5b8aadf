"""One run of one cell: set-up, the measured window, the per-layer reading
of a traced window, and the check against the plain reference.

Set-up builds the program and its state from the seed, then drives it
through the first ``checked_steps`` steps with the window's own call
(``Trainer.run``) and feed; those steps compile the step and warm it up,
and their losses, first gradient and parameter change are what the
reference is compared with. The window is one ``Trainer.run(state, n)``
call on the same trainer and state, ``n`` sized from the warm-up steps to
fill ``--seconds``. After it the peak memory is read, the program's state
is freed and the reference runs in its place.
"""
from __future__ import annotations

import math
import os
import shutil
import statistics
import sys
import time
from contextlib import ExitStack

import jax
import numpy as np

from . import catalog, check, flops, program, weights, xtrace
from .traffic import TokenStream


class NoChip(Exception):
    """No TPU, or fewer chips than the cell asks for."""


def devices_for(chips: int, require_tpu: bool = True):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts backend compilations while active."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_):
        if self._on and name == self.EVENT:
            self.count += 1

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False


def use_compile_cache(root) -> None:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed path ``<checkout>/.jax_cache``; every program,
    however short its compile, is kept, so later runs compile nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def program_readings(tr, table: dict, seed: int, traffic: dict, state):
    """Drive the first checked steps through ``tr.run``; return the state
    and what the check compares: each step's loss, each leaf's first
    gradient (Adam's first moment after step 1, over 1 - beta1) and each
    leaf's change over the checked steps."""
    k = traffic["checked_steps"]
    b1 = traffic["hparams"]["betas"][0]
    start = len(tr.log.losses)
    state = tr.run(state, 1, log_every=0)
    grad1 = weights.leaf_norms(state["opt_m"], 1.0 / (1.0 - b1))
    state = tr.run(state, k - 1, log_every=0)
    change = weights.change_norms(state["master"], table, seed)
    return state, dict(losses=tr.log.losses[start:start + k], grad1=grad1,
                       change=change)


def free(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def reference_mesh(devices):
    from jax.sharding import Mesh
    return Mesh(np.array(devices), ("b",)) if len(devices) > 1 else None


def reference_readings(c: dict, table: dict, seed: int, devices, *,
                       precision: str = "f32", rows: int | None = None):
    from .reference import common
    cfg, traffic = c["config"], c["traffic"]
    stream = TokenStream.for_traffic(traffic, cfg["vocab_size"], seed)
    batches = [stream.batch(i) for i in range(traffic["checked_steps"])]
    with jax.default_matmul_precision("highest"):
        return common.train_readings(
            catalog.reference(cfg["reference"]), cfg, table, seed, batches,
            traffic["hparams"], precision=precision, rows=rows,
            mesh=reference_mesh(devices))


def peak_bytes(devices) -> int:
    """The fullest chip's high-water mark: its peak of live buffers plus
    its peak reservation for compiled programs' temporaries, which the TPU
    runtime holds apart from ``peak_bytes_in_use``."""
    def one(d):
        st = d.memory_stats() or {}
        return int(st.get("peak_bytes_in_use", 0)) \
            + int(st.get("peak_bytes_reserved", 0))
    return max(one(d) for d in devices)


def device_info(devices) -> dict:
    d = devices[0]
    return dict(platform=d.platform, kind=d.device_kind,
                count=len(jax.devices()))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: dict | None = None,
             require_tpu: bool = True, trace_dir=None,
             base=catalog.HERE, root=catalog.ROOT) -> dict:
    """The result line's fields for one run (see run.py). ``base`` and
    ``root`` say where the cell's files are (tests point them elsewhere)."""
    bench = bench or catalog.load_benchmark(root)
    c = catalog.cell(name, bench, base, root)
    devices = devices_for(c["chips"], require_tpu)
    cfg, traffic = c["config"], c["traffic"]
    table = catalog.reference(cfg["reference"]).param_table(cfg)
    prog = program.build(cfg, traffic, devices)
    weights.check_layout(table, prog.engine.specs)
    data = TokenStream.for_traffic(traffic, cfg["vocab_size"], seed)
    tr = program.trainer(prog, data)

    state = weights.program_state(prog.engine.abstract_state(), table, seed)
    state, mine = program_readings(tr, table, seed, traffic, state)
    k = traffic["checked_steps"]
    step_s = statistics.median(tr.log.step_times[1:k])
    n = max(1, round(seconds / step_s))
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; warm-up steps {tr.log.step_times[:k]}; "
        f"window of {n} steps")

    compiles = CompileCounter()
    before = len(tr.log.step_times)
    with ExitStack() as stack:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            stack.enter_context(jax.profiler.trace(str(trace_dir)))
        stack.enter_context(compiles)
        stack.enter_context(jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN))
        t0 = time.perf_counter()
        state = tr.run(state, n, log_every=0)
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    step_times = tr.log.step_times[before:]
    losses = tr.log.losses
    peak = peak_bytes(devices)
    log(f"memory_stats of chip 0 after the window: {devices[0].memory_stats()}")
    print(f"window: {n} steps in {window_s:.6f} s, compilations inside it: "
          f"{compiles.count}", flush=True)

    tokens_per_step = traffic["global_batch"] * traffic["seq_len"]
    peaks = catalog.load_json("peaks.json")
    kind = devices[0].device_kind
    ctx = dict(window_s=window_s, n_steps=n, step_times=step_times,
               tokens_per_step=tokens_per_step, chips=c["chips"],
               flops_per_token=flops.model_flops_per_token(
                   cfg, table, traffic["seq_len"]),
               peaks=peaks.get(kind))
    out = dict(device=dict(device_info(devices), memory_peak_bytes=peak))
    if trace:
        if ctx["peaks"] is None:
            raise ValueError(f"device kind {kind!r} is not in peaks.json")
        from repro.data.pipeline import shard_batch
        batch = shard_batch(data.batch(0), prog.mesh, tr.bspecs)
        hlo = tr.step_fn.lower(state, batch).compile().as_text()
        rec = xtrace.load(xtrace.find_xplane(str(trace_dir)))
        ctx["devices"] = xtrace.device_summary(rec)
        ctx["kernels"] = xtrace.kernel_summary(
            rec, xtrace.custom_calls(hlo), catalog.load_json("kernels.json"),
            ctx["peaks"], flops.WORK)
        dev = ctx["devices"]
        busy = [ch["busy_ns"] for ch in dev["chips"].values()]
        out["device"].update(busy_s=sum(busy) / len(busy) * 1e-9,
                             window_s=dev["window_ns"] * 1e-9)
        out["breakdown"] = xtrace.breakdown(rec)
        metrics = {}
        for m in c["per_layer"]:
            v = catalog.metric_reader(m["name"], base)(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        values = dict(
            tokens_per_s_per_chip=tokens_per_step * n / window_s / c["chips"],
            peak_hbm_gb=peak / 1e9, setup_s=setup_s)
        metrics = {m["name"]: dict(value=values[m["name"]], unit=m["unit"])
                   for m in c["end_to_end"]}

    free(state)
    del state, tr
    ref = reference_readings(c, table, seed, devices)
    nums = check.numbers(mine, ref)
    ok, report = check.decide(nums, c["limits"])
    log(f"program losses {mine['losses']}, reference {ref['losses']}")
    log(f"worst gradient leaf {nums['worst_grad_leaf']}, worst change leaf "
        f"{nums['worst_update_leaf']}, left out of the change "
        f"{nums['left_out']}")
    bad_steps = sum(1 for x in losses if not math.isfinite(x))
    out.update(correct=bool(ok) and bad_steps == 0,
               attempted=len(losses), failed=bad_steps + (0 if ok else k),
               metrics=metrics)
    out["check"] = report
    return out
