"""Serving load generator: the INT8-resident decode vs the seed
fp-materialized gather, under a request storm with SLO admission.

Two phases on the same reduced model over 8 fake devices:

* **throughput** — the same request stream through two continuous batchers:
  ``gathered`` over an unquantized engine (per-token compute-dtype weight
  all-gather + dense matmul — the seed fp-materialized serving path) and
  ``resident`` over the INT8 wire residency (per-token INT8 re-gather into
  the fused ``dequant_matmul``, built once from the training engine's
  shards). Decode-rate wall-clock is *recorded* for trend inspection and the
  run asserts resident >= gathered before emitting, but never baseline-gated.

* **storm** — >= 1000 queued requests against a few slots under a
  step-count SLO (``ServeSLO.max_queue_steps``) with an oversubscribed page
  pool. Admission / rejection / preemption / retirement counts depend only
  on deterministic step arithmetic, so they ARE gated, alongside the pool
  geometry, the serve JSONL schema, and the fused-dispatch proof
  (``ops.dispatch_counters`` shows the resident decode traced
  ``dequant_matmul``). p50/p99 latency is reported, not gated.

    PYTHONPATH=src python -m benchmarks.serve_load          # full storm
    PYTHONPATH=src python -m benchmarks.serve_load --quick  # CI leg

CPU only for now: it forces 8 fake CPU devices through XLA_FLAGS, and its
wall-clock numbers are CPU numbers, not device metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.engine import TrainHparams, ZeroEngine  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.mesh import make_test_mesh, scheme_config  # noqa: E402
from repro.models.registry import build_model, get_arch  # noqa: E402
from repro.obs.metrics import (SERVE_REQUIRED_FIELDS, MetricsWriter,  # noqa: E402
                               read_jsonl, serve_aggregates)
from repro.serve.resident import build_resident  # noqa: E402
from repro.serve.scheduler import ContinuousBatcher, Request, ServeSLO  # noqa: E402

AX = ("data", "node", "gcd")
N_SLOTS = 4
PROMPT_LEN = 8
MAX_LEN = 32
PAGE = 8
MAX_NEW = 6


def _bench_path() -> Path:
    return Path(os.environ.get("REPRO_BENCH_DIR", ".")) / "BENCH_serve.json"


def _setup(mesh):
    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    cfg_q = scheme_config("zero_topo", mesh, quant_block=64)
    cfg_fp = dataclasses.replace(
        cfg_q, quantize_weights=False, quantize_grads=False,
        axes=dataclasses.replace(cfg_q.axes, secondary=None))
    cfg_fp.validate_dependency_rule()
    return arch, model, cfg_q, cfg_fp


def _requests(arch, n, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, arch.vocab,
                                        PROMPT_LEN).astype(np.int32),
                    max_new=MAX_NEW) for i in range(n)]


def _run_backend(model, eng, mesh, params, *, backend, res_axes, arch,
                 n_requests, metrics_path, slo=None, n_pages=0,
                 seed=0) -> dict:
    mw = MetricsWriter(metrics_path, fields=SERVE_REQUIRED_FIELDS)
    cb = ContinuousBatcher(model, eng, mesh, n_slots=N_SLOTS,
                           max_len=MAX_LEN, prompt_len=PROMPT_LEN,
                           page_size=PAGE, n_pages=n_pages, slo=slo,
                           backend=backend, res_axes=res_axes, metrics=mw)
    cb.run(params, _requests(arch, n_requests, seed), max_steps=5000)
    mw.close()
    agg = serve_aggregates(read_jsonl(metrics_path))
    agg["counters"] = dict(cb.counters)
    agg["steps"] = cb.step_count
    agg["pool"] = dict(page_size=cb.paged.page_size,
                       n_pages=cb.paged.n_pages,
                       blocks_per_slot=cb.paged.blocks_per_slot)
    agg.update(cb.latency_percentiles())
    return agg


def run(print_fn=print, quick: bool = False) -> bool:
    mesh = make_test_mesh(shape=(2, 2, 2), axes=AX)
    arch, model, cfg_q, cfg_fp = _setup(mesh)
    # the storm census is baseline-gated, so its size is FIXED across
    # quick/full modes; --quick only shrinks the (ungated) throughput phase
    n_storm = 1000
    tmp = Path(tempfile.mkdtemp(prefix="serve_load_"))

    # seed fp-materialized path: unquantized engine, per-token fp gathers
    eng_fp = ZeroEngine(model.leaf_specs(), cfg_fp, mesh, TrainHparams())
    state_fp = eng_fp.init_state(jax.random.key(0))
    # INT8 wire residency from the quantized training engine's shards
    eng_q = ZeroEngine(model.leaf_specs(), cfg_q, mesh, TrainHparams())
    state_q = eng_q.init_state(jax.random.key(0))
    layout, residency = build_resident(eng_q, state_q, mesh)
    print_fn(f"residency: axes={layout.res_axes} degree={layout.res_degree} "
             f"wire={layout.memory_report()['wire_bytes']}B/device")

    # -- throughput: same stream, both backends (wall-clock, never gated) --
    # best-of-2 per backend: the first pass of each pays its jit compiles
    # and OS noise, so a single sample is ratio-flaky at this reduced size
    n_tp = 24 if quick else 48

    def _best_of(eng, params, *, backend, res_axes, tag):
        runs = [_run_backend(model, eng, mesh, params, backend=backend,
                             res_axes=res_axes, arch=arch, n_requests=n_tp,
                             metrics_path=tmp / f"{tag}{rep}.jsonl")
                for rep in range(2)]
        return max(runs, key=lambda a: a["tokens_per_s"])

    tp_fp = _best_of(eng_fp, state_fp["primaries"],
                     backend="gathered", res_axes=None, tag="fp")
    before = dict(ops.dispatch_counters())
    tp_res = _best_of(eng_q, residency,
                      backend="resident", res_axes=layout.res_axes,
                      tag="res")
    fused = {k: v - before.get(k, 0) for k, v in
             ops.dispatch_counters().items()
             if k.startswith("dequant_matmul/") and v > before.get(k, 0)}
    print_fn(f"throughput ({n_tp} reqs, {N_SLOTS} slots): "
             f"gathered-fp {tp_fp['tokens_per_s']:.1f} tok/s, "
             f"resident-int8 {tp_res['tokens_per_s']:.1f} tok/s "
             f"({tp_res['tokens_per_s'] / max(tp_fp['tokens_per_s'], 1e-9):.2f}x)"
             )
    print_fn(f"resident fused dispatch: {fused}")
    assert fused, "resident decode never traced ops.dequant_matmul"
    assert tp_res["tokens_per_s"] >= tp_fp["tokens_per_s"], \
        (tp_res["tokens_per_s"], tp_fp["tokens_per_s"],
         "INT8-resident decode must beat the fp-materialized gather")
    assert tp_fp["retired"] == n_tp and tp_res["retired"] == n_tp

    # -- storm: SLO admission under >= 1000 queued requests (gated census) --
    storm = _run_backend(
        model, eng_q, mesh, residency, backend="resident",
        res_axes=layout.res_axes, arch=arch, n_requests=n_storm,
        metrics_path=tmp / "storm.jsonl",
        slo=ServeSLO(max_queue_steps=6, reserve_pages=1),
        # 4 slots x 1 prompt page admit fine, but each slot needs a 2nd
        # page mid-decode: 6 pages can't hold 4x2, forcing preemption
        n_pages=6, seed=1)
    c = storm["counters"]
    print_fn(f"storm ({n_storm} queued): admitted {c['admitted']}, "
             f"rejected {c['rejected']}, preempted {c['preempted']}, "
             f"retired {c['retired']} in {storm['steps']} steps; "
             f"p50 {storm['p50_ms']:.1f}ms p99 {storm['p99_ms']:.1f}ms")
    assert c["rejected"] > 0, "storm must exercise SLO rejection"
    assert c["preempted"] > 0, "storm must exercise page preemption"
    # every request ends exactly once; every admission ends exactly once
    assert c["rejected"] + c["retired"] == n_storm, c
    assert c["admitted"] == c["retired"] + c["preempted"], c

    rec = dict(
        model=arch.name, scheme="zero_topo",
        n_slots=N_SLOTS, prompt_len=PROMPT_LEN, max_len=MAX_LEN,
        residency=dict(axes=list(layout.res_axes),
                       degree=layout.res_degree,
                       wire_bytes=layout.memory_report()["wire_bytes"]),
        pool=storm["pool"],
        slo=dict(max_queue_steps=6, reserve_pages=1),
        storm=dict(n_requests=n_storm, steps=storm["steps"], **c),
        dispatch=dict(resident_dequant_matmul=bool(fused)),
        jsonl_schema=dict(serve_fields=list(SERVE_REQUIRED_FIELDS)),
        # wall-clock trend fields (recorded, never gated)
        throughput=dict(
            gathered_fp_tokens_per_s=tp_fp["tokens_per_s"],
            resident_tokens_per_s=tp_res["tokens_per_s"],
            speedup=tp_res["tokens_per_s"] / max(tp_fp["tokens_per_s"],
                                                 1e-9),
            storm_p50_ms=storm["p50_ms"], storm_p99_ms=storm["p99_ms"]),
    )
    _bench_path().write_text(json.dumps(rec, indent=1))
    print_fn(f"wrote {_bench_path()}")
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized storm (1000 queued requests)")
    args = ap.parse_args()
    run(quick=args.quick)


if __name__ == "__main__":
    main()
