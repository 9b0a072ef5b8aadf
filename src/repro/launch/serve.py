"""Serving launcher: continuous batching over the paged KV pool.

Two weight backends share the scheduler (DESIGN.md §12): ``gathered``
re-gathers fp weights per decoded token (the seed serving path) and
``resident`` serves from the INT8 wire residency built once from the
training engine's shards.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --backend resident --requests 16 --devices 8
    PYTHONPATH=src python -m repro.launch.serve --n-pages 6 \
        --max-queue-steps 8 --requests 64        # oversubscribed + SLO

CPU only for now: it forces fake CPU devices through XLA_FLAGS and serves
the reduced model on the 8-device test mesh; it has not run on a TPU.
"""
import argparse
import os


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Continuous-batching serving demo: paged KV pool + "
                    "SLO admission over the gathered or INT8-resident "
                    "weight backend")
    ap.add_argument("--arch", default="qwen2-0.5b",
                    help="registered architecture (reduced for CPU)")
    ap.add_argument("--scheme", default="zero_topo")
    ap.add_argument("--devices", type=int, default=8,
                    help="fake CPU device count (XLA_FLAGS)")
    ap.add_argument("--quant-block", type=int, default=128)
    ap.add_argument("--backend", default="gathered",
                    choices=("gathered", "resident"),
                    help="weight path: fp re-gather per token, or the INT8 "
                         "wire residency")
    ap.add_argument("--res-axes", default="",
                    help="comma-separated residency axes (resident backend; "
                         "default: the scheme's secondary partition)")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of random requests to queue")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent decode slots")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64,
                    help="per-slot KV provisioning length")
    ap.add_argument("--gen", type=int, default=16,
                    help="max new tokens per request")
    ap.add_argument("--page-size", type=int, default=0,
                    help="KV page size in tokens (0 = auto)")
    ap.add_argument("--n-pages", type=int, default=0,
                    help="KV pool pages (0 = fully provisioned; fewer "
                         "oversubscribes and triggers preemption)")
    ap.add_argument("--max-queue-steps", type=int, default=0,
                    help="SLO: reject requests queued longer than N "
                         "scheduler steps (0 = never)")
    ap.add_argument("--reserve-pages", type=int, default=0,
                    help="SLO: keep N pages free when admitting")
    ap.add_argument("--metrics-jsonl", default="",
                    help="write per-step serving metrics (obs JSONL "
                         "schema; feeds dryrun --compare)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main():
    args = build_parser().parse_args()

    if "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={args.devices}"

    import time

    import jax
    import numpy as np
    from ..core.engine import TrainHparams, ZeroEngine
    from ..models.registry import build_model, get_arch
    from ..obs.metrics import SERVE_REQUIRED_FIELDS, MetricsWriter
    from ..serve.scheduler import ContinuousBatcher, Request, ServeSLO
    from .mesh import make_test_mesh, scheme_config

    mesh = make_test_mesh()
    arch = get_arch(args.arch).reduced()
    model = build_model(arch)
    cfg = scheme_config(args.scheme, mesh, quant_block=args.quant_block)
    eng = ZeroEngine(model.leaf_specs(), cfg, mesh, TrainHparams())
    state = eng.init_state(jax.random.key(0))

    res_axes = None
    if args.backend == "resident":
        from ..serve.resident import build_resident
        want = tuple(a for a in args.res_axes.split(",") if a) or None
        layout, params = build_resident(eng, state, mesh, want)
        res_axes = layout.res_axes
        rep = layout.memory_report()
        print(f"residency: axes={rep['res_axes']} degree={rep['res_degree']} "
              f"wire={rep['wire_bytes']}B dense={rep['dense_bytes']}B "
              f"per device")
    else:
        params = state["primaries"]

    metrics = MetricsWriter(args.metrics_jsonl,
                            fields=SERVE_REQUIRED_FIELDS) \
        if args.metrics_jsonl else None
    slo = ServeSLO(max_queue_steps=args.max_queue_steps,
                   reserve_pages=args.reserve_pages)
    cb = ContinuousBatcher(
        model, eng, mesh, n_slots=args.slots, max_len=args.max_len,
        prompt_len=args.prompt_len, page_size=args.page_size or None,
        n_pages=args.n_pages, slo=slo, backend=args.backend,
        res_axes=res_axes, metrics=metrics)
    print(f"paged pool: {cb.paged.n_pages} pages x {cb.paged.page_size} "
          f"tokens ({cb.paged.blocks_per_slot}/slot)")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, arch.vocab, args.prompt_len)
                    .astype(np.int32),
                    max_new=args.gen) for i in range(args.requests)]
    t0 = time.time()
    cb.run(params, reqs)
    dt = time.time() - t0
    if metrics is not None:
        metrics.close()

    c = cb.counters
    tok = sum(len(r.out) for r in reqs)
    lat = cb.latency_percentiles()
    print(f"arch={arch.name} backend={args.backend} {args.requests} reqs "
          f"-> {tok} tokens in {dt:.2f}s ({tok / max(dt, 1e-9):.1f} tok/s, "
          f"{cb.step_count} steps)")
    print(f"admitted {c['admitted']} rejected {c['rejected']} "
          f"preempted {c['preempted']} retired {c['retired']}; "
          f"p50 {lat['p50_ms']:.1f}ms p99 {lat['p99_ms']:.1f}ms")
    done = next((r for r in reqs if r.out), None)
    if done is not None:
        print("sample:", done.out[:16])


if __name__ == "__main__":
    main()
