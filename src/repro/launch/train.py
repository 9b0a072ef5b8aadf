"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --scheme zero_topo --steps 100 --reduced --devices 8

``--devices N`` runs on N fake CPU devices (the platform is pinned to the
CPU); without it the run takes the live devices, e.g. one TPU chip or a
2x2 host, and the mesh is built from them (``mesh.make_device_mesh``).
``--reduced`` swaps in the smoke-scale variant of the architecture;
without it the model trains at its published widths. On a TPU backend the
compiled Pallas kernels are the default (``--kernel-impl`` overrides).

Multi-process (one process per node/GCD; README "Multi-host quickstart"):
either pass --coordinator/--num-processes/--process-id explicitly, or let
SLURM / OpenMPI / REPRO_* env autodetection fill them in. ``--devices`` is
the *global* device count; each process brings its share.
"""
import argparse
import os

from .distributed import (add_cli_args, enable_compile_cache, from_args,
                          initialize)


def build_parser() -> argparse.ArgumentParser:
    """The launcher's full CLI surface (also rendered into docs/CLI.md by
    ``repro.launch.cli_reference``)."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.train",
        description="training launcher (the live devices, or N fake CPU "
                    "devices with --devices N)")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--scheme", default="zero_topo",
                    help="partition preset, or 'auto' to let the topology "
                         "planner (repro.topo) pick for the live mesh")
    ap.add_argument("--mesh", default="auto", choices=["auto", "prod", "topo"],
                    help="auto: (data, node, gcd) over the live devices "
                         "(1 -> 1x1x1, 4 -> 1x2x2, 8 -> 2x2x2); prod/topo: "
                         "the 256/512-device pod meshes")
    ap.add_argument("--devices", type=int, default=None,
                    help="run on this many fake CPU devices (global count); "
                         "omit to use the live devices")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant-block", type=int, default=128)
    ap.add_argument("--overlap", action="store_true",
                    help="double-buffered prefetch of the per-layer weight "
                         "all-gather (DESIGN.md §3)")
    ap.add_argument("--stream-grads", action="store_true",
                    help="streaming gradient path (DESIGN.md §8): per-layer "
                         "grad reduce-scatter fused into the backward, "
                         "microbatch grads accumulated in fp32 "
                         "optimizer-shard layout (grad buffer 4*psi/os "
                         "instead of 4*psi/w)")
    ap.add_argument("--kernel-impl", default=None,
                    choices=["jnp", "pallas", "pallas_interpret"],
                    help="quantization-kernel implementation (DESIGN.md §5):"
                         " jnp oracle (default off TPU), compiled Pallas "
                         "(default on TPU), or interpreted Pallas bodies "
                         "(CPU validation)")
    ap.add_argument("--compute-dtype", default=None,
                    choices=["bfloat16", "float32"],
                    help="activation/primary dtype (default: the scheme's, "
                         "bf16). float32 also pins matmul precision — the "
                         "cross-process bitwise-comparison regime "
                         "(DESIGN.md §6; at bf16, or above XLA CPU's "
                         "threaded-reduction thresholds, layouts differ by "
                         "~1e-5 deterministic reassociation noise)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint in --ckpt-dir; a "
                         "checkpoint written under a different mesh/process "
                         "layout or scheme is resharded onto the live one "
                         "(elastic restore, DESIGN.md §11)")
    ap.add_argument("--strict-restore", action="store_true",
                    help="with --resume: refuse any layout difference "
                         "(MeshMismatch/SchemeMismatch) instead of "
                         "resharding — the pre-elastic behavior")
    ap.add_argument("--budget-gb", type=float, default=0.0,
                    help="--scheme auto: per-device memory budget in GB "
                         "(0 = unbounded; fake CPU devices have no real HBM)")
    ap.add_argument("--log-json", default="")
    g = ap.add_argument_group(
        "observability", "opt-in runtime tracing (DESIGN.md §10); without "
        "--trace the monolithic step runs untouched and every bitwise "
        "contract holds")
    g.add_argument("--trace", action="store_true",
                   help="run the phased fenced step: per-phase spans, "
                        "comm-attribution probes, JSONL metrics stream")
    g.add_argument("--metrics-jsonl", default="",
                   help="per-step JSONL metrics path (multi-process runs "
                        "write per-rank .rank<k> lanes next to it)")
    g.add_argument("--chrome-trace", default="",
                   help="write collected spans as a Chrome/Perfetto "
                        "trace.json at end of run")
    g.add_argument("--heartbeat-dir", default="",
                   help="per-rank heartbeat files + straggler report "
                        "(launch.distributed.Heartbeat)")
    g.add_argument("--probe-every", type=int, default=4,
                   help="steps between out-of-band comm-attribution probe "
                        "runs (0 disables probes)")
    add_cli_args(ap)
    return ap


def main(argv=None):
    """Run the launcher on ``argv`` (default: the command line); returns
    the ``Trainer`` (its ``log`` holds the per-step record) and the final
    state."""
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")

    dcfg = from_args(args)
    if args.devices:
        if args.devices % dcfg.num_processes:
            ap.error(f"--devices {args.devices} not divisible by the "
                     f"{dcfg.num_processes} processes ({dcfg.source})")
        # fake devices are CPU devices: this run never takes an accelerator
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # rendezvous (no-op single-process) BEFORE the first jax device access;
    # each process only forces its local share of the fake CPU devices
    initialize(dcfg, local_devices=args.devices // dcfg.num_processes
               if args.devices else None)
    enable_compile_cache()
    log0 = print if dcfg.process_id == 0 else (lambda *a, **k: None)

    import jax
    if args.compute_dtype == "float32":
        jax.config.update("jax_default_matmul_precision", "float32")
    # process default: covers every config built from here on (attention
    # dispatches on it; the explicit per-config override below pins the
    # engine's own cfg). On a TPU a shape-gate fallback is then an error.
    from ..kernels import ops as kernel_ops
    impl = args.kernel_impl or \
        ("pallas" if jax.default_backend() == "tpu" else None)
    if impl:
        kernel_ops.set_default_impl(impl)
    from ..core.engine import TrainHparams, ZeroEngine
    from ..models.config import ShapeConfig
    from ..models.registry import build_model, get_arch
    from ..train.trainer import Trainer
    from .mesh import make_device_mesh, make_production_mesh, \
        make_topo_mesh, scheme_config

    mesh = {"auto": make_device_mesh, "prod": make_production_mesh,
            "topo": make_topo_mesh}[args.mesh]()
    arch = get_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    model = build_model(arch)
    planner_kw = {}
    if args.scheme == "auto":
        # workload for the planner: the real model on the live mesh
        planner_kw = dict(psi=model.param_count(), n_layers=arch.n_layers,
                          memory_budget=args.budget_gb * 1e9
                          if args.budget_gb else None)
    dtype_kw = {"compute_dtype": args.compute_dtype} \
        if args.compute_dtype else {}
    cfg = scheme_config(args.scheme, mesh, quant_block=args.quant_block,
                        overlap=args.overlap, stream_grads=args.stream_grads,
                        impl=impl, **dtype_kw, **planner_kw)
    if args.scheme == "auto":
        a = cfg.axes
        log0(f"planner choice: w={a.weight} e={a.extra_grad} r={a.replica} "
             f"sec={a.secondary} int8w={cfg.quantize_weights} "
             f"int4g={cfg.quantize_grads}")
    hp = TrainHparams(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(args.steps // 20, 2),
                      overlap=args.overlap, stream_grads=args.stream_grads)
    eng = ZeroEngine(model.leaf_specs(), cfg, mesh, hp)
    log0(f"arch={arch.name} scheme={cfg.name} mesh={dict(mesh.shape)} "
         f"params={eng.param_count():,} overlap={eng.cfg.overlap} "
         f"stream_grads={eng.cfg.stream_grads} "
         f"kernel_impl={eng.cfg.impl or 'jnp'} "
         f"processes={dcfg.num_processes} ({dcfg.source})")
    log0(f"per-device state bytes: {eng.memory_report()}")

    trace = None
    if args.trace or args.metrics_jsonl or args.chrome_trace \
            or args.heartbeat_dir:
        from ..obs.spans import TraceConfig
        trace = TraceConfig(metrics_path=args.metrics_jsonl or None,
                            chrome_trace=args.chrome_trace or None,
                            heartbeat_dir=args.heartbeat_dir or None,
                            probe_every=args.probe_every)
        log0(f"trace mode: phased fenced step (float-close, NOT bitwise, "
             f"to the fused step) probes_every={args.probe_every}")

    from ..train.trainer import _host_int
    tr = Trainer(model, eng, mesh, shape, trace=trace)
    if args.resume and args.ckpt_dir:
        state = tr.restore(args.ckpt_dir, reshard=not args.strict_restore)
        log0(f"resumed from step {_host_int(state['step'])}"
             + ("" if args.strict_restore else " (elastic restore enabled)"))
    else:
        state = eng.init_state(jax.random.key(0))
    state = tr.run(state, args.steps,
                   ckpt_dir=args.ckpt_dir or None,
                   ckpt_every=args.ckpt_every,
                   print_fn=log0)
    if args.log_json and dcfg.process_id == 0:
        tr.log.save(args.log_json)
    if args.heartbeat_dir:
        from .distributed import heartbeat
        log0(heartbeat(args.heartbeat_dir).format_report())
    agg = tr.log.aggregates()
    if agg.get("n_timed_steps"):
        log0(f"throughput (excl. compile step): "
             f"{agg['tokens_per_s_mean']:.0f} tok/s, "
             f"{agg['tflops_per_gpu_mean']:.3f} model-TFLOPS/GPU")
    log0(f"final loss: {tr.log.losses[-1]}")
    return tr, state


if __name__ == "__main__":
    main()
