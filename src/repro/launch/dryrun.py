"""Multi-pod dry-run (deliverable (e)): lower + compile every
(architecture x input shape x mesh x scheme) combination with
ShapeDtypeStruct stand-ins — no device allocation — and record
memory_analysis / cost_analysis / the loop-aware collective census.

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch gemma3-1b --shape train_4k --mesh prod --scheme zero_topo

    --arch all --shape all --mesh prod,prod_mp   # the full 40-combo sweep

Exit code != 0 if any combination fails to lower/compile: failures here
(sharding mismatch, OOM at compile, unsupported collective) are bugs.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.engine import TrainHparams, ZeroEngine
from ..models.config import SHAPES, shape_supported
from ..models.registry import (batch_axes, build_model, data_axes, get_arch,
                               list_archs)
from ..serve.engine import ServeEngine, make_serve_config
from . import hlo, roofline
from .distributed import add_cli_args, from_args, initialize
from .mesh import make_production_mesh, make_topo_mesh, scheme_config

MESHES = {
    "prod": lambda: make_production_mesh(),
    "prod_mp": lambda: make_production_mesh(multi_pod=True),
    "topo": lambda: make_topo_mesh(),
    "topo_mp": lambda: make_topo_mesh(multi_pod=True),
}


def train_batch_candidates(mesh):
    """Batch-shard axes for training: every non-pod axis (ZeRO = pure DP),
    pod last (replicated unless batch demands it)."""
    non_pod = tuple(a for a in mesh.axis_names if a != "pod")
    return non_pod


def lower_combo(arch_name: str, shape_name: str, mesh_name: str,
                scheme: str, quant_block: int = 2048,
                serve_mode: str = "zero", engine_opts: dict | None = None):
    import dataclasses
    mesh = MESHES[mesh_name]()
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    model = build_model(arch)
    planner_kw = {}
    if scheme == "auto":
        planner_kw = dict(psi=model.param_count(), n_layers=arch.n_layers)
    cfg = scheme_config(scheme, mesh, quant_block=quant_block, **planner_kw)
    if engine_opts:
        cfg = dataclasses.replace(cfg, **engine_opts)
    eng = ZeroEngine(model.leaf_specs(), cfg, mesh, TrainHparams())

    if shape.kind == "train":
        baxes = batch_axes(mesh, shape.global_batch,
                           candidates=train_batch_candidates(mesh))
        shapes = model.train_batch_shapes(shape)
        bspecs = model.batch_pspecs(shapes, baxes)
        batch_sds = model.batch_sds(shapes, mesh, baxes)
        step = eng.make_train_step(model.loss_fn(), bspecs)
        with mesh:
            lowered = step.lower(eng.abstract_state(), batch_sds)
    else:
        sp = "sp" in serve_mode
        if "resident" in serve_mode:
            from ..serve.resident import ResidentServeEngine
            se = ResidentServeEngine(model, eng, mesh, shape)
            prims = se.abstract_params()
        else:
            se = ServeEngine(model, eng, mesh, shape)
            prims = eng.abstract_primaries()
        if shape.kind == "prefill":
            step = se.make_prefill(seq_parallel=sp)
            with mesh:
                lowered = step.lower(prims, se.prefill_inputs_sds())
        else:
            step = se.make_decode()
            caches, batch = se.decode_inputs_sds()
            with mesh:
                lowered = step.lower(prims, caches, batch)
    return eng, lowered, mesh, arch, shape


def compare_phases(eng, arch, shape, mesh, metrics_path, topology: str = ""):
    """Predicted-vs-measured per-phase table (DESIGN.md §10).

    Predicted: ``topo.cost.phase_breakdown`` for THIS combo's config on
    ``--topology`` (default: the live mesh's synthetic Topology). Measured:
    the last ``phase_ms`` record in a ``--metrics-jsonl`` stream from a
    traced run (all rank lanes merged). The two need not share a mesh —
    the point is eyeballing where the model and a real trace diverge.

    ``--topology`` is applied as an overlay: its link bandwidths replace
    the same-named axes of the mesh's synthetic topology, so a calibration
    file from a differently-shaped mesh (obs.calibrate on the 8-device test
    mesh, say) still prices the axes it actually measured.
    """
    from ..obs import metrics as obs_metrics
    from ..topo import cost as tcost
    from ..topo.model import Topology, calibrated, load_topology
    topo = Topology.from_mesh(mesh)
    if topology:
        src = load_topology(topology)
        known = {l.name: l.bandwidth for l in src.links}
        topo = calibrated(
            topo, {l.name: known[l.name] for l in topo.links
                   if l.name in known},
            name=f"{topo.name}<-{src.name}")
    n_mb = max(eng.hp.n_microbatch, 1)
    wl = tcost.Workload(
        psi=float(eng.param_count()), n_layers=arch.n_layers,
        tokens_per_device_mb=shape.global_batch * shape.seq_len
        // mesh.size // n_mb,
        n_microbatch=n_mb, stream_grads=eng.cfg.stream_grads)
    pred = tcost.phase_breakdown(eng.cfg, topo, wl)
    measured = obs_metrics.last_phase_ms(obs_metrics.read_lanes(metrics_path))
    rows = {}
    lines = [f"{'phase':<16}{'predicted_ms':>14}{'measured_ms':>14}"]
    for ph in tcost.PHASES:
        p = pred[ph]["seconds"] * 1e3
        m = measured.get(ph)
        rows[ph] = dict(predicted_ms=p, measured_ms=m)
        lines.append(f"{ph:<16}{p:>14.3f}" +
                     (f"{m:>14.2f}" if m is not None else f"{'--':>14}"))
    return rows, "\n".join(lines)


def compare_serve_phases(eng, arch, shape, mesh, metrics_path,
                         topology: str = "", resident: bool = True):
    """Predicted-vs-measured for one serving decode step (DESIGN.md §12).

    Predicted: ``topo.cost.serve_step_cost`` for this combo's residency
    layout on ``--topology`` (overlay semantics as ``compare_phases``).
    Measured: the last serve ``phase_ms`` record from a continuous-batching
    run's ``--metrics-jsonl`` stream (repro.launch.serve) — the scheduler's
    ``serve_decode`` span is the decode step, ``serve_admit`` the admission
    work; the per-layer comm phases are predicted-only (they live inside
    the compiled step and are not separately spanned)."""
    from ..obs import metrics as obs_metrics
    from ..topo import cost as tcost
    from ..topo.model import Topology, calibrated, load_topology
    from ..topo.planner import serve_workload_for_model
    topo = Topology.from_mesh(mesh)
    if topology:
        src = load_topology(topology)
        known = {l.name: l.bandwidth for l in src.links}
        topo = calibrated(
            topo, {l.name: known[l.name] for l in topo.links
                   if l.name in known},
            name=f"{topo.name}<-{src.name}")
    wl = serve_workload_for_model(
        arch.name, n_slots=shape.global_batch, context=shape.seq_len,
        max_len=shape.seq_len, quant_block=eng.cfg.quant_block)
    res_axes = tuple(eng.cfg.axes.secondary or ())
    pred = tcost.serve_step_cost(topo, wl, res_axes, resident=resident)
    measured = obs_metrics.last_phase_ms(
        obs_metrics.read_lanes(metrics_path))
    rows = {}
    lines = [f"{'phase':<16}{'predicted_ms':>14}{'measured_ms':>14}"]
    preds = dict(pred.comm_s)
    preds["serve_decode"] = pred.step_s()
    preds["serve_admit"] = None
    for ph in tcost.SERVE_PHASES + ("serve_decode", "serve_admit"):
        p = preds[ph]
        m = measured.get(ph)
        rows[ph] = dict(
            predicted_ms=None if p is None else p * 1e3, measured_ms=m)
        lines.append(
            f"{ph:<16}" +
            (f"{p * 1e3:>14.3f}" if p is not None else f"{'--':>14}") +
            (f"{m:>14.2f}" if m is not None else f"{'--':>14}"))
    return rows, "\n".join(lines)


def run_combo(arch_name, shape_name, mesh_name, scheme, outdir: Path,
              quant_block: int = 2048, save_hlo: bool = False,
              serve_mode: str = "zero", engine_opts: dict | None = None,
              tag: str = "", compare: str = "", topology: str = ""):
    t0 = time.time()
    eng, lowered, mesh, arch, shape = lower_combo(
        arch_name, shape_name, mesh_name, scheme, quant_block, serve_mode,
        engine_opts)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    census = hlo.analyze(txt).summary()

    n_params = eng.param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    rl = roofline.build(
        census, n_chips=mesh.size, n_params=n_params,
        n_active_params=roofline.active_params(arch, n_params),
        tokens=tokens, kind=shape.kind)

    rec = dict(
        arch=arch_name, shape=shape_name, mesh=mesh_name, scheme=scheme,
        serve_mode=serve_mode, n_chips=mesh.size, n_params=n_params,
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
        ),
        cost_analysis=dict(flops=float(cost.get("flops", -1)),
                           bytes_accessed=float(cost.get("bytes accessed", -1))),
        census=census,
        roofline=rl.summary(),
    )
    if compare and shape.kind == "train":
        rows, table = compare_phases(eng, arch, shape, mesh, compare,
                                     topology)
        rec["phase_compare"] = rows
        print(table, flush=True)
    elif compare and shape.kind == "decode":
        rows, table = compare_serve_phases(
            eng, arch, shape, mesh, compare, topology,
            resident="resident" in serve_mode)
        rec["phase_compare"] = rows
        print(table, flush=True)
    outdir.mkdir(parents=True, exist_ok=True)
    name = f"{arch_name}__{shape_name}__{mesh_name}__{scheme}"
    if serve_mode != "zero":
        name += f"__{serve_mode}"
    if tag:
        name += f"__{tag}"
    (outdir / f"{name}.json").write_text(json.dumps(rec, indent=1))
    if save_hlo:
        (outdir / f"{name}.hlo.txt").write_text(txt)
    print(f"OK  {name}  lower={t_lower:.0f}s compile={t_compile:.0f}s "
          f"bottleneck={rl.bottleneck} "
          f"terms(c/m/x)={rl.compute_s:.3f}/{rl.memory_s:.3f}/"
          f"{rl.collective_s:.3f}s", flush=True)
    return rec


def build_parser() -> argparse.ArgumentParser:
    """The dry-run CLI surface (rendered into docs/CLI.md by
    ``repro.launch.cli_reference``)."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.dryrun",
        description="lower + compile (arch x shape x mesh x scheme) combos "
                    "on 512 fake devices, no allocation")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="prod")
    ap.add_argument("--scheme", default="zero_topo",
                    help="comma-separated presets, or 'auto' for the "
                         "topology planner's choice on each mesh")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--quant-block", type=int, default=2048)
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--serve-mode", default="zero",
                    choices=["zero", "resident", "zero_sp", "resident_sp"])
    ap.add_argument("--cross-replica", default="",
                    choices=["", "allreduce", "reduce_scatter"])
    ap.add_argument("--quant-update", action="store_true")
    ap.add_argument("--stream-grads", action="store_true",
                    help="lower the streaming gradient path (DESIGN.md §8)")
    ap.add_argument("--kernel-impl", default="",
                    choices=["", "jnp", "pallas", "pallas_interpret"],
                    help="quantization-kernel implementation to lower with "
                         "(DESIGN.md §5); empty inherits the process default")
    ap.add_argument("--tag", default="")
    ap.add_argument("--compare", default="",
                    help="metrics JSONL from a traced run (--metrics-jsonl): "
                         "print a predicted-vs-measured per-phase column for "
                         "each train combo (DESIGN.md §10); serve JSONL from "
                         "repro.launch.serve does the same for decode "
                         "combos (DESIGN.md §12)")
    ap.add_argument("--topology", default="",
                    help="topology preset or JSON (e.g. obs.calibrate "
                         "output) pricing --compare's predicted column; "
                         "default: the live mesh's synthetic topology")
    add_cli_args(ap)
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()
    # 512 fake devices, forced only now (not at import: jax reads XLA_FLAGS
    # at backend initialization, and the first device touch is the mesh
    # construction below — importing this module must stay side-effect free
    # so cli_reference can render the parser)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    # multi-process dry-run: each process forces its share of the 512 fake
    # devices; rendezvous before the first device access
    dcfg = from_args(args)
    if dcfg.is_distributed:
        if 512 % dcfg.num_processes:
            ap.error(f"the 512-device dry-run meshes are not divisible by "
                     f"{dcfg.num_processes} processes ({dcfg.source})")
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                   f"{512 // dcfg.num_processes}")
    initialize(dcfg)
    engine_opts = {}
    if args.cross_replica:
        engine_opts["cross_replica"] = args.cross_replica
    if args.quant_update:
        engine_opts["quantize_update_gather"] = True
    if args.stream_grads:
        engine_opts["stream_grads"] = True
    if args.kernel_impl:
        engine_opts["impl"] = args.kernel_impl

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    # default "all" = the 10 assigned archs (paper's neox models via explicit)
    if args.arch == "all":
        archs = [a for a in archs if not a.startswith("gpt-neox")]
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = args.mesh.split(",")
    schemes = args.scheme.split(",")
    outdir = Path(args.out)

    failures = []
    for arch in archs:
        for shape in shapes:
            if not shape_supported(get_arch(arch), SHAPES[shape]):
                print(f"SKIP {arch} {shape} (sub-quadratic attention "
                      f"required; see DESIGN.md)", flush=True)
                continue
            for mesh in meshes:
                for scheme in schemes:
                    try:
                        run_combo(arch, shape, mesh, scheme, outdir,
                                  args.quant_block, args.save_hlo,
                                  args.serve_mode, engine_opts or None,
                                  args.tag, args.compare, args.topology)
                    except Exception as e:
                        failures.append((arch, shape, mesh, scheme, str(e)))
                        print(f"FAIL {arch} {shape} {mesh} {scheme}: "
                              f"{type(e).__name__}: {e}", flush=True)
                        traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        raise SystemExit(1)
    print("\nall combinations lowered + compiled")


if __name__ == "__main__":
    main()
