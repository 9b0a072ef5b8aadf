"""Multi-device (8 fake CPU devices) test scenarios.

Run in a subprocess by test_distributed.py so the main pytest process keeps
the real single-device view:  python tests/_scenarios.py <name>
Each scenario asserts internally and prints "SCENARIO_OK <name>".
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses  # noqa: E402
from functools import partial  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.compat import shard_map  # noqa: E402

# propagate the CI interpret leg's kernel impl into subprocess scenarios
# (same hook as tests/conftest.py)
if os.environ.get("REPRO_KERNEL_IMPL"):
    from repro.kernels import ops as _kops
    _kops.set_default_impl(os.environ["REPRO_KERNEL_IMPL"])

AX = ("data", "node", "gcd")


def _mesh(shape=(2, 2, 2)):
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(shape=shape, axes=AX)


def _cfg(scheme, mesh, **over):
    from repro.launch.mesh import scheme_config
    return scheme_config(scheme, mesh, quant_block=64, **over)


# ---------------------------------------------------------------------------

def collectives():
    """Quantized collectives == plain collectives within quant tolerance."""
    from repro.core import collectives as col
    mesh = _mesh()
    cfg = _cfg("zero_topo", mesh)

    def metric(fn, x):
        """Run fn(local_shard) -> scalar metric; return per-device maxima."""
        sm = shard_map(lambda s: fn(s.reshape(-1))[None],
                           mesh=mesh, in_specs=P(AX), out_specs=P(AX),
                           check_vma=False)
        return np.asarray(jax.jit(sm)(x))

    x = jax.random.normal(jax.random.key(0), (8 * 64 * 4,))

    def quant_gather_err(shard):
        full, qf, sf = col.quant_all_gather_int8(shard, AX, cfg)
        plain = col.all_gather_flat(shard, AX)
        return jnp.max(jnp.abs(full.astype(jnp.float32)
                               - plain.astype(jnp.float32)))

    assert metric(quant_gather_err, x).max() < 0.1

    def secondary_rebuild_err(shard):
        full, qf, sf = col.quant_all_gather_int8(shard, AX, cfg)
        sq, ss = col.secondary_slice(qf, sf, ("node", "gcd"), cfg)
        rebuilt = col.gather_secondary(sq, ss, ("node", "gcd"), cfg)
        return jnp.max(jnp.abs(rebuilt.astype(jnp.float32)
                               - full.astype(jnp.float32)))

    assert metric(secondary_rebuild_err, x).max() == 0.0

    def wire_a2a_vs_plain(shard):
        """The wire all-to-all exchanges lane-shaped rows; the bytes each
        member receives must be exactly the plain all-to-all's."""
        out = []
        for dtype in (jnp.int8, jnp.uint8):
            q = (shard * 40).astype(jnp.int32).astype(dtype).reshape(8, -1)
            plain = lax.all_to_all(q, AX, 0, 0, tiled=False)
            wire = col._wire_all_to_all(q, AX)
            out.append(jnp.max(jnp.abs(plain.astype(jnp.int32)
                                       - wire.astype(jnp.int32))))
        return jnp.max(jnp.stack(out)).astype(jnp.float32)

    assert metric(wire_a2a_vs_plain, x).max() == 0.0

    y = jax.random.normal(jax.random.key(1), (2048 * 8,))

    def rs4_abs_over_bound(shard):
        exact = lax.psum_scatter(shard, AX, tiled=True)
        quant = col.a2a_quant_reduce_scatter(shard, AX, cfg, bits=4)
        # one quantize/dequantize round-trip per contribution: error of each
        # of the 8 summands is <= blockmax/7/2 <= globalmax/14
        gmax = lax.pmax(jnp.max(jnp.abs(shard)), AX)
        bound = 8 * (gmax / 14.0 + 1e-6)
        return jnp.max(jnp.abs(quant - exact)) / bound

    assert metric(rs4_abs_over_bound, y).max() <= 1.0, \
        metric(rs4_abs_over_bound, y).max()

    def rs8_abs(shard):
        exact = lax.psum_scatter(shard, AX, tiled=True)
        quant = col.a2a_quant_reduce_scatter(shard, AX, cfg, bits=8)
        gmax = lax.pmax(jnp.max(jnp.abs(shard)), AX)
        bound = 8 * (gmax / 254.0 + 1e-6)      # 8 summands, half-LSB each
        return jnp.max(jnp.abs(quant - exact)) / bound

    assert metric(rs8_abs, y).max() <= 1.0

    cfg_rs = dataclasses.replace(cfg, cross_replica="reduce_scatter")
    z = jax.random.normal(jax.random.key(2), (1024 * 8,))

    def cross_replica_diff(shard):
        a = col.cross_replica_grad(shard, cfg)       # allreduce + select
        b = col.cross_replica_grad(shard, cfg_rs)    # psum_scatter
        return jnp.max(jnp.abs(a - b))

    assert metric(cross_replica_diff, z).max() < 1e-5

    w = jax.random.normal(jax.random.key(3), (2048 * 8,))

    def update_gather_err(shard):
        # canonical slice hierarchy: [W major, E, R minor] == cfg.axes.all
        prim = col.update_all_gather(shard, cfg, jnp.float32)
        full_a = col.all_gather_flat(prim, cfg.axes.weight)
        full_b = col.all_gather_flat(shard, cfg.axes.all)
        return jnp.max(jnp.abs(full_a - full_b))

    assert metric(update_gather_err, w).max() == 0.0
    print("SCENARIO_OK collectives")


# ---------------------------------------------------------------------------

def collectives_split():
    """The gather-issue/gather-wait split primitives (prefetch/overlap path)
    are bitwise the fused quant_all_gather_int8, the secondary partition
    sliced from a prefetched buffer rebuilds the identical full tensor, and
    the quantized reduce_scatter_flat tracks the plain one within the
    block-quantization bound."""
    from jax import lax as jlax
    from repro.core import collectives as col
    mesh = _mesh()
    cfg = _cfg("zero_topo", mesh)

    def metric(fn, x):
        sm = shard_map(lambda s: fn(s.reshape(-1))[None],
                       mesh=mesh, in_specs=P(AX), out_specs=P(AX),
                       check_vma=False)
        return np.asarray(jax.jit(sm)(x))

    x = jax.random.normal(jax.random.key(0), (8 * 64 * 4,))

    def split_vs_fused(shard):
        full, qf, sf = col.quant_all_gather_int8(shard, AX, cfg)
        qf2, sf2 = col.gather_issue_int8(shard, AX, cfg)
        full2 = col.gather_wait_int8(qf2, sf2, cfg)
        sq, ss = col.secondary_slice(qf2, sf2, ("node", "gcd"), cfg)
        rebuilt = col.gather_secondary(sq, ss, ("node", "gcd"), cfg)
        return jnp.stack([
            jnp.max(jnp.abs(full.astype(jnp.float32)
                            - full2.astype(jnp.float32))),
            jnp.max(jnp.abs(qf - qf2).astype(jnp.float32)),
            jnp.max(jnp.abs(sf - sf2)),
            jnp.max(jnp.abs(rebuilt.astype(jnp.float32)
                            - full.astype(jnp.float32))),
        ])

    assert metric(split_vs_fused, x).max() == 0.0

    y = jax.random.normal(jax.random.key(1), (2048 * 8,))

    def rs_quant_vs_plain(shard):
        exact = col.reduce_scatter_flat(shard, AX, cfg, quantized=False)
        quant = col.reduce_scatter_flat(shard, AX, cfg, quantized=True)
        # INT4 path: one quantize round-trip per summand, 8 summands
        gmax = jlax.pmax(jnp.max(jnp.abs(shard)), AX)
        bound = 8 * (gmax / 14.0 + 1e-6)
        return jnp.max(jnp.abs(quant - exact)) / bound

    assert metric(rs_quant_vs_plain, y).max() <= 1.0

    # a2a-RS issue/wait split (streaming grad path, DESIGN.md §8): the
    # split halves compose bitwise into the fused reduce-scatter, for the
    # quantized (a2a) and plain (psum-scatter) paths and for sub-groups
    from repro.core import schedule as sched

    def rs_split_vs_fused(shard):
        outs = []
        for axes in (AX, ("node", "gcd"), ("data",)):
            for quantized in (False, True):
                fused = col.reduce_scatter_flat(shard, axes, cfg,
                                                quantized=quantized)
                tok = sched.grad_rs_issue(shard, axes, cfg,
                                          quantized=quantized)
                split = sched.grad_rs_wait(tok, cfg)
                outs.append(jnp.max(jnp.abs(fused - split)))
        return jnp.stack(outs)

    assert metric(rs_split_vs_fused, y).max() == 0.0
    print("SCENARIO_OK collectives_split")


def overlap_equivalence():
    """ZeroConfig.overlap (double-buffered gather prefetch) is bitwise
    equivalent to the serial schedule on the 8-device test mesh: scan path
    (uniform qwen2, stacked leaves + remat) for zero3/zeropp/zero_topo and
    the heterogeneous loop path (gemma3 local:global pattern)."""
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.models.registry import build_model, get_arch

    jax.config.update("jax_default_matmul_precision", "float32")
    mesh = _mesh()
    rng = np.random.default_rng(0)
    cases = [("qwen2-0.5b", s) for s in ("zero3", "zeropp", "zero_topo")]
    cases.append(("gemma3-1b", "zero_topo"))
    for name, scheme in cases:
        arch = get_arch(name).reduced(n_layers=4, d_model=128, vocab=256) \
            if name == "qwen2-0.5b" else get_arch(name).reduced()
        model = build_model(arch)
        batch_np = rng.integers(0, arch.vocab, (8, 33), dtype=np.int32)
        out = {}
        for overlap in (False, True):
            cfg = _cfg(scheme, mesh, compute_dtype="float32", overlap=overlap)
            eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                             TrainHparams(lr=1e-3, total_steps=8,
                                          warmup_steps=0))
            state = eng.init_state(jax.random.key(0))
            step = eng.make_train_step(model.loss_fn(), {"tokens": P(AX)})
            batch = {"tokens": jax.device_put(
                jnp.asarray(batch_np), NamedSharding(mesh, P(AX)))}
            ls = []
            for _ in range(3):
                state, m = step(state, batch)
                ls.append((float(m["loss"]), float(m["grad_norm"])))
            out[overlap] = ls
        assert out[False] == out[True], (name, scheme, out)
    print("SCENARIO_OK overlap_equivalence")


def stream_grads_equivalence():
    """Streaming gradient path (DESIGN.md §8) on the 8-device topo mesh:

    * n_microbatch=1: seed vs stream vs stream+overlap are BITWISE
      identical (losses, grad norms, every per-leaf master shard) with the
      full quantized zero_topo hot path;
    * impl="jnp" vs impl="pallas_interpret" with streaming on: bitwise;
    * n_microbatch=2: the per-microbatch stage-2 quantization reassociates
      vs the seed's once-per-step pass — within block-quant tolerance;
    * memory_report: grad_buffer drops to the exact per-leaf
      grad_buffer_bytes sum (os layout for the stacked leaves).
    """
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.core.partition import grad_buffer_bytes
    from repro.models.registry import build_model, get_arch

    jax.config.update("jax_default_matmul_precision", "float32")
    mesh = _mesh()
    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    rng = np.random.default_rng(0)
    batch_np = rng.integers(0, arch.vocab, (8, 33), dtype=np.int32)
    batch_np16 = rng.integers(0, arch.vocab, (16, 33), dtype=np.int32)

    def run(n_mb=1, **over):
        cfg = _cfg("zero_topo", mesh, compute_dtype="float32", **over)
        eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                         TrainHparams(lr=1e-3, total_steps=8, warmup_steps=0,
                                      n_microbatch=n_mb))
        state = eng.init_state(jax.random.key(0))
        step = eng.make_train_step(model.loss_fn(), {"tokens": P(AX)})
        # n_mb microbatches need n_mb rows per device (the local batch is
        # split along dim 0 inside the step)
        batch = {"tokens": jax.device_put(
            jnp.asarray(batch_np if n_mb == 1 else batch_np16),
            NamedSharding(mesh, P(AX)))}
        ms = []
        for _ in range(3):
            state, m = step(state, batch)
            ms.append((float(m["loss"]), float(m["grad_norm"])))
        masters = {n: np.asarray(state["master"][n].addressable_data(0))
                   for n in sorted(eng.specs)}
        return eng, ms, masters

    e0, ms0, ma0 = run(stream_grads=False)
    e1, ms1, ma1 = run(stream_grads=True)
    _, ms2, ma2 = run(stream_grads=True, overlap=True)
    assert ms0 == ms1 == ms2, (ms0, ms1, ms2)
    for n in ma0:
        np.testing.assert_array_equal(ma0[n], ma1[n], err_msg=n)
        np.testing.assert_array_equal(ma0[n], ma2[n], err_msg=n)

    # kernel-impl bitwise with streaming on
    _, msj, maj = run(stream_grads=True, impl="jnp")
    _, msp, map_ = run(stream_grads=True, impl="pallas_interpret")
    assert msj == msp, (msj, msp)
    for n in maj:
        np.testing.assert_array_equal(maj[n], map_[n], err_msg=n)

    # n_microbatch=2: per-microbatch stage-2 INT4 quantization vs the
    # seed's single pass over the accumulated grads — same math modulo one
    # extra quantize round-trip per microbatch, so losses track within the
    # block-quant tolerance the quantized-vs-exact tests already use
    _, msa, _ = run(n_mb=2, stream_grads=False)
    _, msb, _ = run(n_mb=2, stream_grads=True)
    for (la, ga), (lb, gb) in zip(msa, msb):
        assert abs(la - lb) / max(abs(la), 1e-9) < 0.02, (msa, msb)
        assert abs(ga - gb) / max(abs(ga), 1e-9) < 0.05, (msa, msb)

    # memory: the streamed (stacked) leaves drop to os layout — exact
    # per-leaf accounting, engine vs the shared partition formula
    rep0, rep1 = e0.memory_report(), e1.memory_report()
    snames = set(e1.stream_leaf_names())
    expect = sum(grad_buffer_bytes(e1.cfg, e1._pad[n] * (s.stack or 1),
                                   streaming=(n in snames))
                 for n, s in e1.specs.items())
    assert rep1["grad_buffer"] == expect
    assert rep1["grad_buffer"] < rep0["grad_buffer"], (rep0, rep1)
    print("SCENARIO_OK stream_grads_equivalence")


def kernel_impl_equivalence():
    """impl="jnp" vs impl="pallas_interpret" are bitwise identical through
    the full quantized hot path on 8 devices: zero_matmul / zero_gather_q
    forward (loss) AND backward (every per-leaf gradient), including the
    fused dequant-matmul and the fused INT4 a2a dequant-reduce."""
    from repro.core.engine import ParamView, TrainHparams, ZeroEngine
    from repro.models.registry import build_model, get_arch

    jax.config.update("jax_default_matmul_precision", "float32")
    mesh = _mesh()
    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    rng = np.random.default_rng(0)
    batch_np = rng.integers(0, arch.vocab, (8, 33), dtype=np.int32)
    loss_fn = model.loss_fn()

    out = {}
    for impl in ("jnp", "pallas_interpret"):
        cfg = _cfg("zero_topo", mesh, compute_dtype="float32", impl=impl)
        assert cfg.quantize_weights and cfg.quantize_grads
        eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                         TrainHparams(lr=1e-3, total_steps=8, warmup_steps=0))
        state = eng.init_state(jax.random.key(0))
        specs = eng.state_in_specs()["primaries"]

        def local(primaries, b, eng=eng):
            def loss(p):
                v = ParamView(eng.fns, p, overlap=eng.cfg.overlap)
                l, t = loss_fn(v, b)
                return l / t
            return jax.value_and_grad(loss)(primaries)

        sm = shard_map(local, mesh=mesh,
                       in_specs=(specs, {"tokens": P(AX)}),
                       out_specs=(P(), specs), check_vma=False)
        batch = {"tokens": jax.device_put(jnp.asarray(batch_np),
                                          NamedSharding(mesh, P(AX)))}
        loss, grads = jax.jit(sm)(state["primaries"], batch)
        out[impl] = (float(loss), {n: np.asarray(g) for n, g in grads.items()})

    l_j, g_j = out["jnp"]
    l_p, g_p = out["pallas_interpret"]
    assert l_j == l_p, (l_j, l_p)
    for n in g_j:
        np.testing.assert_array_equal(g_j[n], g_p[n], err_msg=n)

    # full train step (adds the stage-2 RS + update gather): losses and
    # updated masters must also match bitwise
    steps = {}
    for impl in ("jnp", "pallas_interpret"):
        cfg = _cfg("zero_topo", mesh, compute_dtype="float32", impl=impl)
        eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                         TrainHparams(lr=1e-3, total_steps=8, warmup_steps=0))
        state = eng.init_state(jax.random.key(0))
        step = eng.make_train_step(loss_fn, {"tokens": P(AX)})
        batch = {"tokens": jax.device_put(jnp.asarray(batch_np),
                                          NamedSharding(mesh, P(AX)))}
        ls = []
        for _ in range(2):
            state, m = step(state, batch)
            ls.append(float(m["loss"]))
        steps[impl] = (ls, {n: np.asarray(state["master"][n])
                            for n in eng.specs})
    assert steps["jnp"][0] == steps["pallas_interpret"][0], steps
    for n in steps["jnp"][1]:
        np.testing.assert_array_equal(steps["jnp"][1][n],
                                      steps["pallas_interpret"][1][n],
                                      err_msg=n)
    print("SCENARIO_OK kernel_impl_equivalence")


def attn_scan_impl_equivalence():
    """impl="jnp" vs impl="pallas_interpret" BITWISE through the model hot
    paths promoted into the ops dispatch (DESIGN.md §5): flash attention
    (qwen2), the selective scan (falcon-mamba), and the fused matmul-quant
    weight-grad epilogue — loss AND every per-leaf gradient on the 8-device
    topo mesh. Dispatch counters prove the kernels actually ran (no silent
    fallback on either impl)."""
    from repro.core.engine import ParamView, TrainHparams, ZeroEngine
    from repro.kernels import ops
    from repro.models.registry import build_model, get_arch

    jax.config.update("jax_default_matmul_precision", "float32")
    mesh = _mesh()
    rng = np.random.default_rng(0)
    prev_impl = ops.get_default_impl()
    try:
        for name, kern in (("qwen2-0.5b", "attention"),
                           ("falcon-mamba-7b", "selective_scan")):
            arch = get_arch(name).reduced(n_layers=2, d_model=128,
                                          vocab=256) \
                if name == "qwen2-0.5b" else get_arch(name).reduced()
            model = build_model(arch)
            batch_np = rng.integers(0, arch.vocab, (8, 33), dtype=np.int32)
            loss_fn = model.loss_fn()
            out = {}
            for impl in ("jnp", "pallas_interpret"):
                # attention/scan inherit the process default (the model
                # layer is not cfg-aware); quant collectives pin via cfg
                ops.set_default_impl(impl)
                ops.reset_dispatch_counters()
                cfg = _cfg("zero_topo", mesh, compute_dtype="float32",
                           impl=impl)
                assert cfg.quantize_weights and cfg.quantize_grads
                eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                                 TrainHparams(lr=1e-3, total_steps=8,
                                              warmup_steps=0))
                state = eng.init_state(jax.random.key(0))
                specs = eng.state_in_specs()["primaries"]

                def local(primaries, b, eng=eng):
                    def loss(p):
                        v = ParamView(eng.fns, p, overlap=eng.cfg.overlap)
                        l, t = loss_fn(v, b)
                        return l / t
                    return jax.value_and_grad(loss)(primaries)

                sm = shard_map(local, mesh=mesh,
                               in_specs=(specs, {"tokens": P(AX)}),
                               out_specs=(P(), specs), check_vma=False)
                batch = {"tokens": jax.device_put(
                    jnp.asarray(batch_np), NamedSharding(mesh, P(AX)))}
                loss, grads = jax.jit(sm)(state["primaries"], batch)
                counts = ops.dispatch_counters()
                assert counts.get(f"{kern}/{impl}", 0) > 0, \
                    (name, impl, counts)
                if name == "qwen2-0.5b":
                    # d_model=128 % block=64 == 0: every matmul leaf takes
                    # the fused epilogue-quant dW path
                    assert counts.get(f"matmul_quant/{impl}", 0) > 0, counts
                    assert not any("fallback" in k for k in counts), counts
                out[impl] = (float(loss),
                             {n: np.asarray(g) for n, g in grads.items()})
            l_j, g_j = out["jnp"]
            l_p, g_p = out["pallas_interpret"]
            assert l_j == l_p, (name, l_j, l_p)
            for n in g_j:
                np.testing.assert_array_equal(g_j[n], g_p[n],
                                              err_msg=f"{name}/{n}")
    finally:
        ops.set_default_impl(prev_impl)
    print("SCENARIO_OK attn_scan_impl_equivalence")


# ---------------------------------------------------------------------------

def schemes_equivalent():
    """zero3 / zeropp / zero_topo (quant off) produce identical losses on 8
    devices; quantized versions stay within tolerance."""
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.models.registry import build_model, get_arch

    mesh = _mesh()
    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    rng = np.random.default_rng(0)
    batch_np = rng.integers(0, arch.vocab, (8, 33), dtype=np.int32)

    losses = {}
    for scheme in ("zero3", "zeropp", "zero_topo"):
        for quant in (False, True):
            cfg = _cfg(scheme, mesh, compute_dtype="float32")
            cfg = dataclasses.replace(cfg, quantize_weights=quant,
                                      quantize_grads=quant)
            eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                             TrainHparams(lr=1e-3, total_steps=8,
                                          warmup_steps=0))
            state = eng.init_state(jax.random.key(0))
            step = eng.make_train_step(model.loss_fn(), {"tokens": P(AX)})
            batch = {"tokens": jax.device_put(
                jnp.asarray(batch_np), NamedSharding(mesh, P(AX)))}
            ls = []
            for _ in range(4):
                state, m = step(state, batch)
                ls.append(float(m["loss"]))
            losses[(scheme, quant)] = ls

    base = losses[("zero3", False)]
    for scheme in ("zeropp", "zero_topo"):
        exact = losses[(scheme, False)]
        for a, b in zip(base, exact):
            assert abs(a - b) / a < 1e-4, (scheme, base, exact)
        quant = losses[(scheme, True)]
        for a, b in zip(base, quant):
            assert abs(a - b) / a < 0.05, (scheme, base, quant)
    # training decreases loss
    assert base[-1] < base[0]
    print("SCENARIO_OK schemes_equivalent")


# ---------------------------------------------------------------------------

def auto_scheme():
    """--scheme auto: the topology planner's choice for the live 8-device
    mesh passes the dependency rule, builds a working engine, trains with a
    finite decreasing loss, and its predicted step time is <= every preset's
    under the same cost model."""
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.launch.mesh import scheme_config
    from repro.models.registry import build_model, get_arch
    from repro.topo import Topology, Workload, plan_for_mesh, step_cost
    from repro.topo.planner import preset_on_topology

    mesh = _mesh()
    plans = plan_for_mesh(mesh, psi=2e6, n_layers=2)
    topo = Topology.from_mesh(mesh)
    wl = Workload(psi=2e6, n_layers=2)
    for scheme in ("zero3", "zeropp", "zero_topo"):
        pc = step_cost(preset_on_topology(scheme, topo), topo, wl)
        assert plans[0].step_s <= pc.step_s(wl.hidden_fraction) + 1e-12, scheme

    cfg = scheme_config("auto", mesh, quant_block=64, psi=2e6, n_layers=2)
    cfg.validate_dependency_rule()
    assert cfg.name == "auto" and cfg.quant_block == 64
    assert cfg.w_degree >= 1 and cfg.os_degree == 8

    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                     TrainHparams(lr=1e-3, total_steps=8, warmup_steps=0,
                                  n_microbatch=2))
    state = eng.init_state(jax.random.key(0))
    step = eng.make_train_step(model.loss_fn(), {"tokens": P(AX)})
    batch = {"tokens": jax.device_put(
        jnp.asarray(np.random.default_rng(0).integers(0, 256, (16, 33)),
                    jnp.int32), NamedSharding(mesh, P(AX)))}
    ls = []
    for _ in range(4):
        state, m = step(state, batch)
        ls.append(float(m["loss"]))
        # microbatch-accumulated token metric: true global count, not zeros
        assert float(m["tokens"]) == 16 * 32, m["tokens"]
    assert all(np.isfinite(ls)) and ls[-1] < ls[0], ls
    print("SCENARIO_OK auto_scheme")


# ---------------------------------------------------------------------------

def dp_vs_single():
    """8-device zero_topo == 1-device zero3 on the same global batch."""
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.models.registry import build_model, get_arch

    arch = get_arch("deepseek-7b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    rng = np.random.default_rng(1)
    batch_np = rng.integers(0, arch.vocab, (8, 25), dtype=np.int32)

    results = {}
    for mesh_shape in [(2, 2, 2), (1, 1, 1)]:
        mesh = _mesh(mesh_shape)
        scheme = "zero_topo" if mesh_shape[0] > 1 else "zero3"
        cfg = _cfg(scheme, mesh, compute_dtype="float32")
        cfg = dataclasses.replace(cfg, quantize_weights=False,
                                  quantize_grads=False)
        eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                         TrainHparams(lr=1e-3, total_steps=8, warmup_steps=0))
        state = eng.init_state(jax.random.key(0))
        step = eng.make_train_step(model.loss_fn(), {"tokens": P(AX)})
        batch = {"tokens": jax.device_put(jnp.asarray(batch_np),
                                          NamedSharding(mesh, P(AX)))}
        ls = []
        for _ in range(3):
            state, m = step(state, batch)
            ls.append((float(m["loss"]), float(m["grad_norm"])))
        results[mesh_shape] = ls
    a, b = results[(2, 2, 2)], results[(1, 1, 1)]
    for (l1, g1), (l2, g2) in zip(a, b):
        assert abs(l1 - l2) / l2 < 5e-4, (a, b)
        assert abs(g1 - g2) / g2 < 5e-3, (a, b)
    print("SCENARIO_OK dp_vs_single")


# ---------------------------------------------------------------------------

def serve_sharded():
    """Sequence-sharded decode == single-device decode (flash-decode combine,
    sharded cache writes)."""
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.models.config import ShapeConfig
    from repro.models.registry import build_model, get_arch
    from repro.serve.engine import ServeEngine

    arch = get_arch("deepseek-7b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, arch.vocab, (4, 24), dtype=np.int32)

    outs = {}
    for mesh_shape in [(2, 2, 2), (1, 1, 1)]:
        mesh = _mesh(mesh_shape)
        cfg = _cfg("zero_topo", mesh, compute_dtype="float32")
        eng = ZeroEngine(model.leaf_specs(), cfg, mesh, TrainHparams())
        state = eng.init_state(jax.random.key(0))
        se = ServeEngine(model, eng, mesh, ShapeConfig("t", 32, 4, "decode"))
        toks = se.generate(state, {"tokens": jnp.asarray(prompt)}, 6)
        outs[mesh_shape] = np.asarray(toks)
    np.testing.assert_array_equal(outs[(2, 2, 2)], outs[(1, 1, 1)])
    print("SCENARIO_OK serve_sharded")


# ---------------------------------------------------------------------------

def hlo_census_real():
    """Census on a real compiled module: scan trip count multiplies
    collectives; wire formula matches the analytic value."""
    from repro.launch import hlo

    mesh = _mesh()
    n_layers, width = 7, 256

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None, AX), P(AX)), out_specs=P(AX),
             check_vma=False)
    def f(ws, x):
        def body(c, w):
            wf = lax.all_gather(w, ("gcd",), tiled=True)
            return jnp.tanh(c + wf.sum() * 1e-6), None
        c, _ = lax.scan(body, x, ws)
        return c

    ws = jnp.ones((n_layers, width))
    x = jnp.ones((64 * 8,))
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct(ws.shape, ws.dtype,
                             sharding=NamedSharding(mesh, P(None, AX))),
        jax.ShapeDtypeStruct(x.shape, x.dtype,
                             sharding=NamedSharding(mesh, P(AX)))).compile()
    s = hlo.analyze(compiled.as_text()).summary()
    assert s["collective_counts"].get("all-gather") == n_layers, s
    # each gather: out = width/(8/2)=64 f32 over d=2 -> wire 64*4*(1/2)
    per = (width // 4) * 4 * (2 - 1) / 2
    assert abs(s["wire_bytes"]["all-gather"] - per * n_layers) < 1, s
    print("SCENARIO_OK hlo_census_real")


# ---------------------------------------------------------------------------

def multipod_mesh():
    """Engine + model lower on a tiny 'multi-pod' mesh (pod axis joins the
    inter tier; batch replicated over pod)."""
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.launch.mesh import scheme_config, make_test_mesh
    from repro.models.registry import build_model, get_arch

    mesh = make_test_mesh(shape=(2, 2, 2), axes=("pod", "node", "gcd"))
    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    cfg = scheme_config("zero_topo", mesh, quant_block=64)
    assert cfg.axes.replica == ("pod",)
    eng = ZeroEngine(model.leaf_specs(), cfg, mesh, TrainHparams())
    state = eng.init_state(jax.random.key(0))
    batch = {"tokens": jax.device_put(
        jnp.asarray(np.random.default_rng(0).integers(0, 256, (4, 17)),
                    jnp.int32),
        NamedSharding(mesh, P(("node", "gcd"))))}
    step = eng.make_train_step(model.loss_fn(),
                               {"tokens": P(("node", "gcd"))})
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    print("SCENARIO_OK multipod_mesh")


def resident_and_sp():
    """8-device: the dense-fallback residency (unquantized engine) and
    sequence-parallel prefill both reproduce the ZeRO-serving results
    BITWISE — the residency stores exactly the training gather's output."""
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.launch.mesh import scheme_config
    from repro.models.config import ShapeConfig
    from repro.models.registry import build_model, get_arch
    from repro.serve.engine import ServeEngine
    from repro.serve.resident import ResidentServeEngine, build_resident

    mesh = _mesh()
    for name in ("jamba-v0.1-52b", "minicpm3-4b"):
        arch = get_arch(name).reduced()
        model = build_model(arch)
        cfg = scheme_config("zero_topo", mesh, quant_block=64,
                            compute_dtype="float32")
        cfg = dataclasses.replace(
            cfg, quantize_weights=False, quantize_grads=False,
            axes=dataclasses.replace(cfg.axes, secondary=None))
        cfg.validate_dependency_rule()
        eng = ZeroEngine(model.leaf_specs(), cfg, mesh, TrainHparams())
        state = eng.init_state(jax.random.key(0))
        rng = np.random.default_rng(0)
        b = 4
        batch = {"tokens": jnp.asarray(rng.integers(0, arch.vocab, (b, 32)),
                                       jnp.int32)}
        shape = ShapeConfig("t", 32, b, "decode")
        se = ServeEngine(model, eng, mesh, shape)
        layout, resident = build_resident(eng, state, mesh)
        rse = ResidentServeEngine(model, eng, mesh, shape,
                                  res_axes=layout.res_axes)
        l0, c0 = se.make_prefill()(state["primaries"], batch)
        l1, c1 = rse.make_prefill()(resident, batch)
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
        d0, d1 = se.make_decode(), rse.make_decode()
        for t in rng.integers(0, arch.vocab, (3, b)).astype(np.int32):
            l0, c0 = d0(state["primaries"], c0, {"token": jnp.asarray(t)})
            l1, c1 = d1(resident, c1, {"token": jnp.asarray(t)})
            np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))

        # SP prefill (attention-family only)
        if model.lm.sp_eligible():
            pshape = ShapeConfig("t", 32, b, "prefill")
            sep = ServeEngine(model, eng, mesh, pshape)
            l0, _ = sep.make_prefill(False)(state["primaries"], batch)
            l1, _ = sep.make_prefill(True)(state["primaries"], batch)
            np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                       rtol=2e-4, atol=2e-4)
    print("SCENARIO_OK resident_and_sp")


def serve_resident_quant_equivalence():
    """THE serving acceptance scenario (DESIGN.md §12), 8 devices: the INT8
    wire-resident path — residency built from the training engine's shards,
    decode through the fused ``dequant_matmul`` — produces prefill logits
    and greedy decode tokens BITWISE identical to the fp training forward
    at matching quant config, under BOTH kernel impls; and the two impls
    agree bitwise with each other (the §5 contract, end to end through
    prefill + decode)."""
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.kernels import ops
    from repro.models.config import ShapeConfig
    from repro.models.registry import build_model, get_arch
    from repro.serve.engine import ServeEngine
    from repro.serve.resident import ResidentServeEngine, build_resident

    mesh = _mesh()
    rng = np.random.default_rng(2)
    prev_impl = ops.get_default_impl()
    out = {}
    try:
        for name in ("qwen2-0.5b", "mixtral-8x7b"):
            arch = get_arch(name).reduced(n_layers=2, d_model=128, vocab=256)
            model = build_model(arch)
            prompt = rng.integers(0, arch.vocab, (4, 24), dtype=np.int32)
            shape = ShapeConfig("t", 32, 4, "decode")
            for impl in ("jnp", "pallas_interpret"):
                ops.set_default_impl(impl)
                ops.reset_dispatch_counters()
                cfg = _cfg("zero_topo", mesh, compute_dtype="float32",
                           impl=impl)
                assert cfg.quantize_weights
                eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                                 TrainHparams())
                state = eng.init_state(jax.random.key(0))
                se = ServeEngine(model, eng, mesh, shape)
                l_ref, _ = se.make_prefill()(state["primaries"],
                                             {"tokens": jnp.asarray(prompt)})
                t_ref = se.generate(state, {"tokens": jnp.asarray(prompt)}, 6)
                layout, resident = build_resident(eng, state, mesh)
                assert layout.res_degree > 1, layout.res_axes
                rse = ResidentServeEngine(model, eng, mesh, shape,
                                          res_axes=layout.res_axes)
                l_res, _ = rse.make_prefill()(resident,
                                              {"tokens": jnp.asarray(prompt)})
                t_res = rse.generate(resident, {"tokens": jnp.asarray(prompt)},
                                     6)
                np.testing.assert_array_equal(np.asarray(l_ref),
                                              np.asarray(l_res),
                                              err_msg=f"{name}/{impl}")
                np.testing.assert_array_equal(np.asarray(t_ref),
                                              np.asarray(t_res),
                                              err_msg=f"{name}/{impl}")
                counts = ops.dispatch_counters()
                assert counts.get(f"dequant_matmul/{impl}", 0) > 0, \
                    (name, impl, counts)
                out[(name, impl)] = (np.asarray(l_res), np.asarray(t_res))
            lj, tj = out[(name, "jnp")]
            lp, tp = out[(name, "pallas_interpret")]
            np.testing.assert_array_equal(lj, lp, err_msg=name)
            np.testing.assert_array_equal(tj, tp, err_msg=name)
    finally:
        ops.set_default_impl(prev_impl)
    print("SCENARIO_OK serve_resident_quant_equivalence")


def obs_trace_equivalence():
    """Trace-mode observability (DESIGN.md §10) on the 8-device topo mesh:

    * the phased fenced step (obs.phased.PhasedStep) reproduces the
      monolithic train step BITWISE at compute_dtype=float32 — losses, grad
      norms, every per-leaf master shard, 3 steps with n_microbatch=2;
    * the fenced segment spans of a warm step sum to that step's wall time
      within 10% (the --trace acceptance bound);
    * trace off == seed: a Trainer with trace=None produces losses
      bitwise-identical to driving engine.make_train_step by hand on the
      same data — the observability wiring is dead weight when disabled;
    * spans.site_inventory of the monolithic step is deterministic and
      equals the static verifier's tag census (analysis.dataflow) — one
      schedule-site inventory, two consumers.
    """
    import time as _time
    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.models.registry import build_model, get_arch
    from repro.obs.phased import PhasedStep
    from repro.obs.spans import SEGMENTS, SpanRecorder, site_inventory

    jax.config.update("jax_default_matmul_precision", "float32")
    mesh = _mesh()
    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    rng = np.random.default_rng(0)
    batch_np16 = rng.integers(0, arch.vocab, (16, 33), dtype=np.int32)
    cfg = _cfg("zero_topo", mesh, compute_dtype="float32")

    def eng():
        return ZeroEngine(model.leaf_specs(), cfg, mesh,
                          TrainHparams(lr=1e-3, total_steps=8,
                                       warmup_steps=0, n_microbatch=2))

    batch = {"tokens": jax.device_put(jnp.asarray(batch_np16),
                                      NamedSharding(mesh, P(AX)))}

    e0 = eng()
    step = e0.make_train_step(model.loss_fn(), {"tokens": P(AX)})
    s0 = e0.init_state(jax.random.key(0))
    ms0 = []
    for _ in range(3):
        s0, m = step(s0, batch)
        ms0.append((float(m["loss"]), float(m["grad_norm"])))
    ma0 = {n: np.asarray(s0["master"][n].addressable_data(0))
           for n in sorted(e0.specs)}

    e1 = eng()
    phased = PhasedStep(e1, model.loss_fn(), {"tokens": P(AX)})
    s1 = e1.init_state(jax.random.key(0))
    rec = SpanRecorder()
    ms1, walls = [], []
    for i in range(3):
        rec.step = i
        t0 = _time.perf_counter()
        s1, m = phased(s1, batch, rec)
        walls.append(_time.perf_counter() - t0)
        ms1.append((float(m["loss"]), float(m["grad_norm"])))
    ma1 = {n: np.asarray(s1["master"][n].addressable_data(0))
           for n in sorted(e1.specs)}
    assert ms0 == ms1, (ms0, ms1)
    for n in ma0:
        np.testing.assert_array_equal(ma0[n], ma1[n], err_msg=n)

    # warm steps: the fenced segments account for the wall, within 10%.
    # Both warm steps must pass on the best sample (host timer jitter on
    # loaded CI runners says don't gate on the worst).
    ratios = []
    for i in (1, 2):
        segs = sum(v for k, v in rec.step_seconds(i).items()
                   if k in SEGMENTS)
        ratios.append(segs / walls[i])
    assert any(abs(1.0 - r) <= 0.10 for r in ratios), (ratios, walls)

    from repro.models.config import ShapeConfig
    from repro.train.trainer import Trainer
    tr = Trainer(model, eng(), mesh, ShapeConfig("obs", 33, 16, "train"),
                 trace=None)
    s_ref = tr.engine.init_state(jax.random.key(0))
    ref_losses = []
    it = iter(tr.data)
    for _ in range(3):
        b = tr._shard_batch(next(it))
        s_ref, m = tr.step_fn(s_ref, b)
        ref_losses.append(float(tr.engine.metrics_to_host(m)["loss"]))
    tr.run(tr.engine.init_state(jax.random.key(0)), 3,
           print_fn=lambda *a, **k: None)
    assert tr.log.losses == ref_losses, (tr.log.losses, ref_losses)

    from repro.analysis import tags
    from repro.analysis.dataflow import analyze_jaxpr
    e2 = eng()
    step2 = e2.make_train_step(model.loss_fn(), {"tokens": P(AX)})
    inv = site_inventory(step2, e2.abstract_state(), batch)
    assert inv and inv == site_inventory(step2, e2.abstract_state(), batch)
    with tags.tagging():
        jx = jax.make_jaxpr(step2)(e2.abstract_state(), batch)
    census = {k[len("tags/"):]: v
              for k, v in analyze_jaxpr(jx).census.items()
              if k.startswith("tags/")}
    assert inv == census, (inv, census)
    print("SCENARIO_OK obs_trace_equivalence")


def obs_step_scopes():
    """Every phase of the monolithic step (obs.spans.SEGMENTS) names its ops
    on the 8-device topo mesh, where the stage-2 reduce-scatter and the
    cross-replica sync are real collectives; and the scopes change no
    number: 2 steps give bitwise the losses, grad norms and master shards
    of the same step built with every scope a null context."""
    import contextlib
    import re

    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.models.registry import build_model, get_arch
    from repro.obs import spans

    jax.config.update("jax_default_matmul_precision", "float32")
    mesh = _mesh()
    arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128, vocab=256)
    model = build_model(arch)
    cfg = _cfg("zero_topo", mesh, compute_dtype="float32")
    rng = np.random.default_rng(0)
    batch = {"tokens": jax.device_put(
        jnp.asarray(rng.integers(0, arch.vocab, (8, 33), dtype=np.int32)),
        NamedSharding(mesh, P(AX)))}

    def run():
        eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                         TrainHparams(lr=1e-3, total_steps=8, warmup_steps=0))
        step = eng.make_train_step(model.loss_fn(), {"tokens": P(AX)})
        state = eng.init_state(jax.random.key(0))
        hlo = step.lower(state, batch).compile().as_text()
        out = []
        for _ in range(2):
            state, m = step(state, batch)
            out.append((float(m["loss"]), float(m["grad_norm"])))
        master = {n: np.asarray(state["master"][n].addressable_data(0))
                  for n in sorted(eng.specs)}
        return hlo, out, master

    hlo, ms, master = run()
    parts = {p for n in re.findall(r'op_name="([^"]*)"', hlo)
             for p in n.split("/")}
    assert set(spans.SEGMENTS) <= parts, set(spans.SEGMENTS) - parts
    real = spans.scope
    spans.scope = lambda name: contextlib.nullcontext()
    try:
        _, ms0, master0 = run()
    finally:
        spans.scope = real
    assert ms == ms0, (ms, ms0)
    for n in master:
        np.testing.assert_array_equal(master[n], master0[n], err_msg=n)
    print("SCENARIO_OK obs_step_scopes")


def reshard_roundtrip():
    """Property test (DESIGN.md §11): random mesh-A -> mesh-B -> mesh-A
    reshard roundtrips are lossless — every state leaf sha256-identical to
    the original after crossing two different mesh shapes, schemes and
    quant blocks (different shard layouts AND different alignment padding).
    Also: strict mode (reshard=False) still refuses each cross-layout hop."""
    import hashlib
    import random
    import tempfile

    from repro.core.engine import TrainHparams, ZeroEngine
    from repro.launch.mesh import make_test_mesh, scheme_config
    from repro.models.registry import build_model, get_arch
    from repro.train import checkpoint

    def build(shape, scheme, qb):
        mesh = make_test_mesh(shape=shape, axes=AX)
        arch = get_arch("qwen2-0.5b").reduced(n_layers=2, d_model=128,
                                              vocab=256)
        model = build_model(arch)
        cfg = scheme_config(scheme, mesh, quant_block=qb,
                            compute_dtype="float32")
        eng = ZeroEngine(model.leaf_specs(), cfg, mesh,
                         TrainHparams(lr=1e-3, total_steps=8,
                                      warmup_steps=0))
        return mesh, model, eng, arch

    def hashes(eng, state, mesh):
        rep = jax.jit(lambda a: a, out_shardings=NamedSharding(mesh, P()))
        out = {}
        for k, v in checkpoint._flatten(state).items():
            a = np.asarray(rep(v).addressable_data(0))
            out[k] = (a.shape, hashlib.sha256(
                np.ascontiguousarray(a).tobytes()).hexdigest())
        return out

    rng = random.Random(2501_04266)
    shapes = [(2, 2, 2), (1, 2, 2), (2, 2, 1), (4, 1, 2), (1, 1, 2)]
    schemes = ["zero_topo", "zeropp", "zero3"]
    blocks = [64, 128]
    # random mesh shapes/blocks per trial; schemes rotate so every preset
    # appears on both sides of a hop (a pure random draw can collapse to
    # one scheme and never cross partition layouts)
    trials = []
    for i in range(3):
        a = (rng.choice(shapes), schemes[i], rng.choice(blocks))
        b = (rng.choice(shapes), schemes[(i + 1) % 3], rng.choice(blocks))
        trials.append((a, b))

    for spec_a, spec_b in trials:
        mesh_a, model_a, eng_a, arch = build(*spec_a)
        state = eng_a.init_state(jax.random.key(0))
        step = eng_a.make_train_step(model_a.loss_fn(), {"tokens": P(AX)})
        from repro.data.pipeline import shard_batch
        batch_np = {"tokens": np.random.default_rng(0).integers(
            0, arch.vocab, (8, 33)).astype(np.int32)}
        state, _ = step(state, shard_batch(batch_np, mesh_a,
                                           {"tokens": P(AX)}))
        want = hashes(eng_a, state, mesh_a)

        d1, d2 = tempfile.mkdtemp(), tempfile.mkdtemp()
        checkpoint.save(state, d1, 1, scheme=eng_a.scheme_fingerprint())

        mesh_b, _, eng_b, _ = build(*spec_b)
        # strict mode still refuses the cross-layout hop
        try:
            checkpoint.restore(d1, 1, eng_b.state_shardings(),
                               expect_scheme=eng_b.scheme_fingerprint())
            raise AssertionError(f"strict restore accepted {spec_a}->"
                                 f"{spec_b}")
        except (checkpoint.MeshMismatch, checkpoint.SchemeMismatch):
            pass
        st_b = checkpoint.restore(d1, 1, eng_b.state_shardings(),
                                  expect_scheme=eng_b.scheme_fingerprint(),
                                  reshard=True)
        checkpoint.save(st_b, d2, 1, scheme=eng_b.scheme_fingerprint())

        mesh_a2, _, eng_a2, _ = build(*spec_a)
        st_a2 = checkpoint.restore(d2, 1, eng_a2.state_shardings(),
                                   expect_scheme=eng_a2.scheme_fingerprint(),
                                   reshard=True)
        got = hashes(eng_a2, st_a2, mesh_a2)
        assert got == want, (spec_a, spec_b,
                             [k for k in want if got.get(k) != want[k]])
        print(f"  roundtrip {spec_a} -> {spec_b} -> {spec_a}: "
              f"{len(want)} leaves sha256-identical")
    print("SCENARIO_OK reshard_roundtrip")


SCENARIOS = dict(collectives=collectives,
                 reshard_roundtrip=reshard_roundtrip,
                 obs_trace_equivalence=obs_trace_equivalence,
                 obs_step_scopes=obs_step_scopes,
                 collectives_split=collectives_split,
                 overlap_equivalence=overlap_equivalence,
                 stream_grads_equivalence=stream_grads_equivalence,
                 kernel_impl_equivalence=kernel_impl_equivalence,
                 attn_scan_impl_equivalence=attn_scan_impl_equivalence,
                 auto_scheme=auto_scheme,
                 schemes_equivalent=schemes_equivalent,
                 dp_vs_single=dp_vs_single,
                 serve_sharded=serve_sharded,
                 hlo_census_real=hlo_census_real,
                 multipod_mesh=multipod_mesh,
                 resident_and_sp=resident_and_sp,
                 serve_resident_quant_equivalence=(
                     serve_resident_quant_equivalence))

if __name__ == "__main__":
    SCENARIOS[sys.argv[1]]()
