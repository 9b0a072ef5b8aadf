"""ZeroEngine: the distributed training/serving runtime (paper §V end-to-end).

Storage model (DeepSpeed-style): every parameter leaf is flattened, padded to
a multiple of ``os_degree * quant_block`` and stored as a 1-D *primary shard*
per device, sharded over the **weight axes** (L0) and replicated over the
extra-grad (L1) + replica (L2) axes. Optimizer state (fp32 master, Adam m/v)
lives in *optimizer-shard* layout: the same flat tensor sharded over **all**
axes. Stacked (per-layer) leaves carry a leading layer dimension that
``lax.scan`` consumes, so the per-layer weight all-gather happens inside the
scan body — one gather per layer per pass, exactly ZeRO-3's schedule.

The train step (inside one ``shard_map`` over the full mesh):

  1. value_and_grad of the model loss. MATMUL / GATHER_Q leaves use the
     custom-VJP path from ``linear.py`` (INT8 gather fwd, secondary-partition
     re-gather bwd, INT4 all-to-all reduce-scatter of the weight grad over
     the weight axes). Seed regime: differentiate w.r.t. the primary shards;
     cross-replica reduction is deferred and grads stay device-varying over
     the E/R axes. Streaming regime (``ZeroConfig.stream_grads``, §8):
     differentiate w.r.t. fp32 os-shard *sinks* — stacked leaves run the
     full reduce chain inside the reverse scan step and the accumulation
     buffer is os-layout (4psi/os instead of 4psi/w).
  2. stage-2 reduce-scatter of the accumulated primary-layout grads over the
     **extra-grad axes** (paper: intra-node a2a INT4 RS). Seed: once per
     step, after the backward; streaming: already folded into step 1, per
     layer per microbatch, overlapped with the backward matmuls.
  3. cross-replica sync over the **replica axes**: the paper's allreduce +
     select, or (beyond-paper) a reduce-scatter at half the volume (also
     folded into step 1 when streaming).
  4. AdamW on the fp32 master shard; grad-norm clipping uses one scalar psum.
  5. *update all-gather* over (E + R) axes rebuilds the bf16 primary shards
     (volume psi*(d-1)/d over the OS group, paper §V-D), optionally
     INT8-quantized (beyond-paper); stacked leaves gather their last axis in
     one batched collective.

Each phase runs under its ``obs.spans.SEGMENTS`` named scope (``fwd_bwd``,
``grad_rs_e``, ``cross_replica``, ``gnorm_clip``, ``update``), so a profiler
trace of the fused step splits by phase through each op's ``op_name``; the
backward shows there as ``transpose(jvp(...))`` inside ``fwd_bwd``.

``check_vma=False``: the engine manages replication manually — automatic
psum-insertion on replicated-input cotangents would defeat the paper's
deferred hierarchical gradient sync.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis.tags import tag as _contract_tag
from ..compat import shard_map
from ..obs import spans
from . import collectives as col
from . import schedule as sched
from .linear import (make_gather_issue, make_plain_gather, make_zero_gather_q,
                     make_zero_gather_q_pre, make_zero_gather_q_stream,
                     make_zero_gather_q_stream_pre, make_zero_matmul,
                     make_zero_matmul_pre, make_zero_matmul_stream,
                     make_zero_matmul_stream_pre)
from .partition import (EXPERT, GATHER_Q, MATMUL, PLAIN, LeafSpec, ZeroConfig,
                        grad_buffer_bytes, padded_flat_size,
                        prefetch_buffer_bytes)


def host_scalar(v):
    """Fetch a replicated scalar as a host numpy value on any process.

    Reading the first *addressable* shard is the whole fetch for a fully
    replicated array; a plain ``np.asarray``/``float`` would demand every
    shard and fail on multi-process arrays under older jax. The single
    shared implementation for trainer step counters, metric fetches and the
    test harness.
    """
    if hasattr(v, "addressable_data"):
        return np.asarray(v.addressable_data(0))
    return v


# ---------------------------------------------------------------------------
# Parameter views
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _LeafFns:
    spec: LeafSpec
    mm: Callable | None
    full: Callable
    issue: Callable | None = None      # prefetch: primary -> gathered buffer
    mm_pre: Callable | None = None     # matmul consuming a prefetched buffer
    full_pre: Callable | None = None   # dense tensor from a prefetched buffer
    # streaming-grad variants (DESIGN.md §8): take an os-shard sink whose
    # cotangent is the fully-reduced fp32 gradient row; built only for
    # stacked MATMUL/GATHER_Q leaves (the layer loop)
    mm_stream: Callable | None = None
    mm_stream_pre: Callable | None = None
    full_stream: Callable | None = None
    full_stream_pre: Callable | None = None


class ParamView:
    """What model code sees: named weights, materialized on demand.

    ``mm(name, x)`` runs the ZeRO matmul (gather fwd / secondary re-gather
    bwd / quantized grad RS) without ever saving the dense weight;
    ``get(name)`` materializes the dense tensor (embeddings, norms, scan
    params). For stacked leaves, ``stacked(names)`` returns the raw stacked
    primaries to feed ``lax.scan`` and ``sub(layer_slice)`` rebinds the view
    inside the scan body.

    With ``overlap=True`` (ZeroConfig.overlap), ``scan_layers``/``loop_layers``
    rotate a 2-slot prefetch buffer through the layer loop (schedule.py):
    views bound inside the loop carry the current layer's pre-gathered
    quantized weights in ``bufs`` and consume them via the ``*_pre`` VJPs
    instead of gathering inline.

    With ``sinks`` (ZeroConfig.stream_grads, DESIGN.md §8), the top-level
    view carries the per-leaf os-shard gradient sinks; the layer loops
    thread one row per layer to the bound sub-views, whose ``mm``/``get``
    route through the ``*_stream`` VJPs so each layer's weight cotangent is
    fully reduced inside the backward.
    """

    # class-level defaults so subclasses with their own __init__
    # (serve.resident.ResidentView, which also has no _fns) inherit the
    # non-overlap behavior without any getattr probing
    _fns: dict[str, "_LeafFns"] | None = None
    _bufs: dict[str, Any] | None = None
    _sinks: dict[str, Any] | None = None
    _overlap: bool = False

    def __init__(self, fns: dict[str, _LeafFns], primaries: dict[str, Any],
                 bufs: dict[str, Any] | None = None, overlap: bool = False,
                 sinks: dict[str, Any] | None = None):
        self._fns = fns
        self._p = primaries
        self._bufs = bufs
        self._overlap = overlap
        self._sinks = sinks

    def _buf(self, name: str):
        return None if self._bufs is None else self._bufs.get(name)

    def _sink(self, name: str):
        return None if self._sinks is None else self._sinks.get(name)

    def sink_stack(self, name: str):
        """Full (layers, os_shard) sink for a stacked leaf, else None."""
        return self._sink(name)

    def sink_stacks(self, names) -> dict[str, Any]:
        return {} if self._sinks is None else \
            {n: self._sinks[n] for n in names if n in self._sinks}

    def mm(self, name: str, x, transpose: bool = False):
        fn = self._fns[name]
        assert fn.mm is not None, f"{name} is not a matmul leaf"
        buf = self._buf(name)
        sink = self._sink(name)
        if sink is not None:
            sink = _contract_tag(sink, role="sink", machine="stream",
                                 name=name)
            if buf is not None and fn.mm_stream_pre is not None:
                return fn.mm_stream_pre(x, self._p[name], buf, sink, transpose)
            if fn.mm_stream is not None:
                return fn.mm_stream(x, self._p[name], sink, transpose)
        if buf is not None and fn.mm_pre is not None:
            return fn.mm_pre(x, self._p[name], buf, transpose)
        return fn.mm(x, self._p[name], transpose)

    def get(self, name: str):
        fn = self._fns[name]
        buf = self._buf(name)
        sink = self._sink(name)
        if sink is not None:
            sink = _contract_tag(sink, role="sink", machine="stream",
                                 name=name)
            if buf is not None and fn.full_stream_pre is not None:
                return fn.full_stream_pre(self._p[name], buf, sink)
            if fn.full_stream is not None:
                return fn.full_stream(self._p[name], sink)
        if buf is not None and fn.full_pre is not None:
            return fn.full_pre(self._p[name], buf)
        return fn.full(self._p[name])

    def embed_lookup(self, name: str, ids):
        """Token-embedding gather. Overridable (resident TP shards rows)."""
        import jax.numpy as jnp
        return jnp.take(self.get(name), ids, axis=0)

    def expert_ffn(self, prefix: str, e_in):
        """MoE expert GLU FFN on dispatched slots e_in (E, C, d) -> (E, C, d).

        Default: dense-materialized experts (ZeRO gather). ResidentView
        overrides with Megatron-style sharded experts + one psum.
        """
        import jax
        import jax.numpy as jnp
        wg = self.get(prefix + "w_gate")
        wu = self.get(prefix + "w_up")
        wd = self.get(prefix + "w_down")
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", e_in, wg)) \
            * jnp.einsum("ecd,edf->ecf", e_in, wu)
        return jnp.einsum("ecf,efd->ecd", h, wd)

    def has(self, name: str) -> bool:
        return name in self._p

    def stacked(self, names) -> dict[str, Any]:
        return {n: self._p[n] for n in names}

    def sub(self, primaries: dict[str, Any],
            bufs: dict[str, Any] | None = None,
            sinks: dict[str, Any] | None = None) -> "ParamView":
        return ParamView(self._fns, primaries, bufs=bufs,
                         overlap=self._overlap, sinks=sinks)

    def scan_layers(self, body, carry, names, *, remat: bool = True,
                    unroll: int = 1, with_ys: bool = False,
                    overlap: bool | None = None):
        """lax.scan over stacked leaves `names`, via the comm-schedule layer
        (core/schedule.py): the 2-slot gather-prefetch rotation and the
        streaming grad sinks both ride the scan xs/carry there.

        body(view, carry) -> carry, or (carry, y) when ``with_ys`` (per-layer
        outputs are stacked like lax.scan's ys). ``overlap=None`` inherits
        the view's setting (ZeroConfig.overlap via the engine).
        """
        return sched.scan_layers(self, body, carry, names, remat=remat,
                                 unroll=unroll, with_ys=with_ys,
                                 overlap=overlap)

    def loop_layers(self, body, carry, steps, *, remat: bool = True,
                    overlap: bool | None = None):
        """Python loop for heterogeneous block patterns, via
        core/schedule.py (same rotation/sink threading as ``scan_layers``,
        across block-kind boundaries — gemma3's 5:1 local:global interleave,
        jamba's mamba/attn mix).

        steps: sequence of ``(tag, layer_primaries)`` pairs — one entry per
        layer in pattern order, ``layer_primaries`` already indexed out of
        the per-kind stacks. body(view, carry, tag) -> (carry, y).
        Returns (carry, [y per layer]).
        """
        return sched.loop_layers(self, body, carry, steps, remat=remat,
                                 overlap=overlap)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

def _storage_shape(spec: LeafSpec, shard_len: int) -> tuple[int, ...]:
    return (spec.stack, shard_len) if spec.stack else (shard_len,)


@dataclass
class TrainHparams:
    lr: float = 3e-4
    betas: tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 10
    total_steps: int = 1000
    min_lr_frac: float = 0.1
    n_microbatch: int = 1
    overlap: bool | None = None   # None = follow ZeroConfig.overlap; a bool
    # here overrides the scheme config (launch/train.py --overlap plumbs this)
    stream_grads: bool | None = None  # None = follow ZeroConfig.stream_grads;
    # a bool overrides the scheme config (launch/train.py --stream-grads)


class ZeroEngine:
    """Builds sharded state + train/serve steps for one model under one scheme."""

    def __init__(self, specs: dict[str, LeafSpec], cfg: ZeroConfig, mesh: Mesh,
                 hp: TrainHparams | None = None):
        if hp is not None:
            over = {}
            if hp.overlap is not None and hp.overlap != cfg.overlap:
                over["overlap"] = hp.overlap
            if hp.stream_grads is not None \
                    and hp.stream_grads != cfg.stream_grads:
                over["stream_grads"] = hp.stream_grads
            if over:
                import dataclasses
                cfg = dataclasses.replace(cfg, **over)
        cfg.validate_dependency_rule()
        for a, size in cfg.axis_sizes:
            assert a in mesh.axis_names and mesh.shape[a] == size, \
                (a, size, dict(mesh.shape))
        self.specs = dict(specs)
        self.cfg = cfg
        self.mesh = mesh
        self.hp = hp or TrainHparams()
        # per-leaf configs: small leaves get a reduced quant block so the
        # os_degree*block alignment padding stays proportionate
        self.leaf_cfg = {n: cfg.for_leaf(s.logical_size)
                         for n, s in self.specs.items()}
        self.fns = {n: self._build_fns(s) for n, s in self.specs.items()}

        self._pad = {n: padded_flat_size(s.logical_size, cfg)
                     for n, s in self.specs.items()}

    # -- per-leaf machinery --------------------------------------------------

    def _layer_spec(self, spec: LeafSpec) -> LeafSpec:
        import dataclasses
        return dataclasses.replace(spec, stack=None)

    def _build_fns(self, spec: LeafSpec) -> _LeafFns:
        ls = self._layer_spec(spec)
        cfg = self.leaf_cfg[spec.name] if spec.name in self.leaf_cfg \
            else self.cfg.for_leaf(ls.logical_size)
        # streaming variants exist only for stacked leaves: a stacked leaf's
        # per-layer slice is consumed exactly once per pass, so its stage-2
        # quantization sees the same values as the seed path (bitwise at
        # n_microbatch=1); a shared non-stacked leaf (tied embeddings) can
        # be used twice per pass and stays on the primary-layout path
        stream = bool(spec.stack)
        if spec.kind == MATMUL:
            return _LeafFns(
                spec, make_zero_matmul(ls, cfg),
                make_zero_gather_q(ls, cfg),
                issue=make_gather_issue(ls, cfg),
                mm_pre=make_zero_matmul_pre(ls, cfg),
                full_pre=make_zero_gather_q_pre(ls, cfg),
                mm_stream=make_zero_matmul_stream(ls, cfg) if stream else None,
                mm_stream_pre=make_zero_matmul_stream_pre(ls, cfg)
                if stream else None,
                full_stream=make_zero_gather_q_stream(ls, cfg)
                if stream else None,
                full_stream_pre=make_zero_gather_q_stream_pre(ls, cfg)
                if stream else None)
        if spec.kind == GATHER_Q:
            return _LeafFns(
                spec, None, make_zero_gather_q(ls, cfg),
                issue=make_gather_issue(ls, cfg),
                full_pre=make_zero_gather_q_pre(ls, cfg),
                full_stream=make_zero_gather_q_stream(ls, cfg)
                if stream else None,
                full_stream_pre=make_zero_gather_q_stream_pre(ls, cfg)
                if stream else None)
        if spec.kind == PLAIN:
            return _LeafFns(spec, None, make_plain_gather(ls, cfg))
        raise ValueError(spec.kind)

    # -- shapes & shardings ---------------------------------------------------

    def primary_shard_len(self, name: str) -> int:
        return self._pad[name] // self.cfg.w_degree

    def os_shard_len(self, name: str) -> int:
        return self._pad[name] // self.cfg.os_degree

    def _primary_spec(self, spec: LeafSpec) -> P:
        w = self.cfg.axes.weight
        return P(None, w) if spec.stack else P(w)

    def _os_spec(self, spec: LeafSpec) -> P:
        a = self.cfg.axes.all
        return P(None, a) if spec.stack else P(a)

    def state_shardings(self):
        prim = {n: NamedSharding(self.mesh, self._primary_spec(s))
                for n, s in self.specs.items()}
        osd = {n: NamedSharding(self.mesh, self._os_spec(s))
               for n, s in self.specs.items()}
        rep = NamedSharding(self.mesh, P())
        return dict(primaries=prim, master=osd, opt_m=osd, opt_v=osd, step=rep)

    def state_in_specs(self):
        prim = {n: self._primary_spec(s) for n, s in self.specs.items()}
        osd = {n: self._os_spec(s) for n, s in self.specs.items()}
        return dict(primaries=prim, master=osd, opt_m=osd, opt_v=osd, step=P())

    def abstract_state(self):
        """ShapeDtypeStructs (global shapes) with shardings — for .lower()."""
        sh = self.state_shardings()
        cdt = jnp.dtype(self.cfg.compute_dtype)

        def leaf(n, s, dtype, kind):
            length = self._pad[n]
            return jax.ShapeDtypeStruct(_storage_shape(s, length), dtype,
                                        sharding=sh[kind][n] if kind != "step" else sh["step"])

        state = dict(
            primaries={n: leaf(n, s, cdt, "primaries") for n, s in self.specs.items()},
            master={n: leaf(n, s, jnp.float32, "master") for n, s in self.specs.items()},
            opt_m={n: leaf(n, s, jnp.float32, "opt_m") for n, s in self.specs.items()},
            opt_v={n: leaf(n, s, jnp.float32, "opt_v") for n, s in self.specs.items()},
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=sh["step"]),
        )
        return state

    def scheme_fingerprint(self) -> dict:
        """Layout identity of this engine's checkpoints (JSON-serializable).

        Everything that determines the on-disk shard layout: a checkpoint
        written under one fingerprint cannot be restored under another
        (train/checkpoint.py fails loudly on mismatch).
        """
        fp = self.cfg.fingerprint()
        fp["padded_sizes"] = {n: self._pad[n] for n in sorted(self._pad)}
        return fp

    def param_count(self) -> int:
        return sum(s.logical_size * (s.stack or 1) for s in self.specs.values())

    def padded_param_count(self) -> int:
        return sum(self._pad[n] * (s.stack or 1) for n, s in self.specs.items())

    def stream_leaf_names(self) -> tuple[str, ...]:
        """Leaves on the streaming grad path (stacked MATMUL/GATHER_Q):
        their microbatch gradients accumulate in fp32 os-shard layout."""
        return tuple(n for n in sorted(self.specs)
                     if self.specs[n].stack
                     and self.specs[n].kind in (MATMUL, GATHER_Q))

    def _prefetch_slot_bytes(self) -> int:
        """One slot of the 2-slot gather-prefetch buffer (DESIGN.md §3): the
        largest single layer's gathered wire-format weights — INT8 payload +
        f32 scales when quantized, compute dtype otherwise — summed over
        that layer's prefetchable leaves."""
        per_kind: dict[str, int] = {}
        bytes_per = jnp.dtype(self.cfg.compute_dtype).itemsize
        for n, s in self.specs.items():
            if not s.stack or self.fns[n].issue is None:
                continue
            kind = n.split(".", 1)[0]
            pad = self._pad[n]
            lcfg = self.leaf_cfg[n]
            b = pad + 4 * pad // lcfg.quant_block \
                if lcfg.quantize_weights else bytes_per * pad
            per_kind[kind] = per_kind.get(kind, 0) + b
        return max(per_kind.values(), default=0)

    def memory_report(self) -> dict[str, float]:
        """Per-device training-state bytes (paper Tables V/VI analogue).

        ``grad_buffer`` is exact per-leaf accounting of what the step
        allocates: streamed leaves (``stream_leaf_names``) at fp32 os-shard
        layout, everything else at the fp32 primary-layout accumulation —
        one shared formula with ``benchmarks/memory_table.py`` and
        ``topo.cost`` (partition.grad_buffer_bytes). ``prefetch_buffer`` is
        the 2-slot gathered-weight buffer the §3 overlap schedule keeps
        live (0 when overlap is off)."""
        cfg = self.cfg
        psi = self.padded_param_count()
        bytes_per = jnp.dtype(cfg.compute_dtype).itemsize
        primary = bytes_per * psi // cfg.w_degree
        sec = 0 if cfg.sec_degree is None else \
            (psi // cfg.sec_degree + 4 * psi // (cfg.quant_block * cfg.sec_degree))
        stream = set(self.stream_leaf_names()) if cfg.stream_grads else set()
        grads_buf = sum(
            grad_buffer_bytes(cfg, self._pad[n] * (s.stack or 1),
                              streaming=(n in stream))
            for n, s in self.specs.items())
        optimizer = 12 * psi // cfg.os_degree
        prefetch = prefetch_buffer_bytes(cfg, self._prefetch_slot_bytes())
        return dict(primary=primary, secondary=sec, grad_buffer=grads_buf,
                    optimizer=optimizer, prefetch_buffer=prefetch,
                    total=primary + sec + grads_buf + optimizer + prefetch)

    # -- init -----------------------------------------------------------------

    def _init_full(self, name: str, key) -> jnp.ndarray:
        """Global padded fp32 init for one leaf (layout: [stack,] pad)."""
        spec = self.specs[name]
        pad = self._pad[name]
        n = spec.logical_size
        shape = _storage_shape(spec, pad)
        if spec.init == "zeros":
            return jnp.zeros(shape, jnp.float32)
        if spec.init == "ones":
            base = jnp.ones((spec.stack or 1, n), jnp.float32)
        elif spec.init == "ssm_a":
            # mamba: A_log = log(1..d_state) broadcast over d_inner
            d_inner, d_state = spec.shape
            a = jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32))
            base = jnp.broadcast_to(a, (spec.stack or 1, d_inner, d_state))
            base = base.reshape(spec.stack or 1, n)
        elif spec.init == "dt_bias":
            import numpy as _np
            lo, hi = 1e-3, 1e-1
            u = jax.random.uniform(key, (spec.stack or 1, n), jnp.float32)
            base = jnp.log(jnp.exp(jnp.exp(u * (math.log(hi) - math.log(lo))
                                           + math.log(lo))) - 1.0 + 1e-9)
        else:
            scale = spec.init_scale
            if scale is None:
                fan_in = spec.shape[0] if len(spec.shape) >= 2 else n
                scale = 1.0 / math.sqrt(max(fan_in, 1))
            base = jax.random.normal(key, (spec.stack or 1, n), jnp.float32) * scale
        full = jnp.zeros((spec.stack or 1, pad), jnp.float32)
        full = lax.dynamic_update_slice_in_dim(full, base, 0, axis=1)
        return full if spec.stack else full[0]

    def init_state(self, key):
        """jit-compiled global init; out_shardings place the shards."""
        sh = self.state_shardings()
        names = sorted(self.specs)
        keys = {n: k for n, k in zip(names, jax.random.split(key, len(names)))}

        def build():
            master = {n: self._init_full(n, keys[n]) for n in names}
            prim = {n: master[n].astype(self.cfg.compute_dtype) for n in names}
            zeros = {n: jnp.zeros_like(master[n]) for n in names}
            return dict(primaries=prim, master=master, opt_m=zeros,
                        opt_v={n: jnp.zeros_like(master[n]) for n in names},
                        step=jnp.zeros((), jnp.int32))

        out_sh = dict(primaries=sh["primaries"], master=sh["master"],
                      opt_m=sh["opt_m"], opt_v=sh["opt_v"], step=sh["step"])
        return jax.jit(build, out_shardings=out_sh)()

    # -- schedule --------------------------------------------------------------

    def _lr(self, step):
        hp = self.hp
        warm = jnp.minimum(step / max(hp.warmup_steps, 1), 1.0)
        t = jnp.clip((step - hp.warmup_steps)
                     / max(hp.total_steps - hp.warmup_steps, 1), 0.0, 1.0)
        cos = hp.min_lr_frac + (1 - hp.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
        return hp.lr * warm * cos

    # -- the train step ---------------------------------------------------------

    # -- post-backward helpers (shared by both grad regimes) -------------------

    def _zero_sinks(self):
        """fp32 optimizer-shard gradient sinks for the streamed leaves: the
        zeros whose cotangent stack IS the os-layout accumulation buffer."""
        return {n: jnp.zeros(_storage_shape(self.specs[n],
                                            self.os_shard_len(n)),
                             jnp.float32)
                for n in self.stream_leaf_names()}

    def _stage2_rs(self, name: str, g):
        """Stage 2 for a primary-layout grad: reduce-scatter over the
        extra-grad axes (paper: intra-node a2a INT4 RS). Output is scattered
        over weight+extra-grad axes but still device-varying over the
        replica axes — stage 3 below completes the sync."""
        lcfg = self.leaf_cfg[name]
        with spans.scope("grad_rs_e"):
            g = g.astype(jnp.float32)
            flat = g.reshape(-1, g.shape[-1]) if g.ndim > 1 else g[None]
            out = jax.vmap(lambda row: col.reduce_scatter_flat(
                row, lcfg.axes.extra_grad, lcfg))(flat)
            return out if g.ndim > 1 else out[0]

    def _replica_sync(self, name: str, g):
        """Stage 3: cross-replica sync of a stage-2-scattered grad."""
        lcfg = self.leaf_cfg[name]
        with spans.scope("cross_replica"):
            flat = g.reshape(-1, g.shape[-1]) if g.ndim > 1 else g[None]
            out = jax.vmap(lambda row: col.cross_replica_grad(row, lcfg))(flat)
            return out if g.ndim > 1 else out[0]

    def _to_os(self, name: str, g):
        """Stage 2 + 3 for a primary-layout grad (seed path; streamed
        leaves arrive here already reduced). Split into the two stages so
        the phased traced step (obs/phased.py) can fence each phase while
        running the identical per-row collectives."""
        return self._replica_sync(name, self._stage2_rs(name, g))

    def _grads_to_os(self, g_primary: dict, g_os: dict) -> dict:
        """Assemble the full optimizer-shard grad dict in sorted-leaf order
        (the order the grad-norm fold below depends on): streamed leaves
        pass through, primary-layout leaves run the seed stage-2/3 chain."""
        return {n: g_os[n] if n in g_os else self._to_os(n, g_primary[n])
                for n in sorted(self.specs)}

    def _apply_updates(self, state, os_grads: dict):
        """AdamW on the master shards + the update all-gather, vectorized
        over stacked leaves (paper §V-C/D).

        ``adamw_update`` is elementwise and runs on the whole (layers,
        shard) leaf at once; ``collectives.update_all_gather`` tiles the
        last axis directly, so stacked leaves rebuild their bf16 primaries
        with one batched collective instead of a per-row vmap (same data
        movement, bitwise-identical values)."""
        from ..optim.adamw import adamw_update
        cfg, hp = self.cfg, self.hp
        b1, b2 = hp.betas
        cdt = jnp.dtype(cfg.compute_dtype)
        new_m, new_v, new_master, new_prim = {}, {}, {}, {}
        with spans.scope("update"):
            step = state["step"] + 1
            lr = self._lr(state["step"])
            for n in sorted(self.specs):
                wd = hp.weight_decay \
                    if self.specs[n].kind in (MATMUL, GATHER_Q) else 0.0
                master, m, v = adamw_update(
                    state["master"][n], state["opt_m"][n], state["opt_v"][n],
                    os_grads[n], step=step, lr=lr, beta1=b1, beta2=b2,
                    eps=hp.eps, weight_decay=wd)
                new_m[n], new_v[n], new_master[n] = m, v, master
                new_prim[n] = col.update_all_gather(master, self.leaf_cfg[n],
                                                    cdt)
        return dict(primaries=new_prim, master=new_master,
                    opt_m=new_m, opt_v=new_v, step=step), lr

    def make_train_step(self, loss_fn: Callable, batch_specs: dict[str, P]):
        """loss_fn(view, batch) -> (loss_sum, token_count). Returns jit'd step.

        Two gradient regimes (DESIGN.md §8):

        * seed (``stream_grads=False``): differentiate w.r.t. the primaries;
          microbatch grads accumulate in fp32 **primary layout**
          (4*psi/w_degree), then one stage-2 reduce-scatter + cross-replica
          sync per step lifts them to optimizer-shard layout (``_to_os``).
        * streaming (``stream_grads=True``): stacked-leaf cotangents leave
          the backward already reduced — differentiate w.r.t. the os-shard
          **sinks** (plus the few non-stacked/PLAIN primaries), so the
          accumulation buffer is fp32 os-shard layout (4*psi/os_degree) and
          the per-layer grad collectives overlap the backward. Bitwise
          identical to the seed regime at n_microbatch=1; at n_microbatch>1
          the stage-2 quantization applies per microbatch (within
          block-quant tolerance of the seed path, still bitwise across
          kernel impls and process layouts).
        """
        cfg = self.cfg
        mesh = self.mesh
        state_specs = self.state_in_specs()
        stream = cfg.stream_grads
        local_grads = self._make_local_grads(loss_fn)

        def local_step(state, batch):
            with spans.scope("fwd_bwd"):
                grads, loss_rep, gtok = local_grads(state["primaries"], batch)

            g_legacy, g_sinks = grads if stream else (grads, {})
            os_grads = self._grads_to_os(g_legacy, g_sinks)

            new_state, metrics = self._finish_step(state, os_grads,
                                                   loss_rep, gtok)
            return new_state, metrics

        sm = shard_map(
            local_step, mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, {k: P() for k in
                                     ("loss", "grad_norm", "lr", "tokens")}),
            check_vma=False)
        return jax.jit(sm, donate_argnums=(0,))

    def _make_local_grads(self, loss_fn: Callable) -> Callable:
        """The microbatch value_and_grad loop of the train step as a
        reusable *local* function (must run inside shard_map):
        ``local_grads(primaries, batch) -> (grads, loss_rep, gtok)`` with
        ``grads`` still in the differentiation layout — a primary dict, or
        ``(legacy_primaries, os_sinks)`` when streaming. Shared verbatim by
        ``make_train_step`` and the phased traced step (obs/phased.py), so
        the two can never diverge."""
        cfg = self.cfg
        hp = self.hp
        stream = cfg.stream_grads
        snames = set(self.stream_leaf_names()) if stream else set()

        def local_grads(primaries, batch):

            def mb_loss(diff, mb):
                if stream:
                    legacy_p, sinks = diff
                    prims = dict(primaries)
                    prims.update(legacy_p)
                else:
                    prims, sinks = diff, None
                view = ParamView(self.fns, prims, overlap=cfg.overlap,
                                 sinks=sinks)
                loss_sum, tok = loss_fn(view, mb)
                # contract: allow[raw-psum] -- integer token counts in f32:
                # exact in any summation order, no det_psum needed
                gtok = lax.psum(tok.astype(jnp.float32), cfg.axes.all)
                return loss_sum.astype(jnp.float32) / jnp.maximum(gtok, 1.0), gtok

            if stream:
                diff0 = ({n: p for n, p in primaries.items()
                          if n not in snames}, self._zero_sinks())
            else:
                diff0 = primaries

            n_mb = hp.n_microbatch
            if n_mb == 1:
                (loss, gtok), grads = jax.value_and_grad(mb_loss, has_aux=True)(
                    diff0, batch)
            else:
                def split(x):
                    return x.reshape((n_mb, x.shape[0] // n_mb) + x.shape[1:])
                mbs = jax.tree.map(split, batch)

                def acc(carry, mb):
                    gacc, lacc, tacc = carry
                    (l, t), g = jax.value_and_grad(mb_loss, has_aux=True)(
                        diff0, mb)
                    gacc = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), gacc, g)
                    return (gacc, lacc + l, tacc + t), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), diff0)
                (grads, loss, gtok), _ = lax.scan(
                    acc, (g0, jnp.zeros((), jnp.float32),
                          jnp.zeros((), jnp.float32)), mbs)
                # each microbatch loss is normalized by its own global token
                # count; average the accumulated means
                grads = jax.tree.map(lambda g: g / n_mb, grads)
                loss = loss / n_mb

            # global loss for reporting: sum of per-device (local/global_tok).
            # det_psum, not lax.psum: the reduction order must not depend on
            # how the mesh is split across processes (tests/_mp.py asserts a
            # 2x4 cluster reproduces the 1x8 run bitwise). gtok above stays a
            # plain psum — token counts are integers in float32, exact in
            # any summation order.
            loss_rep = col.det_psum(loss, cfg.axes.all)
            return grads, loss_rep, gtok

        return local_grads

    def _clip_grads(self, os_grads: dict):
        """Grad-norm clip (global: os shards partition the full gradient).
        det_psum: gnorm feeds the clip scale applied to every gradient, so
        a transport-dependent reduction order here would make the entire
        update drift across process layouts."""
        with spans.scope("gnorm_clip"):
            sq = sum(jnp.sum(jnp.square(g)) for g in os_grads.values())
            gnorm = jnp.sqrt(col.det_psum(sq, self.cfg.axes.all))
            scale = jnp.minimum(1.0, self.hp.grad_clip / (gnorm + 1e-6))
            return {n: g * scale for n, g in os_grads.items()}, gnorm

    def _finish_step(self, state, os_grads: dict, loss_rep, gtok):
        """Post-reduction tail of the train step (local, inside shard_map):
        clip + AdamW/update-gather + metrics assembly.

        gtok: global token count summed over every microbatch (with
        n_mb == 1 it is the single microbatch's global count). Both it and
        loss_rep/gnorm are psummed over cfg.axes.all — which includes any
        process-spanning axis — so the metrics leaving the step are
        CLUSTER-global, not process-local; metrics_to_host fetches them on
        every process without a second collective."""
        os_grads, gnorm = self._clip_grads(os_grads)
        new_state, lr = self._apply_updates(state, os_grads)
        metrics = dict(loss=loss_rep, grad_norm=gnorm, lr=lr, tokens=gtok)
        return new_state, metrics

    @staticmethod
    def metrics_to_host(metrics) -> dict[str, float]:
        """Fetch step metrics as python floats on every process.

        The train/eval steps emit metrics with out_spec ``P()`` after a psum
        over ``cfg.axes.all``, so each metric is fully replicated — globally
        aggregated already, even when the mesh spans processes.
        """
        return {k: float(host_scalar(v)) for k, v in metrics.items()}

    # -- eval / serve steps ------------------------------------------------------

    def make_eval_step(self, loss_fn: Callable, batch_specs: dict[str, P]):
        state_specs = self.state_in_specs()

        def local_eval(state, batch):
            view = ParamView(self.fns, state["primaries"],
                             overlap=self.cfg.overlap)
            loss_sum, tok = loss_fn(view, batch)
            # gtok: integer-valued, exact under any order; loss: det_psum so
            # eval losses match bitwise across process layouts (train step
            # rationale above)
            # contract: allow[raw-psum] -- integer token counts, order-exact
            gtok = lax.psum(tok.astype(jnp.float32), self.cfg.axes.all)
            loss = col.det_psum(loss_sum.astype(jnp.float32),
                                self.cfg.axes.all)
            return loss / jnp.maximum(gtok, 1.0)

        sm = shard_map(local_eval, mesh=self.mesh,
                           in_specs=(state_specs, batch_specs),
                           out_specs=P(), check_vma=False)
        return jax.jit(sm)

    def make_apply(self, fn: Callable, in_specs, out_specs):
        """Generic shard_map-wrapped forward: fn(view, *args)."""
        prim_specs = self.state_in_specs()["primaries"]

        def local(primaries, *args):
            view = ParamView(self.fns, primaries, overlap=self.cfg.overlap)
            return fn(view, *args)

        sm = shard_map(local, mesh=self.mesh,
                           in_specs=(prim_specs,) + tuple(in_specs),
                           out_specs=out_specs, check_vma=False)
        return jax.jit(sm)

    def abstract_primaries(self):
        sh = self.state_shardings()["primaries"]
        cdt = jnp.dtype(self.cfg.compute_dtype)
        return {n: jax.ShapeDtypeStruct(
            _storage_shape(s, self._pad[n]), cdt, sharding=sh[n])
            for n, s in self.specs.items()}
