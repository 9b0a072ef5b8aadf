"""Quantization-assisted collectives (paper §V, ZeRO++ §III-C).

All functions run *inside* ``shard_map`` and take mesh axis-name tuples,
ordered major -> minor, matching the canonical flat-slice hierarchy of
``partition.py``. Empty axis tuples degrade to no-ops so the same engine code
expresses ZeRO-1/2/3, ZeRO++ and ZeRO-topo.

The key primitive is the **all-to-all based quantized reduce-scatter**
(ZeRO++ §"quantized gradients"): instead of a ring reduce-scatter that would
quantize/dequantize at every hop (accumulating error log(d) times), the input
is split into d chunks, each chunk is quantized once, exchanged with a single
all-to-all, dequantized once, and reduced locally.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..analysis.tags import tag as _tag
from ..compat import pvary as _compat_pvary
from ..kernels import ops
from .partition import ZeroConfig

AxisTuple = tuple[str, ...]


def pvary(x, axes: AxisTuple):
    """Mark x as device-varying over `axes` (defers cross-replica psums)."""
    return _compat_pvary(x, axes)


def unvary(x, axes: AxisTuple):
    """Assert x is replicated over `axes` and drop the varying type."""
    if not axes:
        return x
    # pcast 'to_invariant' isn't exposed portably; an axis-wise max is a
    # semantic no-op on replicated values and re-types the array.
    return x


def det_psum(x, axes: AxisTuple):
    """Order-deterministic psum of a (near-)scalar: all-gather the per-device
    partials and reduce them locally in axis-index order.

    ``lax.psum``'s reduction order is transport-dependent — the in-process
    XLA ring and a cross-process gloo/NCCL tree associate the sum
    differently, so a metric computed with it drifts in the last float bits
    when the same mesh is split across processes. The all-gather is pure
    data movement (bitwise-safe on any transport) and lands the partials in
    canonical axis-index order on every device, so the local sum is bitwise
    identical across process layouts. Scalars/metrics only: the gather
    costs group_size elements per device.
    """
    if not axes:
        return x
    g = lax.all_gather(x, tuple(axes))
    return jnp.sum(g, axis=0)


def activation_psum(x, axes: AxisTuple, out_dtype=None):
    """Tensor-parallel activation reduction (serving/inference paths).

    Accumulates in fp32 regardless of the activation dtype — partial matmul
    products are the classic catastrophic-cancellation site — and is the one
    sanctioned home for a floating-point ``lax.psum`` on activations: TP
    activation sums stay on the intra tier by construction (the TP axes are
    the model axes), so the dtype-tier policy (DESIGN.md §9) does not apply,
    but routing them through here keeps the raw-psum lint rule's allowlist
    at exactly one file.
    """
    if not axes:
        return x if out_dtype is None else x.astype(out_dtype)
    out = lax.psum(x.astype(jnp.float32), tuple(axes))
    return out if out_dtype is None else out.astype(out_dtype)


def all_gather_flat(shard, axes: AxisTuple):
    """Plain (unquantized) tiled all-gather of a flat shard. AD: psum_scatter."""
    if not axes:
        return shard
    return lax.all_gather(shard, tuple(axes), tiled=True, axis=shard.ndim - 1)


def quant_all_gather_int8(shard, axes: AxisTuple, cfg: ZeroConfig,
                          out_dtype=jnp.bfloat16):
    """INT8 block-quantized all-gather: quantize -> gather(q, s) -> dequant.

    Halves the gather volume vs FP16/BF16 (paper Table VII). Returns the full
    dequantized tensor *and* the gathered quantized copy + scales (the caller
    may slice a secondary partition out of them at zero extra cost).
    """
    if not axes:
        q, s = ops.quantize_int8(shard, cfg.quant_block, impl=cfg.impl)
        return ops.dequantize_int8(q, s, cfg.quant_block, out_dtype, impl=cfg.impl), q, s
    q, s = ops.quantize_int8(shard, cfg.quant_block, impl=cfg.impl)
    qf = lax.all_gather(q, tuple(axes), tiled=True)
    sf = lax.all_gather(s, tuple(axes), tiled=True)
    full = ops.dequantize_int8(qf, sf, cfg.quant_block, out_dtype, impl=cfg.impl)
    return full, qf, sf


def dequant_gathered(qf, sf, axes_idx_len, cfg: ZeroConfig, out_dtype=jnp.bfloat16):
    return ops.dequantize_int8(qf, sf, cfg.quant_block, out_dtype, impl=cfg.impl)


# -- gather-issue / gather-wait split (prefetch/overlap, DESIGN.md §3) -------
#
# ``quant_all_gather_int8`` fuses quantize -> gather -> dequant into the
# consuming block, which puts the collective on the critical path of the
# layer that uses the weights.  The split primitives below let the engine
# *issue* layer i+1's gather while layer i computes: ``gather_issue_int8``
# ends at the collective (its result has no data dependency on the current
# layer's math, so XLA's latency-hiding scheduler can run it concurrently)
# and ``gather_wait_int8`` performs the local dequant at consume time.
# issue+wait is op-for-op the fused path, so results are bitwise identical.

def gather_issue_int8(shard, axes: AxisTuple, cfg: ZeroConfig):
    """Quantize + all-gather a flat shard, *without* dequantizing.

    Returns the gathered (q, scales) pair — the 2-slot prefetch buffer
    format. Same wire traffic as ``quant_all_gather_int8``.
    """
    q, s = ops.quantize_int8(shard, cfg.quant_block, impl=cfg.impl)
    if axes:
        q = lax.all_gather(q, tuple(axes), tiled=True)
        s = lax.all_gather(s, tuple(axes), tiled=True)
    return _tag((q, s), role="issue", machine="gather")


def gather_wait_int8(qf, sf, cfg: ZeroConfig, out_dtype=jnp.bfloat16):
    """Local dequant of a prefetched (q, scales) buffer (no communication)."""
    qf, sf = _tag((qf, sf), role="wait", machine="gather")
    return ops.dequantize_int8(qf, sf, cfg.quant_block, out_dtype,
                               impl=cfg.impl)


# -- a2a-RS issue / wait split (streaming grad path, DESIGN.md §8) -----------
#
# Mirrors the ``gather_issue_int8``/``gather_wait_int8`` split above, for the
# other direction: ``a2a_rs_issue`` ends at the all-to-all (quantize + a2a,
# no dequant — the point where the collective leaves the device), and
# ``a2a_rs_wait`` is the pure-local receive side (fused unpack + dequant +
# reduce). issue+wait composes op-for-op into ``a2a_quant_reduce_scatter``,
# so the streaming backward tap that uses the split halves is bitwise the
# fused primitive (tests/_scenarios.py::collectives_split). The issue half's
# result feeds nothing in the current layer's backward compute, so XLA's
# latency-hiding scheduler can run layer i's grad all-to-all concurrently
# with layer i-1's backward matmuls — the same mechanism as the forward
# gather prefetch (core/schedule.py owns both idioms).

def _wire_all_to_all(q, axes: AxisTuple):
    """``all_to_all`` of a (d, m) 8-bit wire buffer over ``axes`` (chunk j
    to group member j), exchanged as (d, m / L, L) with L = gcd(m, 128).
    XLA:TPU lowers an all-to-all of long 8-bit rows through relayout
    reshapes that take about two minutes each to compile at embedding size
    (68 MB per device); with a lane-shaped minor dim it compiles in about a
    second and needs no temporaries. The exchanged bytes are the same."""
    d, m = q.shape
    lanes = math.gcd(m, 128)
    r = lax.all_to_all(q.reshape(d, m // lanes, lanes), tuple(axes),
                       split_axis=0, concat_axis=0, tiled=False)
    return r.reshape(d, m)


def a2a_rs_issue(x, axes: AxisTuple, cfg: ZeroConfig, bits: int = 4):
    """Quantize the d chunks of a flat shard and exchange them with one
    all-to-all, *without* the receive-side dequant-reduce.

    Returns the received (q2, s2) wire buffers; same wire traffic as the
    fused ``a2a_quant_reduce_scatter``.
    """
    d = cfg.size(axes)
    chunks = x.reshape(d, -1)          # chunk j -> group member j (major order)
    flatc = chunks.reshape(-1)
    if bits == 4:
        q, s = ops.quantize_int4(flatc, cfg.quant_block, impl=cfg.impl)
        q = q.reshape(d, -1)
    else:
        q, s = ops.quantize_int8(flatc, cfg.quant_block, impl=cfg.impl)
        q = q.reshape(d, -1)
    s = s.reshape(d, -1)
    q2 = _wire_all_to_all(q, axes)
    s2 = lax.all_to_all(s, tuple(axes), split_axis=0, concat_axis=0, tiled=False)
    return q2, s2


def a2a_rs_issue_q(q, s, axes: AxisTuple, cfg: ZeroConfig):
    """Exchange *pre-quantized* wire buffers: the collective half of
    ``a2a_rs_issue`` (same two all-to-alls, same wire bytes) for producers
    that already emitted wire format — the fused matmul-quant epilogue
    (kernels/ops.matmul_quant) quantizes the weight grad inside the matmul,
    so the dense f32 tensor never round-trips through HBM here."""
    d = cfg.size(axes)
    q = q.reshape(d, -1)
    s = s.reshape(d, -1)
    q2 = _wire_all_to_all(q, axes)
    s2 = lax.all_to_all(s, tuple(axes), split_axis=0, concat_axis=0, tiled=False)
    return q2, s2


def a2a_rs_wait(q2, s2, d: int, cfg: ZeroConfig, bits: int = 4,
                out_dtype=jnp.float32):
    """Receive side of the a2a quantized RS: fused unpack + dequant + reduce
    over the d chunks in one kernel pass (no communication). The unfused
    tail would materialize d dequantized copies and re-read them for the
    sum."""
    if bits == 4:
        red = ops.dequantize_int4_sum(q2.reshape(-1), s2.reshape(-1), d,
                                      cfg.quant_block, jnp.float32,
                                      impl=cfg.impl)
    else:
        red = ops.dequantize_int8_sum(q2.reshape(-1), s2.reshape(-1), d,
                                      cfg.quant_block, jnp.float32,
                                      impl=cfg.impl)
    return red.astype(out_dtype)


def a2a_quant_reduce_scatter(x, axes: AxisTuple, cfg: ZeroConfig,
                             bits: int = 4, out_dtype=jnp.float32):
    """All-to-all based quantized reduce-scatter over `axes`.

    x: flat (n,) with n % (D * block) == 0, D = group size. Returns the
    (n // D,) shard for this device's group index, summed over the group,
    with exactly one quantize/dequantize round-trip (INT4 by default ->
    0.25x communication volume, paper Table VIII). Composition of the
    ``a2a_rs_issue``/``a2a_rs_wait`` halves above.
    """
    d = cfg.size(axes)
    if d == 1:
        return x.astype(out_dtype)
    q2, s2 = a2a_rs_issue(x, axes, cfg, bits)
    return a2a_rs_wait(q2, s2, d, cfg, bits, out_dtype)


def reduce_scatter_flat(x, axes: AxisTuple, cfg: ZeroConfig, *,
                        quantized: bool | None = None, out_dtype=jnp.float32):
    """Gradient reduce-scatter over `axes`, quantized per config."""
    if not axes or cfg.size(axes) == 1:
        return x.astype(out_dtype)
    if quantized is None:
        quantized = cfg.quantize_grads
    if quantized:
        return a2a_quant_reduce_scatter(x, axes, cfg, bits=4, out_dtype=out_dtype)
    return lax.psum_scatter(x, tuple(axes), tiled=True).astype(out_dtype)


def cross_replica_grad(x, cfg: ZeroConfig, out_dtype=jnp.float32):
    """Final gradient sync over the replica tier (paper §V-C).

    "allreduce": the paper's flow -- all-reduce node-sharded grads across
    nodes, then each device *selects* the sub-slice matching its optimizer
    shard and discards the rest.
    "reduce_scatter": beyond-paper -- a psum_scatter lands each device's
    optimizer slice directly at ~half the volume.
    Either way the result is the optimizer-shard gradient (degree = all axes).
    """
    axes = cfg.axes.replica
    if not axes or cfg.size(axes) == 1:
        return x.astype(out_dtype)
    if cfg.cross_replica == "reduce_scatter":
        return lax.psum_scatter(x, tuple(axes), tiled=True).astype(out_dtype)
    full = lax.psum(x, tuple(axes))
    r = cfg.size(axes)
    idx = lax.axis_index(tuple(axes))
    piece = x.shape[-1] // r if x.ndim else x.size // r
    return lax.dynamic_slice_in_dim(full, idx * piece, piece, axis=-1).astype(out_dtype)


def update_all_gather(master_shard, cfg: ZeroConfig, out_dtype=jnp.bfloat16):
    """Rebuild primary weight shards from updated optimizer shards.

    All-gather over (E + R) in major->minor order; comm volume
    psi*(d-1)/d over the OS group (paper §V-D). Optionally INT8-quantized
    (beyond-paper; consistent across replicas because dequant is
    deterministic).

    Accepts flat 1-D shards or stacked (layers, shard) 2-D leaves — the
    gather tiles the last axis, so stacked leaves need no per-row vmap
    (same data movement, one batched collective).
    """
    axes = cfg.axes.extra_grad + cfg.axes.replica
    x = master_shard.astype(out_dtype)
    if not axes or cfg.size(axes) == 1:
        return x
    if cfg.quantize_update_gather:
        # quantize blocks never cross rows (shard length % block == 0 by
        # padded_flat_size), so flat quantization of the stacked leaf is
        # bitwise the per-row quantization; gather per row, then dequant
        q, s = ops.quantize_int8(x.reshape(-1), cfg.quant_block, impl=cfg.impl)
        q = q.reshape(x.shape)
        s = s.reshape(x.shape[:-1] + (-1,))
        qf = lax.all_gather(q, tuple(axes), tiled=True, axis=x.ndim - 1)
        sf = lax.all_gather(s, tuple(axes), tiled=True, axis=x.ndim - 1)
        out = ops.dequantize_int8(qf.reshape(-1), sf.reshape(-1),
                                  cfg.quant_block, out_dtype, impl=cfg.impl)
        return out.reshape(x.shape[:-1] + (-1,))
    return lax.all_gather(x, tuple(axes), tiled=True, axis=x.ndim - 1)


def secondary_slice(qf, sf, axes: AxisTuple, cfg: ZeroConfig):
    """Slice this device's secondary partition out of gathered (q, scales).

    Both are block-aligned, so the slice keeps whole quantization blocks and
    their matching scales.
    """
    s_deg = cfg.size(axes)
    idx = lax.axis_index(tuple(axes))
    qlen = qf.shape[-1] // s_deg
    slen = sf.shape[-1] // s_deg
    q = lax.dynamic_slice_in_dim(qf, idx * qlen, qlen, axis=-1)
    s = lax.dynamic_slice_in_dim(sf, idx * slen, slen, axis=-1)
    return q, s


def gather_secondary_q(sec_q, sec_s, axes: AxisTuple, cfg: ZeroConfig):
    """Backward weight all-gather from the INT8 secondary partition, kept in
    wire format (q, scales) — the fused dequant-matmul backward consumes it
    without ever materializing the dense weight."""
    qf = lax.all_gather(sec_q, tuple(axes), tiled=True)
    sf = lax.all_gather(sec_s, tuple(axes), tiled=True)
    return _tag((qf, sf), role="issue", machine="regather")


def gather_secondary(sec_q, sec_s, axes: AxisTuple, cfg: ZeroConfig,
                     out_dtype=jnp.bfloat16):
    """Backward weight all-gather from the INT8 secondary partition (intra tier)."""
    qf, sf = gather_secondary_q(sec_q, sec_s, axes, cfg)
    return gather_wait_int8(qf, sf, cfg, out_dtype)


# -- serving residency (DESIGN.md §12) ---------------------------------------
#
# The serving weight residency IS the secondary-partition wire format: at
# server start each leaf is quantized + gathered once (``gather_issue_int8``)
# and every device keeps only its ``residency_slice``; the decode hot path
# re-gathers the INT8 payload + scales per layer (``gather_residency_q``)
# and feeds them straight to the fused dequant-matmul. slice-then-regather
# is a bitwise identity (tests/_scenarios.py::collectives), which is what
# makes the resident forward bitwise-equal to the training engine's.

def gather_issue_int8_rows(rows, axes: AxisTuple, cfg: ZeroConfig):
    """Row-batched ``gather_issue_int8`` for stacked (layers, shard) leaves.

    Every row's shard length is a whole number of quant blocks (the
    ``os_degree * block`` padding guarantees it), so quantizing the
    flattened stack produces exactly the per-row blocks — no block straddles
    a row boundary — and the tiled last-axis gather concatenates shards in
    axis-index order. Row ``r`` of the result is therefore bitwise
    ``gather_issue_int8(rows[r], ...)``.
    """
    stack, shard = rows.shape
    q, s = ops.quantize_int8(rows.reshape(-1), cfg.quant_block, impl=cfg.impl)
    q = q.reshape(stack, shard)
    s = s.reshape(stack, shard // cfg.quant_block)
    if axes:
        q = lax.all_gather(q, tuple(axes), tiled=True, axis=1)
        s = lax.all_gather(s, tuple(axes), tiled=True, axis=1)
    return _tag((q, s), role="issue", machine="gather")


def residency_slice(qf, sf, axes: AxisTuple, cfg: ZeroConfig):
    """Slice the serving residency partition out of gathered (q, scales).

    Same block-aligned last-axis slice as ``secondary_slice``; the
    empty-axes guard makes replicated residency (1-device meshes) a no-op.
    """
    if not axes:
        return qf, sf
    return secondary_slice(qf, sf, axes, cfg)


def gather_residency_q(res_q, res_s, axes: AxisTuple, cfg: ZeroConfig):
    """Decode-path wire re-gather: residency shards -> full (q, scales)."""
    if not axes:
        return _tag((res_q, res_s), role="issue", machine="regather")
    return gather_secondary_q(res_q, res_s, axes, cfg)
