"""Jit'd public wrappers around the quantization kernels.

The engine quantizes *flat 1-D parameter shards* (DeepSpeed-style flattened
storage); these wrappers own the (pad, reshape-to-blocks, kernel, unreshape)
plumbing and the implementation dispatch:

  impl="jnp"               pure-jnp oracle (default: inlines into the big
                           distributed XLA graph; what the CPU dry-run uses)
  impl="pallas"            compiled Pallas TPU kernel (the deploy target)
  impl="pallas_interpret"  Pallas kernel body interpreted on CPU (tests)

Set the process-wide default with ``set_default_impl``; the train launcher
sets "pallas" on TPU backends. Under that default a shape-gate fallback is
an error (``record_fallback``): on the chip a kernel is never silently
replaced by the jnp path.
"""
from __future__ import annotations

import collections
import functools
import warnings

import jax
import jax.numpy as jnp

from . import ref
from .dequant_matmul import dequant_matmul_flat_pallas, matmul_quant_pallas
from .flash_attention import flash_attention_pallas
from .quant_blockwise import (dequantize_int8_pallas,
                              dequantize_int8_sum_pallas,
                              quantize_int8_pallas)
from .quant_int4 import (dequantize_int4_pallas, dequantize_int4_sum_pallas,
                         quantize_int4_pallas)
from .selective_scan import selective_scan_pallas

DEFAULT_BLOCK = 512
_DEFAULT_IMPL = "jnp"


def set_default_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    assert impl in ("jnp", "pallas", "pallas_interpret"), impl
    _DEFAULT_IMPL = impl


def get_default_impl() -> str:
    return _DEFAULT_IMPL


# ---------------------------------------------------------------------------
# Dispatch / fallback accounting (trace-time, python-side)
# ---------------------------------------------------------------------------
#
# Every hot-path dispatch increments a counter; shape-gate rejections land in
# ``<kernel>/fallback/<reason>`` and additionally emit ONE structured warning
# per (kernel, reason), so a silently degraded run (e.g. a seq length that
# pushes attention off the Pallas path) is visible in logs and in the
# obs/metrics layer (repro.obs reads ``dispatch_counters()``).

_DISPATCH_COUNTS: collections.Counter = collections.Counter()
_WARNED_FALLBACKS: set = set()


def record_dispatch(kernel: str, impl: str) -> None:
    _DISPATCH_COUNTS[f"{kernel}/{impl}"] += 1


def record_fallback(kernel: str, reason: str) -> None:
    if _DEFAULT_IMPL == "pallas":
        raise RuntimeError(
            f"repro.kernels.ops: {kernel} cannot use the compiled Pallas "
            f"kernel for this call shape (reason: {reason}); refusing the "
            "jnp fallback under the compiled default")
    _DISPATCH_COUNTS[f"{kernel}/fallback/{reason}"] += 1
    key = (kernel, reason)
    if key not in _WARNED_FALLBACKS:
        _WARNED_FALLBACKS.add(key)
        warnings.warn(
            f"repro.kernels.ops: {kernel} fell back to the chunked jnp path "
            f"(reason: {reason}); the Pallas kernel will not be used for "
            "this call shape. Warned once per reason.",
            stacklevel=3)


def dispatch_counters() -> dict[str, int]:
    """Trace-time dispatch/fallback counts, keyed ``kernel/impl`` or
    ``kernel/fallback/reason`` (obs surfaces these; tests reset them)."""
    return dict(_DISPATCH_COUNTS)


def reset_dispatch_counters() -> None:
    _DISPATCH_COUNTS.clear()
    _WARNED_FALLBACKS.clear()


# ---------------------------------------------------------------------------
# Fusion isolation (the bitwise-impl-swap contract's other half)
# ---------------------------------------------------------------------------
#
# XLA:CPU contracts mul+add chains into FMAs per fusion cluster, and cluster
# boundaries are context-sensitive: in interpret mode a pallas_call lowers to
# ordinary HLO that INLINES into the surrounding model graph, so swapping an
# impl between the jnp oracle and the interpret-mode kernel can perturb
# fusion decisions (hence FMA contraction, hence ULPs) in code *outside* the
# kernel — loss can stay bitwise while every gradient drifts 1e-8.
#
# Two mechanisms keep the swap bitwise:
#  1. ``optimization_barrier`` on every dispatched region's inputs and
#     outputs pins the boundary against HLO-pass reordering. This is NOT
#     sufficient on its own: XLA:CPU expands the barriers before the passes
#     that pick fusion clusters, so a structurally different region still
#     shifts neighbouring clusters.
#  2. The real fusion barrier is a REAL WHILE LOOP: XLA fusion never
#     crosses control flow, so when both impls of a dispatched region lower
#     to a genuine (trip-count >= 2) loop consuming the same interface
#     arrays, the surrounding graph compiles identically no matter what is
#     inside. Interpret-mode pallas_call lowers its grid to a lax.while_loop
#     over grid points; each jnp oracle therefore runs its sequential
#     dimension as a matching lax.fori_loop / lax.scan with every op — input
#     casts, tile dequant, quantize epilogues — INSIDE the loop body, and no
#     layout ops (transposes/moveaxis) at the loop interface. Both halves of
#     that rule were root-caused empirically: a trip-count-1 grid gets
#     inlined by the while-loop simplifier and its "near-identical" HLO
#     flips neighbouring FMA contraction as surrounding code evolves, and a
#     time-major moveaxis at the scan oracle's interface fused into producer
#     clusters and drifted *their* output 1 ULP per step (loss bitwise,
#     every gradient 1e-8 off). ``_loop_split`` picks the >= 2-step
#     contraction blocking for dequant_matmul / matmul_quant; the scan walks
#     time; attention remains a single full-extent block whose inlined HLO
#     is exactly identical between impls (its oracle replays the kernel op
#     for op with no interface layout ops).


def _isolated(fn, args):
    """Run fn behind optimization_barriers (fusion isolation, see above)."""
    args = jax.lax.optimization_barrier(args)
    return jax.lax.optimization_barrier(fn(*args))


def _isolated_vjp(oracle, res, g):
    """jax.vjp of the oracle at the saved primals, fusion-isolated so the
    identical bwd subgraph compiles identically under every impl."""
    res = jax.lax.optimization_barrier(res)
    g = jax.lax.optimization_barrier(g)
    _, vjp = jax.vjp(oracle, *res)
    return jax.lax.optimization_barrier(vjp(g))


def _blocks(x: jnp.ndarray, block: int) -> jnp.ndarray:
    assert x.ndim == 1 and x.size % block == 0, (x.shape, block)
    return x.reshape(-1, block)


def quantize_int8(x, block: int = DEFAULT_BLOCK, impl: str | None = None):
    """1-D x (size % block == 0) -> (int8 same shape, f32 scales (size//block,))."""
    impl = impl or _DEFAULT_IMPL
    b = _blocks(x, block)
    if impl == "jnp":
        q, s = ref.quantize_int8_ref(b)
    else:
        q, s = quantize_int8_pallas(b, interpret=(impl == "pallas_interpret"))
    return q.reshape(-1), s.reshape(-1)


def dequantize_int8(q, scales, block: int = DEFAULT_BLOCK, dtype=jnp.float32,
                    impl: str | None = None):
    impl = impl or _DEFAULT_IMPL
    qb = _blocks(q, block)
    sb = scales.reshape(-1, 1)
    if impl == "jnp":
        out = ref.dequantize_int8_ref(qb, sb, dtype)
    else:
        out = dequantize_int8_pallas(qb, sb, dtype,
                                     interpret=(impl == "pallas_interpret"))
    return out.reshape(-1)


def quantize_int4(x, block: int = DEFAULT_BLOCK, impl: str | None = None):
    """1-D x -> (uint8 packed (size//2,), f32 scales (size//block,))."""
    impl = impl or _DEFAULT_IMPL
    b = _blocks(x, block)
    if impl == "jnp":
        q, s = ref.quantize_int4_ref(b)
    else:
        q, s = quantize_int4_pallas(b, interpret=(impl == "pallas_interpret"))
    return q.reshape(-1), s.reshape(-1)


def dequantize_int4(packed, scales, block: int = DEFAULT_BLOCK,
                    dtype=jnp.float32, impl: str | None = None):
    impl = impl or _DEFAULT_IMPL
    qb = packed.reshape(-1, block // 2)
    sb = scales.reshape(-1, 1)
    if impl == "jnp":
        out = ref.dequantize_int4_ref(qb, sb, dtype)
    else:
        out = dequantize_int4_pallas(qb, sb, dtype,
                                     interpret=(impl == "pallas_interpret"))
    return out.reshape(-1)


def dequantize_int4_sum(packed, scales, d: int, block: int = DEFAULT_BLOCK,
                        dtype=jnp.float32, impl: str | None = None):
    """Fused unpack + dequant + reduce of a2a-received INT4 chunks.

    packed: flat (d * n/2,) uint8 (d chunks, row-major); scales: flat
    (d * n/block,). Returns (n,) = sum over the d chunks, dequantized once
    — the receive-side half of the ZeRO++ quantized reduce-scatter in a
    single pass (no d dequantized copies round-tripping through HBM)."""
    impl = impl or _DEFAULT_IMPL
    qb = packed.reshape(d, -1, block // 2)
    sb = scales.reshape(d, -1, 1)
    if impl == "jnp":
        out = ref.dequantize_int4_sum_ref(qb, sb, dtype)
    else:
        out = dequantize_int4_sum_pallas(qb, sb, dtype,
                                         interpret=(impl == "pallas_interpret"))
    return out.reshape(-1)


def dequantize_int8_sum(q, scales, d: int, block: int = DEFAULT_BLOCK,
                        dtype=jnp.float32, impl: str | None = None):
    """INT8 variant of ``dequantize_int4_sum`` (bits=8 gradient RS)."""
    impl = impl or _DEFAULT_IMPL
    qb = q.reshape(d, -1, block)
    sb = scales.reshape(d, -1, 1)
    if impl == "jnp":
        out = ref.dequantize_int8_sum_ref(qb, sb, dtype)
    else:
        out = dequantize_int8_sum_pallas(qb, sb, dtype,
                                         interpret=(impl == "pallas_interpret"))
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# Fused dequant x matmul (flat-shard scale layout)
# ---------------------------------------------------------------------------

def matmul_fusable(shape: tuple[int, ...], block: int) -> bool:
    """Can a weight of logical ``shape`` feed the fused dequant matmul?

    Requires >= 2 dims and the last (column) dim to be a whole number of
    quantization blocks, so the flat blocks tile each row of the (K, N)
    view exactly. Non-fusable leaves fall back to dequant -> matmul."""
    return len(shape) >= 2 and shape[-1] % block == 0


@functools.cache
def _tile(n: int, cap: int, unit: int) -> int:
    """Block size for one dim of a *compiled* kernel: the largest multiple
    of ``unit`` that divides ``n`` and is <= ``cap``, else ``n`` itself.

    Mosaic takes a block whose last two dims are (8, 128)-aligned or span
    the whole array, so ``unit`` is 8 for a sublane dim and 128 for a lane
    dim. A lane dim that also carries per-block scales (scale tile
    ``t // block`` wide) needs ``unit = 128 * block``, which at real widths
    leaves the full extent."""
    for d in range(min(n, cap) // unit * unit, 0, -unit):
        if n % d == 0:
            return d
    return n


@functools.cache
def _loop_split(n: int, granule: int = 1) -> int:
    """Contraction step for the bitwise pair: the largest granule-aligned
    divisor of ``n`` that yields >= 2 accumulation steps, so both the jnp
    oracle's fori_loop and the interpret kernel's grid loop survive to the
    backend as real while loops (the fusion barrier the bitwise contract
    rests on — see the fusion-isolation note at the top of this module).
    Falls back to a single full-extent step when n == granule (nothing to
    split); n % granule must be 0."""
    units = n // granule
    for p in range(2, units + 1):
        if units % p == 0:
            return granule * (units // p)
    return n


def dequant_matmul(x2, q_flat, scales, w_shape: tuple[int, int],
                   block: int = DEFAULT_BLOCK, *, transpose: bool = False,
                   dtype=jnp.bfloat16, impl: str | None = None):
    """y = x @ dequant(W) (or x @ dequant(W).T) without materializing W.

    ``q_flat``/``scales`` are the flat gathered INT8 buffer + per-block
    scales exactly as the collectives produce them (padded; only the first
    K*N / K*N//block entries are consumed). ``w_shape`` = (K, N) logical.
    x2: (M, K) (or (M, N) when transpose). Output rows are padded to the
    f32 sublane multiple internally and sliced back.

    impl="jnp" runs ``ref.dequant_matmul_flat_ref`` with the *same*
    contraction blocking and accumulation order as the kernel, so jnp and
    pallas_interpret results are bitwise identical (tests/test_kernels.py).
    The bitwise pair splits the contraction into >= 2 steps (``_loop_split``)
    so both the oracle's fori_loop and the interpret grid loop reach the
    backend as real while loops with identical operands — an opaque fusion
    boundary the surrounding graph compiles identically around (see the
    fusion-isolation note at the top of this module for why that matters).
    """
    impl = impl or _DEFAULT_IMPL
    record_dispatch("dequant_matmul", impl)
    k, n = w_shape
    assert n % block == 0, (w_shape, block)
    q2 = q_flat.reshape(-1)[: k * n].reshape(k, n)
    s2 = scales.reshape(-1)[: (k * n) // block].reshape(k, n // block)
    m = x2.shape[0]
    m_pad = padded_size(max(m, 1), 8)
    if m_pad != m:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
    c_len = n if transpose else k
    out_dim = k if transpose else n
    bc_pair = _loop_split(c_len, block if transpose else 1)
    if impl == "jnp":
        def run(x2, q2, s2):
            return ref.dequant_matmul_flat_ref(x2, q2, s2, block, bc=bc_pair,
                                               transpose=transpose,
                                               dtype=dtype)
    elif impl == "pallas_interpret":
        # full row/col extents, grid (1, 1, c_len // bc_pair): only the
        # sequential contraction dim is blocked, >= 2 steps so the grid
        # loop is a real while loop (bitwise contract, §5)
        def run(x2, q2, s2):
            return dequant_matmul_flat_pallas(
                x2, q2, s2, block=block, bm=m_pad, bo=out_dim, bc=bc_pair,
                transpose=transpose, dtype=dtype, interpret=True)
    else:
        # compiled TPU: VMEM-sized tiles (the fused win is HBM traffic, so
        # the accumulation order may differ from the CPU oracle here — like
        # any other MXU-vs-CPU matmul). The N dim of q carries the scale
        # tile, so it is 128*block-aligned or whole (see ``_tile``).
        bm = _tile(m_pad, 256, 8)
        if transpose:
            bc = _tile(c_len, 512, 128 * block)
            bo = _tile(out_dim, 512, 128)
        else:
            bc = _tile(c_len, 512, 128)
            bo = _tile(out_dim, 512, 128 * block)

        def run(x2, q2, s2):
            return dequant_matmul_flat_pallas(
                x2, q2, s2, block=block, bm=bm, bo=bo, bc=bc,
                transpose=transpose, dtype=dtype, interpret=False)
    out = _isolated(run, (x2, q2, s2))
    return out[:m] if m_pad != m else out


@functools.cache
def padded_size(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# Attention / selective scan (first-class hot-path dispatch, DESIGN.md §5)
# ---------------------------------------------------------------------------
#
# Both kernels are exposed as per-static-config ``jax.custom_vjp`` functions
# (cached so jit tracing caches stay warm): the forward primal dispatches on
# impl, the backward is ALWAYS ``jax.vjp`` of the jnp oracle at the saved
# primals. Because every impl shares the oracle backward and the oracle
# mirrors the interpret-mode kernel body op for op, impl="jnp" and
# impl="pallas_interpret" agree bitwise through fwd AND bwd. The compiled
# TPU path ("pallas") carries no bitwise contract — its tiles are chosen
# for the MXU, like any other accelerator matmul.
#
# Both the primal and the shared backward run behind the fusion-isolation
# barriers (``_isolated`` / ``_isolated_vjp``, see the top of this module):
# the surrounding model graph sees the same opaque boundary under every
# impl, and the bwd's fusion depends only on its own (identical) structure.


def attention_fusable(sq: int, sk: int, d: int, dv: int, *,
                      softmax_scale=None,
                      q_offset=0) -> tuple[bool, str | None]:
    """Can this attention call use the Pallas kernel path?

    Returns (ok, reason): reason names the rejection for the fallback
    warning/counter — "mla_dv_mismatch" (MLA heads with dv != d),
    "custom_scale" (non-default softmax scale), "traced_q_offset"
    (q_offset is a tracer, the kernel needs it static), "seq_unaligned"
    (seq lengths not tileable to the 128-aligned kernel grid)."""
    if dv != d:
        return False, "mla_dv_mismatch"
    if softmax_scale is not None:
        return False, "custom_scale"
    if not isinstance(q_offset, int):
        return False, "traced_q_offset"
    if sq < 8 or sk < 8 or sq % min(128, sq) or sk % min(128, sk):
        return False, "seq_unaligned"
    return True, None


@functools.cache
def _attention_fn(causal: bool, window: int, q_offset: int, impl: str):
    def oracle(q, k, v):
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       q_offset=q_offset)

    if impl == "jnp":
        prim = oracle
    elif impl == "pallas_interpret":
        def prim(q, k, v):
            bh, sq, _ = q.shape
            # full extents, grid (1,1,1): the bitwise configuration
            return flash_attention_pallas(
                q, k, v, causal=causal, window=window, q_offset=q_offset,
                bb=bh, bq=sq, bk=k.shape[1], interpret=True)
    else:
        def prim(q, k, v):
            sq, sk = q.shape[1], k.shape[1]
            return flash_attention_pallas(
                q, k, v, causal=causal, window=window, q_offset=q_offset,
                bb=1, bq=min(128, sq), bk=min(128, sk), interpret=False)

    @jax.custom_vjp
    def fn(q, k, v):
        return _isolated(prim, (q, k, v))

    def fwd(q, k, v):
        return _isolated(prim, (q, k, v)), (q, k, v)

    def bwd(res, g):
        return _isolated_vjp(oracle, res, g)

    fn.defvjp(fwd, bwd)
    return fn


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, impl: str | None = None):
    """q (BH, Sq, D); k, v (BH, Sk, D) -> (BH, Sq, D), softmax attention.

    Caller (models/layers.py) folds heads/GQA and checks
    ``attention_fusable`` first; this dispatch assumes a fusable shape."""
    impl = impl or _DEFAULT_IMPL
    record_dispatch("attention", impl)
    return _attention_fn(causal, window, q_offset, impl)(q, k, v)


@functools.cache
def _selective_scan_fn(bs: int, impl: str):
    def oracle(dt, x, b, c, a, h0):
        return ref.selective_scan_ref(dt, x, b, c, a, h0, bs=bs)

    if impl == "jnp":
        prim = oracle
    elif impl == "pallas_interpret":
        def prim(dt, x, b, c, a, h0):
            batch, _, d = dt.shape
            return selective_scan_pallas(dt, x, b, c, a, h0, bb=batch,
                                         bd=d, bs=bs, interpret=True)
    else:
        def prim(dt, x, b, c, a, h0):
            return selective_scan_pallas(dt, x, b, c, a, h0, bs=bs,
                                         interpret=False)

    @jax.custom_vjp
    def fn(dt, x, b, c, a, h0):
        return _isolated(prim, (dt, x, b, c, a, h0))

    def fwd(dt, x, b, c, a, h0):
        return _isolated(prim, (dt, x, b, c, a, h0)), (dt, x, b, c, a, h0)

    def bwd(res, g):
        return _isolated_vjp(oracle, res, g)

    fn.defvjp(fwd, bwd)
    return fn


def selective_scan(dt, x, b, c, a, h0, *, impl: str | None = None):
    """Mamba-1 selective scan: dt, x (B, S, D); b, c (B, S, N); a (D, N);
    h0 (B, D, N) -> (y (B, S, D) f32, h_last (B, D, N) f32).

    Always fusable (the kernel grid divides any B/S/D); the time-block
    size is derived from S identically for every impl."""
    impl = impl or _DEFAULT_IMPL
    s = dt.shape[1]
    bs = min(256, s)
    while s % bs:
        bs //= 2
    record_dispatch("selective_scan", impl)
    return _selective_scan_fn(bs, impl)(dt, x, b, c, a, h0)


# ---------------------------------------------------------------------------
# Fused matmul x quantize (the weight-grad -> reduce-scatter seam)
# ---------------------------------------------------------------------------


def matmul_quant(x2, g2, block: int = DEFAULT_BLOCK, *, bits: int = 8,
                 pad_to: int | None = None, impl: str | None = None):
    """Wire-format weight grad: C = x2.T @ g2, block-quantized in the
    matmul epilogue (no dense f32 C round-trip through HBM).

    x2 (M, K); g2 (M, N); N % block == 0. Returns flat (q, scales) in the
    exact layout ``quantize_int{8,4}(C.reshape(-1))`` produces — INT8 q is
    (K*N,) int8, INT4 q is (K*N//2,) packed uint8 — optionally padded to
    ``pad_to`` logical elements with exact zero blocks (q=0 / 0x88,
    scale=1), matching the quantize-of-zero-padding the unfused path
    ships. Not differentiable: it lives inside core/linear.py's custom
    backward. impl="jnp" mirrors the kernel's blocked accumulation order,
    so jnp and pallas_interpret agree bitwise (tests/test_kernels.py)."""
    impl = impl or _DEFAULT_IMPL
    m, kk = x2.shape
    n = g2.shape[1]
    assert n % block == 0, (g2.shape, block)
    record_dispatch("matmul_quant", impl)
    bc_pair = _loop_split(m)
    if impl == "jnp":
        # >= 2 contraction steps mirroring the interpret grid loop (same
        # rationale as dequant_matmul: both legs lower to a real while
        # loop with identical operands — the bitwise contract, §5)
        def run(x2, g2):
            return ref.matmul_quant_ref(x2, g2, block, bc=bc_pair, bits=bits)
    elif impl == "pallas_interpret":
        def run(x2, g2):
            return matmul_quant_pallas(x2, g2, block=block, bits=bits,
                                       bk=kk, bn=n, bc=bc_pair, interpret=True)
    else:
        # the output's N dim carries the scale tile (and the nibble pairs):
        # 128*block-aligned or whole, see ``_tile``. bk <= 128 keeps the
        # INT4 pack scratch (bn, bk) within what a strided load takes; the
        # g tile (bc, bn) is held near 2 MiB of f32 so the pipeline fits
        # the default scoped VMEM.
        bn = _tile(n, 512, 128 * block)
        bk = _tile(kk, 128, 128)
        bc = _tile(m, max(8, (1 << 19) // bn), 8)

        def run(x2, g2):
            return matmul_quant_pallas(x2, g2, block=block, bits=bits,
                                       bk=bk, bn=bn, bc=bc, interpret=False)
    q, s = _isolated(run, (x2, g2))
    qf, sf = q.reshape(-1), s.reshape(-1)
    logical = kk * n
    if pad_to is not None and pad_to != logical:
        assert pad_to > logical and (pad_to - logical) % block == 0, \
            (pad_to, logical, block)
        pad = pad_to - logical
        if bits == 4:
            qf = jnp.concatenate(
                [qf, jnp.full((pad // 2,), 0x88, jnp.uint8)])
        else:
            qf = jnp.concatenate([qf, jnp.zeros((pad,), jnp.int8)])
        sf = jnp.concatenate([sf, jnp.ones((pad // block,), jnp.float32)])
    return qf, sf
