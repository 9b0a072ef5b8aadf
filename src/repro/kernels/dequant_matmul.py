"""Pallas TPU kernel: fused INT8-dequant x matmul (beyond-paper optimization).

The paper dequantizes gathered weights to FP16 in HBM and then runs the
matmul, paying a full extra read+write of the weight matrix. On TPU the
dequant is essentially free if fused into the matmul's VMEM pipeline: each
(bk, bn) int8 weight tile is scaled to f32 *in VMEM* right before hitting the
MXU, so HBM only ever sees 1 byte/param. This kernel implements
``x @ dequant(q, scales)`` with K-blocked accumulation.

Tiling: grid (M/bm, N/bn, K/bk); the scale blocking along K must equal the
kernel's K tile (one scale row per K tile) so scaling is a broadcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quant_int4 import pack_nibbles


def _dequant_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = q_ref[...].astype(jnp.float32) * s_ref[...]  # (bk, bn) * (1, bn)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "dtype", "interpret"))
def dequant_matmul_pallas(x: jnp.ndarray, q: jnp.ndarray, scales: jnp.ndarray,
                          *, bm: int = 128, bn: int = 128, bk: int = 128,
                          dtype=jnp.float32, interpret: bool = False):
    """x: (M, K); q: (K, N) int8; scales: (K // bk, N) f32 -> (M, N).

    M % bm == K % bk == N % bn == 0 and scales.shape[0] == K // bk.
    """
    m, k = x.shape
    k2, n = q.shape
    assert k == k2 and scales.shape == (k // bk, n), (x.shape, q.shape, scales.shape)
    k_steps = k // bk
    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_dequant_matmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scales)


# ---------------------------------------------------------------------------
# Flat-shard-layout variant: the hot-path kernel behind ``core/linear.py``.
#
# The ZeRO engine gathers weights as a *flat* INT8 shard with one f32 scale
# per ``block`` consecutive flat elements (DeepSpeed layout). Viewed as the
# logical (K, N) weight (row-major, N % block == 0), the scale for element
# (k, j) is ``scales[k, j // block]`` — scales block along columns *within*
# a row, not down a column. This kernel consumes that layout directly, so
# the gathered INT8 buffer feeds the MXU without ever materializing the
# dequantized weight in HBM, and emits bf16 (or any requested dtype).
#
# Both matmul orientations are supported because the backward pass needs
# g @ W.T against the re-gathered INT8 secondary partition:
#   transpose=False: x (M, K) @ dequant(q (K, N))    -> (M, N)
#   transpose=True : x (M, N) @ dequant(q (K, N)).T  -> (M, K)
# In both cases the q/scales tile layout is identical ((rows, cols) with
# scales (rows, cols//block)); only the grid index maps and the dot_general
# contraction dims differ.
# ---------------------------------------------------------------------------


def _dequant_mm_flat_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *,
                            block, k_steps, transpose):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    q = q_ref[...].astype(jnp.float32)
    r, c = q.shape
    s = jnp.broadcast_to(s_ref[...][:, :, None], (r, c // block, block))
    w = q * s.reshape(r, c)
    if transpose:
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "bm", "bo", "bc",
                                             "transpose", "dtype",
                                             "interpret"))
def dequant_matmul_flat_pallas(x: jnp.ndarray, q: jnp.ndarray,
                               scales: jnp.ndarray, *, block: int,
                               bm: int, bo: int, bc: int,
                               transpose: bool = False,
                               dtype=jnp.bfloat16, interpret: bool = False):
    """Fused dequant x matmul on the flat-shard scale layout.

    ``q``: (K, N) int8, ``scales``: (K, N // block) f32 (see module note).
    transpose=False: x (M, K) -> (M, N);  transpose=True: x (M, N) -> (M, K).
    ``bm``/``bo``/``bc`` tile M / the output dim / the contraction dim.
    Scale tiles must stay block-aligned: bc % block == 0 when the
    contraction runs along N (transpose=True), bo % block == 0 otherwise.
    """
    k, n = q.shape
    m = x.shape[0]
    assert scales.shape == (k, n // block), (q.shape, scales.shape, block)
    c_len, out_dim = (n, k) if transpose else (k, n)
    assert x.shape == (m, c_len) and m % bm == 0 and out_dim % bo == 0 \
        and c_len % bc == 0, (x.shape, q.shape, bm, bo, bc)
    k_steps = c_len // bc
    grid = (m // bm, out_dim // bo, k_steps)
    if transpose:
        assert bc % block == 0, (bc, block)
        q_spec = pl.BlockSpec((bo, bc), lambda i, j, kk: (j, kk))
        s_spec = pl.BlockSpec((bo, bc // block), lambda i, j, kk: (j, kk))
    else:
        assert bo % block == 0, (bo, block)
        q_spec = pl.BlockSpec((bc, bo), lambda i, j, kk: (kk, j))
        s_spec = pl.BlockSpec((bc, bo // block), lambda i, j, kk: (kk, j))
    return pl.pallas_call(
        functools.partial(_dequant_mm_flat_kernel, block=block,
                          k_steps=k_steps, transpose=transpose),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bc), lambda i, j, kk: (i, kk)),
            q_spec,
            s_spec,
        ],
        out_specs=pl.BlockSpec((bm, bo), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, out_dim), dtype),
        scratch_shapes=[pltpu.VMEM((bm, bo), jnp.float32)],
        interpret=interpret,
    )(x, q, scales)


# ---------------------------------------------------------------------------
# Fused matmul x quantize: the weight-grad producer (beyond-paper).
#
# The unfused gradient path materializes the dense f32 dW = x.T @ g in HBM,
# then re-reads it to block-quantize for the a2a reduce-scatter — a full
# extra write+read of 4 bytes/param on the hottest backward seam. Here the
# quantize runs in the matmul's epilogue instead: the f32 accumulator tile
# is still in VMEM when the last contraction step finishes, so HBM only
# ever sees the INT8 (or packed INT4) wire bytes + per-block scales that
# the collective actually ships. Scale blocks follow the flat shard layout
# (scales[k, j // block], N % block == 0), i.e. the output *is* the wire
# format core/linear.py previously produced via quantize_int{8,4}.
# ---------------------------------------------------------------------------

INT8_QMAX = 127.0
INT4_QMAX = 7.0


def _matmul_quant_kernel(x_ref, g_ref, q_ref, s_ref, acc_ref, *pack_ref,
                         block, bits, m_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                 # (bc, bk)
    g = g_ref[...].astype(jnp.float32)                 # (bc, bn)
    acc_ref[...] += jax.lax.dot_general(
        x, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == m_steps - 1)
    def _done():
        acc = acc_ref[...]
        r, c = acc.shape
        qmax = INT4_QMAX if bits == 4 else INT8_QMAX
        a3 = acc.reshape(r, c // block, block)
        absmax = jnp.max(jnp.abs(a3), axis=-1, keepdims=True)
        # reciprocal-multiply, not division: jit folds `/const` into
        # `*(1/const)` but eager does not — ref.matmul_quant_ref matches
        scales = jnp.where(absmax == 0.0, 1.0, absmax * (1.0 / qmax))
        qv = jnp.clip(jnp.round(a3 / scales), -qmax, qmax)
        s_ref[...] = scales.reshape(r, c // block)
        if bits == 4:
            # pack_ref: the (bn, bk) scratch only INT4 allocates
            q_ref[...] = pack_nibbles(qv.reshape(r, c).astype(jnp.int32) + 8,
                                      *pack_ref)
        else:
            q_ref[...] = qv.reshape(r, c).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block", "bits", "bk", "bn",
                                             "bc", "interpret"))
def matmul_quant_pallas(x: jnp.ndarray, g: jnp.ndarray, *, block: int,
                        bits: int = 8, bk: int, bn: int, bc: int,
                        interpret: bool = False):
    """Fused C = x.T @ g + block-quantize epilogue.

    x: (M, K); g: (M, N); N % block == 0, M % bc == 0. Returns
    (q (K, N) int8 | (K, N//2) uint8, scales (K, N//block) f32) in the
    flat-shard wire layout. Grid (K/bk, N/bn, M/bc) with the contraction
    innermost; the epilogue quantizes each output tile at the last step,
    mirrored op-for-op by ref.matmul_quant_ref (bitwise with bk=K, bn=N).
    ``bn`` must stay a whole number of scale blocks (and even for INT4).
    """
    m, k = x.shape
    m2, n = g.shape
    assert m == m2 and n % block == 0, (x.shape, g.shape, block)
    assert k % bk == 0 and n % bn == 0 and m % bc == 0 and bn % block == 0, \
        (x.shape, g.shape, bk, bn, bc, block)
    m_steps = m // bc
    grid = (k // bk, n // bn, m_steps)
    if bits == 4:
        q_shape = jax.ShapeDtypeStruct((k, n // 2), jnp.uint8)
        q_spec = pl.BlockSpec((bk, bn // 2), lambda i, j, kk: (i, j))
    else:
        q_shape = jax.ShapeDtypeStruct((k, n), jnp.int8)
        q_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (i, j))
    return pl.pallas_call(
        functools.partial(_matmul_quant_kernel, block=block, bits=bits,
                          m_steps=m_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bk), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bc, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            q_spec,
            pl.BlockSpec((bk, bn // block), lambda i, j, kk: (i, j)),
        ],
        out_shape=[q_shape,
                   jax.ShapeDtypeStruct((k, n // block), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)]
        + ([pltpu.VMEM((bn, bk), jnp.int32)] if bits == 4 else []),
        interpret=interpret,
    )(x, g)
