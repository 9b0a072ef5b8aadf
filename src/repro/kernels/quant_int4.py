"""Pallas TPU kernels: INT4 block quantize (nibble-packed) / dequantize.

Used for the all-to-all based gradient reduce-scatter (ZeRO++ §"quantized
gradients"): FP16/FP32 gradient blocks are quantized to 4 bits, packed two
nibbles per uint8, exchanged, and dequantized exactly once on the receiver.

TPU note: there is no native int4 vector type on the VPU, so packing is done
with integer arithmetic on even/odd element pairs: low nibble = even element,
high nibble = odd. Mosaic can neither split the lane dim into (bs//2, 2) nor
load with a lane stride, so ``pack_nibbles`` transposes the codes into a VMEM
scratch (pairs on adjacent sublanes), reads the even and odd rows with
sublane-strided loads and transposes the packed bytes back.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT4_QMAX = 7.0
ROWS_PER_TILE = 8


def pack_nibbles(codes, t_ref):
    """(r, c) int32 codes in [0, 15] -> (r, c // 2) uint8, element 2i in the
    low nibble and 2i+1 in the high one. ``t_ref``: (c, r) int32 VMEM
    scratch (see the module note)."""
    half = codes.shape[1] // 2
    t_ref[...] = codes.T
    lo = t_ref[pl.ds(0, half, stride=2), :]
    hi = t_ref[pl.ds(1, half, stride=2), :]
    return (lo | (hi << 4)).T.astype(jnp.uint8)


def _quant_int4_kernel(x_ref, q_ref, s_ref, t_ref):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / INT4_QMAX)
    q = jnp.clip(jnp.round(x / scale), -INT4_QMAX, INT4_QMAX).astype(jnp.int32) + 8
    q_ref[...] = pack_nibbles(q, t_ref)
    s_ref[...] = scale


def _dequant_int4_kernel(q_ref, s_ref, o_ref, *, dtype):
    p = q_ref[...].astype(jnp.int32)
    lo = (p & 0xF) - 8
    hi = ((p >> 4) & 0xF) - 8
    r, ch = p.shape
    out = jnp.stack([lo, hi], axis=-1).reshape(r, ch * 2).astype(jnp.float32)
    o_ref[...] = (out * s_ref[...]).astype(dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int4_pallas(blocks: jnp.ndarray, *, interpret: bool = False):
    """(nb, bs) -> ((nb, bs//2) uint8 packed, (nb, 1) f32). bs % 256 == 0."""
    nb, bs = blocks.shape
    rows = math.gcd(nb, ROWS_PER_TILE)
    grid = (nb // rows,)
    return pl.pallas_call(
        _quant_int4_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, bs), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, bs // 2), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, bs // 2), jnp.uint8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bs, rows), jnp.int32)],
        interpret=interpret,
    )(blocks)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def dequantize_int4_pallas(packed: jnp.ndarray, scales: jnp.ndarray,
                           dtype=jnp.float32, *, interpret: bool = False):
    nb, half = packed.shape
    rows = math.gcd(nb, ROWS_PER_TILE)
    grid = (nb // rows,)
    return pl.pallas_call(
        functools.partial(_dequant_int4_kernel, dtype=dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, half), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, half * 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, half * 2), dtype),
        interpret=interpret,
    )(packed, scales)


def _dequant_int4_sum_kernel(q_ref, s_ref, o_ref, *, d, dtype):
    # fused unpack + dequant + reduce: one pass over the a2a-received chunks
    # (the unfused tail would write d dequantized copies back to HBM and
    # re-read them for the reduction)
    def chunk(j):
        p = q_ref[j].astype(jnp.int32)
        lo = (p & 0xF) - 8
        hi = ((p >> 4) & 0xF) - 8
        r, ch = p.shape
        out = jnp.stack([lo, hi], axis=-1).reshape(r, ch * 2).astype(jnp.float32)
        return out * s_ref[j]

    acc = chunk(0)
    for j in range(1, d):
        acc = acc + chunk(j)
    o_ref[...] = acc.astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def dequantize_int4_sum_pallas(packed: jnp.ndarray, scales: jnp.ndarray,
                               dtype=jnp.float32, *, interpret: bool = False):
    """Fused unpack + dequant + reduce over the leading (group) axis.

    packed: (d, nb, bs//2) uint8; scales: (d, nb, 1) f32 -> (nb, bs)
    = sum_j dequant(packed[j]). Sequential f32 accumulation over j, same
    order as ``ref.dequantize_int4_sum_ref`` (bitwise in interpret mode)."""
    d, nb, half = packed.shape
    rows = math.gcd(nb, ROWS_PER_TILE)
    grid = (nb // rows,)
    return pl.pallas_call(
        functools.partial(_dequant_int4_sum_kernel, d=d, dtype=dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, rows, half), lambda i: (0, i, 0)),
            pl.BlockSpec((d, rows, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, half * 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, half * 2), dtype),
        interpret=interpret,
    )(packed, scales)
