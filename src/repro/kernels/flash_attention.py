"""Pallas TPU flash attention (beyond-paper optimization, see EXPERIMENTS.md
§Perf).

The jnp reference attention (models/layers.flash_attention) streams its
(bq, bk) probability tiles through HBM — on the CPU dry-run census this is
the dominant memory-term contributor for every train/prefill shape. This
kernel keeps the running-softmax state (acc, m, l) in VMEM scratch across the
KV-block grid dimension, so HBM traffic collapses to q + k + v + out.

Layout: inputs are (BH, S, D) with heads folded into the leading dim (GQA
k/v are repeated by the ops.py wrapper — on TPU the repeat is a broadcast
the compiler keeps virtual). Grid: (BH, num_q_blocks, num_kv_blocks), KV
innermost so scratch carries across it. Causal and sliding-window masking
are applied from absolute positions (q_offset supports decode/sequence-
parallel callers). Nothing pads D: each block takes the whole head size.
The compiled tiles (bq, bk) come from ``ops.flash_tiles``. The k/v index
maps clamp to the kv blocks a q block needs, so a step that the mask skips
repeats the previous block index and the pipeline fetches nothing for it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, q_offset, bq, bk, kv_steps):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_i = pl.program_id(1)
    q_pos = q_offset + q_i * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # skip kv blocks that are entirely masked out (causal / window)
    first_q = q_offset + q_i * bq
    last_q = first_q + bq - 1
    first_k = kv_i * bk
    last_k = first_k + bk - 1
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, first_k <= last_q)
    if window:
        run = jnp.logical_and(run, last_k > first_q - window)

    @pl.when(run)
    def _compute():
        # scale is folded into q *before* the dot: a post-dot multiply would
        # sit next to the `s - m_new` subtract and XLA is free to contract
        # mul+add chains into FMAs differently per context, breaking the
        # jnp-vs-interpret bitwise contract
        q = q_ref[...].astype(jnp.float32) * scale    # (bb, bq, d)
        k = k_ref[...].astype(jnp.float32)            # (bb, bk, d)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kv_i == kv_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "bb", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           q_offset: int = 0, bb: int = 1, bq: int = 128,
                           bk: int = 128, interpret: bool = False):
    """q (BH, Sq, D); k, v (BH, Sk, D) -> (BH, Sq, D).

    BH % bb == Sq % bq == Sk % bk == 0 (ops.py gates); a block takes the
    whole head size D.
    ``bb`` blocks the folded batch*heads dim: compiled TPU runs bb=1 tiles
    with the multi-block online softmax; the interpret/bitwise configuration
    runs FULL extents (bb=BH, bq=Sq, bk=Sk, grid (1,1,1)) — with a single
    KV block the zero-initialized rescale combines (`acc*corr + pv`,
    `l*corr + Σp`) are exact regardless of FMA contraction, which is what
    lets the jnp oracle (kernels/ref.flash_attention_ref) mirror the body
    bitwise. Multi-block accumulation is validated by allclose tests only.
    """
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    assert bh % bb == 0 and sq % bq == 0 and sk % bk == 0, \
        (q.shape, k.shape, bb, bq, bk)
    scale = 1.0 / math.sqrt(d)
    kv_blocks = sk // bk
    grid = (bh // bb, sq // bq, kv_blocks)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, kv_steps=kv_blocks)

    def kv_index(b, i, j):
        """Clamp kv block j to the blocks q block i attends to (the bounds
        of the kernel's skip test): a skipped step names a block the
        pipeline already holds, so it copies nothing."""
        if kv_blocks == 1:
            return b, j, 0              # nothing to clamp (the bitwise grid)
        first_q = q_offset + i * bq
        if causal:
            j = jnp.minimum(j, jnp.clip((first_q + bq - 1) // bk, 0,
                                        kv_blocks - 1))
        if window:
            j = jnp.maximum(j, jnp.clip((first_q - window + 1) // bk, 0,
                                        kv_blocks - 1))
        return b, j, 0

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((bb, bk, d), kv_index),
            pl.BlockSpec((bb, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((bb, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bb, bq, d), jnp.float32),
            pltpu.VMEM((bb, bq, 1), jnp.float32),
            pltpu.VMEM((bb, bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
