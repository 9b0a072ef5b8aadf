"""Shared model layers: norms, RoPE, memory-bounded attention, chunked CE.

Training/prefill attention routes through the first-class ``kernels/ops``
dispatch (``jnp | pallas | pallas_interpret``, inherited from
``ops.set_default_impl`` / ``--kernel-impl`` / ``REPRO_KERNEL_IMPL``):
``ops.attention_fusable`` decides whether a call shape can use the Pallas
kernel path, and rejected shapes (MLA value dims, traced decode offsets,
unaligned seqs) fall back to the chunked jnp scan below — with a one-time
structured warning and a dispatch-counter record, never silently; under the
compiled "pallas" default (TPU) such a shape is an error instead.

``flash_decode`` is the sequence-sharded single-token decode attention used
for 32k/500k KV caches: each device computes a partial softmax over its local
KV slice and the partials are combined exactly with a global max/denominator
reduction over the sharding axes (one pmax + two psums).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (scale.astype(jnp.float32))
    return out.astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(positions, dim: int, theta: float):
    """positions (...,) -> (cos, sin) of shape (..., dim//2), fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin (..., S, D//2) broadcast over heads."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x32_1 * c - x32_2 * s, x32_2 * c + x32_1 * s], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash attention (training / prefill)
# ---------------------------------------------------------------------------

def _best_chunk(s: int, target: int) -> int:
    """Largest divisor of s that is <= target (repeated halving fails badly
    for non-power-of-two lengths, e.g. whisper's 1500 frames -> chunk 4)."""
    target = min(target, s)
    for c in range(target, 0, -1):
        if s % c == 0:
            return c
    return 1


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_chunk: int = 512, kv_chunk: int = 1024,
                    q_offset: int = 0, softmax_scale: float | None = None):
    """q (B,Sq,H,D); k,v (B,Sk,Hkv,D). Returns (B,Sq,H,D).

    ``window`` > 0: sliding-window causal attention (each query attends to the
    previous ``window`` positions, inclusive of itself).
    ``q_offset``: global position of q[0] relative to k[0] (prefill=0;
    cross-attention uses causal=False).
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    dv = v.shape[-1]                      # MLA: value dim may differ from qk dim
    fusable, reason = ops.attention_fusable(
        sq, sk, d, dv, softmax_scale=softmax_scale, q_offset=q_offset)
    if fusable:
        kf = _repeat_kv(k, h // hkv)
        vf = _repeat_kv(v, h // hkv)
        qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        kt = kf.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
        vt = vf.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
        o = ops.flash_attention(qt, kt, vt, causal=causal, window=window,
                                q_offset=q_offset)
        return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    ops.record_fallback("attention", reason)
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)

    q_chunk = _best_chunk(sq, q_chunk)
    kv_chunk = _best_chunk(sk, kv_chunk)
    nq, nk = sq // q_chunk, sk // kv_chunk

    qc = q.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 3, 2, 4)  # (nq,B,H,C,D)
    kc = k.reshape(b, nk, kv_chunk, h, d).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, kv_chunk, h, dv).transpose(1, 0, 3, 2, 4)

    q_pos = q_offset + jnp.arange(sq).reshape(nq, q_chunk)
    k_pos = jnp.arange(sk).reshape(nk, kv_chunk)

    def q_body(_, qi):
        qb, qp = qi  # (B,H,C,D), (C,)

        def kv_body(carry, ki):
            acc, m, denom = carry
            kb, vb, kp = ki
            s = jnp.einsum("bhqd,bhkd->bhqk", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window:
                mask &= qp[:, None] - kp[None, :] < window
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            denom = denom * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
            return (acc, m_new, denom), None

        acc0 = jnp.zeros((b, h, q_chunk, dv), jnp.float32)
        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        d0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        (acc, m, denom), _ = lax.scan(kv_body, (acc0, m0, d0), (kc, vc, k_pos))
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, out = lax.scan(jax.checkpoint(q_body, prevent_cse=False), None,
                      (qc, q_pos))
    # (nq, B, H, C, Dv) -> (B, S, H, Dv)
    return out.transpose(1, 0, 3, 2, 4).reshape(b, sq, h, dv)


# ---------------------------------------------------------------------------
# Decode attention
# ---------------------------------------------------------------------------

def _row_positions(pos, b):
    """Broadcast a scalar or (B,) position to (B,) int32."""
    p = jnp.asarray(pos, jnp.int32)
    return jnp.broadcast_to(p.reshape(-1), (b,)) if p.ndim <= 1 \
        else p.reshape(b)


def flash_decode(q, k_loc, v_loc, pos, *, seq_axes: tuple[str, ...] = (),
                 seq_offset=0, softmax_scale: float | None = None):
    """Single-token decode over a (possibly sequence-sharded) KV cache.

    q: (B, H, D); k_loc/v_loc: (B, S_loc, Hkv, D) — this device's slice of the
    cache; valid entries are global positions <= pos (scalar or per-row (B,),
    for continuous batching). ``seq_offset``: global position of k_loc[0]
    (devices differ). Partial softmax combined exactly over ``seq_axes``.
    """
    b, h, d = q.shape
    _, s_loc, hkv, _ = k_loc.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    n_rep = h // hkv
    kpos = seq_offset + jnp.arange(s_loc)
    pos_b = _row_positions(pos, b)
    valid = kpos[None, :] <= pos_b[:, None]               # (B, S_loc)

    qg = q.reshape(b, hkv, n_rep, d).astype(jnp.float32)
    s = jnp.einsum("bgrd,bsgd->bgrs", qg,
                   k_loc.astype(jnp.float32)) * scale     # (B,Hkv,rep,S_loc)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m_loc = jnp.max(s, axis=-1)
    if seq_axes:
        m = lax.pmax(m_loc, seq_axes)
    else:
        m = m_loc
    p = jnp.exp(s - m[..., None])
    num = jnp.einsum("bgrs,bsgd->bgrd", p, v_loc.astype(jnp.float32))
    den = jnp.sum(p, axis=-1)
    if seq_axes:
        # contract: allow[raw-psum] -- seq-parallel softmax partials over the
        # intra-tier seq axes; fp32 throughout, single-process decode path
        num = lax.psum(num, seq_axes)
        den = lax.psum(den, seq_axes)  # contract: allow[raw-psum]
    out = num / jnp.maximum(den[..., None], 1e-30)
    return out.reshape(b, h, d).astype(q.dtype)


def ring_decode(q, k_ring, v_ring, pos, window: int,
                softmax_scale: float | None = None):
    """Decode over a sliding-window ring cache (B, W, Hkv, D), write-pos =
    pos % W. ``pos`` may be per-row (B,)."""
    b, h, d = q.shape
    _, w, hkv, _ = k_ring.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(d)
    n_rep = h // hkv
    # ring slot i holds global position: the largest p <= pos with p % W == i
    slot = jnp.arange(w)
    pos_b = _row_positions(pos, b)[:, None]
    gpos = pos_b - (pos_b - slot[None, :]) % w
    valid = (gpos >= 0) & (gpos > pos_b - window)         # (B, W)
    qg = q.reshape(b, hkv, n_rep, d).astype(jnp.float32)
    s = jnp.einsum("bgrd,bsgd->bgrs", qg, k_ring.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p, v_ring.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def sharded_cache_write(cache_loc, new, pos, *, seq_axes: tuple[str, ...],
                        axis_sizes: dict[str, int]):
    """Write ``new`` (B, 1, Hkv, D) at global seq position ``pos``.

    cache_loc: (B, S_loc, Hkv, D), this device's contiguous slice of the
    global (B, S, ...) cache (major->minor over seq_axes). Only the owner
    updates; others keep their slice via a where-mask. ``pos`` may be
    per-row (B,) (continuous batching): a masked one-hot write is used.
    """
    b = cache_loc.shape[0]
    s_loc = cache_loc.shape[1]
    p = jnp.asarray(pos, jnp.int32)
    if seq_axes:
        idx = _linear_index(seq_axes, axis_sizes)
        local = p - idx * s_loc
    else:
        local = p
    if p.ndim == 0:
        inb = (local >= 0) & (local < s_loc)
        upd = lax.dynamic_update_slice_in_dim(
            cache_loc, new.astype(cache_loc.dtype),
            jnp.clip(local, 0, s_loc - 1), axis=1)
        return jnp.where(inb, upd, cache_loc)
    # per-row positions: one-hot masked write
    oh = jnp.arange(s_loc)[None, :] == local.reshape(b)[:, None]   # (B,S_loc)
    return jnp.where(oh[:, :, None, None], new.astype(cache_loc.dtype),
                     cache_loc)


def _linear_index(axes: tuple[str, ...], axis_sizes: dict[str, int]):
    """Row-major device index over `axes` (major -> minor)."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * axis_sizes[a] + lax.axis_index(a)
    return idx


def seq_offset(axes: tuple[str, ...], axis_sizes: dict[str, int], s_loc: int):
    return _linear_index(axes, axis_sizes) * s_loc


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def chunked_cross_entropy(x, w_vocab, labels, mask, *, chunk: int = 512,
                          logit_softcap: float = 0.0):
    """Next-token CE without materializing (B, S, V).

    x: (B, S, d) final hidden states; w_vocab: (V, d) dense lm-head (gathered
    once — its AD cotangent is reduced over chunks by scan); labels (B, S)
    int32; mask (B, S) {0,1}. Returns (loss_sum, token_count).
    """
    b, s, d = x.shape
    chunk = _best_chunk(s, chunk)
    n = s // chunk
    xc = x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n, chunk).transpose(1, 0, 2)
    mc = mask.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        xi, li, mi = inp
        logits = jnp.einsum("bcd,vd->bcv", xi.astype(jnp.float32),
                            w_vocab.astype(jnp.float32))
        if logit_softcap:
            logits = logit_softcap * jnp.tanh(logits / logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mi
        return carry + jnp.sum(nll), None

    body = jax.checkpoint(body, prevent_cse=False)
    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc, mc))
    return total, jnp.sum(mask)


def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]
