"""What the reference models share: norms, rotary embedding, causal
attention, chunked cross-entropy, the precision of the control, and three
AdamW steps.

Everything runs in float32 under ``default_matmul_precision("highest")``
(the caller sets it). Attention and the loss are computed in chunks of
queries and of positions under ``jax.checkpoint``, and each layer is
rematerialized, so the reference fits beside nothing else on one chip at
the timed sizes. With a device mesh (axis ``b``) the batch rows are split
over the chips and each large leaf over its largest divisible dimension;
XLA inserts the exchanges.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..weights import base_key, logical_values

NEG = -1e30


# ---------------------------------------------------------------------------
# Precision: "f32" (the reference), and the controls one step below what a
# configuration states: "int4_weights" (INT8 weights -> INT4, per flat block
# of 128, as the program blocks them) and "fp8" (bf16 -> float8 e4m3 matmul
# operands with per-tensor scaling). Gradients pass straight through.
# ---------------------------------------------------------------------------

def _straight(x, xq):
    return x + lax.stop_gradient(xq - x)


def _int4_blocks(w, block: int = 128):
    flat = w.reshape(-1, block)
    scale = jnp.max(jnp.abs(flat), axis=-1, keepdims=True) / 7.0
    q = jnp.clip(jnp.round(flat / jnp.maximum(scale, 1e-30)), -7, 7)
    return (q * scale).reshape(w.shape)


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0
    scale = jnp.maximum(scale, 1e-30)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


class Precision:
    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "int4_weights", "fp8"):
            raise ValueError(f"unknown precision {mode!r}")
        self.mode = mode

    def weight(self, w):
        if self.mode == "int4_weights":
            return _straight(w, _int4_blocks(w))
        if self.mode == "fp8":
            return _straight(w, _fp8(w))
        return w

    def act(self, x):
        return _straight(x, _fp8(x)) if self.mode == "fp8" else x

    def mm(self, x, w):
        """x (..., K) @ w (K, N)."""
        return self.act(x) @ self.weight(w)

    def mm_t(self, x, w):
        """x (..., N) @ w (K, N).T."""
        return self.act(x) @ self.weight(w).T


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * w + b


def rope(x, theta: float, rot_dim: int):
    """Rotary embedding of the first ``rot_dim`` features of each head, in
    the half-split pairing (feature i with i + rot_dim / 2).
    x: (B, S, H, D) at positions 0..S-1."""
    s = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    r, rest = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = r[..., :rot_dim // 2], r[..., rot_dim // 2:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn, rest], -1)


def _chunk(s: int, target: int) -> int:
    c = min(target, s)
    while s % c:
        c -= 1
    return c


def causal_attention(q, k, v, q_chunk: int = 512):
    """Softmax attention, causal, scale 1/sqrt(D); k, v may have fewer heads
    (grouped queries). q (B, S, H, D), k, v (B, S, KV, D) -> (B, S, H, D)."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    c = _chunk(s, q_chunk)
    qc = q.reshape(b, s // c, c, h, d).swapaxes(0, 1)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qi, start = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(d)
        mask = (start + jnp.arange(c))[:, None] >= kpos[None, :]
        p = jax.nn.softmax(jnp.where(mask, sc, NEG), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = lax.map(one, (qc, jnp.arange(s // c) * c))
    return out.swapaxes(0, 1).reshape(b, s, h, d)


def cross_entropy(x, w_vocab, labels, prec: Precision, chunk: int = 512):
    """Mean next-token cross-entropy; x (B, S, d), w_vocab (V, d), labels
    (B, S). Logits are made for ``chunk`` positions of every row at a time."""
    b, s, d = x.shape
    c = _chunk(s, chunk)
    xc = x.reshape(b, s // c, c, d).swapaxes(0, 1)
    lc = labels.reshape(b, s // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def body(total, inp):
        xi, li = inp
        logits = prec.mm_t(xi, w_vocab)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return total + jnp.sum(jax.nn.logsumexp(logits, -1) - gold), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (b * s)


def scan_layers(layer, x, stacked: dict):
    """Run ``layer(x, params_of_one_layer)`` over the stacked leaves,
    rematerializing each layer in the backward pass."""
    body = jax.checkpoint(lambda h, p: (layer(h, p), None))
    x, _ = lax.scan(body, x, stacked)
    return x


def stacked(params: dict, prefix: str) -> dict:
    return {n[len(prefix):]: v for n, v in params.items()
            if n.startswith(prefix)}


def row_spec(rows: int, ndim: int, mesh) -> P:
    """Batch rows split over the mesh where they divide, else replicated
    (a planted fault may keep fewer rows than there are chips)."""
    split = rows % mesh.devices.size == 0
    return P("b" if split else None, *([None] * (ndim - 1)))


def keep_rows(x, mesh):
    """Hold activations split over the mesh by batch row."""
    if mesh is None:
        return x
    return lax.with_sharding_constraint(
        x, NamedSharding(mesh, row_spec(x.shape[0], x.ndim, mesh)))


# ---------------------------------------------------------------------------
# Three AdamW steps, as the configuration's job states them
# ---------------------------------------------------------------------------

def lr_at(t, hp: dict):
    """Linear warm-up to ``lr``, then cosine decay to ``min_lr_frac`` of it;
    ``t`` is the 0-based index of the step."""
    w, total = hp["warmup_steps"], hp["total_steps"]
    warm = jnp.minimum(t / max(w, 1), 1.0)
    prog = jnp.clip((t - w) / max(total - w, 1), 0.0, 1.0)
    cos = hp["min_lr_frac"] + (1 - hp["min_lr_frac"]) * 0.5 \
        * (1 + jnp.cos(jnp.pi * prog))
    return hp["lr"] * warm * cos


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x)))


def _sharding(shape, stack, mesh):
    if mesh is None:
        return None
    n = mesh.devices.size
    lead = 1 if stack else 0
    dims = [i for i in range(lead, len(shape))
            if shape[i] % n == 0 and math.prod(shape) >= 2**20]
    spec = [None] * len(shape)
    if dims:
        spec[max(dims, key=lambda i: shape[i])] = "b"
    return NamedSharding(mesh, P(*spec))


def adamw_step(model, cfg: dict, table: dict, hp: dict, prec: Precision,
               mesh=None):
    """``step(params, m, v, tokens, t) -> (params, m, v, loss, leaf grad
    norms)``: one AdamW step with global-norm clipping, weight decay on the
    leaves of two or more dimensions, ``t`` the 0-based step index."""
    names = sorted(table)
    decay = {n: len(table[n]["shape"]) >= 2 for n in names}
    b1, b2 = hp["betas"]

    def step(p, m, v, tokens, t):
        loss, g = jax.value_and_grad(
            lambda p: model.loss(p, tokens, cfg, prec, mesh))(p)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, hp["grad_clip"] / (gnorm + 1e-6)), g)
        lr = lr_at(t, hp)
        k = (t + 1).astype(jnp.float32)
        new_p, new_m, new_v = {}, {}, {}
        for n in names:
            new_m[n] = b1 * m[n] + (1 - b1) * g[n]
            new_v[n] = b2 * v[n] + (1 - b2) * jnp.square(g[n])
            upd = (new_m[n] / (1 - b1 ** k)) \
                / (jnp.sqrt(new_v[n] / (1 - b2 ** k)) + hp["eps"])
            wd = hp["weight_decay"] if decay[n] else 0.0
            new_p[n] = p[n] * (1 - lr * wd) - lr * upd
        return new_p, new_m, new_v, loss, {n: _norm(g[n]) for n in names}

    return step


def param_shardings(table: dict, mesh) -> dict | None:
    if mesh is None:
        return None
    return {n: _sharding(((e["stack"],) if e["stack"] else ()) + tuple(e["shape"]),
                         e["stack"], mesh) for n, e in table.items()}


def train_readings(model, cfg: dict, table: dict, seed: int, batches,
                   hp: dict, *, precision: str = "f32", rows: int | None = None,
                   mesh=None) -> dict:
    """Run the reference through ``len(batches)`` AdamW steps from the
    seeded weights. ``rows`` keeps only the first rows of each batch (a
    planted fault). Returns the loss of each step, the norm of each leaf's
    first gradient after clipping, and of each leaf's change over all steps.
    """
    names = sorted(table)
    init = jax.jit(lambda key: {n: logical_values(key, n, table[n])
                                for n in names},
                   out_shardings=param_shardings(table, mesh))
    key = base_key(seed)
    params = init(key)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(adamw_step(model, cfg, table, hp, Precision(precision), mesh),
                   donate_argnums=(0, 1, 2))
    losses, grad1 = [], None
    for t, batch in enumerate(batches):
        tokens = batch["tokens"] if rows is None else batch["tokens"][:rows]
        if mesh is not None:
            tokens = jax.device_put(tokens, NamedSharding(
                mesh, row_spec(tokens.shape[0], 2, mesh)))
        params, m, v, loss, gn = step(params, m, v, tokens,
                                      jnp.asarray(t, jnp.int32))
        losses.append(float(loss))
        if grad1 is None:
            grad1 = {n: float(x) for n, x in gn.items()}
    del m, v
    change = jax.jit(lambda p, key: {
        n: _norm(p[n] - logical_values(key, n, table[n])) for n in names})(
            params, key)
    return dict(losses=losses, grad1=grad1,
                change={n: float(x) for n, x in change.items()})
