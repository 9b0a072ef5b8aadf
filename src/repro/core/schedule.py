"""The comm-schedule layer: every overlap machine in the engine, one idiom.

The engine hides collectives behind compute in three places, and all three
are the same *issue/wait* pattern — start the collective where its inputs
are ready, consume its result where the data is needed, and keep the two
ends data-independent from the compute in between so XLA's latency-hiding
scheduler can run them concurrently:

1. **Forward gather prefetch** (DESIGN.md §3; ZeRO++ §IV, Dash et al. 2023).
   A 2-slot buffer of gathered-quantized weights rotates through the layer
   loop: slot A holds layer i's buffer (being consumed), slot B holds layer
   i+1's, whose quantize + all-gather (``collectives.gather_issue_int8``) is
   already in flight. ``scan_layers`` threads the buffer through the
   ``lax.scan`` carry (prologue issues layer 0, each step issues layer i+1,
   the last layer runs as an epilogue); ``loop_layers`` applies the same
   rotation across heterogeneous Python-unrolled patterns (gemma3 5:1
   local:global, jamba mamba/attn). Gather count stays exactly L per leaf
   per pass — comm volume unchanged, only the schedule moves.

2. **Backward secondary re-gather** (DESIGN.md §5). The weight
   re-materialization for dX is issued in wire format
   (``regather_issue`` -> ``collectives.gather_secondary_q`` /
   ``gather_issue_int8``) and *waited* only where it is consumed — by the
   fused dequant-matmul kernel directly, or by ``regather_wait`` (the local
   dequant) on the unfused fallback.

3. **Backward grad reduce-scatter** (DESIGN.md §8, streaming grad path).
   Each layer's weight cotangent is reduce-scattered *inside* the reverse
   scan step: ``grad_rs_issue`` ends at the collective (quantize + a2a, or
   the plain psum-scatter) and ``grad_rs_wait`` runs the local fused
   dequant-reduce. The result feeds only the optimizer-shard sink cotangent
   — nothing in layer i-1's backward matmuls depends on it — so layer i's
   grad collective overlaps layer i-1's backward compute exactly the way
   slot B's gather overlaps slot A's forward matmuls.

Every split composes op-for-op into its fused primitive
(``quant_all_gather_int8`` / ``a2a_quant_reduce_scatter`` /
``reduce_scatter_flat``), so issue/wait schedules are **bitwise identical**
to the serial ones (tests/test_overlap.py, tests/test_stream_grads.py,
tests/_scenarios.py).

Buffers are ``lax.stop_gradient``'d at issue time: the consuming ``*_pre``
custom VJPs route the true weight gradient to the primary shard (or the
streaming sink), so no cotangent — in particular no transposed collective —
flows back through a rotation.

Memory: forward overlap holds at most two layers' quantized buffers live
(the "2 slots", reported as ``memory_report()["prefetch_buffer"]``). Under
``remat=True`` the scan checkpoint saves its carry per step, which includes
the rotating buffer — an extra ~psi INT8 bytes across the backward pass.
See DESIGN.md §3/§8 for the trade-off tables.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..analysis.tags import tag as _tag
from ..obs import spans as _spans
from . import collectives as col
from .partition import ZeroConfig

AxisTuple = tuple[str, ...]


# ---------------------------------------------------------------------------
# Machine 1: forward gather prefetch (issue half; the *_pre VJPs are the wait)
# ---------------------------------------------------------------------------

def prefetchable_names(fns, names) -> tuple[str, ...]:
    """Leaves with an issue() half (MATMUL / GATHER_Q); PLAIN leaves are
    norm-scale sized and keep their (negligible) inline gather."""
    return tuple(n for n in names if fns[n].issue is not None)


def issue_buffers(fns, primaries, names):
    """Issue the gathers for one layer's prefetchable leaves.

    Returns {name: buffer pytree}. stop_gradient on the *input* keeps the
    whole issue chain (quantize kernel + collective) primal-only: no tangent
    ever enters it (the Pallas quantize has no JVP rule) and no cotangent —
    in particular no transposed collective — flows back through the scan
    carry (see module docstring).
    """
    # obs scope: names this issue site in the op_name of every op it emits
    with _spans.scope("gather/issue"):
        return {n: fns[n].issue(lax.stop_gradient(primaries[n]))
                for n in names}


# ---------------------------------------------------------------------------
# Machine 2: backward secondary re-gather (issue in wire format, wait = local
# dequant or the fused dequant-matmul kernel)
# ---------------------------------------------------------------------------

def regather_issue(primary, sec_q, sec_s, cfg: ZeroConfig):
    """Backward weight re-materialization, kept in wire format (q, scales).

    Gathers the INT8 secondary partition over the secondary axes when one
    exists (never crossing the slow tier), else re-gathers the primary over
    the weight axes. Ends at the collective — the dense weight is never
    built here.
    """
    with _spans.scope("regather/issue"):
        if sec_q is not None:
            return col.gather_secondary_q(sec_q, sec_s, cfg.axes.secondary,
                                          cfg)
        return col.gather_issue_int8(primary, cfg.axes.weight, cfg)


def regather_wait(qf, sf, cfg: ZeroConfig, out_dtype=jnp.bfloat16):
    """Local dequant of a re-gathered wire buffer (unfused fallback; the
    fused dX kernel consumes the wire format directly and skips this)."""
    with _spans.scope("regather/wait"):
        return col.gather_wait_int8(qf, sf, cfg, out_dtype)


# ---------------------------------------------------------------------------
# Machine 3: backward grad reduce-scatter (streaming grad path, DESIGN.md §8)
# ---------------------------------------------------------------------------

def grad_rs_issue(flat, axes: AxisTuple, cfg: ZeroConfig, *,
                  quantized: bool | None = None, bits: int = 4):
    """Issue half of a gradient reduce-scatter over ``axes``: ends at the
    collective (quantize + all-to-all when quantized, the psum-scatter
    itself otherwise). Returns an opaque token for ``grad_rs_wait`` — the
    group size and quantization width ride the token, so mismatched
    issue/wait pairs cannot silently decode the wrong wire format."""
    with _spans.scope("grad_rs/issue"):
        if not axes or cfg.size(axes) == 1:
            return ("nop", _tag(flat, role="issue", machine="grad_rs"))
        if quantized is None:
            quantized = cfg.quantize_grads
        if not quantized:
            return ("rs",
                    _tag(lax.psum_scatter(flat, tuple(axes), tiled=True),
                         role="issue", machine="grad_rs"))
        return ("a2a", _tag(col.a2a_rs_issue(flat, axes, cfg, bits),
                            role="issue", machine="grad_rs"),
                cfg.size(axes), bits)


def grad_rs_issue_q(q, s, axes: AxisTuple, cfg: ZeroConfig, *, bits: int = 4):
    """Issue half for a *pre-quantized* gradient: the wire-format (q, s)
    came out of the fused matmul-quant epilogue (ops.matmul_quant), so only
    the a2a exchange remains. Token format and contract tags are identical
    to the quantized branch of ``grad_rs_issue`` — the verifier census and
    ``grad_rs_wait`` cannot tell the producers apart. Callers gate on
    ``cfg.quantize_grads`` and group size > 1 (the dense nop/rs branches
    have no wire format to skip)."""
    with _spans.scope("grad_rs/issue"):
        assert axes and cfg.size(axes) > 1, axes
        return ("a2a", _tag(col.a2a_rs_issue_q(q, s, axes, cfg),
                            role="issue", machine="grad_rs"),
                cfg.size(axes), bits)


def grad_rs_wait(token, cfg: ZeroConfig, *, out_dtype=jnp.float32):
    """Wait half: local fused dequant + reduce of the received chunks (no
    communication). Everything the receive side needs — group size, bit
    width, payload — rides the token, so issue/wait pairs cannot mismatch.
    ``grad_rs_wait(grad_rs_issue(x)) == collectives.reduce_scatter_flat(x)``
    op-for-op — bitwise."""
    with _spans.scope("grad_rs/wait"):
        kind = token[0]
        if kind in ("nop", "rs"):
            return _tag(token[1], role="wait",
                        machine="grad_rs").astype(out_dtype)
        _, (q2, s2), d, bits = token
        q2, s2 = _tag((q2, s2), role="wait", machine="grad_rs")
        return col.a2a_rs_wait(q2, s2, d, cfg, bits, out_dtype)


# ---------------------------------------------------------------------------
# The buffer-rotation idiom over layer loops (used via ParamView)
# ---------------------------------------------------------------------------

def scan_layers(view, body, carry, names, *, remat: bool = True,
                unroll: int = 1, with_ys: bool = False,
                overlap: bool | None = None):
    """lax.scan over stacked leaves `names` with the prefetch rotation and
    the streaming grad sinks threaded through the xs.

    body(view, carry) -> carry, or (carry, y) when ``with_ys`` (per-layer
    outputs are stacked like lax.scan's ys). ``overlap=None`` inherits the
    view's setting (ZeroConfig.overlap via the engine).

    Overlapped schedule: a prologue issues layer 0's gathers, each scan step
    consumes the carried buffer for layer i while issuing layer i+1's, and
    the last layer runs as an epilogue — so the gather count stays exactly
    one per leaf per layer (comm volume unchanged; only the schedule moves).

    Streaming grads (DESIGN.md §8): when the view carries optimizer-shard
    sinks, each layer's sink row rides the xs next to that layer's
    primaries, so the reverse scan step emits that layer's fully-reduced
    cotangent straight into the stacked os-layout accumulation.
    """
    stacked = view.stacked(names)
    if overlap is None:
        overlap = view._overlap
    fns = view._fns
    pf = prefetchable_names(fns, names) if overlap and fns else ()
    sinks = view.sink_stacks(names)

    def sub(lp, ls, buf=None):
        kw = {}
        if buf is not None:
            kw["bufs"] = buf
        if ls:
            kw["sinks"] = ls
        return view.sub(lp, **kw)

    if not pf:
        def f(c, xs):
            lp, ls = xs
            out = body(sub(lp, ls), c)
            return out if with_ys else (out, None)

        if remat:
            f = jax.checkpoint(f, prevent_cse=False)
        c, ys = lax.scan(f, carry, (stacked, sinks), unroll=unroll)
        return (c, ys) if with_ys else c

    buf0 = issue_buffers(fns, {n: stacked[n][0] for n in pf}, pf)

    def f(c, xs):
        cur, cur_s, nxt = xs
        inner, buf = c
        buf_next = issue_buffers(fns, nxt, pf)
        out = body(sub(cur, cur_s, buf), inner)
        inner, y = out if with_ys else (out, None)
        return (inner, buf_next), y

    def last(c):
        inner, buf = c
        out = body(sub({n: stacked[n][-1] for n in names},
                       {n: sinks[n][-1] for n in sinks}, buf), inner)
        return out if with_ys else (out, None)

    if remat:
        f = jax.checkpoint(f, prevent_cse=False)
        last = jax.checkpoint(last, prevent_cse=False)
    cur = {n: stacked[n][:-1] for n in names}
    cur_s = {n: sinks[n][:-1] for n in sinks}
    nxt = {n: stacked[n][1:] for n in pf}
    c2, ys = lax.scan(f, (carry, buf0), (cur, cur_s, nxt), unroll=unroll)
    carry, y_last = last(c2)
    if not with_ys:
        return carry
    if y_last is not None:
        ys = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b[None]], axis=0), ys, y_last)
    return carry, ys


def loop_layers(view, body, carry, steps, *, remat: bool = True,
                overlap: bool | None = None):
    """Python loop for heterogeneous block patterns.

    steps: sequence of ``(tag, layer_primaries)`` pairs — one entry per
    layer in pattern order, ``layer_primaries`` already indexed out of the
    per-kind stacks. body(view, carry, tag) -> (carry, y).
    Returns (carry, [y per layer]).

    With overlap, layer j+1's gathers are issued alongside layer j's
    compute — including across block-kind boundaries (gemma3's 5:1
    local:global interleave, jamba's mamba/attn mix). Streaming sinks are
    indexed per leaf by occurrence order: leaf names are unique to their
    block kind, so the running count of a name across steps IS its layer
    index within its stacked leaf.
    """
    if overlap is None:
        overlap = view._overlap
    fns = view._fns
    overlap = overlap and fns is not None
    bufs_next = None
    if overlap and len(steps):
        _, lp0 = steps[0]
        bufs_next = issue_buffers(fns, lp0, prefetchable_names(fns, lp0))
    counts: dict[str, int] = {}
    ys = []
    for j, (tag, lp) in enumerate(steps):
        bufs, bufs_next = bufs_next, None
        if overlap and j + 1 < len(steps):
            _, lpn = steps[j + 1]
            bufs_next = issue_buffers(fns, lpn, prefetchable_names(fns, lpn))
        ls = {}
        for n in lp:
            i = counts.get(n, 0)
            counts[n] = i + 1
            sink = view.sink_stack(n)
            if sink is not None:
                ls[n] = sink[i]
        # plain positional sub() for subclasses that don't know about
        # bufs/sinks (serve.resident.ResidentView)
        kw = {}
        if bufs is not None:
            kw["bufs"] = bufs
        if ls:
            kw["sinks"] = ls
        v = view.sub(lp, **kw) if kw else view.sub(lp)

        def one(c, v=v, tag=tag):
            return body(v, c, tag)

        if remat:
            one = jax.checkpoint(one, prevent_cse=False)
        carry, y = one(carry)
        ys.append(y)
    return carry, ys
