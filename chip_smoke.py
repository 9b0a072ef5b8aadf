#!/usr/bin/env python3
"""Smoke run of the training path on a TPU: qwen2-0.5b at its published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the three ZeRO schemes on a 2x2 host

One process, no children. With one chip the phases are, in order:

1. the device check: prints platform, device kind and count, and stops with
   a non-zero exit when the platform is not ``tpu`` (there is no CPU
   fallback);
2. each compiled Pallas kernel of the path at real widths (qwen2-0.5b:
   K=896, d_ff 4864, vocab 151,936, quant block 128, 2048 rows), and the
   selective scan at falcon-mamba-7b's d_inner, against its jnp reference
   from ``repro.kernels.ref`` computed at float32 ("highest") matmul
   precision; each error is printed beside its tolerance (``TOL``);
3. ``TRAIN_STEPS`` training steps through ``repro.launch.train.main``
   (``ZeroEngine`` step via ``Trainer.run``): scheme zero_topo, INT8
   weights, INT4 gradients, global batch ``BATCH`` x seq ``SEQ``, the
   compiled kernels only — any ``jnp``, ``pallas_interpret`` or fallback
   dispatch fails the run. Prints the loss of every step, the step time
   after the first and ``peak_bytes_in_use``; losses must be finite and
   the mean of the last three below step 1;
4. step 1 again at ``--kernel-impl jnp``: its loss must match step 1 of
   phase 3 within ``STEP1_RTOL``.

``--chips 4`` runs only the scheme comparison: zero3, zeropp and zero_topo
for ``SCHEME_STEPS`` steps each on the (1, 2, 2) mesh with the same seed
and global batch. Their losses must agree within ``SCHEME_RTOL``, every
state leaf must be placed on all 4 chips with the optimizer state split
four ways, and every chip must report memory in use.

The last line of stdout is ``{"ok": true, "device": {...}}``; it is printed
only when every phase passed. Any failure prints its traceback and exits 1.
The script sets no XLA or libtpu flags.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent

ARCH = "qwen2-0.5b"
SEQ = 4096
# Global batch 2 x 4096: compiled for v5e, the one-chip step needs 6.9 GB
# of arguments + 7.0 GB of temporaries of the 15.75 GB HBM. At 3 x 4096 the
# temporaries grow to 9.6 GB (the sum no longer fits the HBM, so there is no
# shown room), and 4 x 4096 is refused (16.11 GB).
BATCH = 2
QUANT_BLOCK = 128
TRAIN_STEPS = 10
SCHEME_STEPS = 4
# 2 x 4096 per chip, as on one chip; every scheme shards the batch over all 4
SCHEME_BATCH = 8
ROWS = 2048

# Kernel errors are max|out - ref| / max|ref| (normalised by the reference's
# largest magnitude). Reasons for each bound:
TOL = {
    # bf16 output (8 mantissa bits: 2**-8 = 3.9e-3 relative rounding) over
    # f32 accumulation in another tile order than the reference
    "matmul_bf16": 1e-2,
    # the epilogue's block quantization: round-to-nearest is at most half a
    # step (absmax / qmax) from the exact product; one step allows for the
    # accumulation order flipping a rounding
    "matmul_quant_int8": 1.0 / 127.0,
    "matmul_quant_int4": 1.0 / 7.0,
    # elementwise f32 ops in the same order as the reference
    "elementwise": 1e-6,
    # a 256-step f32 recurrence with exp() from another library
    "scan": 1e-3,
}
# A quantize kernel may land one code away from the reference where x /
# scale sits on a rounding boundary (the division is not bitwise-specified).
CODE_SLACK = 1
# Step-1 loss, compiled kernels vs jnp: the forward runs in bf16 (2**-8
# relative rounding per op), and the mean over 8192 tokens averages the
# per-token differences, so the losses agree to well under one bf16 ulp of
# the loss itself.
STEP1_RTOL = 4e-3
# Scheme comparison on 4 chips. zeropp and zero_topo gather INT8 weights
# (rounding <= 1/254 of a block's absmax per element) and reduce INT4
# gradients (<= 1/14); zero3 gathers and reduces in full precision. Before
# the first non-zero update (steps 1-2: the warm-up learning rate is 0 at
# step 1) only the weight rounding separates them; after it the quantized
# gradients move the weights differently as well.
SCHEME_RTOL = {"forward": 5e-3, "trained": 2e-2}


def device_check(want: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"platform={d.platform} device_kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        print(f"no TPU: JAX found platform {d.platform!r}; this smoke run "
              "needs a TPU and has no CPU fallback", file=sys.stderr)
        return None
    if len(devs) != want:
        print(f"expected {want} TPU chip(s), found {len(devs)}",
              file=sys.stderr)
        return None
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


# -- phase 2: compiled kernels against their jnp references -------------------

def _err(out, ref) -> float:
    import jax.numpy as jnp
    out = jnp.asarray(out, jnp.float32).reshape(-1)
    ref = jnp.asarray(ref, jnp.float32).reshape(-1)
    scale = jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30)
    return float(jnp.max(jnp.abs(out - ref)) / scale)


def _report(name: str, err: float, tol: float, failures: list) -> None:
    ok = math.isfinite(err) and err <= tol
    print(f"kernel {name:<42s} err={err:.3e} tol={tol:.3e} "
          f"{'ok' if ok else 'FAIL'}", flush=True)
    if not ok:
        failures.append(name)


def _unpack_int4(packed):
    import jax.numpy as jnp
    p = packed.astype(jnp.int32)
    return jnp.stack([p & 0xF, (p >> 4) & 0xF], axis=-1).reshape(
        *packed.shape[:-1], -1)


def check_kernels() -> list:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.models.registry import get_arch

    arch = get_arch(ARCH)
    d, ff, vocab, hd = arch.d_model, arch.d_ff, arch.vocab, arch.hdim
    b = QUANT_BLOCK
    keys = iter(jax.random.split(jax.random.key(0), 64))
    failures: list = []
    hi = jax.default_matmul_precision("highest")

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def weight(k, n):
        q = jax.random.randint(next(keys), (k * n,), -127, 128, jnp.int32)
        s = jax.random.uniform(next(keys), (k * n // b,), jnp.float32,
                               0.5e-2, 1.5e-2)
        return q.astype(jnp.int8), s

    # fused dequant x matmul: every (K, N) the model gathers, both
    # orientations (forward x @ W, backward g @ W.T; the tied lm head runs
    # x @ E.T forward and g @ E backward)
    for k, n in ((d, d), (d, arch.kv_heads * hd), (d, ff), (ff, d),
                 (vocab, d)):
        q, s = weight(k, n)
        w = ref.dequant_w_flat_ref(q.reshape(k, n), s.reshape(k, n // b), b)
        for transpose in (False, True):
            x = normal((ROWS, n if transpose else k), jnp.bfloat16)
            out = jax.jit(lambda x, q, s: ops.dequant_matmul(
                x, q, s, (k, n), b, transpose=transpose,
                impl="pallas"))(x, q, s)
            with hi:
                want = jnp.dot(x.astype(jnp.float32), w.T if transpose else w)
            _report(f"dequant_matmul {k}x{n} transpose={transpose}",
                    _err(out, want), TOL["matmul_bf16"], failures)
            del out, want

    # fused dW matmul x block quantize (the wire format of the gradient
    # reduce-scatter), both bit widths
    for k, n in ((d, ff), (ff, d), (d, d)):
        x, g = normal((ROWS, k)), normal((ROWS, n))
        with hi:
            want = jnp.dot(x.T, g)
        for bits in (8, 4):
            qf, sf = jax.jit(lambda x, g: ops.matmul_quant(
                x, g, b, bits=bits, impl="pallas"))(x, g)
            codes = _unpack_int4(qf) - 8 if bits == 4 else qf
            deq = (codes.reshape(-1, b).astype(jnp.float32)
                   * sf.reshape(-1, 1)).reshape(k, n)
            _report(f"matmul_quant int{bits} {k}x{n}", _err(deq, want),
                    TOL[f"matmul_quant_int{bits}"], failures)

    # block quantize / dequantize of a flat shard (the d_ff weight)
    x = normal((d * ff,))
    blocks = x.reshape(-1, b)
    q8, s8 = jax.jit(lambda x: ops.quantize_int8(x, b, impl="pallas"))(x)
    q8r, s8r = ref.quantize_int8_ref(blocks)
    dq = int(jnp.max(jnp.abs(q8.astype(jnp.int32)
                             - q8r.reshape(-1).astype(jnp.int32))))
    _report("quantize_int8 scales", _err(s8, s8r), TOL["elementwise"],
            failures)
    _report("quantize_int8 codes (max code diff)", float(dq), CODE_SLACK,
            failures)
    out = jax.jit(lambda q, s: ops.dequantize_int8(
        q, s, b, impl="pallas"))(q8r.reshape(-1), s8r.reshape(-1))
    _report("dequantize_int8", _err(out, ref.dequantize_int8_ref(q8r, s8r)),
            TOL["elementwise"], failures)

    q4, s4 = jax.jit(lambda x: ops.quantize_int4(x, b, impl="pallas"))(x)
    q4r, s4r = ref.quantize_int4_ref(blocks)
    dq = int(jnp.max(jnp.abs(_unpack_int4(q4) - _unpack_int4(
        q4r.reshape(-1)))))
    _report("quantize_int4 scales", _err(s4, s4r), TOL["elementwise"],
            failures)
    _report("quantize_int4 codes (max code diff)", float(dq), CODE_SLACK,
            failures)
    out = jax.jit(lambda q, s: ops.dequantize_int4(
        q, s, b, impl="pallas"))(q4r.reshape(-1), s4r.reshape(-1))
    _report("dequantize_int4", _err(out, ref.dequantize_int4_ref(q4r, s4r)),
            TOL["elementwise"], failures)

    # receive side of the quantized reduce-scatter: 2 chunks summed
    qd, sd = ops.quantize_int4(normal((2 * d * ff,)), b, impl="jnp")
    out = jax.jit(lambda q, s: ops.dequantize_int4_sum(
        q, s, 2, b, impl="pallas"))(qd, sd)
    want = ref.dequantize_int4_sum_ref(qd.reshape(2, -1, b // 2),
                                       sd.reshape(2, -1, 1))
    _report("dequantize_int4_sum", _err(out, want), TOL["elementwise"],
            failures)
    qd, sd = ops.quantize_int8(normal((2 * d * ff,)), b, impl="jnp")
    out = jax.jit(lambda q, s: ops.dequantize_int8_sum(
        q, s, 2, b, impl="pallas"))(qd, sd)
    want = ref.dequantize_int8_sum_ref(qd.reshape(2, -1, b),
                                       sd.reshape(2, -1, 1))
    _report("dequantize_int8_sum", _err(out, want), TOL["elementwise"],
            failures)

    # causal flash attention forward over the training batch's heads
    bh = BATCH * arch.n_heads
    qa, ka, va = (normal((bh, SEQ, hd), jnp.bfloat16) for _ in range(3))
    out = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, impl="pallas"))(qa, ka, va)
    with hi:
        want = jax.jit(lambda q, k, v: ref.flash_attention_ref(
            q, k, v))(qa, ka, va)
    _report(f"flash_attention fwd {bh}x{SEQ}x{hd}", _err(out, want),
            TOL["matmul_bf16"], failures)
    del out, want

    # selective scan at falcon-mamba-7b's d_inner (8192, d_state 16)
    mamba = get_arch("falcon-mamba-7b")
    s_len, din, n = 256, mamba.d_inner, mamba.ssm.d_state
    dt = jax.nn.softplus(normal((1, s_len, din))) * 0.1
    xs, bs, cs = normal((1, s_len, din)), normal((1, s_len, n)), \
        normal((1, s_len, n))
    a = -jnp.exp(normal((din, n)) * 0.5)
    h0 = jnp.zeros((1, din, n), jnp.float32)
    y, h = jax.jit(lambda *t: ops.selective_scan(*t, impl="pallas"))(
        dt, xs, bs, cs, a, h0)
    with hi:
        yr, hr = jax.jit(lambda *t: ref.selective_scan_ref(*t))(
            dt, xs, bs, cs, a, h0)
    _report(f"selective_scan y 1x{s_len}x{din}x{n}", _err(y, yr),
            TOL["scan"], failures)
    _report("selective_scan h_last", _err(h, hr), TOL["scan"], failures)
    return failures


# -- phases 3-4: training through the launcher --------------------------------

def _train_argv(scheme: str, steps: int, batch: int, *extra: str):
    return ["--arch", ARCH, "--scheme", scheme, "--steps", str(steps),
            "--batch", str(batch), "--seq", str(SEQ),
            "--quant-block", str(QUANT_BLOCK), *extra]


def _memory_stats() -> list:
    import jax
    return [d.memory_stats() for d in jax.devices()]


def _compiled_only(counters: dict) -> list:
    """Dispatch entries that are not the compiled Pallas kernel (its
    ``kernel/pallas`` count or a breakdown of it, such as the tile)."""
    return sorted(k for k in counters if k.split("/")[1] != "pallas")


def train_one_chip() -> list:
    from repro.kernels import ops
    from repro.launch import train

    failures: list = []
    ops.reset_dispatch_counters()
    tr, state = train.main(_train_argv("zero_topo", TRAIN_STEPS, BATCH))
    del state
    counters = ops.dispatch_counters()
    peak = _memory_stats()[0]["peak_bytes_in_use"]
    losses, times = tr.log.losses, tr.log.step_times
    print(f"dispatch counters: {counters}", flush=True)
    for i, (loss, dt) in enumerate(zip(losses, times), 1):
        print(f"train step {i:2d} loss {loss:.6f} time {dt:.4f}s", flush=True)
    timed = times[1:]
    print(f"batch x seq = {BATCH} x {SEQ}; step time after the first: "
          f"mean {sum(timed) / len(timed):.4f}s min {min(timed):.4f}s "
          f"max {max(timed):.4f}s", flush=True)
    print(f"peak_bytes_in_use {peak}", flush=True)

    bad = _compiled_only(counters)
    if bad or not counters:
        print(f"FAIL dispatch: non-compiled entries {bad}", flush=True)
        failures.append("dispatch")
    if not all(math.isfinite(x) for x in losses):
        print("FAIL losses not finite", flush=True)
        failures.append("finite")
    last3 = sum(losses[-3:]) / 3
    if not last3 < losses[0]:
        print(f"FAIL loss did not fall: step 1 {losses[0]:.6f}, "
              f"mean of the last 3 {last3:.6f}", flush=True)
        failures.append("falling")
    else:
        print(f"loss falls: step 1 {losses[0]:.6f} -> mean of the last 3 "
              f"{last3:.6f}", flush=True)

    tr_j, state = train.main(_train_argv("zero_topo", 1, BATCH,
                                         "--kernel-impl", "jnp"))
    del state
    l_p, l_j = losses[0], tr_j.log.losses[0]
    rel = abs(l_p - l_j) / abs(l_j)
    ok = rel <= STEP1_RTOL
    print(f"step-1 loss pallas {l_p:.6f} jnp {l_j:.6f} rel {rel:.3e} "
          f"tol {STEP1_RTOL:.1e} {'ok' if ok else 'FAIL'}", flush=True)
    if not ok:
        failures.append("step1_vs_jnp")
    return failures


def compare_schemes() -> list:
    import jax
    from repro.kernels import ops
    from repro.launch import train

    failures: list = []
    runs = {}
    for scheme in ("zero3", "zeropp", "zero_topo"):
        ops.reset_dispatch_counters()
        tr, state = train.main(_train_argv(scheme, SCHEME_STEPS,
                                           SCHEME_BATCH))
        runs[scheme] = tr.log.losses
        bad = _compiled_only(ops.dispatch_counters())
        print(f"{scheme}: losses {tr.log.losses} step times "
              f"{tr.log.step_times} dispatch {ops.dispatch_counters()}",
              flush=True)
        if bad:
            failures.append(f"{scheme} dispatch {bad}")
        for part in ("primaries", "master", "opt_m", "opt_v", "step"):
            leaves = jax.tree.leaves(state[part])
            for leaf in leaves:
                if len(leaf.sharding.device_set) != 4:
                    failures.append(f"{scheme} {part} on "
                                    f"{len(leaf.sharding.device_set)} chips")
                    break
            if part in ("master", "opt_m", "opt_v"):
                split = {leaf.shape[-1] // leaf.addressable_shards[0]
                         .data.shape[-1] for leaf in leaves}
                if split != {4}:
                    failures.append(f"{scheme} {part} split {split}")
        in_use = [m["bytes_in_use"] for m in _memory_stats()]
        print(f"{scheme}: bytes_in_use per chip {in_use}", flush=True)
        if not all(in_use):
            failures.append(f"{scheme} memory_stats {in_use}")
        if not all(math.isfinite(x) for x in tr.log.losses):
            failures.append(f"{scheme} losses not finite")
        del state

    base = runs["zero3"]
    for scheme in ("zeropp", "zero_topo"):
        for i, (a, b) in enumerate(zip(runs[scheme], base), 1):
            tol = SCHEME_RTOL["forward" if i <= 2 else "trained"]
            rel = abs(a - b) / abs(b)
            ok = rel <= tol
            print(f"step {i} {scheme} {a:.6f} vs zero3 {b:.6f} rel "
                  f"{rel:.3e} tol {tol:.1e} {'ok' if ok else 'FAIL'}",
                  flush=True)
            if not ok:
                failures.append(f"{scheme} step {i} loss")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: compare the ZeRO schemes on a 2x2 host only")
    args = ap.parse_args()

    if not (REPO / "src" / "repro").is_dir():
        print(f"the repo's sources (src/repro) are not beside {__file__}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(REPO / "src"))

    device = device_check(args.chips)
    if device is None:
        return 1
    from repro.launch.distributed import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    phases = ([("schemes", compare_schemes)] if args.chips == 4 else
              [("kernels", check_kernels), ("train", train_one_chip)])
    failed = []
    for name, phase in phases:
        t0 = time.time()
        print(f"== phase {name}", flush=True)
        try:
            bad = phase()
        except Exception:
            traceback.print_exc()
            bad = ["exception"]
        print(f"== phase {name}: {time.time() - t0:.1f}s "
              f"{'ok' if not bad else 'FAILED ' + str(bad)}", flush=True)
        if bad:
            failed.append(name)
    if failed:
        print(f"chip smoke failed in phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
